"""StableLM-2 1.6B [hf:stabilityai/stablelm-2-1_6b, ``config.json``,
``StableLmForCausalLM``].

24L d_model=2048 32H (MHA, kv=32, head_dim 64) d_ff=5632 vocab=100352,
untied head.  The published block, by its config keys:

- ``LayerNorm`` with weight and bias (``layer_norm_eps`` 1e-5) before
  attention, before the MLP and at the end (``norm="layer"``);
- ``partial_rotary_factor`` 0.25: rotary turns the first 16 of the 64
  head dims (half-split within those 16), dims 16-63 pass through;
- ``use_qkv_bias`` true: q, k and v projections add a bias, o has none;
- ``qk_layernorm`` false, ``use_parallel_residual`` false (attention,
  then the MLP, each on its own residual), a SiLU-gated MLP without
  bias, ``rope_theta`` 10000, ``tie_word_embeddings`` false.

``REDUCED`` keeps the block (norms, rotary share, biases) at a size the
CPU test suite runs.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="stablelm-1.6b",
    family="dense",
    n_layers=24,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=64,
    d_ff=5632,
    vocab_size=100352,
    rope_theta=10_000.0,
    rotary_fraction=0.25,
    qkv_bias=True,
    norm="layer",
    norm_eps=1e-5,
)

REDUCED = ModelConfig(
    name="stablelm-1.6b-reduced",
    family="dense",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=160,
    vocab_size=512,
    rotary_fraction=0.25,
    qkv_bias=True,
    norm="layer",
    norm_eps=1e-5,
)
