"""The plain reference against the served paged path at small size.

The program decodes a prompt through ``decode_step_paged`` (prefill walked
token by token, then greedy decode) on the benchmark's seeded weights; the
reference runs once over the whole sequence.  Their logits must agree
within a tolerance that bf16 serving explains: every MLP matmul quantizes
its bf16 input to 4 or 8-bit codes, and an input within bf16 rounding of
a code boundary lands one code away, which moves that product by a whole
code step.  On these models that leaves logits within 0.15 of the f32
reference (logits have unit spread); the tolerance is 0.25.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import reference, serve, weights

FIXTURES = Path(__file__).parent / "fixtures"
TOL = 0.25


def load(name):
    doc = json.loads((FIXTURES / f"{name}.json").read_text())
    return doc, weights.Dims.from_doc(doc)


def served_logits(doc, d, seed, prompt, gen):
    """Logits of the program's paged decode step, one position at a time."""
    from repro.models import decode_paged_fn, init_paged_caches

    cfg = serve.program_config(doc).with_approx_mlp(bits=d.lut_bits)
    params = weights.make_params(d, seed)
    luts = jnp.asarray(reference.served_tables(doc), jnp.int32)
    page, total = 4, len(prompt) + gen
    pages = -(-total // page)
    caches = init_paged_caches(cfg, 1, pages, page, total)
    tables = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, c, t, pos: decode_paged_fn(cfg)(
        cfg, p, c, t, pos, jnp.ones(1, bool), tables, luts=luts))
    toks, out = list(prompt), []
    for pos in range(total - 1):
        lg, caches = step(params, caches, jnp.asarray([[toks[pos]]]),
                          jnp.asarray([pos]))
        out.append(np.asarray(lg[0]))
        if pos >= len(prompt) - 1:
            toks.append(int(np.argmax(lg[0])))
    return np.stack(out), np.asarray(toks, np.int32)


F50 = "f50f16b39dff26c3"


@pytest.mark.parametrize("name", ["qwen3-tiny-w4", "qwen3-tiny-w4+f50",
                                  "stablelm-tiny-w8"])
def test_paged_prefill_then_decode_matches_reference(name):
    doc, d = load(name.split("+")[0])
    if name.endswith("+f50"):
        # an approximate table on layer 0 only: each layer runs its own
        doc["lut_plan"]["layers"][0] = F50
    seed = 2**33 + 5
    prompt = traffic_prompt(d, seed)
    got, toks = served_logits(doc, d, seed, prompt, gen=5)
    want = np.asarray(reference.forward(d, seed, toks[None, :-1],
                                        reference.layer_tiles(doc)))[0]
    assert np.abs(got - want).max() <= TOL * want.std()
    # the gap of each served token, as the benchmark's check reads it
    gaps = reference.served_gaps(d, seed, reference.layer_tiles(doc),
                                 [prompt], [toks[len(prompt):]])
    assert gaps.shape == (5,) and (gaps >= 0).all()
    assert gaps.max() <= 2 * TOL * want.std()


def traffic_prompt(d, seed):
    from benchmarks.chip import traffic

    return traffic.zipf_tokens(traffic.rng_for(seed, 9), 7, d.vocab, 1.2)


def test_reference_lut_linear_equals_the_programs_on_f32_inputs():
    from repro.quant.int4 import approx_linear

    doc, _ = load("qwen3-tiny-w4")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(48, 24)), jnp.float32)
    f50 = np.asarray(doc["lut_plan"]["tables"][F50])
    for tile, table, bits in (
            (f50, f50, 4),
            (reference.exact_tile(), reference.compose8(reference.exact_tile()),
             8)):
        got = approx_linear(x, w, jnp.asarray(table, jnp.int32),
                            backend="ref")
        want = reference.lut_linear(x, w, jnp.asarray(tile, jnp.int32), bits)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-5)


def test_weights_one_layer_at_a_time_equal_the_served_stack():
    _, d = load("qwen3-tiny-w4")
    params = weights.make_params(d, 12345)
    one = weights.layer_weights(d, jax.random.fold_in(
        weights.base_key(12345), 1))
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(jax.tree.map(lambda t: t[1],
                                                 params["layers"]))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_composed_table_is_the_exact_8bit_product_for_the_exact_tile():
    a = np.arange(256)
    assert np.array_equal(reference.compose8(reference.exact_tile()),
                          a[:, None] * a[None, :])


def test_e4m3_rounding_in_arithmetic_equals_the_float8_cast():
    x = jnp.concatenate([
        jnp.linspace(-448.0, 448.0, 20001),
        jnp.asarray(np.random.default_rng(1).normal(size=5000) * 3.0,
                    jnp.float32),
        jnp.asarray([0.0, 2.0 ** -9, 3 * 2.0 ** -10, 2.0 ** -6, 1e-4])])
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(reference.round_e4m3(x)),
                                  np.asarray(want))
