"""StableLM-2's runner (``serve_stablelm2``) at a size the CPU can run:
whole runs through the harness on ``stablelm2-tiny-w8.json``, the
published block at W8A8.

The fixture's limits sit between the readings of the control test's
seeds on the CPU, 96 served tokens each: the widest gap's (0.15) between
the program's 0.022-0.057 and the control's 0.302-0.802, the mean gap's
(0.008) between the program's 0.00051-0.00105 and the control's
0.0215-0.0269.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, serve_stablelm2, trace, work

import tiny

CONFIG = "stablelm2-tiny-w8"


def test_a_run_is_correct_and_reports_the_cells_end_to_end_metrics(
        tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, CONFIG, "tiny-batch")
    res = tiny.run(cell, monkeypatch, tmp_path)
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"gap_p90_ms", "tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("seed", [1, 2, 2**32 + 7])
def test_the_float8_control_fails_where_the_program_passes(
        seed, tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, CONFIG, "tiny-batch")
    monkeypatch.setitem(cell.traffic, "lead_in_steps", 10)
    monkeypatch.setattr(work, "PEAKS", tiny.FIXTURES / "cpu-peaks.json")
    monkeypatch.setattr(trace, "DEVICE_PLANE", r"^/host:CPU$")
    device = harness.device_info(cell.chips, require_tpu=False)
    res = harness.run_cell(cell, seed, 0.4, False, time.perf_counter(),
                           device, tiny.ROOT, tmp_path / "scratch",
                           control=True)
    assert res["program_correct"] and not res["correct"]
    for name in ("served_logit_gap", "served_logit_gap_mean"):
        gap = res["check"][name]
        assert res["program_check"][name]["value"] <= \
            gap["limit"] < gap["value"]


def test_an_altered_token_is_not_correct(tmp_path, monkeypatch):
    orig = serve_stablelm2.build_engine

    def build(*a, **k):
        engine = orig(*a, **k)
        step = engine._jit_step

        def altered(*args):
            """Token 7 always wins."""
            logits, caches = step(*args)
            return logits.at[:, 7].add(1e4), caches

        engine._jit_step = altered
        return engine

    monkeypatch.setattr(serve_stablelm2, "build_engine", build)
    cell = tiny.cell(tmp_path, CONFIG, "tiny-batch")
    res = tiny.run(cell, monkeypatch, tmp_path)
    gap = res["check"]["served_logit_gap"]
    assert not res["correct"] and gap["value"] > gap["limit"]


@pytest.mark.parametrize("variant", [{"rotary_fraction": 1.0},
                                     {"norm": "rms"}],
                         ids=["full_rotary", "rmsnorm"])
def test_serving_another_block_is_not_correct(variant, tmp_path,
                                              monkeypatch):
    """The check sees the block: the program serving full rotary, or
    RMSNorm in place of LayerNorm, on the same weights reads false."""
    orig = serve_stablelm2.program_config
    monkeypatch.setattr(serve_stablelm2, "program_config",
                        lambda doc: dataclasses.replace(orig(doc), **variant))
    cell = tiny.cell(tmp_path, CONFIG, "tiny-batch")
    res = tiny.run(cell, monkeypatch, tmp_path)
    gap = res["check"]["served_logit_gap"]
    assert not res["correct"] and gap["value"] > gap["limit"]


def test_the_engine_serves_the_published_block_on_w8_tables(tmp_path):
    from repro.models import init_model
    from repro.obs import trace as obs_trace

    cell = tiny.cell(tmp_path, CONFIG, "tiny-batch")
    doc = cell.config
    cfg = serve_stablelm2.program_config(doc)
    assert (cfg.norm, cfg.rotary_dims, cfg.hd, cfg.qkv_bias,
            cfg.n_kv_heads, cfg.tie_embeddings) == (
                "layer", 4, 16, True, cfg.n_heads, False)
    d = serve_stablelm2.Dims.from_doc(doc)
    params = serve_stablelm2.make_params(d, 5)
    # the benchmark's weights fill every parameter the program reads
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0))))
    assert float(jnp.abs(params["layers"]["attn"]["bq"]).max()) > 0
    obs_trace.configure(tmp_path / "trace", process_tag="plan")
    try:
        engine = serve_stablelm2.build_engine(doc, params, cell.traffic,
                                              tiny.ROOT)
    finally:
        obs_trace.reset(clear_env=True)
    assert engine.cfg.approx_mlp and engine.cfg.approx_bits == 8
    assert engine._luts.shape == (d.layers, 256, 256)
    events = {s["name"]: s["attrs"]
              for s in obs_trace.read_trace(tmp_path / "trace")}
    assert events["serve.plan"]["lut_bits"] == 8
