#!/usr/bin/env python3
"""Smoke test of the system's two main paths on a TPU.

    python chip_smoke.py [--seed 0] [--out DIR]     # one chip
    python chip_smoke.py --four-chips               # four chips

One chip, in order:

1. the device: a TPU, or the script fails before doing anything;
2. search: ``repro.fleet.run_sweep`` fills a fresh operator library with
   the ``smoke`` sweep (its tensor jobs run the ``template_eval`` kernel),
   then the kernel scores one paper-scale population bit-exactly against
   the jnp reference;
3. kernels: the LUT matmul at the Qwen3-4B MLP shapes (up/gate and down,
   M = the serve's slot count), W4 on a searched table and W8 on a
   composed one, integer-exact against the reference;
4. serving: Qwen3-4B at its published widths (random bf16 weights from
   ``--seed``) through ``ContinuousServingEngine``, built as
   ``python -m repro.launch.serve --continuous --library DIR --width 4``
   builds it, on a plan that puts searched operators on some MLP layers.
   Every request must complete, the decode step must trace once and hold
   the LUT kernel (``tpu_custom_call``).  Times and memory are printed
   for information only.

``--four-chips`` runs only the path that spans chips: the fleet search's
population scorer sharded over four devices, compared with the same
population scored on one, then one sharded ``tensor_search`` loop.

Everything is made from the seed inside ``--out`` (default
``.chip_smoke/`` in this checkout), in this one process: a chip
belongs to one process at a time.  Any failed check exits non-zero.  Only
when every phase passed is the last line of standard output
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen3-4b"
SLOTS, PROMPT_LEN, GEN_LEN, PROMPT_DIST = 4, 64, 16, "uniform:16-64"
POPULATION, PIT = 4096, 16  # paper-scale template_eval population


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d.platform == "tpu", f"no TPU: JAX runs on {d.platform!r}")
    check(len(devs) >= n_chips, f"needs {n_chips} chip(s), has {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def import_repro():
    """The package of this checkout, never one from elsewhere."""
    sys.path.insert(0, str(ROOT / "src"))
    import repro

    where = [Path(p).resolve() for p in repro.__path__]
    check(all(p.is_relative_to(ROOT / "src") for p in where),
          f"repro imported from {where}, not this checkout")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")


def population(seed: int, P: int, T: int, n: int, m: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lits = rng.integers(0, 3, (P, T, n)).astype(np.int32)
    sel = (rng.random((P, m, T)) < 0.3).astype(np.int32)
    return lits, sel


def same(got, want) -> bool:
    import numpy as np

    return all(np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in zip(got, want))


def phase_search(lib: Path, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.arith import benchmark
    from repro.core.circuits import input_truth_tables
    from repro.fleet import load_spec, run_sweep
    from repro.kernels import ops

    if lib.exists():
        shutil.rmtree(lib)   # the library this script builds, rebuilt
    t = time.perf_counter()
    results = run_sweep(load_spec("smoke", seed=seed), lib, workers=1)
    failed = [r for r in results if r.status != "ok"]
    check(not failed, f"{len(failed)} fleet job(s) failed: "
          + "; ".join(f"{r.job.describe()}: {r.error}" for r in failed))
    check(any(r.job.engine == "tensor" for r in results),
          "the sweep ran no tensor job")
    print(f"phase search: {len(results)} job(s) ok, "
          f"{sum(r.n_results for r in results)} result(s), "
          f"{time.perf_counter() - t:.1f} s", flush=True)

    exact = benchmark("mul_i8")
    lits, sel = population(seed, POPULATION, PIT, exact.n_inputs,
                           exact.n_outputs)
    args = (jnp.asarray(lits), jnp.asarray(sel),
            jnp.asarray(input_truth_tables(exact.n_inputs)),
            jnp.asarray(exact.eval_words().astype(np.int32)))
    got = ops.template_eval(*args, backend="pallas")
    check(same(got, ops.template_eval(*args, backend="ref")),
          "template_eval kernel differs from the reference")
    print(f"phase search: template_eval P={POPULATION} T={PIT} "
          f"n={exact.n_inputs} matches the reference", flush=True)


def phase_kernels(lib: Path, seed: int, cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.precision.plans import load_frontier

    key = jax.random.PRNGKey(seed)
    for bits in (4, 8):
        compiled, _, _ = load_frontier(lib, bits)
        check(bool(compiled), f"no W{bits} operator in the library")
        rec, comp = compiled[0]
        lut = jnp.asarray(comp.lut)
        for name, (K, N) in (("up/gate", (cfg.d_model, cfg.d_ff)),
                             ("down", (cfg.d_ff, cfg.d_model))):
            key, ka, kb = jax.random.split(key, 3)
            a = jax.random.randint(ka, (SLOTS, K), 0, comp.side, jnp.int32)
            b = jax.random.randint(kb, (K, N), 0, comp.side, jnp.int32)
            got = ops.approx_matmul(a, b, lut, backend="pallas")
            want = ops.approx_matmul(a, b, lut, backend="ref")
            check(bool(jnp.array_equal(got, want)),
                  f"W{bits} {name} LUT matmul differs from the reference")
            print(f"phase kernels: W{bits} {name} ({SLOTS}x{K})·({K}x{N}) "
                  f"on operator {rec.key[:12]} matches the reference",
                  flush=True)


def phase_serve(lib: Path, seed: int, cfg) -> None:
    import jax
    import numpy as np

    from repro import parallel
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.serve import library_frontier, startup_plan
    from repro.models import init_model
    from repro.precision.plans import select_width
    from repro.serving import (ContinuousServingEngine, Telemetry,
                               make_profile, parse_prompt_dist)

    width = select_width(cfg, requested=4)
    cfg = cfg.with_approx_mlp(bits=width.bits)
    compiled, exact_area, _ = library_frontier(lib, width)
    plan = startup_plan(cfg, compiled, exact_area)   # serve's default budget
    n_approx = sum(c.key is not None for c in plan.choices)
    check(n_approx >= 1, "the default budget downgrades no MLP layer")
    profile = make_profile(
        "steady", ticks=1, per_tick=SLOTS, prompt_len=PROMPT_LEN,
        gen_len=GEN_LEN, prompt_dist=parse_prompt_dist(PROMPT_DIST,
                                                       PROMPT_LEN))
    mesh = make_smoke_mesh()
    with parallel.activate(mesh), mesh:
        t = time.perf_counter()
        params = jax.jit(init_model, static_argnums=0)(
            cfg, jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
        init_s = time.perf_counter() - t
        engine = ContinuousServingEngine(
            cfg, params, max_slots=SLOTS, prompt_len=PROMPT_LEN,
            gen_len=GEN_LEN, plan=plan, compiled=compiled,
            exact_area=exact_area)
        marks = []
        t0 = time.perf_counter()
        tel = engine.serve(profile, telemetry=Telemetry(), seed=seed,
                           on_step_end=lambda *_: marks.append(
                               time.perf_counter()))
        wall = time.perf_counter() - t0
        traces = engine.trace_count
        text = engine.lowered_step().as_text()

    done = engine.completions
    check(len(done) == profile.total_requests,
          f"{len(done)}/{profile.total_requests} requests completed")
    check(all(len(g) == GEN_LEN for g in done.values()),
          "a request generated the wrong number of tokens")
    check(traces == 1, f"decode step traced {traces}x")
    check("tpu_custom_call" in text,
          "decode step holds no tpu_custom_call: LUT kernel not used")
    s = tel.summary()
    steps = np.diff([t0] + marks)
    mem = jax.devices()[0].memory_stats() or {}
    print(f"phase serve: {cfg.name} {cfg.n_layers}L d={cfg.d_model} "
          f"ff={cfg.d_ff}, W{width.bits} plan with {n_approx}/"
          f"{cfg.n_layers} MLP layers on searched operators, "
          f"{len(done)}/{profile.total_requests} requests done, "
          f"decode step traced {traces}x", flush=True)
    print(f"info: init {init_s:.1f} s, first step (compile included) "
          f"{steps[0]:.1f} s, median step {np.median(steps[1:]) * 1e3:.1f} "
          f"ms over {len(steps)} steps, serve wall {wall:.1f} s")
    print(f"info: ttft_ms {s.get('ttft_ms')}, decode {s['decode_tok_s']} "
          f"tok/s, peak_bytes_in_use {mem.get('peak_bytes_in_use')}",
          flush=True)


def phase_four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.arith import benchmark
    from repro.core.circuits import input_truth_tables
    from repro.core.tensor_search import population_scorer, tensor_search
    from repro.launch.mesh import make_fleet_mesh

    mesh = make_fleet_mesh()
    check(mesh.size == 4, f"fleet mesh spans {mesh.size} device(s), not 4")
    exact = benchmark("mul_i8")
    in_tt = jnp.asarray(input_truth_tables(exact.n_inputs))
    ev = jnp.asarray(exact.eval_words().astype(np.int32))
    lits, sel = population(seed, POPULATION, PIT, exact.n_inputs,
                           exact.n_outputs)
    one = jax.devices()[0]
    want = jax.jit(population_scorer(in_tt, ev))(
        jax.device_put(lits, one), jax.device_put(sel, one))
    split = NamedSharding(mesh, PartitionSpec("data"))
    got = jax.jit(population_scorer(in_tt, ev, mesh))(
        jax.device_put(lits, split), jax.device_put(sel, split))
    check(len(got[0].sharding.device_set) == 4,
          "sharded scores do not span four devices")
    check(same(got, want), "sharded template_eval differs from one chip")
    print(f"phase four-chips: template_eval of P={POPULATION} split over "
          f"4 chips equals one chip", flush=True)

    # an ET loose enough that 8 generations reach sound candidates, whose
    # exhaustive re-verification then cross-checks the kernel's scores
    generations, et = 8, 96
    t = time.perf_counter()
    out = tensor_search(exact, et, population=POPULATION,
                        generations=generations, seed=seed, keep=4,
                        mesh=mesh)
    check(out.stats["generations"] == generations,
          f"tensor_search ran {out.stats['generations']} generation(s)")
    check(bool(out.results), f"no sound candidate at ET {et}")
    print(f"phase four-chips: sharded tensor_search {generations} "
          f"generations, {len(out.results)} verified result(s), "
          f"{time.perf_counter() - t:.1f} s", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=ROOT / ".chip_smoke",
                    help="where the library is built")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the search sharded over four chips")
    args = ap.parse_args(argv)
    try:
        device = device_info(4 if args.four_chips else 1)
        import_repro()
        if args.four_chips:
            phase_four_chips(args.seed)
        else:
            from repro.configs import get_config

            cfg = get_config(ARCH)
            lib = args.out / "lib"
            phase_search(lib, args.seed)
            phase_kernels(lib, args.seed, cfg)
            phase_serve(lib, args.seed, cfg)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
