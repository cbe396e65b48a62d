"""Fleet invariants: deterministic planning, concurrent-writer safety,
and the end-to-end smoke sweep densifying the frontier."""

import dataclasses
import json
import multiprocessing

import numpy as np
import pytest

from repro.core.arith import benchmark
from repro.core.engine import (
    Candidate,
    SearchJob,
    SearchOutcome,
    UnsoundResultError,
    available_engines,
    get_engine,
    harvest,
    verify_circuit,
)
from repro.core.templates import SharedTemplate, TemplateParams
from repro.fleet import SweepSpec, load_spec, plan_jobs, run_job, run_sweep
from repro.fleet.worker import RECEIPT_DIR
from repro.library import OperatorSignature, OperatorStore, frontier_sizes

SPEC = SweepSpec(
    name="test",
    benchmarks=("adder", "mul"),
    bits=(2,),
    ets=(2,),
    engines=("anneal",),
    budget_s=30.0,
    engine_opts={"anneal": {"steps": 3000, "restarts": 2, "keep": 3}},
)


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------
def test_plan_expansion_is_deterministic_and_seed_stable():
    jobs1 = plan_jobs(SPEC)
    jobs2 = plan_jobs(SPEC)
    assert jobs1 == jobs2
    assert len(jobs1) == 2  # 2 benchmarks x 1 bits x 1 et x 1 engine
    assert [j.benchmark for j in jobs1] == ["adder", "mul"]

    # per-job seeds derive from the job's own fields: adding a benchmark
    # must not reshuffle the seeds of existing jobs
    wider = dataclasses.replace(SPEC, ets=(1, 2))
    by_fields = {(j.benchmark, j.bits, j.et, j.engine): j.seed
                 for j in plan_jobs(wider)}
    for j in jobs1:
        assert by_fields[(j.benchmark, j.bits, j.et, j.engine)] == j.seed

    # a different base seed changes every job seed, nothing else
    reseeded = plan_jobs(dataclasses.replace(SPEC, seed=1))
    assert [(j.benchmark, j.et) for j in reseeded] == [(j.benchmark, j.et) for j in jobs1]
    assert all(a.seed != b.seed for a, b in zip(reseeded, jobs1))


def test_plan_et_fracs_scale_with_operator_range():
    spec = SweepSpec(name="t", benchmarks=("mul",), bits=(2,),
                     et_fracs=(0.25,), engines=("anneal",))
    (job,) = plan_jobs(spec)
    assert job.et == round(0.25 * 9)  # 2-bit mul: max value 3*3
    spec_a = dataclasses.replace(spec, benchmarks=("adder",))
    (job_a,) = plan_jobs(spec_a)
    assert job_a.et == round(0.25 * 6)  # 2-bit adder: max value 3+3


def test_load_spec_rejects_unknown_engine_and_missing_grid():
    with pytest.raises(ValueError, match="unknown engine"):
        SweepSpec(name="t", benchmarks=("mul",), bits=(2,), ets=(1,),
                  engines=("no-such-engine",))
    with pytest.raises(ValueError, match="neither ets nor et_fracs"):
        SweepSpec(name="t", benchmarks=("mul",), bits=(2,),
                  engines=("anneal",))
    assert load_spec("smoke").name == "smoke"
    assert load_spec("smoke", budget_s=1.0).budget_s == 1.0
    with pytest.raises(FileNotFoundError):
        load_spec("no-such-sweep")


# ---------------------------------------------------------------------------
# unified engine layer
# ---------------------------------------------------------------------------
def test_job_key_is_stable_and_field_sensitive():
    j = SearchJob(benchmark="mul", bits=2, et=1, engine="anneal")
    assert j.key() == SearchJob(benchmark="mul", bits=2, et=1,
                                engine="anneal").key()
    assert j.key() != dataclasses.replace(j, et=2).key()
    assert j.signature() == OperatorSignature("mul", 2, "wce", 1)
    assert j.benchmark_name == "mul_i4"


def test_anneal_engine_emits_verified_candidates():
    job = SearchJob(benchmark="adder", bits=2, et=2, engine="anneal",
                    budget_s=20.0, seed=1)
    out = get_engine("anneal", steps=3000, restarts=2).run(job)
    assert isinstance(out, SearchOutcome) and out.engine == "anneal"
    assert out.results, "annealer found nothing at the easy ET"
    exact = benchmark("adder_i4").eval_words().astype(np.int64)
    for cand in out.results:
        assert isinstance(cand, Candidate)
        got = cand.circuit.eval_words().astype(np.int64)
        assert np.abs(got - exact).max() <= 2
    assert out.best.area == min(c.area for c in out.results)


def test_harvest_raises_descriptive_error_on_unsound_params():
    """The shared harvest replaces the old bare asserts: an unsound result
    must name the engine and the measured violation."""
    exact = benchmark("adder_i4")
    tpl = SharedTemplate(exact.n_inputs, exact.n_outputs, pit=2)
    # all-IGNORE products selected everywhere => constant-1 outputs: way off
    params = TemplateParams(
        np.full((2, exact.n_inputs), 2, dtype=np.int8),
        np.ones((exact.n_outputs, 2), dtype=bool),
    )
    with pytest.raises(UnsoundResultError, match="wce .* > ET 0"):
        harvest(tpl, params, exact.eval_words(), 0, engine="test")
    with pytest.raises(UnsoundResultError, match="re-verification"):
        verify_circuit(tpl.instantiate(params), exact.eval_words(), 0)


def test_available_engines_always_include_solver_free_ones():
    names = available_engines()
    for engine in ("tensor", "anneal", "muscat", "mecals"):
        assert engine in names


# ---------------------------------------------------------------------------
# concurrent writers
# ---------------------------------------------------------------------------
def _put_worker(args):
    root, n_inputs, nodes, outputs, name, area_ = args
    from repro.core.circuits import Circuit, Gate, Op

    c = Circuit(n_inputs=n_inputs, name=name)
    c.nodes = [Gate(Op(op), tuple(a)) for op, a in nodes]
    c.outputs = list(outputs)
    store = OperatorStore(root)
    rec = store.put_circuit(c, OperatorSignature("mul", 2, "wce", 2),
                            area=area_, source="muscat")
    return rec.key


def test_concurrent_puts_of_same_netlist_are_idempotent(tmp_path):
    """Two workers committing the same netlist into one store must land
    exactly one record, never torn JSON."""
    from repro.core.baselines import muscat_like

    res = muscat_like(benchmark("mul_i4"), et=2, restarts=1, wall_budget_s=5)
    payload = (str(tmp_path / "lib"), res.circuit.n_inputs,
               [[g.op.value, list(g.args)] for g in res.circuit.nodes],
               list(res.circuit.outputs), res.circuit.name, res.area)
    # spawn, not fork: the pytest process has jax (multithreaded) loaded,
    # and fork-with-threads can deadlock — same trap run_sweep dodges
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(2) as pool:
        keys = pool.map(_put_worker, [payload] * 4)
    assert len(set(keys)) == 1
    store = OperatorStore(tmp_path / "lib")
    assert len(store) == 1
    # and the published record parses cleanly
    (rec,) = store.records(OperatorSignature("mul", 2, "wce", 2))
    assert rec.key == keys[0] and rec.wce <= 2
    # no leftover temp files
    assert not list((tmp_path / "lib").rglob("*.tmp"))


# ---------------------------------------------------------------------------
# end-to-end sweep
# ---------------------------------------------------------------------------
def test_smoke_sweep_densifies_frontier_and_resumes_as_noop(tmp_path):
    lib = tmp_path / "lib"
    results = run_sweep(SPEC, lib, workers=0, log=lambda *_: None)
    assert all(r.status == "ok" for r in results)
    store = OperatorStore(lib)
    sizes = frontier_sizes(store)
    assert len(sizes) >= 2, sizes        # >= 2 distinct signatures populated
    assert all(front >= 1 for _, front in sizes.values())
    n_records = len(store)
    assert n_records > 0

    # receipts were written and a re-run is a complete no-op
    receipts = list((lib / RECEIPT_DIR).glob("*.json"))
    assert len(receipts) == len(results)
    assert all(json.loads(p.read_text())["status"] == "ok" for p in receipts)
    again = run_sweep(SPEC, lib, workers=0, log=lambda *_: None)
    assert all(r.status == "skipped" for r in again)
    assert len(store) == n_records

    # even without receipts the searches are deterministic: same netlists,
    # same content keys, 0 new records
    for p in receipts:
        p.unlink()
    rerun = run_sweep(SPEC, lib, workers=0, log=lambda *_: None)
    assert all(r.status == "ok" for r in rerun)
    assert len(store) == n_records

    # changed engine options must re-run the jobs, not skip on receipts
    deeper = dataclasses.replace(SPEC, engine_opts={
        "anneal": {"steps": 3500, "restarts": 2, "keep": 3}})
    assert all(r.status == "ok"
               for r in run_sweep(deeper, lib, workers=0, log=lambda *_: None))


def test_failed_job_writes_receipt_and_is_retried(tmp_path):
    job = SearchJob(benchmark="mul", bits=2, et=1, engine="shared",
                    budget_s=1.0)
    from repro.core.miter import HAVE_Z3

    if HAVE_Z3:
        pytest.skip("needs a z3-less image to exercise the failure path")
    res = run_job(job, tmp_path / "lib")
    assert res.status == "failed" and "z3" in res.error
    from repro.fleet.worker import _receipt_path

    doc = json.loads(_receipt_path(tmp_path / "lib", job, {}).read_text())
    assert doc["status"] == "failed"
    # failed receipts do not block a retry
    assert run_job(job, tmp_path / "lib").status == "failed"


def test_fleet_cli_reports_densification(tmp_path, capsys):
    from repro.fleet.__main__ import main

    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps({
        "name": "cli-test",
        "benchmarks": ["adder"],
        "bits": [2],
        "ets": [2],
        "engines": ["anneal"],
        "budget_s": 20.0,
        "engine_opts": {"anneal": {"steps": 3000, "restarts": 2, "keep": 3}},
    }))
    rc = main(["--library", str(tmp_path / "lib"), "--sweep", str(spec_file),
               "--workers", "0", "--min-new", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "frontier densification" in out
    assert "adder2b_wce2" in out


def _cli_spec(tmp_path, engines):
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps({
        "name": "cli-fail", "benchmarks": ["adder"], "bits": [2],
        "ets": [2], "engines": engines, "budget_s": 20.0,
        "engine_opts": {"anneal": {"steps": 500, "restarts": 1,
                                   "keep": 1}},
    }))
    return ["--library", str(tmp_path / "lib"), "--sweep", str(spec_file),
            "--workers", "0"]


def test_fleet_cli_exits_nonzero_when_a_job_fails(tmp_path, monkeypatch,
                                                   capsys):
    """A job that raises (say, a kernel the chip's compiler refuses) is
    receipted as failed, and the sweep's exit status says so."""
    from repro.fleet import worker
    from repro.fleet.__main__ import main

    class Refused:
        def run(self, job):
            raise RuntimeError("kernel refused")

    monkeypatch.setattr(worker, "get_engine", lambda name, **kw: Refused())
    assert main(_cli_spec(tmp_path, ["anneal"])) == 1
    assert "1 job(s) failed" in capsys.readouterr().err


def test_fleet_cli_dropped_engine_is_not_a_failure(tmp_path, capsys):
    from repro.core.miter import HAVE_Z3
    from repro.fleet.__main__ import main

    if HAVE_Z3:
        pytest.skip("needs a z3-less image, where the SMT engine is dropped")
    assert main(_cli_spec(tmp_path, ["shared", "anneal"])) == 0
    assert "skipping 1 job(s) on unavailable engines" in \
        capsys.readouterr().out
