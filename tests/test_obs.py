"""Observability plane: histogram quantile exactness, deterministic span
traces, cross-process merge idempotence, exporters, the instrumented
telemetry, the trace-inspector CLI gates, and the classed serve e2e
(per-class p95 present, decode traced once, spans in the trace dir)."""

import json

import numpy as np
import pytest

from repro.core.arith import benchmark
from repro.core.circuits import Circuit, Op
from repro.core.synth import area
from repro.library import OperatorSignature, OperatorStore
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.export import dump_metrics, prometheus_text, read_metrics
from repro.obs.metrics import Histogram, MetricRegistry
from repro.obs.trace import Tracer, read_trace


@pytest.fixture(autouse=True)
def _isolate_obs_globals():
    """Every test gets a pristine global tracer and registry."""
    obs_trace.reset()
    prev = obs_metrics.set_registry(MetricRegistry())
    yield
    obs_trace.reset()
    obs_metrics.set_registry(prev)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_histogram_quantiles_match_numpy_while_exact():
    h = Histogram(buckets=(0.5, 1.0, 5.0, 10.0))
    rng = np.random.default_rng(7)
    vals = rng.uniform(0.01, 12.0, size=500).tolist()
    for v in vals:
        h.observe(v)
    assert h.exact
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(
            np.percentile(vals, q * 100), rel=1e-12)
    ps = h.percentiles()
    assert set(ps) == {"p50", "p95", "p99"}
    assert h.mean == pytest.approx(np.mean(vals))
    assert h.min == min(vals) and h.max == max(vals)


def test_histogram_bucket_counts_and_wrapped_quantiles():
    h = Histogram(buckets=(1.0, 2.0, 4.0), max_samples=4)
    vals = [0.5, 1.5, 3.0, 3.5, 5.0, 8.0, 0.2, 1.1]
    for v in vals:
        h.observe(v)
    # bucket counts stay exact regardless of the reservoir
    assert h.counts == [2, 2, 2, 2]   # <=1, <=2, <=4, overflow
    assert h.count == len(vals) and not h.exact
    # wrapped quantiles degrade to bucket interpolation but stay bounded
    for q in (0.1, 0.5, 0.9):
        assert h.min <= h.quantile(q) <= h.max
    assert h.quantile(0.0) >= h.min and h.quantile(1.0) <= h.max


def test_histogram_empty_and_bad_quantile():
    h = Histogram(buckets=(1.0,))
    assert h.quantile(0.5) is None and h.mean == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram(buckets=())


def test_registry_kind_conflicts_and_find():
    reg = MetricRegistry()
    reg.counter("jobs", engine="anneal").inc(3)
    with pytest.raises(TypeError):
        reg.gauge("jobs", engine="anneal")
    assert reg.find("jobs", engine="anneal").value == 3
    assert reg.find("jobs", engine="tensor") is None
    assert reg.with_name("jobs")[0][0] == {"engine": "anneal"}
    with pytest.raises(ValueError):
        reg.counter("jobs", engine="anneal").inc(-1)


def test_snapshot_merge_semantics():
    a, b = MetricRegistry(), MetricRegistry()
    for reg, n, depth in ((a, 2, 5), (b, 3, 9)):
        reg.counter("jobs").inc(n)
        reg.gauge("depth").set(depth)
        h = reg.histogram("lat", buckets=(1.0, 10.0))
        for v in (0.5, 2.0):
            h.observe(v * n)
    merged = MetricRegistry.from_snapshots([a.snapshot(), b.snapshot()])
    assert merged.find("jobs").value == 5           # counters sum
    assert merged.find("depth").value == 9          # gauges keep the max
    h = merged.find("lat")
    assert h.count == 4 and h.sum == pytest.approx(1.0 + 4.0 + 1.5 + 6.0)
    # merging histograms with different buckets is refused, not mangled
    c = MetricRegistry()
    c.histogram("lat", buckets=(2.0, 20.0)).observe(1.0)
    with pytest.raises(ValueError):
        merged.merge(c.snapshot())


def test_prometheus_text_format_and_escaping():
    reg = MetricRegistry()
    reg.counter("fleet_jobs", engine='an"ne\\al\n').inc(2)
    reg.gauge("depth", **{"class": "gold"}).set(4)
    h = reg.histogram("lat_ms", buckets=(1.0, 5.0), **{"class": "gold"})
    for v in (0.5, 3.0, 9.0):
        h.observe(v)
    text = prometheus_text(reg)
    assert "# TYPE fleet_jobs_total counter" in text
    assert 'engine="an\\"ne\\\\al\\n"' in text     # escaped label value
    assert 'depth{class="gold"} 4' in text
    # cumulative buckets + +Inf + sum/count triplet
    assert 'lat_ms_bucket{class="gold",le="1"} 1' in text
    assert 'lat_ms_bucket{class="gold",le="5"} 2' in text
    assert 'lat_ms_bucket{class="gold",le="+Inf"} 3' in text
    assert 'lat_ms_count{class="gold"} 3' in text


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------
def _fixed_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return clock


def test_span_nesting_and_deterministic_ids(tmp_path):
    def run(root):
        tr = Tracer(root, clock=_fixed_clock(), process_tag="w0")
        with tr.span("fleet.job", engine="anneal") as outer:
            with tr.span("search.run"):
                pass
            outer.set(status="ok")
        tr.event("serve.swap", reason="qos-load")
        tr.close()
        return read_trace(root)

    spans_a = run(tmp_path / "a")
    spans_b = run(tmp_path / "b")
    # injected clock + pinned tag -> byte-identical traces across runs
    assert spans_a == spans_b
    by_name = {s["name"]: s for s in spans_a}
    assert by_name["search.run"]["parent"] == by_name["fleet.job"]["id"]
    assert by_name["serve.swap"]["parent"] is None
    assert by_name["fleet.job"]["attrs"] == {"engine": "anneal",
                                             "status": "ok"}
    assert by_name["fleet.job"]["dur_s"] == pytest.approx(3.0)
    assert len({s["id"] for s in spans_a}) == 3


def test_trace_merge_is_idempotent_and_skips_torn_lines(tmp_path):
    tr = Tracer(tmp_path, clock=_fixed_clock(), process_tag="w0")
    for i in range(3):
        tr.event("tick", i=i)
    tr.close()
    spans = read_trace(tmp_path)
    assert len(spans) == 3
    # a crashed writer tears at most the trailing line; reader skips it
    src = tmp_path / "spans-w0.jsonl"
    with open(src, "a") as f:
        f.write('{"name": "torn", "id": "zz')
    # a re-copied file (same span ids) must not double anything
    (tmp_path / "spans-w0-copy.jsonl").write_text(src.read_text())
    assert read_trace(tmp_path) == spans


def test_trace_merge_spans_rotated_segments_across_tags(tmp_path):
    """Two writers (a router's engines, a fleet's workers) rotating into
    one trace dir: the merge unions every active AND rotated segment of
    every tag, stays deduped under re-copied rotated files, and keeps
    the global (t0, id) order."""
    clock = _fixed_clock()
    writers = {name: Tracer(tmp_path, clock=clock, process_tag=name,
                            max_segment_bytes=256)   # a few lines/segment
               for name in ("eng-a", "eng-b")}
    for i in range(30):
        writers["eng-a" if i % 2 == 0 else "eng-b"].event(
            "tick", i=i, pad="x" * 32)
    for tr in writers.values():
        tr.close()

    # both tags actually rotated — otherwise the test is vacuous
    for name in writers:
        rotated = list(tmp_path.glob(f"spans-{name}.*.jsonl"))
        assert rotated, f"{name} never rotated"
        assert (tmp_path / f"spans-{name}.jsonl").exists()

    spans = read_trace(tmp_path)
    assert [s["attrs"]["i"] for s in spans] == list(range(30))
    assert len({s["id"] for s in spans}) == 30

    # re-copying a rotated segment (backup restore, scp -r twice) must
    # not double its spans
    seg = sorted(tmp_path.glob("spans-eng-a.*.jsonl"))[0]
    (tmp_path / "spans-eng-a-restored.jsonl").write_text(seg.read_text())
    assert read_trace(tmp_path) == spans


def test_global_tracer_configure_and_noop(tmp_path):
    # unconfigured: spans are free no-ops, handles still accept set()
    assert not obs_trace.tracing_enabled()
    with obs_trace.span("x") as sp:
        sp.set(ok=True)
    obs_trace.event("y")
    assert list(tmp_path.glob("spans-*.jsonl")) == []

    import os
    obs_trace.configure(tmp_path, clock=_fixed_clock(), process_tag="t")
    assert os.environ[obs_trace.TRACE_DIR_ENV] == str(tmp_path)
    with obs_trace.span("job"):
        pass
    assert [s["name"] for s in read_trace(tmp_path)] == ["job"]
    obs_trace.reset()
    assert os.environ.get(obs_trace.TRACE_DIR_ENV) is None


def _profiled(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` trace; returns the
    ``serve.*`` host events by name and the trace's start (ns, wall)."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    env = next(p for p in data.planes if p.name == "Task Environment")
    events = {e.name: e for p in data.planes for line in p.lines
              for e in line.events if e.name.startswith("serve.")}
    return events, dict(env.stats)["profile_start_time"]


def test_spans_reach_the_profiler_on_the_jsonl_clock(tmp_path):
    obs_trace.configure(tmp_path / "spans", process_tag="t")

    def body():
        with obs_trace.span("serve.test", step=3, who="a") as sp:
            sp.set(rows=2)
            with obs_trace.span("serve.test.child"):
                pass
        obs_trace.event("serve.mark", n=1)

    events, start_ns = _profiled(tmp_path, body)
    assert dict(events["serve.test"].stats) == {"step": 3, "who": "a",
                                                "rows": 2}
    assert dict(events["serve.mark"].stats) == {"n": 1}
    parent, child = events["serve.test"], events["serve.test.child"]
    assert parent.start_ns <= child.start_ns
    assert child.start_ns + child.duration_ns <= \
        parent.start_ns + parent.duration_ns
    jsonl = {s["name"]: s for s in read_trace(tmp_path / "spans")}
    assert jsonl["serve.test"]["attrs"] == {"step": 3, "who": "a", "rows": 2}
    for name in ("serve.test", "serve.test.child", "serve.mark"):
        t0_profiler = (start_ns + events[name].start_ns) * 1e-9
        assert abs(t0_profiler - jsonl[name]["t0"]) < 1e-3


def test_spans_reach_the_profiler_with_the_jsonl_tracer_off(tmp_path):
    assert not obs_trace.tracing_enabled()

    def body():
        with obs_trace.span("serve.alone", k=5) as sp:
            sp.set(rows=4)
        assert obs_trace.event("serve.alone.mark") == ""

    events, _ = _profiled(tmp_path, body)
    assert dict(events["serve.alone"].stats) == {"k": 5, "rows": 4}
    assert "serve.alone.mark" in events
    assert list(tmp_path.glob("**/spans-*.jsonl")) == []


def test_obs_trace_needs_no_jax():
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(obs_trace.__file__).resolve().parents[2])
    env = dict(os.environ, PYTHONPATH=src)
    code = ("import sys\n"
            "from repro.obs import trace\n"
            "with trace.span('serve.x', a=1) as sp:\n"
            "    sp.set(b=2)\n"
            "trace.event('serve.y')\n"
            "assert 'jax' not in sys.modules, 'obs.trace imported jax'\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, env=env)
    assert out.returncode == 0, out.stderr


def test_metric_snapshots_roundtrip_through_trace_dir(tmp_path):
    reg = MetricRegistry()
    reg.counter("jobs", engine="anneal").inc(4)
    dump_metrics(tmp_path, reg, tag="w0")
    reg2 = MetricRegistry()
    reg2.counter("jobs", engine="anneal").inc(1)
    dump_metrics(tmp_path, reg2, tag="w1")
    merged = read_metrics(tmp_path)
    assert merged.find("jobs", engine="anneal").value == 5


# ---------------------------------------------------------------------------
# telemetry on the metric core
# ---------------------------------------------------------------------------
def _record_batches(tel, n, *, cls=None, decode_s=0.2):
    for b in range(n):
        tel.record_batch(batch=b, tick=b, n_requests=2, prefill_s=0.1,
                         decode_s=decode_s, prefill_tokens=8,
                         decode_tokens=16, decode_steps=8, plan_id="p",
                         drift=0.01, qos_class=cls)


def test_telemetry_per_class_percentiles_and_isolation():
    from repro.serving.telemetry import Telemetry

    tel = Telemetry()
    _record_batches(tel, 4, cls="gold", decode_s=0.08)
    _record_batches(tel, 4, cls="batch", decode_s=0.8)
    s = tel.summary()
    assert s["batches"] == 8 and set(s["classes"]) == {"gold", "batch"}
    gold, batch = s["classes"]["gold"], s["classes"]["batch"]
    for row in (gold, batch):
        for k in ("p50_ms_per_step", "p95_ms_per_step", "p99_ms_per_step"):
            assert k in row
    assert gold["p95_ms_per_step"] == pytest.approx(10.0)
    assert batch["p95_ms_per_step"] == pytest.approx(100.0)
    assert s["latency_ms_per_step"]["p99"] <= 100.0
    # two Telemetry instances never share counters
    assert Telemetry().summary()["batches"] == 0


def test_telemetry_dump_is_atomic_and_creates_parents(tmp_path):
    from repro.serving.telemetry import Telemetry

    tel = Telemetry(capacity=2)
    _record_batches(tel, 5)
    tel.record_queue("gold", 3, [0.01, 0.02])
    out = tmp_path / "deep" / "nested" / "tele.json"
    doc = tel.dump(out)
    on_disk = json.loads(out.read_text())
    assert on_disk == json.loads(json.dumps(doc))
    assert len(on_disk["events"]) == 2                 # ring stayed bounded
    assert on_disk["summary"]["batches"] == 5          # counters did not
    # no leftover temp files from the atomic write
    assert [p.name for p in out.parent.iterdir()] == ["tele.json"]
    assert tel.registry.find("serve_queue_depth",
                             **{"class": "gold"}).value == 3
    assert tel.registry.find("serve_queue_delay_ms",
                             **{"class": "gold"}).count == 2


def test_class_scheduler_backoff_metrics():
    from repro.sensitivity.classes import ClassBook, ClassScheduler

    class _Plan:
        def __init__(self, p):
            self.predicted_total = p

    class _Ladder:
        plans = [_Plan(0.0), _Plan(0.1), _Plan(0.5)]

        def __len__(self):
            return len(self.plans)

    reg = MetricRegistry()
    s = ClassScheduler(ClassBook.parse("gold:0.2,batch:2.0"), _Ladder(),
                       relax_patience=1, registry=reg)
    assert s.observe("gold", 10.0)     # overrun -> tighten
    assert reg.find("class_backoff_moves_total", move="tighten",
                    **{"class": "gold"}).value == 1
    assert reg.find("class_backoff_level", **{"class": "gold"}).value == 1
    assert s.observe("gold", 0.0)      # calm -> relax
    assert reg.find("class_backoff_moves_total", move="relax",
                    **{"class": "gold"}).value == 1
    assert reg.find("class_backoff_level", **{"class": "gold"}).value == 0


# ---------------------------------------------------------------------------
# instrumented search + fleet
# ---------------------------------------------------------------------------
def test_fleet_job_spans_and_receipt_timing(tmp_path):
    from repro.core.engine import SearchJob
    from repro.fleet.worker import RECEIPT_DIR, run_job

    trace_dir = tmp_path / "trace"
    obs_trace.configure(trace_dir, process_tag="w0")
    job = SearchJob("adder", 2, 1, "anneal", budget_s=5.0)
    res = run_job(job, tmp_path / "lib",
                  engine_opts={"anneal": {"steps": 300, "restarts": 1}})
    assert res.status == "ok" and res.stats["steps"] > 0

    receipts = list((tmp_path / "lib" / RECEIPT_DIR).glob("*.json"))
    assert len(receipts) == 1
    receipt = json.loads(receipts[0].read_text())
    assert receipt["engine_s"] > 0 and receipt["commit_s"] >= 0
    assert receipt["wall_s"] >= receipt["engine_s"]

    spans = {s["name"]: s for s in read_trace(trace_dir)}
    fj = spans["fleet.job"]
    assert fj["attrs"]["engine"] == "anneal"
    assert fj["attrs"]["status"] == "ok"
    assert fj["attrs"]["key"] == job.key()
    assert spans["search.run"]["parent"] == fj["id"]   # nested
    # the worker flushed its metric snapshot into the trace dir
    merged = read_metrics(trace_dir)
    assert merged.find("fleet_jobs_total", engine="anneal",
                       status="ok").value == 1
    assert merged.find("search_evaluations_total",
                       engine="anneal").value > 0


def test_smt_outcome_carries_solver_time():
    z3 = pytest.importorskip("z3")
    from repro.core.engine import SearchJob, get_engine

    out = get_engine("shared").run(
        SearchJob("adder", 2, 1, "shared", budget_s=20.0))
    assert out.stats["grid_points_tried"] > 0
    assert out.stats["smt_solve_s"] > 0
    assert out.stats["smt_solve_s"] <= out.wall_s


# ---------------------------------------------------------------------------
# the inspector CLI
# ---------------------------------------------------------------------------
def _seed_trace(trace_dir):
    tr = Tracer(trace_dir, clock=_fixed_clock(), process_tag="w0")
    with tr.span("fleet.job", engine="anneal", n_results=3):
        pass
    tr.close()
    reg = MetricRegistry()
    from repro.obs.__main__ import MS_PER_STEP_METRIC
    for cls, v in (("_all", 2.0), ("gold", 1.0), ("gold", 3.0)):
        reg.histogram(MS_PER_STEP_METRIC, **{"class": cls}).observe(v)
    dump_metrics(trace_dir, reg, tag="w0")


def test_obs_cli_summary_and_gates(tmp_path, capsys):
    from repro.obs.__main__ import main

    _seed_trace(tmp_path)
    rc = main(["summary", "--trace", str(tmp_path),
               "--require-span", "fleet.job",
               "--require-class-latency"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "fleet.job" in out and "gold" in out
    assert "anneal" in out     # per-engine table

    # missing span -> gate fails
    assert main(["summary", "--trace", str(tmp_path),
                 "--require-span", "serve.decode"]) == 1
    # count-qualified gate
    assert main(["summary", "--trace", str(tmp_path),
                 "--require-span", "fleet.job=2"]) == 1
    assert main(["summary", "--trace", str(tmp_path),
                 "--require-span", "fleet.job=1"]) == 0
    # nonexistent dir -> usage error
    assert main(["summary", "--trace", str(tmp_path / "nope")]) == 2


def test_obs_cli_prom_and_slowest(tmp_path, capsys):
    from repro.obs.__main__ import main

    _seed_trace(tmp_path)
    assert main(["prom", "--trace", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "serve_ms_per_step" in out and 'class="gold"' in out
    assert main(["slowest", "--trace", str(tmp_path),
                 "--name", "fleet"]) == 0
    assert "fleet.job" in capsys.readouterr().out


def test_empty_class_latency_gate_fails(tmp_path):
    from repro.obs.__main__ import main

    tr = Tracer(tmp_path, clock=_fixed_clock(), process_tag="w0")
    tr.event("fleet.job")
    tr.close()
    assert main(["summary", "--trace", str(tmp_path),
                 "--require-class-latency"]) == 1


# ---------------------------------------------------------------------------
# classed serve e2e: spans + per-class p95 + single trace
# ---------------------------------------------------------------------------
def _trunc_mul2() -> Circuit:
    c = Circuit.empty(4, "trunc_mul2")
    a0, a1, b0, b1 = 0, 1, 2, 3
    p0 = c.add(Op.AND, a0, b0)
    p1 = c.add(Op.XOR, c.add(Op.AND, a1, b0), c.add(Op.AND, a0, b1))
    z = c.const(False)
    for out in (p0, p1, z, z):
        c.mark_output(out)
    return c


def test_e2e_classed_serve_traces_and_percentiles(tmp_path):
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.library.compile import load_mul_frontier
    from repro.models import init_model
    from repro.obs.__main__ import main as obs_main
    from repro.sensitivity.classes import ClassBook, ClassScheduler
    from repro.serving import PlanLadder, ServingEngine, Telemetry, steady

    lib = tmp_path / "lib"
    store = OperatorStore(lib)
    exact = benchmark("mul_i4")
    exact_vals = exact.eval_words().astype(np.int64)
    for circ in (exact, _trunc_mul2()):
        wce = int(np.abs(circ.eval_words().astype(np.int64)
                         - exact_vals).max())
        store.put_circuit(circ, OperatorSignature("mul", 2, "wce",
                                                  max(1, wce)),
                          area=area(circ), source="test")
    compiled, exact_area, _ = load_mul_frontier(lib)

    trace_dir = tmp_path / "trace"
    obs_trace.configure(trace_dir, process_tag="serve")

    cfg = get_config("gemma3-1b", reduced=True).with_approx_mlp()
    params = init_model(cfg, jax.random.PRNGKey(0))
    ladder = PlanLadder.build(compiled, cfg.n_layers,
                              exact_area=exact_area, levels=4)
    scheduler = ClassScheduler(ClassBook.parse("gold:1e9,batch:1e9"),
                               ladder, shadow_every=2)
    engine = ServingEngine(cfg, params, batch=2, prompt_len=4, gen_len=4,
                           plan=ladder.plan(0), compiled=compiled,
                           exact_area=exact_area)
    profile = steady(4, 3, prompt_len=4, gen_len=4,
                     class_mix=(("gold", 0.5), ("batch", 0.5)))
    tel = engine.serve(profile, scheduler=scheduler, telemetry=Telemetry())

    # the one-trace invariant holds with spans enabled
    assert engine.trace_count == 1
    s = tel.summary()
    assert s["batches"] > 0
    for row in s["classes"].values():
        assert "p95_ms_per_step" in row and row["p95_ms_per_step"] > 0
        assert row["p95_ms_per_step"] >= row["p50_ms_per_step"]

    # spans landed: one serve.batch/prefill/decode per batch
    obs_trace.reset(clear_env=True)
    spans = read_trace(trace_dir)
    counts = {}
    for sp in spans:
        counts[sp["name"]] = counts.get(sp["name"], 0) + 1
    assert counts["serve.batch"] == s["batches"]
    assert counts["serve.prefill"] == s["batches"]
    assert counts["serve.decode"] == s["batches"]
    by_id = {sp["id"]: sp for sp in spans}
    for sp in spans:
        if sp["name"] == "serve.decode":
            assert by_id[sp["parent"]]["name"] == "serve.batch"

    # the CLI gate passes on the dumped per-class metrics
    dump_metrics(trace_dir, tel.registry, tag="serve")
    assert obs_main(["summary", "--trace", str(trace_dir),
                     "--require-span", "serve.decode",
                     "--require-class-latency"]) == 0
