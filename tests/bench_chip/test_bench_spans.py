"""The program's spans and the step's scopes in the profiler trace: the
loader keeps every existing reading, and each reader of them on small
synthetic traces, with None where the trace has nothing for it."""

from pathlib import Path

import pytest

from benchmarks.chip import harness, spans, trace
from benchmarks.chip.spans import Span
from benchmarks.chip.trace import Event

import tiny

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_trace.xplane.pb"
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA")
DEV = "/device:TPU:0"
OLD = ["serve.idle_share", "serve.host_ms_per_step", "serve.step_device_ms",
       "serve.mfu", "lut_matmul_roofline", "chat.queue_wait_p90_ms"]
NEW = ["serve.step_host_ms", "serve.inputs_ms", "serve.sample_ms",
       "serve.attention_device_ms", "serve.quantize_device_ms"]


def reader(name):
    return harness.load_reader(harness.HERE / "metrics" / f"{name}.py")


def ev(name, s, t):
    return Event(name, float(s), float(t))


def test_load_keeps_what_every_existing_reader_reads():
    assert trace.load.reads_spans
    got = trace.load(FIXTURE, **CPU)
    want = trace.load.__wrapped__(FIXTURE, **CPU)
    assert (got.ops, got.modules, got.host) == (want.ops, want.modules,
                                                want.host)
    assert spans.spans(got) == []
    assert [e for e, _ in spans.scoped_ops(got, "/host:CPU")] == \
        got.ops["/host:CPU"]
    (window,) = got.annotations("bench.window")
    lo, hi = window.start, window.end
    ops = got.ops["/host:CPU"]
    assert trace.top_ops(ops, lo, hi) == trace.top_ops(
        want.ops["/host:CPU"], lo, hi)
    assert trace.idle_gaps(ops, got.host, lo, hi) == trace.idle_gaps(
        want.ops["/host:CPU"], want.host, lo, hi)
    import json

    from benchmarks.chip import work
    from benchmarks.chip.serve import Step
    from benchmarks.chip.weights import Dims

    dims = Dims.from_doc(json.loads(
        (tiny.FIXTURES / "qwen3-tiny-w4.json").read_text()))
    steps = [Step(end=0.0, rows=3, flops=1000)] * 3
    peak = work.peaks("TPU v5 lite")
    for name in OLD:
        read = reader(name)
        a, b = ({"trace": t, "devices": ["/host:CPU"], "window": (lo, hi),
                 "window_steps": steps, "dims": dims, "peak": peak,
                 "queue_waits": [0.1, 0.2]} for t in (got, want))
        assert read(a) == read(b), name


def synthetic(scoped=True, with_spans=True):
    """Two steps on one device: each a ``jit_step_fn`` run holding an
    attention op (2 ns), a quantize op (1), the kernel (4) and a head op
    (1), inside the program's spans for that step; then a call with no
    live rows."""
    ops, scoped_ops, runs, all_spans = [], [], [], []
    for i in range(2):
        t = 100.0 * i
        runs.append(ev("jit_step_fn(1)", t + 10, t + 20))
        paths = [("fusion.1", "jit(step_fn)/attention/dot_general", 10, 12),
                 ("fusion.2", "jit(step_fn)/mlp/quantize/round", 12, 13),
                 ("%approx_matmul_pallas.3 = s32[2]", "jit(step_fn)/mlp/x",
                  13, 17),
                 ("fusion.4", "jit(step_fn)/head/dot_general", 17, 18)]
        for name, path, s, e in paths:
            op = ev(name, t + s, t + e)
            ops.append(op)
            scoped_ops.append((op, path if scoped else "jit(step_fn)/x"))
        all_spans += [
            Span("serve.step", t + 2, t + 30, {"step": i, "rows": 3}),
            Span("serve.step.admit", t + 2, t + 4),
            Span("serve.step.inputs", t + 4, t + 8),
            Span("serve.step.launch", t + 8, t + 10),
            Span("serve.step.wait", t + 10, t + 20),
            Span("serve.step.sample", t + 20, t + 27),
            Span("serve.step.book", t + 27, t + 30)]
    all_spans.append(Span("serve.step", 250, 252, {"step": 2, "rows": 0}))
    tr = trace.Trace(ops={DEV: ops}, modules={DEV: runs},
                     host=[ev("bench.window", 0, 300),
                           ev("bench.submit", 0, 2),
                           ev("bench.step_once", 1, 31),
                           ev("bench.step_once", 101, 131)])
    if with_spans:
        tr._spans = all_spans
    tr._scoped = {DEV: scoped_ops}
    return {"trace": tr, "devices": [DEV], "window": (0.0, 300.0),
            "window_steps": [None, None]}


def test_the_new_readers_on_a_synthetic_trace():
    ctx = synthetic()
    got = {name: reader(name)(ctx) for name in NEW}
    assert got == pytest.approx({
        "serve.step_host_ms": (28 - 10) * 1e-6,
        "serve.inputs_ms": 4e-6, "serve.sample_ms": 7e-6,
        "serve.attention_device_ms": 2e-6,
        "serve.quantize_device_ms": 1e-6})
    assert spans.device_ms_by_scope(ctx["trace"], DEV, 0, 300) == \
        pytest.approx({"attention": 2e-6, "quantize": 1e-6, "kernel": 4e-6,
                       "head": 1e-6})


def test_the_new_readers_give_none_where_nothing_matches():
    # no window steps: the run was not traced
    assert all(reader(n)({}) is None for n in NEW)
    # the program writes no spans and its ops no scopes, as before them
    bare = synthetic(scoped=False, with_spans=False)
    assert all(reader(n)(bare) is None for n in NEW)
    # spans without any step program in the window
    ctx = synthetic()
    ctx["trace"].modules = {}
    assert reader("serve.attention_device_ms")(ctx) is None
    assert reader("serve.quantize_device_ms")(ctx) is None
    assert reader("serve.step_host_ms")(ctx) is not None
    # a window before every step
    ctx["window"] = (0.0, 1.0)
    assert all(reader(n)(ctx) is None for n in NEW)


def test_idle_goes_to_the_innermost_covering_span():
    ctx = synthetic()
    tr, (lo, hi) = ctx["trace"], ctx["window"]
    idle = spans.idle_by_span(tr.ops[DEV],
                              spans.spans(tr) + tr.annotations("bench.submit"),
                              lo, hi)
    assert idle == pytest.approx({
        "bench.submit": 2, "serve.step.admit": 4, "serve.step.inputs": 8,
        "serve.step.launch": 4, "serve.step.wait": 4,
        "serve.step.sample": 14,
        "serve.step.book": 6, "serve.step": 2, "none": 300 - 20 - 40})
    assert sum(idle.values()) == pytest.approx(
        (hi - lo) - trace.busy(tr.ops[DEV], lo, hi))


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 11 offset_ps: 30000 duration_ps: 5000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 4000 }
    events { metadata_id: 2 offset_ps: 6000 duration_ps: 2000 }
    events { metadata_id: 3 offset_ps: 9000 duration_ps: 1000 }
    events { metadata_id: 4 offset_ps: 31000 duration_ps: 2000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[2] fusion()"
    stats { metadata_id: 1 str_value: "jit(step_fn)/attention/dot_general:" }
    stats { metadata_id: 2 uint64_value: 7 } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[2] fusion()"
    stats { metadata_id: 1 ref_value: 3 }
    stats { metadata_id: 2 uint64_value: 7 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.1 = f32[2] copy()"
    stats { metadata_id: 2 uint64_value: 7 } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.1 = f32[2] fusion()"
    stats { metadata_id: 1 str_value: "jit(argmax)/argmax:" }
    stats { metadata_id: 2 uint64_value: 8 } } }
  event_metadata { key: 10 value { id: 10 name: "jit_step_fn(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_argmax(8)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(step_fn)/mlp/quantize/round:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 25000
      stats { metadata_id: 1 int64_value: 4 } }
    events { metadata_id: 2 offset_ps: 500 duration_ps: 21000 } }
  event_metadata { key: 1 value { id: 1 name: "serve.step" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step_once" } }
  stat_metadata { key: 1 value { id: 1 name: "rows" } }
}
"""


def test_load_reads_spans_and_each_ops_path(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = trace.load(path)
    (step,) = spans.spans(tr)
    assert (step.name, step.attrs) == ("serve.step", {"rows": 4})
    assert [a.name for a in tr.host] == ["bench.step_once"]
    got = [(e.name.split(" ")[0], p) for e, p in spans.scoped_ops(tr, DEV)]
    # one name in two programs: each op takes the path of the program
    # whose run holds it; an op without a path has ""
    assert got == [("%fusion.1", "jit(step_fn)/attention/dot_general"),
                   ("%fusion.2", "jit(step_fn)/mlp/quantize/round"),
                   ("%copy.1", ""),
                   ("%fusion.1", "jit(argmax)/argmax")]
    assert [e for e, _ in spans.scoped_ops(tr, DEV)] == tr.ops[DEV]


def test_scope_is_the_innermost_and_the_kernel_is_its_own():
    assert spans.scope_of("fusion.2", "jit(f)/mlp/quantize/round") == \
        "quantize"
    assert spans.scope_of("fusion.2", "jit(f)/mlp/silu") == "mlp"
    assert spans.scope_of("%approx_matmul_pallas.7 = s32[2]",
                          "jit(f)/mlp") == "kernel"
    assert spans.scope_of("copy.3", "jit(f)/broadcast_in_dim") == "unscoped"
    assert spans.scope_of("copy.3", "") == "unscoped"


def test_a_traced_cpu_run_reports_the_program_spans(tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, "qwen3-tiny-w4", "tiny-chat")
    res = tiny.run(cell, monkeypatch, tmp_path, tracing=True)
    got = res["metrics"]
    assert {"serve.step_host_ms", "serve.inputs_ms",
            "serve.sample_ms"} <= set(got)
    assert 0 < got["serve.inputs_ms"]["value"] < \
        got["serve.step_host_ms"]["value"]
    # the CPU trace's operations carry no op_name path
    assert "serve.attention_device_ms" not in got
