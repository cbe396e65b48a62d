"""``serve.lut_device_ms`` on a trace recorded on the CPU
(``fixtures/record_lut_trace.py``): a jitted step whose MLP matmul runs
``approx_linear`` at W8, its ``lut.w8`` and ``quantize`` scopes apart.

A CPU trace carries no ``op_name`` paths, so as recorded the reader reads
nothing, as it does on a program without the scope; with the paths of the
recorded program's operations put on them, as a TPU trace carries them,
and each ``bench.step_once`` taken as a run of the step program, it reads
the device time of the ``lut.w8`` operations per run."""

import json

import pytest

from benchmarks.chip import harness, spans, trace
from benchmarks.chip.trace import Event

import tiny

FIXTURE = tiny.FIXTURES / "cpu_lut_trace.xplane.pb"
PATHS = json.loads((tiny.FIXTURES / "cpu_lut_trace.paths.json").read_text())
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA")
DEV = "/host:CPU"
read = harness.load_reader(harness.HERE / "metrics" /
                           "serve.lut_device_ms.py")


def context(tr):
    (window,) = tr.annotations("bench.window")
    return {"trace": tr, "devices": [DEV], "window_steps": [None, None],
            "window": (window.start, window.end)}


def with_recorded_paths(tr):
    """The recorded trace as a TPU trace reads: each operation with its
    program path, each ``bench.step_once`` a run of ``jit_step_fn``."""
    tr._scoped = {DEV: [(e, PATHS.get(e.name, ""))
                        for e, _ in spans.scoped_ops(tr, DEV)]}
    tr.modules = {DEV: [Event("jit_step_fn(1)", a.start, a.end)
                        for a in tr.annotations("bench.step_once")]}
    return tr


@pytest.mark.parametrize("fixture", ["cpu_trace", "cpu_lut_trace"])
def test_reads_nothing_where_no_operation_carries_the_scope(fixture):
    tr = trace.load(tiny.FIXTURES / f"{fixture}.xplane.pb", **CPU)
    assert read(context(tr)) is None
    assert read({}) is None


def test_reads_the_lut_scopes_device_time_per_step_run():
    tr = with_recorded_paths(trace.load(FIXTURE, **CPU))
    ctx = context(tr)
    runs = tr.modules[DEV]
    assert len(runs) == 2
    lut = [e for e, p in spans.scoped_ops(tr, DEV) if "/lut.w8/" in p]
    assert lut and all(any(r.start <= e.start and e.end <= r.end
                           for r in runs) for e in lut)
    got = read(ctx)
    assert got == pytest.approx(sum(e.dur for e in lut) / 2 * 1e-6)
    # quantize keeps its own operations, which the reader leaves out
    lo, hi = ctx["window"]
    by = spans.device_ms_by_scope(tr, DEV, lo, hi)
    assert by["quantize"] > 0 and 0 < got < sum(by.values())
    # an operation outside every run of the step does not count
    tr.modules = {DEV: runs[:1]}
    assert read(ctx) == pytest.approx(
        sum(e.dur for e in lut if e.end <= runs[0].end) * 1e-6)
