"""Trace reduction: busy/idle union, kernel time by stable name, and idle
gaps labelled by the harness's host annotations."""

from pathlib import Path

import pytest

from benchmarks.chip import trace
from benchmarks.chip.trace import Event

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_trace.xplane.pb"
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA")


def ev(name, s, t):
    return Event(name, float(s), float(t))


def test_union_merges_overlaps_and_clips():
    evs = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40), ev("d", 38, 45)]
    assert trace.union(evs) == [(0, 20), (30, 45)]
    assert trace.union(evs, 8, 42) == [(8, 20), (30, 42)]
    assert trace.busy(evs, 0, 50) == 35
    assert trace.idle_share(evs, 0, 50) == pytest.approx(15 / 50)


def test_time_by_stable_name_drops_instance_suffixes():
    evs = [ev("fusion.12", 0, 3), ev("fusion.7", 4, 6), ev("dot.1.2", 6, 10),
           ev("fusion.3", 90, 100)]
    assert trace.stable_name("fusion.12") == "fusion"
    # the TPU trace names an op by its HLO text; operands do not count
    hlo = ("%approx_matmul_pallas.164 = s32[128,2560]{1,0} custom-call("
           "s32[128,9728]{1,0} %fusion.3)")
    assert trace.stable_name(hlo) == "approx_matmul_pallas"
    refers = "%slice.9 = s32[4,2560]{1,0} slice(%approx_matmul_pallas.164)"
    assert [e.name for e in trace.matching(
        [ev(hlo, 0, 4), ev(refers, 4, 5)], r"^approx_matmul_pallas$",
        0, 10)] == [hlo]
    assert trace.time_by_name(evs, 0, 50) == {"fusion": 5.0, "dot": 4.0}
    assert trace.top_ops(evs, 0, 50, top=1) == [("fusion", 5e-9)]


def test_idle_gaps_take_the_innermost_covering_annotation():
    ops = [ev("k", 0, 10), ev("k", 30, 40), ev("k", 45, 50)]
    host = [ev("bench.window", 0, 50), ev("bench.submit", 10, 30),
            ev("bench.step_once", 30, 50)]
    gaps = trace.idle_gaps(ops, host, 0, 50)
    assert gaps == [("bench.submit", pytest.approx(20e-9)),
                    ("bench.step_once", pytest.approx(5e-9))]


def test_recorded_cpu_trace_reduces_to_its_own_events():
    from jax.profiler import ProfileData

    tr = trace.load(FIXTURE, **CPU)
    (window,) = tr.annotations("bench.window")
    steps = tr.annotations("bench.step_once")
    assert len(steps) == 3 and len(tr.annotations("bench.submit")) == 3
    ops = tr.ops["/host:CPU"]
    assert ops and not tr.modules
    # the same numbers straight from the raw file, by brute force
    raw = [(e.start_ns, e.start_ns + e.duration_ns)
           for p in ProfileData.from_file(str(FIXTURE)).planes
           if p.name == "/host:CPU" for line in p.lines
           if line.name.startswith("tf_XLA") for e in line.events
           if not e.name.startswith("bench.")]
    assert len(raw) == len(ops)
    lo, hi = window.start, window.end
    cover = sorted({int(t) for s, t in raw} | {int(s) for s, t in raw}
                   | {int(lo), int(hi)})
    brute = sum(b - a for a, b in zip(cover, cover[1:])
                if lo <= a and b <= hi
                and any(s <= a and b <= t for s, t in raw))
    assert trace.busy(ops, lo, hi) == pytest.approx(brute, abs=2)
    assert 0.0 < trace.idle_share(ops, lo, hi) < 1.0
    # the sleeps in bench.submit are the longest idle stretches
    gaps = trace.idle_gaps(ops, tr.host, lo, hi, top=3)
    assert [g[0] for g in gaps] == ["bench.submit"] * 3
    assert all(g[1] >= 4e-3 for g in gaps)
