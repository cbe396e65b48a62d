"""Reduction of a JAX profiler trace to busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of the ``XLA Ops`` line of each device plane
(``/device:TPU:<n>``); whole programs are the events of its
``XLA Modules`` line; the harness's own host annotations are the events
whose names start with ``bench.``.  Everything else is plain
interval arithmetic on ``(start_ns, end_ns)`` pairs, which the tests
check on a small trace recorded on the CPU.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
MODULE_LINE = r"^XLA Modules$"
HOST_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    end: float        # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: dict[str, list[Event]] = field(default_factory=dict)
    modules: dict[str, list[Event]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)

    def annotations(self, name: str) -> list[Event]:
        return [e for e in self.host if e.name == name]


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def load(path: Path, device_plane: str | None = None,
         op_line: str | None = None, module_line: str | None = None,
         host_prefix: str = HOST_PREFIX) -> Trace:
    """Read a trace.  The patterns default to the module's constants, read
    at call time, so a test can point them at a CPU trace's threads."""
    from jax.profiler import ProfileData

    dev = re.compile(device_plane or DEVICE_PLANE)
    ops = re.compile(op_line or OP_LINE)
    mods = re.compile(module_line or MODULE_LINE)
    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        is_device = bool(dev.match(plane.name))
        for line in plane.lines:
            kind = (None if not is_device else "ops" if ops.match(line.name)
                    else "modules" if mods.match(line.name) else None)
            for ev in line.events:
                e = Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith(host_prefix):
                    tr.host.append(e)
                elif kind is not None:
                    getattr(tr, kind).setdefault(plane.name, []).append(e)
    for evs in (*tr.ops.values(), *tr.modules.values(), tr.host):
        evs.sort(key=lambda e: e.start)
    return tr


def union(events, lo: float = float("-inf"), hi: float = float("inf")
          ) -> list[tuple[float, float]]:
    """Merged intervals covered by ``events``, clipped to ``[lo, hi]``."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out: list[list[float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def covered(spans, lo: float, hi: float) -> float:
    """Length of merged ``spans`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(t, hi) - max(s, lo)) for s, t in spans)


def busy(events, lo: float, hi: float) -> float:
    return covered(union(events, lo, hi), lo, hi)


def idle_share(events, lo: float, hi: float) -> float:
    return 1.0 - busy(events, lo, hi) / (hi - lo)


def stable_name(name: str) -> str:
    """An op's own name, without the numeric suffix XLA adds per instance:
    ``fusion.123`` and the TPU trace's ``%fusion.123 = f32[...] ...`` are
    both ``fusion``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def time_by_name(events, lo: float, hi: float) -> dict[str, float]:
    """Summed duration (ns) per stable name, of events inside the window."""
    out: dict[str, float] = {}
    for e in events:
        if e.start >= lo and e.end <= hi:
            k = stable_name(e.name)
            out[k] = out.get(k, 0.0) + e.dur
    return out


def matching(events, pattern: str, lo: float, hi: float) -> list[Event]:
    """Events inside the window whose stable name matches ``pattern``."""
    pat = re.compile(pattern)
    return [e for e in events if e.start >= lo and e.end <= hi
            and pat.search(stable_name(e.name))]


def idle_gaps(events, host: list[Event], lo: float, hi: float,
              top: int = 10) -> list[tuple[str, float]]:
    """The longest stretches with no device op, longest first, each
    labelled by the innermost host annotation covering at least half of
    it, else by the one covering most of it."""
    spans = union(events, lo, hi)
    gaps, cur = [], lo
    for s, t in spans:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        gaps.append((cur, hi))
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover = [(max(0.0, min(a.end, t) - max(a.start, s)), a)
                 for a in host]
        half = [a for c, a in cover if c >= 0.5 * (t - s)]
        if half:
            label = min(half, key=lambda a: a.dur).name
        else:
            c, a = max(cover, key=lambda ca: ca[0], default=(0.0, None))
            label = a.name if c > 0 else "no annotation"
        out.append((label, (t - s) * 1e-9))
    return out


def top_ops(events, lo: float, hi: float, top: int = 10
            ) -> list[tuple[str, float]]:
    """Device operations by summed time (s), largest first."""
    by = time_by_name(events, lo, hi)
    return [(k, v * 1e-9) for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:top]]
