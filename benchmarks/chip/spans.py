"""The program's own spans and the device operations' scopes, read from
the same profiler trace as :mod:`benchmarks.chip.trace`.

The program writes a host annotation for each of its ``serve.*`` spans
(``repro.obs.trace``), with the span's attributes as the event's stats,
and labels its step's operations with ``jax.named_scope``
(``attention``, ``mlp``, ``quantize``, ``head``).  This module reads
both: ``spans(tr)`` gives the ``serve.*`` host events with their
attributes, and ``scoped_ops(tr, device)`` gives each device operation
with the ``op_name`` path XLA gave it.

On a TPU the path is the ``tf_op`` stat of the operation's event
*metadata* (``jit(step_fn)/.../attention/dot_general:``), keyed with the
program's ``program_id``; ``ProfileData`` shows only the events' own
stats, so :func:`op_paths` reads the metadata from the file's protobuf
wire format.  An operation is matched to it by its name and the program
of the ``XLA Modules`` run that holds it.  A CPU trace carries no such
stat: there every path is "".

They are read while the trace file exists: :func:`install` makes
``trace.load`` read them too and keep them on the ``Trace`` it returns,
beside its own ``ops``, ``modules`` and ``host``, which stay exactly as
``trace.load`` makes them.  Each reader that needs them imports this
module, which installs it; the harness loads every reader before the
run.  A trace without them (a program that writes no such spans or
scopes) gives empty lists, and the readers then return None.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from dataclasses import dataclass, field
from pathlib import Path

from . import trace
from .trace import Event

SPAN_PREFIX = "serve."
SCOPES = ("attention", "mlp", "quantize", "head")   # the step's scopes
KERNEL = r"^approx_matmul_pallas$"
PROGRAM = re.compile(r"\((\d+)\)$")     # ``jit_step_fn(<program_id>)``


@dataclass(frozen=True)
class Span:
    name: str
    start: float      # ns, on the trace's clock
    end: float
    attrs: dict = field(default_factory=dict, compare=False)

    @property
    def dur(self) -> float:
        return self.end - self.start


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _fields(b):
    """``(field number, value)`` of each field of a protobuf message:
    an int for a varint, a memoryview for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def op_paths(path, device_plane: str | None = None
             ) -> dict[str, dict[tuple[int, str], str]]:
    """Per device plane, the ``op_name`` path of each operation's event
    metadata, keyed by ``(program_id, name)``.  Read from the XSpace:
    planes (field 1) hold a name (2), event metadata (4: id, name 2,
    stats 5) and stat metadata (5: id, name 2); a stat holds its
    metadata id (1) and a uint64 (3), int64 (4), string (5) or a string
    by reference (7)."""
    dev = re.compile(device_plane or trace.DEVICE_PLANE)
    out: dict[str, dict[tuple[int, str], str]] = {}
    for num, plane in _fields(memoryview(Path(path).read_bytes())):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                name = _text(v)
            elif k == 4:
                metas.append(v)
            elif k == 5:
                for kk, entry in _fields(v):
                    if kk == 2:
                        sm = dict(_fields(entry))
                        stat_names[sm.get(1)] = _text(sm.get(2, b""))
        if not dev.match(name):
            continue
        table = out.setdefault(name, {})
        for entry in metas:
            for kk, meta in _fields(entry):
                if kk != 2:
                    continue
                op, stats = "", {}
                for f, v in _fields(meta):
                    if f == 2:
                        op = _text(v)
                    elif f == 5:
                        st = dict(_fields(v))
                        key = stat_names.get(st.get(1))
                        if 5 in st:
                            stats[key] = _text(st[5])
                        elif 7 in st:
                            stats[key] = stat_names.get(st[7], "")
                        else:
                            stats[key] = st.get(3, st.get(4))
                tf_op = stats.get("tf_op")
                if tf_op:
                    table[(stats.get("program_id"), op)] = \
                        tf_op.rsplit(":", 1)[0]
    return out


def scope_of(name: str, path: str) -> str:
    """The innermost of the step's scopes on ``path``; the LUT kernel is
    ``kernel`` whatever its scope, and an operation outside every scope
    is ``unscoped``."""
    if re.search(KERNEL, trace.stable_name(name)):
        return "kernel"
    parts = path.split("/")
    for p in reversed(parts):
        if p in SCOPES:
            return p
    return "unscoped"


def read_extras(path, device_plane: str | None = None,
                op_line: str | None = None, module_line: str | None = None,
                host_prefix: str = trace.HOST_PREFIX
                ) -> tuple[list[Span], dict[str, list[tuple[Event, str]]]]:
    """The ``serve.*`` spans, and each device operation with its path, of
    the trace at ``path``, selected by the same patterns as
    ``trace.load``."""
    from jax.profiler import ProfileData

    dev = re.compile(device_plane or trace.DEVICE_PLANE)
    ops = re.compile(op_line or trace.OP_LINE)
    mods = re.compile(module_line or trace.MODULE_LINE)
    paths = op_paths(path, device_plane)
    data = ProfileData.from_file(str(path))
    spans: list[Span] = []
    scoped: dict[str, list[tuple[Event, str]]] = {}
    for plane in data.planes:
        is_device = bool(dev.match(plane.name))
        runs: list[tuple[float, float, int | None]] = []
        plane_ops: list[Event] = []
        for line in plane.lines:
            kind = (None if not is_device else "ops" if ops.match(line.name)
                    else "modules" if mods.match(line.name) else None)
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(ev.name, ev.start_ns, end,
                                      dict(ev.stats)))
                elif ev.name.startswith(host_prefix):
                    continue
                elif kind == "ops":
                    plane_ops.append(Event(ev.name, ev.start_ns, end))
                elif kind == "modules":
                    m = PROGRAM.search(ev.name)
                    runs.append((ev.start_ns, end,
                                 int(m.group(1)) if m else None))
        if plane_ops:
            scoped[plane.name] = _with_paths(
                plane_ops, sorted(runs), paths.get(plane.name, {}))
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans, scoped


def _with_paths(ops: list[Event], runs, table) -> list[tuple[Event, str]]:
    """Each operation with the path of its metadata in the program of the
    module run that holds it (by name alone outside any run)."""
    by_name = {name: p for (_, name), p in table.items()}
    starts = [r[0] for r in runs]
    out = []
    for e in sorted(ops, key=lambda e: e.start):
        i = bisect_right(starts, e.start) - 1
        prog = runs[i][2] if i >= 0 and e.end <= runs[i][1] else None
        out.append((e, table.get((prog, e.name), by_name.get(e.name, ""))))
    return out


def install() -> None:
    """Make ``trace.load`` also keep the spans and the scoped operations
    on the ``Trace`` it returns (``_spans``, ``_scoped``).  Idempotent.

    The paths a trace shows are those of the executable that ran.  JAX's
    persistent compile cache leaves them out of its key by default, so a
    program whose scopes changed could be served an executable compiled
    from an earlier version, with the earlier paths: the key keeps them
    here."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if getattr(trace.load, "reads_spans", False):
        return
    base = trace.load

    def load(path, device_plane=None, op_line=None, module_line=None,
             host_prefix=trace.HOST_PREFIX):
        tr = base(path, device_plane, op_line, module_line, host_prefix)
        tr._spans, tr._scoped = read_extras(path, device_plane, op_line,
                                            module_line, host_prefix)
        return tr

    load.reads_spans = True
    load.__wrapped__ = base
    load.__doc__ = base.__doc__
    trace.load = load


def spans(tr, name: str | None = None, lo: float = float("-inf"),
          hi: float = float("inf")) -> list[Span]:
    """The trace's ``serve.*`` spans inside ``[lo, hi]``, of one name if
    given."""
    return [s for s in getattr(tr, "_spans", ())
            if (name is None or s.name == name)
            and s.start >= lo and s.end <= hi]


def scoped_ops(tr, device: str) -> list[tuple[Event, str]]:
    """Each device operation of ``device`` with its ``op_name`` path."""
    return getattr(tr, "_scoped", {}).get(device, [])


def steps(tr, lo: float, hi: float) -> list[Span]:
    """The engine steps that ran inside the window: ``serve.step`` spans
    with live rows."""
    return [s for s in spans(tr, "serve.step", lo, hi)
            if s.attrs.get("rows", 0) > 0]


def mean_ms(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) * 1e-6 if values else None


def step_runs(tr, device: str, lo: float, hi: float) -> list[Event]:
    """Runs of the step program (``jit_step_fn``) inside the window."""
    return trace.matching(tr.modules.get(device, []), r"^jit_step_fn\(",
                          lo, hi)


def device_ms_by_scope(tr, device: str, lo: float, hi: float
                       ) -> dict[str, float] | None:
    """Device time per scope, per run of the step program (ms), of the
    operations inside those runs; None where the window has no run or
    no operation carries a path."""
    runs = step_runs(tr, device, lo, hi)
    ops = scoped_ops(tr, device)
    if not runs or not any(p for _, p in ops):
        return None
    starts = [r.start for r in runs]
    out: dict[str, float] = {}
    for e, path in ops:
        i = bisect_right(starts, e.start) - 1
        if i < 0 or e.end > runs[i].end:
            continue
        k = scope_of(e.name, path)
        out[k] = out.get(k, 0.0) + e.dur
    return {k: v / len(runs) * 1e-6 for k, v in out.items()}


def idle_by_span(ops, labels: list[Span | Event], lo: float, hi: float
                 ) -> dict[str, float]:
    """The window's device-idle time (ns), summed by the innermost (the
    shortest) of ``labels`` that covers it, else ``none``."""
    busy = trace.union(ops, lo, hi)
    gaps, cur = [], lo
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        gaps.append((cur, hi))
    labels = sorted(labels, key=lambda a: a.start)
    starts = [a.start for a in labels]
    out: dict[str, float] = {}
    for s, t in gaps:
        near = [a for a in labels[:bisect_right(starts, t)]
                if a.end > s]
        cuts = sorted({s, t} | {x for a in near for x in (a.start, a.end)
                                if s < x < t})
        for a, b in zip(cuts, cuts[1:]):
            cover = [x for x in near if x.start <= a and x.end >= b]
            k = min(cover, key=lambda x: x.end - x.start).name \
                if cover else "none"
            out[k] = out.get(k, 0.0) + (b - a)
    return out


install()
