"""Compiled-artifact and plan reports.

:func:`collective_stats` parses optimized HLO text and sums the wire
bytes of every collective op (``compiled.cost_analysis()`` leaves them
out), using ring-algorithm wire factors with the participant count taken
from ``replica_groups``.  :func:`plan_report` and
:func:`sensitivity_report` render a QoS plan and a measured sensitivity
profile as text tables.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0,
}

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?\S+\s*=\s*(?P<otype>\([^)]*\)|\S+?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+[0-9]*)\[(?P<dims>[0-9,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(?P<body>.*?)\}\}?")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(?P<g>\d+),(?P<n>\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group("n"))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        first = m.group("body").split("}", 1)[0].lstrip("{")
        ids = [x for x in first.split(",") if x.strip()]
        return max(1, len(ids))
    return 1


# ring-algorithm wire factors: bytes on the wire per participant,
# as a multiple of the (per-shard input / full output) payload.
def _wire_bytes(op: str, out_bytes: int, group: int) -> float:
    if op == "collective-permute":  # uses source_target_pairs, not groups
        return float(out_bytes)
    if group <= 1:
        return 0.0
    f = (group - 1) / group
    if op == "all-gather":
        return f * out_bytes                 # output is the gathered buffer
    if op == "all-reduce":
        return 2.0 * f * out_bytes           # reduce-scatter + all-gather
    if op == "reduce-scatter":
        return f * out_bytes * group         # output is the scattered shard
    if op == "all-to-all":
        return f * out_bytes
    if op == "collective-permute":
        return float(out_bytes)
    return 0.0


@dataclass
class CollectiveStats:
    counts: dict[str, int] = field(default_factory=dict)
    payload_bytes: dict[str, float] = field(default_factory=dict)
    wire_bytes_total: float = 0.0

    def add(self, op: str, payload: int, wire: float) -> None:
        self.counts[op] = self.counts.get(op, 0) + 1
        self.payload_bytes[op] = self.payload_bytes.get(op, 0.0) + payload
        self.wire_bytes_total += wire


def collective_stats(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        if "-done(" in line:  # async pair: count the -start only
            continue
        op = m.group("op")
        out_bytes = _shape_bytes(m.group("otype"))
        group = _group_size(line)
        stats.add(op, out_bytes, _wire_bytes(op, out_bytes, group))
    return stats


def plan_report(plan) -> str:
    """Human-readable per-layer operator table for a QoS
    :class:`~repro.library.qos.LayerPlan` — operator key, area vs the exact
    baseline, compiled-table error, and the plan-level totals."""
    lines = [
        f"{'layer':>5s}  {'operator':<18s} {'area µm²':>9s} {'Δarea':>7s} "
        f"{'pred.drift':>10s}"
    ]
    for c in plan.choices:
        name = c.key if c.key is not None else "exact"
        saving = 1.0 - c.area / plan.exact_area if plan.exact_area else 0.0
        lines.append(
            f"{c.layer:>5d}  {name:<18s} {c.area:>9.3f} {100 * saving:>6.1f}% "
            f"{c.predicted_drift:>10.5f}"
        )
    lines.append(
        f"total area {plan.total_area:.3f} µm² vs exact "
        f"{plan.exact_total_area:.3f} µm² "
        f"({100 * plan.area_saving:.1f}% saving), predicted drift "
        f"{plan.predicted_total:.5f} <= budget {plan.budget:.5f}"
    )
    return "\n".join(lines)


def sensitivity_report(profile) -> str:
    """Human-readable measured per-layer sensitivity table for a
    :class:`~repro.sensitivity.profile.SensitivityProfile` — one column
    per profiled serving width (drift per unit compiled-table mae),
    printed next to the per-layer operator table so a plan can be read
    against the measurements that priced it."""
    widths = profile.widths
    head = f"{'layer':>5s}"
    for b in widths:
        head += f"  {'w' + str(b) + ' drift/mae':>14s}"
    lines = [f"measured sensitivities: {profile.model} "
             f"({profile.n_layers} layers)", head]
    sens = {b: profile.sensitivities(b) for b in widths}
    for l in range(profile.n_layers):
        row = f"{l:>5d}"
        for b in widths:
            row += f"  {sens[b][l]:>14.5f}"
        lines.append(row)
    for b in widths:
        hot = int(sens[b].argmax())
        lines.append(
            f"w{b}: most sensitive layer {hot} "
            f"({sens[b][hot]:.5f}), least {int(sens[b].argmin())} "
            f"({sens[b].min():.5f})"
            + (f", measured cost matrix over "
               f"{len(profile.costs[b][0])} operator(s)"
               if b in profile.costs else "")
        )
    return "\n".join(lines)
