"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * fig4_*      — proxy<->area correlation runs (paper Fig. 4)
  * fig5_*      — best area per (benchmark, ET, method) (paper Fig. 5)
  * kernel rows — micro-benchmarks of the three kernels' workloads
"""

from __future__ import annotations

import os
import sys


def main() -> None:
    quick = os.environ.get("BENCH_QUICK", "0") == "1"
    budget = 30.0 if quick else 75.0
    rows: list[tuple[str, float, str]] = []

    from . import fig4_proxy_area, fig5_area_vs_et, kernels_bench

    for r in fig4_proxy_area.main(budget_s=budget):
        rows.append((
            f"fig4_{r['bench']}_et{r['et']}", r["wall_s"] * 1e6,
            f"corr_pit_its={r['pearson_pit_its_vs_area']:.3f};"
            f"shared={r['shared_best']};xpat={r['xpat_best']};"
            f"random={r['random_best']};exact={r['exact_area']}",
        ))

    for r in fig5_area_vs_et.main(budget_s=budget):
        rows.append((
            f"fig5_{r['bench']}_et{r['et']}", r["wall_s"] * 1e6,
            f"shared={r['shared']};xpat={r['xpat']};"
            f"muscat~={r['muscat']};mecals~={r['mecals']};"
            f"hybrid={r['hybrid']};exact={r['exact_area']}",
        ))

    kernels_bench.main(rows)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    # paper-claim assertions (soft: report, don't crash the harness)
    problems = []
    for name, _, derived in rows:
        if name.startswith("fig5_"):
            vals = dict(kv.split("=") for kv in derived.split(";"))
            sh, xp = vals.get("shared"), vals.get("xpat")
            if sh not in (None, "None") and xp not in (None, "None"):
                if float(sh) > float(xp) + 1e-6:
                    problems.append(f"{name}: SHARED({sh}) > XPAT({xp})")
    if problems:
        print("CLAIM-CHECK FAILURES:", *problems, sep="\n  ", file=sys.stderr)
    else:
        print("# claim-check: SHARED <= XPAT area on every fig5 row", file=sys.stderr)


if __name__ == "__main__":
    main()
