"""The control of a cell's check, at the cell's own size, on the chip.

    python3 -m benchmarks.chip.control --workload <cell> --seeds S1 S2 S3 \\
        [--seconds S]

Not part of a benchmark run.  For each seed, in this one process, it runs
the cell as a benchmark run does (set-up, a window of ``--seconds``,
the check) with the harness's own ``run_cell``, but with the check
judging the control in the program's place: the token that the
reference computed with float8 (e4m3) matmul operands puts first, read
at the same positions of the same prompts and served tokens.  A sound
check gives ``correct`` false there.  The program's own check of the same
run rides beside it as ``program_correct`` and ``program_check``.

One JSON line per seed goes to standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from .harness import (ROOT, device_info, enable_compile_cache,
                      load_benchmark, resolve, run_cell)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = resolve(load_benchmark(ROOT), args.workload, ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    enable_compile_cache(ROOT)
    device = device_info(cell.chips)
    for seed in args.seeds:
        res = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       device, ROOT, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed, **res}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
