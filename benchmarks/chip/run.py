"""Run one cell of the chip benchmark.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  It builds its inputs and weights from ``--seed``, warms up,
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``), and last ``check``, each number
compared with its limit.  The same numbers are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.  JAX's compile cache lives in
``.bench_chip/jax_cache`` in the checkout unless
``JAX_COMPILATION_CACHE_DIR`` names another.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from .harness import (ROOT, BenchError, device_info,  # noqa: E402
                      enable_compile_cache, load_benchmark, resolve, run_cell)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = resolve(load_benchmark(ROOT), args.workload, ROOT)
        sys.path.insert(0, str(ROOT / "src"))
        enable_compile_cache(ROOT)
        device = device_info(cell.chips)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS, device, ROOT)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
