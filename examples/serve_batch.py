"""Batched serving example: greedy-decode a reduced model with KV caches —
the serve-side counterpart of train_small.py (uses the real serve
path).

    PYTHONPATH=src python examples/serve_batch.py --arch gemma3-1b
"""

import argparse
import sys

from repro.launch import serve

if __name__ == "__main__":
    if "--reduced" not in sys.argv:
        sys.argv.append("--reduced")
    serve.main()
