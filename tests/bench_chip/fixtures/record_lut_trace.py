"""Record ``cpu_lut_trace.xplane.pb`` and ``cpu_lut_trace.paths.json``,
the small trace the ``serve.lut_device_ms`` tests read.

    JAX_PLATFORMS=cpu python tests/bench_chip/fixtures/record_lut_trace.py

Two annotated runs of a jitted ``step_fn`` whose MLP matmul goes through
``approx_linear`` at W8.  A CPU trace names each operation but carries
no ``op_name`` path, so the paths of the compiled program's operations
(its HLO metadata, what a TPU trace carries per operation) are written
beside it.
"""

import json
import re
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from repro.precision.widths import exact_table  # noqa: E402
from repro.quant.int4 import approx_linear  # noqa: E402

OUT = Path(__file__).with_name("cpu_lut_trace.xplane.pb")
PATHS = Path(__file__).with_name("cpu_lut_trace.paths.json")
OP = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*metadata=\{op_name="([^"]+)"')


def step_fn(x, w, lut):
    with jax.named_scope("mlp"):
        return approx_linear(x, w, lut)


def main() -> None:
    lut = jnp.asarray(exact_table("mul", 8).astype(np.int32))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128))
    f = jax.jit(step_fn)
    f(x, w, lut).block_until_ready()
    text = f.lower(x, w, lut).compile().as_text()
    paths = {m.group(1): m.group(2) for m in map(OP.match, text.splitlines())
             if m}
    tmp = Path(tempfile.mkdtemp())
    try:
        jax.profiler.start_trace(str(tmp))
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step_once"):
                    f(x, w, lut).block_until_ready()
        jax.profiler.stop_trace()
        shutil.copy(next(tmp.glob("plugins/profile/*/*.xplane.pb")), OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    PATHS.write_text(json.dumps(paths, indent=1, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
