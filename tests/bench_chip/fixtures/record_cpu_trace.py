"""Record ``cpu_trace.xplane.pb``, the small trace the reduction tests read.

    JAX_PLATFORMS=cpu python tests/bench_chip/fixtures/record_cpu_trace.py

Three annotated steps of a jitted matmul on the CPU, with host sleeps
between them, so the trace has device work, idle gaps and the harness's
``bench.`` annotations.
"""

import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).with_name("cpu_trace.xplane.pb")


def main() -> None:
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tmp = Path(tempfile.mkdtemp())
    try:
        jax.profiler.start_trace(str(tmp))
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step_once"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.submit"):
                    time.sleep(0.005)
        jax.profiler.stop_trace()
        shutil.copy(next(tmp.glob("plugins/profile/*/*.xplane.pb")), OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
