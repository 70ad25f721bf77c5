"""Record ``data/v5e_small.xplane.pb``, the small trace that
``test_bench_trace.py`` reduces. Run on a machine with one TPU:

    python bench/tests/make_trace_fixture.py

Three runs of a small jitted matmul, each in a ``bench.step`` annotation,
inside a ``bench.window`` annotation, with a short host sleep after each.
"""
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


def main() -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = Path(tempfile.mkdtemp())
    try:
        jax.profiler.start_trace(str(d))
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
                time.sleep(0.002)
        jax.profiler.stop_trace()
        shutil.copy(next(d.rglob("*.xplane.pb")), OUT)
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
