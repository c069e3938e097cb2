"""Records ``cpu_trace.xplane.pb``, the small trace the tests reduce.

    JAX_PLATFORMS=cpu python bench/tests/data/record_cpu_trace.py

Three matmul calls under ``bench/step`` spans, each followed by a 20 ms
host sleep under ``bench/host_wait``, all inside ``bench/window``.
"""
import glob
import pathlib
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent


def main():
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/host_wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, HERE / "cpu_trace.xplane.pb")
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
