"""Record the small CPU trace that test_chipbench_trace.py reads.

    JAX_PLATFORMS=cpu python chipbench/tests/record_cpu_trace.py

Three harness ticks, each running a jitted ``serve_decode_chunk`` on the
CPU backend, with a sleep between them (device idle, host in
``chipbench.wait``), inside one ``chipbench.window`` span.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "cpu_trace.xplane.pb")


@jax.jit
def serve_decode_chunk(x):
    return jnp.tanh(x @ x)


def main() -> None:
    x = jnp.ones((128, 128))
    serve_decode_chunk(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("chipbench.tick", tick=i):
                serve_decode_chunk(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, OUT)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
