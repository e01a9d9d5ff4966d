"""Record the small trace that ``tests/test_tracelib.py`` reads: three
bursts of matrix products on the device, with the host asleep inside a
named span between them.  Run on the chip:

    python3 -m chipbench.tools.record_small_trace chiprun_out/small_trace
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

from chipbench import tracelib


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f = jax.jit(lambda a: (a @ a) * 0.001)
    jax.block_until_ready(f(x))
    logdir = os.path.join(out, "log")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("burst"):
                y = x
                for _ in range(20):
                    y = f(y)
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("idle_sleep"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    src = tracelib.find_xplane(logdir)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(logdir, ignore_errors=True)
    print(os.path.getsize(os.path.join(out, "small.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
