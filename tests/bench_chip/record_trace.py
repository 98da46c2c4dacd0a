#!/usr/bin/env python3
"""Record the small chip trace that test_bench_chip_trace.py reads.

  python3 tests/bench_chip/record_trace.py <out.xplane.pb>

On a TPU: inside one ``bench.window`` span, five rounds of a dispatched
matmul chain (span ``bench.dispatch``), a wait for it, and 20 ms of host
work with the device idle (span ``bench.host``).  Copies the profiler's
``.xplane.pb`` to ``out``.  Refuses to run off a TPU.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

ROUNDS, HOST_S = 5, 0.02


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: not a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        with TraceAnnotation("bench.window"):
            for _ in range(ROUNDS):
                with TraceAnnotation("bench.dispatch"):
                    y = f(x)
                y.block_until_ready()
                with TraceAnnotation("bench.host"):
                    time.sleep(HOST_S)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        shutil.copy(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
