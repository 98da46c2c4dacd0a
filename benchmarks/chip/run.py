#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

  python benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from BENCHMARK.json (see harness.py).  The last line of stdout is the
result: {"correct", "attempted", "failed", "metrics", "device"[,
"breakdown"], "checks"}; the last lines of stderr are the numbers compared
beside their limits.  Off a TPU, or with fewer chips than the cell needs,
it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]
# libtpu would otherwise log under /tmp, a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell = harness.entry(bench["workloads"], args.workload)
    devices = harness.device_gate(cell["chips"])
    return harness.run_cell(bench, cell, devices, seed=args.seed,
                            seconds=args.seconds, trace=bool(args.trace),
                            t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
