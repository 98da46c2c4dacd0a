#!/usr/bin/env python3
"""Readings of a cell's control and faults, on the chips of this machine,
at the cell's own size: what the limits in limits/<workload>.json are set
against (each limit lies between the program's readings and these).

  python benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \\
      [--seconds 10]

Prints one JSON line per seed.  The benchmark's own runs do not run it.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]
# libtpu would otherwise log under /tmp, a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell = harness.entry(bench["workloads"], args.workload)
    devices = harness.device_gate(cell["chips"])
    harness.place_compile_cache()
    cfg, tr = harness.config(cell["config"]), harness.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.driver(tr["driver"]).control(
            config=cfg, traffic=tr, seed=seed, seconds=args.seconds,
            devices=devices)
        print(json.dumps({"workload": cell["name"], "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
