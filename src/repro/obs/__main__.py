"""CLI: render a recorded run's timeline + calibration report.

  PYTHONPATH=src python -m repro.obs OBS_DIR

Reads only the JSONL artifacts an ``--obs-dir`` run wrote.  For a
timeline view, run the program under ``jax.profiler.trace(dir,
create_perfetto_trace=True)``: the program's spans sit there beside the
device's lanes.
"""
from __future__ import annotations

import argparse
import sys

from repro.obs import report as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.obs")
    ap.add_argument("obs_dir", help="directory an --obs-dir run wrote")
    args = ap.parse_args(argv)

    run = R.load_run(args.obs_dir)
    if not any(run.values()):
        print(f"no obs streams found under {args.obs_dir}",
              file=sys.stderr)
        return 1
    print(R.render(run))
    return 0


if __name__ == "__main__":
    sys.exit(main())
