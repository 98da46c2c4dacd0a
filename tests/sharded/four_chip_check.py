"""chip_smoke's four-chip phase on 4 host devices at a reduced width.

The weights path and the psum path over a (4,) "data" mesh must agree
with the one-device LOCAL run, and the psum step must all-reduce.  Prints
FAIL on any violated property; driven by tests/test_chip_smoke.py.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import chip_smoke  # noqa: E402
from repro.configs.base import get_config  # noqa: E402

cfg = get_config("qwen2-0.5b").reduced()
report, fails = chip_smoke.four_chip_phase(cfg, jax.devices()[:4], seq=16,
                                           per_worker=2, steps=3,
                                           ce_chunk=100)
print(json.dumps(report, default=str))
for f in fails:
    print("FAIL", f)
print("four_chip_check:", "FAIL" if fails else "OK")
