"""Four-chip Trainer cells on four host CPU devices at reduced widths: the
data mesh, the batch placed on its axis, the cross-device gradient
reduction, and the reference check.  Every four-chip traffic mix is run,
whether or not BENCHMARK.json lists a cell for it yet, held to the limits
of the one-chip cell of the same configuration."""
import glob
import json
import os
import subprocess
import sys

import pytest

from bench_chip_util import CHIP, ROOT, result_line

HERE = os.path.dirname(os.path.abspath(__file__))


def four_chip_mixes():
    out = []
    for path in sorted(glob.glob(os.path.join(CHIP, "traffic", "*.json"))):
        with open(path) as f:
            if json.load(f).get("layout") == "train_fsdp":
                out.append(os.path.basename(path)[:-5])
    return out


@pytest.mark.parametrize("mix", four_chip_mixes())
def test_four_chip_cell_on_host_devices(mix):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable,
                        os.path.join(HERE, "four_chip_payload.py"),
                        "qwen2-0.5b.train4k.w8", "qwen2-0.5b", mix],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    res = result_line(r.stdout)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
