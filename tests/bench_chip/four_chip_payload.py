"""Runs a four-chip cell on four host CPU devices at reduced widths and
prints its result line (driven by test_bench_chip_four.py in a process of
its own, since the device count is fixed when JAX starts)."""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

import bench_chip_util as u  # noqa: E402
import harness  # noqa: E402

config, traffic = harness.config, harness.traffic


def small_config(name):
    c = config(name)
    c.update(u.SMALL_LM)
    c["program"]["replace"].update(
        {u.PROGRAM[k]: v for k, v in u.SMALL_LM.items()})
    c["program"]["knobs"] = {"ce_chunk": 96}
    return c


def small_traffic(name):
    t = traffic(name)
    t["seq_len"] = u.SMALL_SEQ
    return t


harness.config, harness.traffic = small_config, small_traffic
# argv: workload name, configuration, traffic mix
bench = harness.benchmark()
cell = {"name": sys.argv[1], "config": sys.argv[2], "traffic": sys.argv[3],
        "chips": 4}
sys.exit(harness.run_cell(bench, cell, jax.devices()[:4], seed=123,
                          seconds=1.0, trace=False, t_start=0.0))
