"""The benchmark's yardsticks: the FLOP count against a hand count, the
peak table, the configuration files against the program they run, and
the harness finding a cell's pieces by name."""
import json
import os
import subprocess
import sys

import pytest

from bench_chip_util import CHIP, ROOT, result_line, workloads

import harness  # noqa: E402  (bench_chip_util puts benchmarks/chip first)
import lm_flops  # noqa: E402
import peaks  # noqa: E402


@pytest.mark.parametrize("name, per_token", [
    # 24 x (896*896 + 2*896*128 + 896*896 + 3*896*4864) + 151936*896
    # = 357,826,560 + 136,134,656 matmul params; attention 6*24*4096*14*64
    ("qwen2-0.5b", 6 * (357_826_560 + 136_134_656) + 6 * 24 * 4096 * 14 * 64),
    # 5 x (3072*3072 + 2*3072*256 + 3072*3072 + 2*3072*12288) + 49152*3072
    # = 479,723,520 + 150,994,944 (tied head); attention 6*5*4096*24*128
    ("starcoder2-3b", 6 * (479_723_520 + 150_994_944)
     + 6 * 5 * 4096 * 24 * 128),
])
def test_flops_per_token_matches_hand_count(name, per_token):
    assert lm_flops.per_token(harness.config(name), 4096) == per_token


def test_peak_table_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["qwen2-0.5b", "starcoder2-3b"])
def test_config_file_is_what_the_program_runs(name):
    """The program's config and parameter tree hold what the file says."""
    import jax
    import numpy as np

    import drive_trainer
    import lm_weights
    from repro.models import model as M
    c = harness.config(name)
    cfg = drive_trainer.program_config(c, 4096)
    tree = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    mine = jax.eval_shape(lambda: drive_trainer.program_params(
        c, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, mine)
    assert sum(a.size for a in jax.tree.leaves(tree)) == sum(
        int(np.prod(s)) for s, _ in lm_weights.shapes(c).values())
    c["hidden_size"] += 1
    with pytest.raises(ValueError, match="differs"):
        drive_trainer.program_config(c, 4096)


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         workloads()[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert "correct" not in r.stdout


TOY_DRIVER = '''
import harness

def run(*, workload, config, traffic, seed, seconds, trace, t_start,
        devices):
    r = harness.Run(config=config, traffic=traffic, chips=len(devices),
                    device_kind=devices[0].device_kind, setup_s=1.5,
                    window_s=2.0, counts={"things": traffic["things"]},
                    samples={})
    lim = harness.limits(workload["name"])
    return r, [harness.Check("gap", config["gap"], lim["gap"])], 3, None
'''


def test_harness_finds_a_new_cell_by_its_files(tmp_path, monkeypatch,
                                                capsys):
    """A configuration, a traffic mix (with its driver), limits and a
    metric reader added as files are found with no edit to the harness."""
    import jax
    for d in ("configs", "traffic", "metrics", "limits"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "toy-1.json").write_text(json.dumps(
        {"name": "toy-1", "gap": 0.25}))
    (tmp_path / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"driver": "toy", "things": 12}))
    (tmp_path / "limits" / "toy-1.mix.json").write_text('{"gap": 0.5}')
    (tmp_path / "metrics" / "things_per_s.py").write_text(
        "def read(run):\n    return run.counts['things'] / run.window_s\n")
    (tmp_path / "metrics" / "setup_s.py").write_text(
        "def read(run):\n    return run.setup_s\n")
    (tmp_path / "drive_toy.py").write_text(TOY_DRIVER)
    bench = {"workloads": [{"name": "toy-1.mix", "config": "toy-1",
                            "traffic": "toy_mix", "chips": 1}],
             "end_to_end": [{"name": "things_per_s", "unit": "1/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    monkeypatch.syspath_prepend(str(tmp_path))
    cell = harness.entry(bench["workloads"], "toy-1.mix")
    assert harness.run_cell(bench, cell, jax.devices()[:1], seed=5,
                            seconds=2, trace=False, t_start=0.0) == 0
    res = result_line(capsys.readouterr().out)
    assert res["correct"] is True
    assert res["metrics"] == {"things_per_s": {"value": 6.0, "unit": "1/s"},
                              "setup_s": {"value": 1.5, "unit": "s"}}
    assert res["checks"] == {"gap": {"value": 0.25, "limit": 0.5}}
    assert list(res)[-1] == "checks"


def test_benchmark_file_names_every_piece():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        harness.config(w["config"])
        harness.limits(w["name"])
        assert harness.traffic(w["traffic"])["driver"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
