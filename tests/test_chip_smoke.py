"""chip_smoke.py off the chip: its device gate, and each phase's function
driven at a reduced width on the CPU (the chip runs them at full width)."""
import dataclasses
import os
import subprocess
import sys

import pytest

from repro.configs.base import get_config

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _env(**kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(kw)
    return env


def test_device_gate_refuses_cpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=_env(JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr


def test_trainer_phase_reduced():
    # a 32-token vocabulary keeps the Markov stream learnable in 6 steps;
    # a 12-column CE chunk leaves an overhanging last chunk
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              vocab_size=32)
    report, fails = chip_smoke.trainer_phase(
        cfg, seq=32, batch=8, workers=8, steps=6, ce_chunk=12,
        fit_steps=20, lr=3e-3)
    assert fails == []
    assert len(report["losses"]) == len(report["cutoffs"]) == 7
    assert report["loss0_rel_err"] < 1e-5      # f32 here: same arithmetic
    assert report["decision_agreement"] == "7/7"
    assert report["compiles_in_timed_steps"] == 0


def test_ps_phase_reduced():
    report, fails = chip_smoke.ps_phase(n_jobs=2, n_per_job=8, ticks=3,
                                        fit_steps=10)
    assert fails == []
    assert report["dispatches_per_tick"] == [2, 1, 1]
    assert len(report["flush_s"]) == 3


def test_kernel_phase_interpret(monkeypatch):
    """Both kernels match their references in interpret mode, and the
    phase flags any backend but "pallas" (on the chip, a fallback)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    report, fails = chip_smoke.kernel_phase(
        get_config("qwen2-0.5b").reduced(), workers=16)
    assert fails == ["kernel backend 'interpret', want 'pallas'"]
    assert report["adam_leaf"] == (256, 64)
    assert report["agg_max_abs_err"] < 1e-6


@pytest.mark.sharded
def test_four_chip_phase_on_host_devices():
    """Weights and psum paths on a 4-device mesh == one device, with the
    psum step's one all-reduce over all four devices."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "sharded",
                                      "four_chip_check.py")],
        capture_output=True, text=True, timeout=600, env=_env())
    assert r.returncode == 0 and "FAIL" not in r.stdout, (
        f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-2000:]}")
    assert '"replica_groups": ["{{0,1,2,3}}"]' in r.stdout


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(tmp_path, env_dir):
    """The cache lands in JAX_COMPILATION_CACHE_DIR where it is set, and
    in <repo>/.jax_cache otherwise (checked here on a stand-in path)."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache as cc\n"
        f"cc.REPO_CACHE_DIR = {str(tmp_path / 'repo_cache')!r}\n"
        "print(cc.place_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()\n")
    env = _env(JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = tmp_path / "repo_cache"
    if env_dir:
        want = tmp_path / "env_cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(want)
    assert os.listdir(want)
    other = tmp_path / ("repo_cache" if env_dir else "env_cache")
    assert not other.exists()


def test_default_compile_cache_is_the_repo_dot_jax_cache():
    from repro.launch import compile_cache
    assert compile_cache.REPO_CACHE_DIR == os.path.abspath(
        os.path.join(ROOT, ".jax_cache"))
