"""attn_fwd_saved_share, the metric that reads how many fused attention
sites keep their forward's residuals through the layer checkpoint: its
arithmetic, its silence where the program has no such counter (the
parent of the change that added it) or fused nothing, its entry in
BENCHMARK.json, and its reading on a tiny qwen2-shaped train step through
the fused kernel (interpret mode)."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench_chip_util import ROOT

import harness  # noqa: E402  (bench_chip_util puts benchmarks/chip first)

from repro import optim
from repro.configs.base import get_config
from repro.kernels import ops
from repro.launch.train import make_train_step
from repro.models import model as M
from repro.obs import trace

METRIC = "attn_fwd_saved_share"
TRAIN_CELLS = ["qwen2-0.5b.train4k.w8", "starcoder2-3b.train4k.w8"]


def _read():
    return harness.reader(METRIC)(harness.Run(
        config={}, traffic={}, chips=1, device_kind="TPU v5 lite",
        setup_s=1.0, window_s=10.0, counts={}, samples={}))


@pytest.mark.parametrize("fused,saved,want", [(1, 1, 100.0), (2, 0, 0.0),
                                              (4, 3, 75.0)])
def test_reader_arithmetic(monkeypatch, fused, saved, want):
    monkeypatch.setattr(trace, "counted", lambda: {
        "attn.fused": fused, "attn.fwd_saved": saved, "attn.unfused": 5})
    assert _read() == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("counts", [{}, {"attn.fused": 1},
                                    {"attn.unfused": 3, "attn.fwd_saved": 0}])
def test_reader_is_silent_without_counters(monkeypatch, counts):
    monkeypatch.setattr(trace, "counted", lambda: counts)
    assert _read() is None
    monkeypatch.delattr(trace, "counted")      # a program without counters
    assert _read() is None


def test_benchmark_lists_the_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = harness.entry(bench["per_layer"], METRIC)
    assert entry["workloads"] == TRAIN_CELLS
    assert (entry["source"], entry["layer"], entry["unit"]) == (
        "program_counter", "Step", "%")
    e2e = harness.entry(bench["end_to_end"], entry["moves"])
    assert all(c in e2e["workloads"] for c in TRAIN_CELLS)


@pytest.mark.parametrize("n_layers", [1, 2])
def test_share_of_a_tiny_train_step(monkeypatch, n_layers):
    """100 through the scanned layers and through one unscanned layer."""
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=n_layers, head_dim=64)
    B, S = 1, 128
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, S), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens,
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    opt = optim.sgd(0.1)
    trace.reset_counted()
    jax.jit(make_train_step(cfg, opt)).lower(      # traces the step
        {"params": params, "opt": opt.init(params)}, batch)
    assert _read() == 100.0
