"""The fused causal flash attention behind ``models.attention.attention_sp``
(interpret mode on CPU): it computes what ``attn_core`` computes, forward
and gradients; every call it does not qualify for stays on ``attn_core``
(asserted through the ``attn.*`` counters); a tiny qwen2-shaped train
step reads the same loss and gradients through it as through XLA; and the
train step's layer checkpoint keeps the kernel's output and logsumexp, so
its backward runs no second forward kernel, alone and under a mesh, while
an ``attn_core`` layer's remat is unchanged."""
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs.base import get_config
from repro.kernels import ops
from repro.launch.train import make_train_step
from repro.models import attention as A
from repro.models import model as M
from repro.obs import trace


def _qkv(B, S, H, KV, hd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, hd)),
            jax.random.normal(ks[1], (B, S, KV, hd)),
            jax.random.normal(ks[2], (B, S, KV, hd)))


def _counted_during(fn, names=("attn.fused", "attn.unfused")):
    """fn's result and the counts of ``names`` it added."""
    before = trace.counted()
    out = fn()
    after = trace.counted()
    return out, {n: after.get(n, 0) - before.get(n, 0) for n in names}


@pytest.mark.parametrize("window", ["0", "S"])
@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("heads", [(4, 2), (14, 2)])
def test_fused_matches_attn_core(monkeypatch, heads, hd, S, window):
    """Forward and dq/dk/dv of the fused path against attn_core's."""
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    H, KV = heads
    w = S if window == "S" else 0
    q, k, v = _qkv(2, S, H, KV, hd)
    qpos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def fused(q, k, v):
        return A.attention_sp(q, k, v, qpos, window=w)

    def core(q, k, v):
        return A.attn_core(q, k, v, qpos, jnp.arange(S), window=w)

    (out, vjp), counts = _counted_during(lambda: jax.vjp(fused, q, k, v))
    assert counts == {"attn.fused": 1, "attn.unfused": 0}
    want, vjp_core = jax.vjp(core, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref in zip(vjp(do), vjp_core(do)):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(got, ref, atol=2e-5 * scale, rtol=1e-4)


def _fallback_call(case):
    """One attention call of ``case`` and the attn_core call it must equal."""
    B, S, H, KV, hd = 2, 256, 4, 2, 64
    q, k, v = _qkv(B, S, H, KV, hd, seed=1)
    qpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kpos = jnp.arange(S)
    if case == "decode":
        cache_k = jnp.zeros((B, S, KV, hd)).at[:, :7].set(k[:, :7])
        cache_v = jnp.zeros((B, S, KV, hd)).at[:, :7].set(v[:, :7])
        return (lambda: A.attn_decode(q[:, 7:8], k[:, 7:8], v[:, 7:8],
                                      cache_k, cache_v, 7)[0],
                A.attn_core(q[:, 7:8], k[:, :8], v[:, :8],
                            jnp.full((B, 1), 7), jnp.arange(8)))
    if case == "prefill_s1":
        return (lambda: A.attention_sp(q[:, :1], k[:, :1], v[:, :1],
                                       qpos[:, :1]),
                A.attn_core(q[:, :1], k[:, :1], v[:, :1], qpos[:, :1],
                            kpos[:1]))
    if case == "not_causal":
        return (lambda: A.attention_sp(q, k, v, qpos, causal=False),
                A.attn_core(q, k, v, qpos, kpos, causal=False))
    if case == "positions_3d":
        pos3 = jnp.stack([qpos, qpos, qpos])
        return (lambda: A.attention_sp(q, k, v, pos3),
                A.attn_core(q, k, v, qpos, kpos))
    if case == "softcap":
        return (lambda: A.attention_sp(q, k, v, qpos, softcap=30.0),
                A.attn_core(q, k, v, qpos, kpos, softcap=30.0))
    if case == "short_window":
        return (lambda: A.attention_sp(q, k, v, qpos, window=64),
                A.attn_core(q, k, v, qpos, kpos, window=64))
    assert case == "not_divisible"
    S = 200
    return (lambda: A.attention_sp(q[:, :S], k[:, :S], v[:, :S],
                                   qpos[:, :S]),
            A.attn_core(q[:, :S], k[:, :S], v[:, :S], qpos[:, :S],
                        kpos[:S]))


@pytest.mark.parametrize("case", ["decode", "prefill_s1", "not_causal",
                                  "positions_3d", "softcap", "short_window",
                                  "not_divisible"])
def test_fallback_stays_on_attn_core(monkeypatch, case):
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    got, want = _fallback_call(case)
    out, counts = _counted_during(got)
    assert counts == {"attn.fused": 0, "attn.unfused": 1}
    np.testing.assert_allclose(out, want, atol=1e-6, rtol=1e-6)


def tiny_qwen2():
    """qwen2-0.5b's structure (GQA with q/k/v biases, SwiGLU, tied head)
    at its head width, two layers, float32."""
    return dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                               n_layers=2, head_dim=64)


def tiny_step(cfg, S=256, B=2):
    """One plain SGD step (lr 1: the parameters' change is minus the
    gradient) of ``make_train_step``; returns (loss, gradient tree)."""
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    opt = optim.sgd(1.0)
    step = jax.jit(make_train_step(cfg, opt))
    new, metrics = step({"params": params, "opt": opt.init(params)}, batch)
    grads = jax.tree.map(lambda p, n: p - n, params, new["params"])
    return float(metrics["loss"]), grads


def test_tiny_train_step_interpret_matches_xla(monkeypatch):
    cfg = tiny_qwen2()
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "xla")
    loss_x, g_x = tiny_step(cfg)
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    (loss_i, g_i), counts = _counted_during(lambda: tiny_step(cfg))
    assert counts["attn.fused"] >= 1 and counts["attn.unfused"] == 0
    assert loss_i == pytest.approx(loss_x, rel=1e-5)
    leaves_x = jax.tree.leaves_with_path(g_x)
    assert len(leaves_x) == len(jax.tree.leaves(g_i))
    for (path, gx), gi in zip(leaves_x, jax.tree.leaves(g_i)):
        scale = float(jnp.max(jnp.abs(gx)))
        np.testing.assert_allclose(gi, gx, atol=1e-4 * scale + 1e-7,
                                   rtol=1e-3, err_msg=str(path))


def _pallas_calls(jaxpr, names=None):
    """The names of every pallas_call in ``jaxpr`` and its sub-jaxprs."""
    names = [] if names is None else names
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)      # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, names)
    return names


def _step_jaxpr(cfg, S=256, B=2):
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, S), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens,
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    opt = optim.sgd(0.1)
    return jax.make_jaxpr(make_train_step(cfg, opt))(
        {"params": params, "opt": opt.init(params)}, batch)


@pytest.mark.parametrize("keep,forwards", [(True, 1), (False, 2)])
@pytest.mark.parametrize("n_layers", [1, 2])
def test_layer_checkpoint_runs_the_forward_kernel_once(monkeypatch, n_layers,
                                                       keep, forwards):
    """The train step's layer checkpoint (scanned at 2 layers, one call at
    1) keeps the kernel's output and logsumexp, so its gradient holds one
    splash forward per layer body, where a checkpoint without the policy
    holds two: the forward's and the backward's recompute."""
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    if not keep:
        monkeypatch.setattr(M, "_KEEP_ATTN", None)
    cfg = dataclasses.replace(tiny_qwen2(), n_layers=n_layers)
    jaxpr, counts = _counted_during(
        lambda: _step_jaxpr(cfg),
        ("attn.fused", "attn.unfused", "attn.fwd_saved"))
    names = _pallas_calls(jaxpr.jaxpr)
    assert names.count("splash_mqa_fwd_residuals") == forwards
    assert names.count("splash_mqa_dq_no_residuals") == 1
    assert names.count("splash_mqa_dkv_no_residuals") == 1
    assert counts["attn.fused"] == 1 and counts["attn.unfused"] == 0
    if keep:
        assert counts["attn.fwd_saved"] == 1


def test_kept_residuals_leave_the_gradients(monkeypatch):
    """The backward pairs recomputed q/k/v with the forward's kept output
    and logsumexp: the same loss and gradients as a full recompute."""
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    cfg = tiny_qwen2()
    loss_k, g_k = tiny_step(cfg)
    monkeypatch.setattr(M, "_KEEP_ATTN", None)
    loss_r, g_r = tiny_step(cfg)
    assert loss_k == pytest.approx(loss_r, rel=1e-6)
    leaves_r = jax.tree.leaves_with_path(g_r)
    for (path, gr), gk in zip(leaves_r, jax.tree.leaves(g_k)):
        scale = float(jnp.max(jnp.abs(gr)))
        np.testing.assert_allclose(gk, gr, atol=1e-6 * scale + 1e-9,
                                   rtol=1e-5, err_msg=str(path))


@pytest.mark.parametrize("n_layers", [1, 2])
def test_attn_core_remat_is_unchanged(monkeypatch, n_layers):
    """attn_core names nothing, so the policy keeps nothing: the gradient's
    jaxpr is the full recompute's, equation for equation."""
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "xla")
    cfg = dataclasses.replace(tiny_qwen2(), n_layers=n_layers)
    policy = re.compile(r"policy=[^\n]*")
    kept = policy.sub("policy", str(_step_jaxpr(cfg)))
    monkeypatch.setattr(M, "_KEEP_ATTN", None)
    full = policy.sub("policy", str(_step_jaxpr(cfg)))
    assert kept == full
    assert "pallas_call" not in kept


def test_layer_checkpoint_keeps_residuals_through_shard_map():
    """On a 4-device host mesh the kernel runs in ``shard_map`` over the
    batch; the checkpoint name survives it (payload in its own process:
    the device count is fixed when JAX starts)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "sharded",
                                      "fused_remat_check.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0 and "FAIL" not in r.stdout, (
        f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-2000:]}")
