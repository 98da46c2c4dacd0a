"""The fused causal flash attention behind ``models.attention.attention_sp``
(interpret mode on CPU): it computes what ``attn_core`` computes, forward
and gradients; every call it does not qualify for stays on ``attn_core``
(asserted through the ``attn.*`` counters); and a tiny qwen2-shaped train
step reads the same loss and gradients through it as through XLA."""
import dataclasses

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


def _counted_during(fn):
    """fn's result and the attn.* counts it added."""
    before = trace.counted()
    out = fn()
    after = trace.counted()
    return out, {n: after.get(n, 0) - before.get(n, 0)
                 for n in ("attn.fused", "attn.unfused")}


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
