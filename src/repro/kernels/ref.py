"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def reference_attention(q, k, v, *, causal=True, window=0):
    """Dense attention; causal, the contract of kernels.causal_attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd).  Query row i sits at global
    position i + Sk - Sq (aligned suffixes).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    qpos = jnp.arange(Sq) + (Sk - Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", a, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def reference_mlstm(q, k, v, g, i):
    """Sequential stabilized mLSTM recurrence (the mlstm_chunk contract).

    q/k/v: (B, S, H, hd); g/i: (B, S, H) log forget/input gates -> fp32 out.
    """
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, gt, it = xs
        m_new = jnp.maximum(gt + m, it)
        fp = jnp.exp(gt + m - m_new)[..., None, None]
        ip = jnp.exp(it - m_new)[..., None, None]
        C = fp * C + ip * (kt[..., :, None] * vt[..., None, :])
        n = fp[..., 0] * n + ip[..., 0] * kt
        num = jnp.einsum("bhq,bhqv->bhv", qt, C) * scale
        den = jnp.einsum("bhq,bhq->bh", qt, n) * scale
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_new))
        return (C, n, m_new), num / den[..., None]

    z = jnp.zeros((B, H, hd, hd), jnp.float32)
    zn = jnp.zeros((B, H, hd), jnp.float32)
    m0 = jnp.full((B, H), -1e30, jnp.float32)
    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
               for t in (q, k, v, g, i))
    _, ys = jax.lax.scan(step, (z, zn, m0), xs)
    return jnp.moveaxis(ys, 0, 1)


def reference_adam(p, g, m, v, scalars, *, b1=0.9, b2=0.999, eps=1e-8,
                   wd=0.0):
    lr, bc1, bc2 = scalars[0], scalars[1], scalars[2]
    gf = g.astype(jnp.float32)
    m_new = b1 * m + (1 - b1) * gf
    v_new = b2 * v + (1 - b2) * gf * gf
    up = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if wd:
        up = up + wd * p.astype(jnp.float32)
    return ((p.astype(jnp.float32) - lr * up).astype(p.dtype), m_new, v_new)


def reference_masked_agg(grads, mask):
    m = mask.astype(jnp.float32)
    c = jnp.maximum(jnp.sum(m), 1.0)
    return (jnp.sum(grads.astype(jnp.float32) * m, axis=0, keepdims=True)
            / c).astype(grads.dtype)
