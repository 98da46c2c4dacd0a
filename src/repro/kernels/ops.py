"""jit'd public wrappers around the Pallas kernels.

``KERNEL_BACKEND`` (env ``REPRO_KERNEL_BACKEND``) picks the execution path:
  * "pallas"    — real TPU lowering
  * "interpret" — Pallas interpret mode (CPU validation; used by tests)
  * "xla"       — the pure-jnp reference
  * unset       — "pallas" when JAX's default backend is a TPU, else "xla"
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import causal_attention as _ca
from repro.kernels import fused_adam as _ad
from repro.kernels import masked_grad_agg as _ma
from repro.kernels import mlstm_chunk as _ml
from repro.kernels import ref

KERNEL_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND")


def _mode():
    if KERNEL_BACKEND:
        return KERNEL_BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def attention_fuses(seq_len: int) -> bool:
    """Whether ``attention`` at ``seq_len`` runs the fused kernel: a Pallas
    backend, and a sequence the kernel's blocks tile."""
    return _mode() != "xla" and _ca.block_size(seq_len) is not None


def attention(q, k, v):
    """Causal self-attention masked by index (``causal_attention``'s
    contract); the dense reference under "xla"."""
    m = _mode()
    if m == "xla":
        return ref.reference_attention(q, k, v, causal=True)
    return _ca.causal_attention(q, k, v, interpret=(m == "interpret"))


def mlstm(q, k, v, g, i, *, chunk=128):
    m = _mode()
    if m == "xla":
        return ref.reference_mlstm(q, k, v, g, i)
    return _ml.mlstm_chunk(q, k, v, g, i, chunk=chunk,
                           interpret=(m == "interpret"))


def _pad_to(x, r, c):
    n = x.size
    cols = c
    rows = -(-n // cols)
    rows = -(-rows // r) * r
    pad = rows * cols - n
    return jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, cols), n


def adam_update_tree(params, grads, m, v, step, lr, *, b1=0.9, b2=0.999,
                     eps=1e-8, wd=0.0):
    """Apply the fused Adam kernel leaf-wise over a pytree."""
    mode = _mode()
    t = step.astype(jnp.float32) + 1.0
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         1.0 - b1 ** t, 1.0 - b2 ** t])

    def one(p, g, m_, v_):
        if mode == "xla":
            return ref.reference_adam(p.reshape(1, -1), g.reshape(1, -1),
                                      m_.reshape(1, -1), v_.reshape(1, -1),
                                      scalars, b1=b1, b2=b2, eps=eps, wd=wd)
        pp, n = _pad_to(p, 8, 128)
        gg, _ = _pad_to(g, 8, 128)
        mm, _ = _pad_to(m_, 8, 128)
        vv, _ = _pad_to(v_, 8, 128)
        po, mo, vo = _ad.fused_adam(pp, gg, mm, vv, scalars, b1=b1, b2=b2,
                                    eps=eps, wd=wd,
                                    interpret=(mode == "interpret"))
        cut = lambda x: x.reshape(-1)[:n].reshape(p.shape)
        return cut(po), cut(mo), cut(vo)

    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(m)
    flat_v = jax.tree.leaves(v)
    outs = [one(p, g, m_, v_)
            for p, g, m_, v_ in zip(flat_p, flat_g, flat_m, flat_v)]
    unf = lambda i: jax.tree.unflatten(tree, [o[i].reshape(p.shape)
                                              for o, p in zip(outs, flat_p)])
    return unf(0), unf(1), unf(2)


def masked_aggregate(grads_stacked, mask, *, block: int = 2048):
    """grads_stacked: (W, N); mask: (W,) -> (N,) cutoff-weighted mean.

    Pads N up to the kernel's lane contract: a multiple of 128 when one
    block covers it, a multiple of ``block`` when the grid tiles it (the
    kernel requires the block size to divide the padded N).
    """
    m = _mode()
    mask2 = mask.reshape(-1, 1)
    if m == "xla":
        return ref.reference_masked_agg(grads_stacked, mask2)[0]
    assert block % 128 == 0, block   # the kernel's lane contract
    W, N = grads_stacked.shape
    tile = block if N > block else 128
    pad = (-N) % tile
    gp = jnp.pad(grads_stacked, ((0, 0), (0, pad)))
    out = _ma.masked_grad_agg(gp, mask2, block=block,
                              interpret=(m == "interpret"))
    return out[0, :N]


def masked_aggregate_tree(grads, mask, *, block: int = 2048):
    """Masked mean over the leading worker dim of a gradient pytree.

    The host-side stacked combine behind ``dist.collectives`` when no mesh
    is active: every leaf (W, ...) is flattened to (W, n) and concatenated
    into one (W, N) buffer so the whole tree is a single fused HBM pass of
    the masked_grad_agg kernel (fp32 accumulation, padded to the 128-lane
    contract), then split and cast back per leaf.  Under the "xla" backend
    it is the pure-jnp reference (``aggregation.masked_mean_local``), which
    keeps each leaf in its own dtype.
    """
    if _mode() == "xla":
        from repro.core import aggregation
        return aggregation.masked_mean_local(grads, mask)
    flat, tree = jax.tree.flatten(grads)
    W = flat[0].shape[0]
    buf = jnp.concatenate(
        [l.reshape(W, -1).astype(jnp.float32) for l in flat], axis=1)
    out = masked_aggregate(buf, jnp.asarray(mask, jnp.float32), block=block)
    outs, off = [], 0
    for l in flat:
        n = l.size // W
        outs.append(out[off:off + n].reshape(l.shape[1:]).astype(l.dtype))
        off += n
    return jax.tree.unflatten(tree, outs)
