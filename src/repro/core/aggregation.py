"""Masked gradient aggregation — the cutoff update on an SPMD mesh.

The paper's production variant (§4.3): the parameter server broadcasts the
participant list as a bit array; dropped workers zero their gradients; the
ring all-reduce runs over the full array; the update divides by c.

Two equivalent implementations:

1. ``example_weights`` — production path: per-example weights w (1 for
   examples on included DP shards, 0 otherwise) folded into the loss,
   ``loss = sum(w*ce)/sum(w)``.  The gradient all-reduce GSPMD already emits
   then implements Alg. 1 line 29 exactly, with zero extra collectives.
2. ``masked_psum_mean`` — explicit shard_map bit-array + psum over
   per-worker gradients, used by tests to prove (1) is equivalent and as
   the reference semantics.  ``psum_mean`` is the full-sync baseline with
   the identical reduction order (so all-ones-mask comparisons can demand
   bitwise equality).  ``masked_mean_local`` is the in-process (no mesh)
   form of the same combine; ``kernels.ops.masked_aggregate_tree`` fuses
   it into one HBM pass on TPU.

The layout-aware entry points live in ``repro.dist.collectives``; this
module stays mesh-explicit so it can be tested against hand-built meshes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def example_weights(mask: np.ndarray, global_batch: int) -> np.ndarray:
    """Expand a per-worker bit array to per-example weights.

    mask: (n_workers,) 0/1 — worker j owns the j-th contiguous slice of the
    global batch (matching the DP sharding of the batch dimension).
    """
    mask = np.asarray(mask, np.float32)
    n = mask.shape[0]
    assert global_batch % n == 0, (global_batch, n)
    return np.repeat(mask, global_batch // n)


def _bc(bit, leaf):
    return bit.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)


def masked_mean_local(grads, mask_bit):
    """In-process reference combine: sum_w bit_w g_w / max(sum bit, 1).

    The no-mesh counterpart of ``masked_psum_mean`` — same math, same
    clamp, over the leading worker dim of each leaf.  This is the oracle
    the Pallas host-combine kernel (``kernels.masked_grad_agg``) is
    checked against, and the LOCAL path of
    ``dist.collectives.masked_grad_mean``.
    """
    bit = jnp.asarray(mask_bit)
    c = jnp.maximum(jnp.sum(bit.astype(jnp.float32)), 1.0)
    return jax.tree.map(
        lambda l: jnp.sum(l * _bc(bit, l), axis=0) / c.astype(l.dtype),
        grads)


def _worker_reduce(grads, mask_bit, mesh, dp_axes, *, apply_mask: bool):
    """Shared shard_map body: psum over ``dp_axes`` of per-worker grads.

    grads: pytree whose leaves carry a leading worker dim (n_workers, ...) —
    worker w's own gradient in slice w, n_workers == prod(dp axis sizes).
    mask_bit: (n_workers,) float.  The worker dim is sharded over the dp
    axes, summed locally, psum'd globally, and dropped from the result
    (replicated everywhere), divided by c = psum(bit) (or n for the plain
    mean, via an all-ones bit with identical op order).
    """
    axes = tuple(dp_axes)

    def body(bit, *leaves):
        c = jax.lax.psum(jnp.sum(bit), axes)
        outs = []
        for l in leaves:
            if apply_mask:
                w = bit.reshape((-1,) + (1,) * (l.ndim - 1)).astype(l.dtype)
                part = jnp.sum(l * w, axis=0)
            else:
                part = jnp.sum(l, axis=0)
            outs.append(jax.lax.psum(part, axes)
                        / jnp.maximum(c, 1.0).astype(l.dtype))
        return tuple(outs)

    flat, tree = jax.tree.flatten(grads)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes),) + tuple(
            P(axes, *([None] * (l.ndim - 1))) for l in flat),
        out_specs=tuple(P(*([None] * (l.ndim - 1))) for l in flat),
    )(jnp.asarray(mask_bit, jnp.float32), *flat)
    return jax.tree.unflatten(tree, list(out))


def masked_psum_mean(grads, mask_bit, mesh, dp_axes):
    """Reference bit-array aggregation: g = psum(bit * g_w) / psum(bit).

    See ``_worker_reduce`` for the contract; a masked-out worker's gradient
    is multiplied by 0.0 before the psum, so it has exactly zero influence.
    """
    return _worker_reduce(grads, mask_bit, mesh, dp_axes, apply_mask=True)


def psum_mean(grads, mesh, dp_axes):
    """Full-sync mean over the worker dim: g = psum(sum_w g_w) / n, with
    the same reduction order as ``masked_psum_mean``."""
    n = jax.tree.leaves(grads)[0].shape[0]
    ones = jnp.ones((n,), jnp.float32)
    return _worker_reduce(grads, ones, mesh, dp_axes, apply_mask=False)
