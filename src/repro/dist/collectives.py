"""Data-parallel collectives behind the layout — the cutoff-SGD story.

The parameter-server decision (``core.controller``) produces a per-worker
bit array each step; this module is how that bit array meets the SPMD mesh:

  * ``example_weights``   — the PRODUCTION path (paper §4.3): expand the
    bit array to per-example weights folded into the loss.  The gradient
    all-reduce GSPMD already emits then implements the masked mean exactly,
    with zero extra collectives.  ``launch.train.Trainer`` uses this.
  * ``masked_grad_mean``  — the EXPLICIT path (``mask_agg="psum"`` in
    ``launch.train``): bit-array aggregation over per-worker gradients
    (leading worker dim).  Under LOCAL the stacked host combine goes
    through ``kernels.ops.masked_aggregate_tree`` (the Pallas
    masked_grad_agg kernel on TPU / interpret, pure jnp under the "xla"
    backend); under a mesh layout it is the shard_map psum of
    ``core.aggregation.masked_psum_mean`` over the layout's dp axes.
    Tests prove the two paths agree.
  * ``grad_mean``         — the full-sync baseline (all-ones mask) with
    identical reduction order, so masked-vs-plain comparisons can demand
    bitwise equality.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation
from repro.dist import sharding as shd


def example_weights(mask: np.ndarray, global_batch: int) -> np.ndarray:
    """Per-worker bit array -> per-example loss weights (production path)."""
    return aggregation.example_weights(mask, global_batch)


def masked_grad_mean(grads, mask_bit, lay: Optional[shd.Layout] = None):
    """Masked mean over per-worker gradients: sum_w bit_w g_w / sum_w bit_w.

    ``grads`` leaves carry a leading worker dim (n_workers, ...); under a
    mesh layout n_workers must equal the layout's dp_size and the psum runs
    over the dp axes.  Under LOCAL the same reduction happens in-process,
    through the kernel-backend dispatch of ``ops.masked_aggregate_tree``.
    The worker dim is dropped from the result.
    """
    lay = lay if lay is not None else shd.layout()
    if lay.mesh is None or not lay.dp:
        from repro.kernels import ops
        return ops.masked_aggregate_tree(grads, jnp.asarray(mask_bit))
    return aggregation.masked_psum_mean(grads, mask_bit, lay.mesh, lay.dp)


def grad_mean(grads, lay: Optional[shd.Layout] = None):
    """Full-sync mean over the worker dim (the all-ones-mask special case,
    with the same reduction order as ``masked_grad_mean``)."""
    n = jax.tree.leaves(grads)[0].shape[0]
    ones = jnp.ones((n,), jnp.float32)
    return masked_grad_mean(grads, ones, lay)
