"""Layouts: how logical axes (dp / sp / tp) map onto mesh axes per mode.

Every model and launch module imports this as ``shd`` and programs against
one small surface:

  * ``Layout``          — frozen description of one execution mode on one
    mesh: which mesh axes carry data-parallel batch shards (``dp``), which
    single axis carries the model sharding (``model_axis``), and how the
    sequence (``seq_axis``) and feature (``tp_axis``) dims of activations
    are split in that mode.
  * ``LOCAL``           — the no-mesh layout: every helper below becomes a
    pure no-op, so the same model code runs eagerly on one CPU device.
  * ``make_layout``     — mode -> Layout.  Modes:
      - ``train_sp``:   batch over the dp axes, sequence over "model"
        (context/sequence parallelism), params ZeRO-3 over "model".
      - ``train_fsdp``: pure batch-parallel ZeRO-3 — batch over the WHOLE
        mesh, no sequence sharding, params still ZeRO-3 over "model".
      - ``decode_tp``:  batch over dp, features over "model" (tensor
        parallelism), KV caches sequence-sharded over "model".
  * ``use_layout`` / ``layout``      — contextvar holding the active layout
    (read at trace time, so ``with use_layout(lay)`` inside a jitted
    function body works).
  * ``unroll_loops`` / ``unrolled``  — ask inner loops (attention q-chunks,
    SSM chunk scans) to unroll instead of ``lax.scan``/``lax.map`` so XLA
    cost analysis counts every iteration (dry-run accounting).
  * ``act(x, dp, sp, tp)``           — activation sharding constraint for
    dims 0/1/2; each argument names a logical kind ("dp"/"sp"/"tp") or
    ``None`` to pin that dim replicated (== force a gather).
  * ``use_weight(tree)``             — FSDP use-site gather hint for
    ZeRO-3-sharded weights (identity under LOCAL and decode_tp).
  * ``named_sharding(tree, lay, stacked_paths=...)`` — NamedShardings for a
    parameter pytree (ZeRO-3 rules; ``stacked_paths`` marks subtrees whose
    leaves carry a leading ``lax.scan`` repeats dim).

See ``src/repro/dist/README.md`` for the LOCAL-vs-mesh contract.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
from jax._src.lax.parallel import all_gather_invariant
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODES = ("local", "train_sp", "train_fsdp", "decode_tp")


@dataclass(frozen=True)
class Layout:
    """One execution mode's logical-axis -> mesh-axis map."""
    mesh: Optional[Mesh] = None
    mode: str = "local"
    dp: Tuple[str, ...] = ()            # axes sharding the batch dim
    model_axis: Optional[str] = None    # the model axis (FSDP / SP / TP)
    seq_axis: Optional[str] = None      # axis sharding the sequence dim
    tp_axis: Optional[str] = None       # axis sharding feature dims

    @property
    def dp_size(self) -> int:
        """Number of data-parallel shards (1 under LOCAL)."""
        if self.mesh is None or not self.dp:
            return 1
        size = 1
        for a in self.dp:
            size *= self.mesh.shape[a]
        return size

    @property
    def n_shards(self) -> int:
        """Size of the model axis (1 under LOCAL)."""
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    def axis(self, kind: Optional[str]):
        """Logical kind -> mesh axis name(s): "dp" -> tuple (or None when
        empty), "sp"/"tp" -> single axis name or None, None -> None."""
        if kind is None:
            return None
        if kind == "dp":
            return self.dp if self.dp else None
        if kind == "sp":
            return self.seq_axis
        if kind == "tp":
            return self.tp_axis
        raise ValueError(f"unknown logical axis kind {kind!r}")

    def dp_for(self, batch_size: int):
        """dp axes if they divide ``batch_size``, else None (replicate)."""
        if not self.dp or batch_size % self.dp_size != 0:
            return None
        return self.dp


LOCAL = Layout()


def make_layout(mesh: Optional[Mesh], mode: str) -> Layout:
    """Build the Layout for ``mode`` on ``mesh``.

    The model axis is the mesh axis named "model" (last axis as fallback);
    every other axis is data-parallel ("pod" crosses DCN and only ever
    carries batch).  ``mesh=None`` returns LOCAL regardless of mode.
    """
    if mesh is None:
        return LOCAL
    if mode not in MODES or mode == "local":
        raise ValueError(f"unknown layout mode {mode!r} (want one of "
                         f"{MODES[1:]})")
    names = tuple(mesh.axis_names)
    model = "model" if "model" in names else names[-1]
    others = tuple(a for a in names if a != model)
    if mode == "train_sp":
        return Layout(mesh=mesh, mode=mode, dp=others, model_axis=model,
                      seq_axis=model, tp_axis=None)
    if mode == "train_fsdp":
        return Layout(mesh=mesh, mode=mode, dp=names, model_axis=model,
                      seq_axis=None, tp_axis=None)
    # decode_tp
    return Layout(mesh=mesh, mode=mode, dp=others, model_axis=model,
                  seq_axis=None, tp_axis=model)


# ---------------------------------------------------------------------------
# Active layout / unroll flags (contextvars: cheap, trace-time, re-entrant).
# ---------------------------------------------------------------------------


_layout_var: contextvars.ContextVar[Layout] = contextvars.ContextVar(
    "repro_layout", default=LOCAL)
_unroll_var: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_unroll", default=False)


def layout() -> Layout:
    """The active Layout (LOCAL when none was installed)."""
    return _layout_var.get()


@contextlib.contextmanager
def use_layout(lay: Layout):
    """Install ``lay`` as the active layout; restores the previous layout
    on exit (nesting-safe)."""
    tok = _layout_var.set(lay)
    try:
        yield lay
    finally:
        _layout_var.reset(tok)


def unrolled() -> bool:
    """True when inner loops should unroll (dry-run cost accounting)."""
    return _unroll_var.get()


@contextlib.contextmanager
def unroll_loops(flag: bool = True):
    """Unroll scan/map inner loops within the context (see ``unrolled``)."""
    tok = _unroll_var.set(flag)
    try:
        yield
    finally:
        _unroll_var.reset(tok)


# ---------------------------------------------------------------------------
# Activation constraints.
# ---------------------------------------------------------------------------


def _axes_size(lay: Layout, ax) -> int:
    axes = ax if isinstance(ax, tuple) else (ax,)
    size = 1
    for a in axes:
        size *= lay.mesh.shape[a]
    return size


def act(x, dp=None, sp=None, tp=None):
    """Sharding constraint for an activation's (batch, seq, feature) dims.

    ``dp``/``sp``/``tp`` name the logical kind for dims 0/1/2 (any of
    "dp"/"sp"/"tp", or None to pin the dim replicated — i.e. force XLA to
    gather it).  Dims past the first three stay unconstrained-replicated.
    A dim whose size does not divide its mesh axes falls back to
    replicated.  No-op under LOCAL.
    """
    lay = layout()
    if lay.mesh is None:
        return x
    kinds = (dp, sp, tp)
    spec = []
    for i in range(x.ndim):
        kind = kinds[i] if i < 3 else None
        ax = lay.axis(kind)
        if ax is not None and x.shape[i] % _axes_size(lay, ax) != 0:
            ax = None
        spec.append(ax)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(lay.mesh, P(*spec)))


# ---------------------------------------------------------------------------
# Weight use-site hint (ZeRO-3 gather).
# ---------------------------------------------------------------------------


def use_weight(tree):
    """Mark ZeRO-3-sharded weights as gathered for use.

    LOCAL and decode_tp: identity (decode keeps weights TP-sharded and lets
    GSPMD partition the matmuls).  Train modes: by default a replicated
    sharding constraint ("wsc") — XLA inserts the use-site all-gather and
    transposes it to a reduce-scatter of the weight gradients; under
    ``knobs().fsdp_gather == "shardmap"`` an explicit shard_map all-gather
    over the model axis (dim-0-sharded leaves only).  That gather is
    ``all_gather_invariant`` (varying in, replicated out, as
    ``out_specs=P()`` states); its AD transpose slices each shard's rows
    out of the reduced weight cotangent.
    """
    lay = layout()
    if lay.mesh is None or lay.mode not in ("train_sp", "train_fsdp"):
        return tree
    from repro.perf.knobs import knobs  # local import: knobs has no deps
    impl = knobs().fsdp_gather
    mesh, m, tp = lay.mesh, lay.model_axis, lay.n_shards

    def gather(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return leaf
        if (impl == "shardmap" and m is not None and tp > 1
                and leaf.shape[0] % tp == 0):
            # varying -> invariant gather: the output really is the same
            # on every model shard, which is what out_specs=P() asserts
            def body(w_l):
                return all_gather_invariant(w_l, m, axis=0, tiled=True)
            return jax.shard_map(body, mesh=mesh, in_specs=P(m),
                                 out_specs=P())(leaf)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, P(*([None] * leaf.ndim))))

    return jax.tree.map(gather, tree)


# ---------------------------------------------------------------------------
# Parameter-tree shardings.
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            parts.append(str(e.key))
        elif isinstance(e, jax.tree_util.SequenceKey):
            parts.append(str(e.idx))
        elif isinstance(e, jax.tree_util.GetAttrKey):
            parts.append(str(e.name))
        else:  # FlattenedIndexKey and friends
            parts.append(str(getattr(e, "key", e)))
    return "/".join(parts)


def named_sharding(tree, lay: Layout, *, stacked_paths: Sequence[str] = ()):
    """NamedShardings for a parameter pytree under ``lay``.

    ZeRO-3 rule: each leaf is sharded over ``lay.model_axis`` on its first
    divisible dim — dim 0 normally, dim 1 for leaves under a
    ``stacked_paths`` prefix (their dim 0 is the ``lax.scan`` repeats dim
    and must stay whole per scan step).  ``decode_tp`` prefers the LAST dim
    (feature tensor-parallelism).  Leaves with no divisible dim replicate.
    Returns a tree of ``None`` when ``lay.mesh`` is None (LOCAL).
    """
    if lay.mesh is None:
        return jax.tree.map(lambda _: None, tree)
    m, tp = lay.model_axis, lay.n_shards
    stacked_paths = tuple(stacked_paths)

    def spec_for(path, leaf):
        ps = _path_str(path)
        stacked = any(ps == s or ps.startswith(s + "/")
                      for s in stacked_paths)
        nd = leaf.ndim
        spec = [None] * nd
        if m is not None:
            start = 1 if stacked else 0
            dims = list(range(start, nd))
            if lay.mode == "decode_tp":
                dims = dims[::-1]
            for i in dims:
                if leaf.shape[i] >= tp and leaf.shape[i] % tp == 0:
                    spec[i] = m
                    break
        return NamedSharding(lay.mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(spec_for, tree)
