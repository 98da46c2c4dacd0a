"""Training entry points: cutoff train step + the production Trainer.

``make_train_step`` builds the jit-able step:

  * ``mask_agg="weights"`` (production, paper Alg. 1 / §4.3 variant):
    per-example weights carry the cutoff bit-array — masked gradients,
    renormalized by c, with no extra collectives beyond the DP psum GSPMD
    already emits;
  * ``mask_agg="psum"`` (explicit, Chen et al.'s PS semantics): the step
    computes per-worker microbatch gradients (leading worker dim, the
    grad-accum scan machinery) and aggregates them through
    ``dist.collectives.masked_grad_mean`` — the Pallas host combine under
    LOCAL, the shard_map psum under a mesh layout;
  * optional gradient accumulation (microbatching) — the activation-memory
    knob, also what overlaps per-microbatch gradient reduce with compute;
  * ZeRO-1/3: params FSDP-sharded over "model", optimizer moments optionally
    sharded over "data" too.

The ``Trainer`` is the host-side driver: controller -> bit-array ->
weights (or the bit array itself under ``mask_agg="psum"``), per-worker
sampling with replacement, simulated (or measured) step times,
checkpoint/restart (controller window + membership included), and mid-run
elastic resize (``Trainer.resize`` / a width-changing timer such as
``cluster.simulator.ChurnSim``).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.dist import collectives
from repro.dist import sharding as shd
from repro.models import model as M
from repro.obs.trace import span


# ---------------------------------------------------------------------------
# Train step.
# ---------------------------------------------------------------------------


def make_loss_fn(cfg, aux_coef: float = 0.01):
    from repro.perf.knobs import knobs

    def loss_fn(params, batch, normalizer):
        w = batch.get("weights")
        if knobs().ce_impl == "ring":
            x, _, aux = M.forward(cfg, params, batch, mode="train",
                                  head=False)
            ce_sum = M.ring_ce_sum(cfg, params, x, batch["labels"], w)
            loss = ce_sum / normalizer
            return loss + aux_coef * aux, {"ce": loss, "aux": aux}
        if knobs().ce_chunk > 0:
            x, _, aux = M.forward(cfg, params, batch, mode="train",
                                  head=False)
            ce_sum = M.chunked_ce_sum(cfg, params, x, batch["labels"], w,
                                      knobs().ce_chunk)
            loss = ce_sum / normalizer
            return loss + aux_coef * aux, {"ce": loss, "aux": aux}
        logits, _, aux = M.forward(cfg, params, batch, mode="train")
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, batch["labels"][..., None],
                                 axis=-1)[..., 0]
        ce = lse - ll
        if w is not None:
            wb = jnp.broadcast_to(w.astype(jnp.float32)[:, None], ce.shape)
            ce = ce * wb
        loss = jnp.sum(ce) / normalizer
        return loss + aux_coef * aux, {"ce": loss, "aux": aux}
    return loss_fn


MASK_AGG_MODES = ("weights", "psum")


def _split_batch(batch, parts: int):
    """Split every batch entry into ``parts`` leading microbatches."""
    def split(k, v):
        if k == "positions" and v.ndim == 3:
            return v.reshape(
                (3, parts, v.shape[1] // parts)
                + v.shape[2:]).swapaxes(0, 1)
        return v.reshape((parts, v.shape[0] // parts) + v.shape[1:])

    return {k: split(k, v) for k, v in batch.items()}


def make_train_step(cfg, optimizer: optim.Optimizer, *,
                    grad_accum: int = 1, aux_coef: float = 0.01,
                    compress_pod_grads: bool = False,
                    mask_agg: str = "weights", stale_reuse: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params", "opt", ["ef"]}.

    mask_agg="weights": batch["weights"] is the per-example cutoff mask
    expanded by ``dist.collectives.example_weights``; the masked mean is
    implicit in the loss normalization + the DP gradient psum.

    mask_agg="psum": batch["mask"] is the per-worker CONTRIBUTION vector
    ((n_workers,) float, n_workers | global batch).  The discard policy
    passes the 0/1 bit array; the anytime policy
    (``core.controller.AnytimeController``) passes completed-microbatch
    fractions in [0, 1].  The step scans the per-worker microbatches; a
    worker with contribution f keeps only its first ``round(f *
    grad_accum)`` microbatch gradients (the ``jax.lax.scan`` grad-accum
    partial sums — the partial work an anytime straggler actually
    shipped), normalized by its completed token count, and the stack is
    aggregated with ``collectives.masked_grad_mean`` weighted by f — an
    explicit combine whose numerics are independent of how many workers
    were dropped.  With an all-0/1 vector every multiplication is by
    exactly 1.0, so the generalized path is bit-identical to the bit-array
    path.  Costs n_workers x gradient memory; the production path is
    "weights".

    stale_reuse=True (mask_agg="psum" only, the
    ``core.controller.StaleReuseController`` policy): the step also
    returns the DROPPED workers' mean gradient under ``metrics["stale"]``
    (a ``(tree, count)`` pair the Trainer buffers), and consumes
    ``batch["stale_g"]`` / ``batch["stale_w"]`` — last step's dropped
    mean and its decayed weight — folding them into this step's masked
    mean in-jit: ``g = (c * g_fresh + w * g_stale) / (c + w)``.  With
    ``stale_w = 0`` the fold multiplies by exactly 1.0/0.0 and the
    update matches plain discard bit-for-bit.

    The weights and psum paths are exactly equivalent when the auxiliary
    loss is zero (dense archs, or aux_coef=0) and the contribution vector
    is 0/1.  For MoE archs they differ on dropped workers' load-balance
    aux: "psum" is the true PS semantics (a dropped worker contributes
    nothing, aux included), while "weights" leaves the aux term
    unweighted over the full batch.  For FRACTIONAL contributions they
    differ by design: "psum" aggregates the true partial microbatch sums,
    "weights" approximates them as f-scaled full-batch gradients (the
    per-example weight is f for every example of worker w).
    """
    if mask_agg not in MASK_AGG_MODES:
        raise ValueError(f"unknown mask_agg {mask_agg!r} "
                         f"(want one of {MASK_AGG_MODES})")
    if stale_reuse and mask_agg != "psum":
        raise ValueError(
            "stale_reuse needs per-worker gradients: build the step with "
            "mask_agg='psum' (the weights path never materializes a "
            "dropped worker's gradient to buffer)")
    loss_fn = make_loss_fn(cfg, aux_coef)

    def normalizer_of(batch):
        w = batch.get("weights")
        B, S = batch["tokens"].shape
        if w is None:
            return jnp.asarray(B * S, jnp.float32)
        return jnp.maximum(jnp.sum(w.astype(jnp.float32)) * S, 1e-6)

    def accum_grads_of(params, batch, norm, mb_w=None):
        """Summed-over-microbatches gradient at a fixed normalizer.

        ``mb_w`` (optional, (grad_accum,) f32): per-microbatch weights —
        the anytime partial-sum tap.  Each microbatch's gradient (and its
        loss/aux share) is scaled by its weight inside the scan, so a 0/1
        prefix vector yields exactly the straggler's completed partial
        sum.  ``None`` keeps the dense path byte-identical.
        """
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, norm)
            if mb_w is not None:
                w0 = mb_w[0]
                grads = jax.tree.map(lambda g: g * w0.astype(g.dtype),
                                     grads)
                loss = loss * w0
                metrics = {"ce": metrics["ce"] * w0,
                           "aux": metrics["aux"] * w0}
            return loss, metrics, grads

        mb = _split_batch(batch, grad_accum)

        def body(carry, xs):
            mbatch, w = xs if mb_w is not None else (xs, None)
            g_acc, l_acc, a_acc = carry
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mbatch, norm)
            aux = metrics["aux"]
            if w is not None:
                g = jax.tree.map(lambda x: x * w.astype(x.dtype), g)
                loss = loss * w
                aux = aux * w
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + loss, a_acc + aux), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss, aux), _ = jax.lax.scan(
            body, (g0, jnp.float32(0), jnp.float32(0)),
            (mb, mb_w) if mb_w is not None else mb)
        return loss, {"ce": loss, "aux": aux / grad_accum}, grads

    def grads_of(params, batch):
        return accum_grads_of(params, batch, normalizer_of(batch))

    def worker_grads_of(params, batch):
        """Per-worker gradients, stacked on a leading worker dim.

        Each worker w owns the w-th contiguous slice of the global batch
        (the ``example_weights`` convention).  A worker with contribution
        f keeps only its first ``round(f * grad_accum)`` microbatch
        gradients and normalizes by its COMPLETED token count (clamped at
        one microbatch so a zero-contribution worker's loss stays finite
        — its weight in the aggregation is 0 anyway), so the f-weighted
        mean over workers equals the anytime mean over completed
        microbatches, and a 0/1 vector reproduces the bit-array masked
        mean bit-for-bit (every scale is exactly 1.0 or 0.0).
        """
        mask = jnp.asarray(batch["mask"], jnp.float32)
        W = mask.shape[0]
        data = {k: v for k, v in batch.items()
                if k not in ("mask", "stale_g", "stale_w")}
        B, S = data["tokens"].shape
        assert B % W == 0, (B, W)
        base_norm = jnp.asarray((B // W) * S, jnp.float32)
        wb = _split_batch(data, W)

        def body(_, xs):
            mbatch, f = xs
            # completed-microbatch prefix: first round(f * G) of G
            done = jnp.round(f * grad_accum)
            mb_w = (jnp.arange(grad_accum) < done).astype(jnp.float32)
            norm = jnp.maximum(f, 1.0 / grad_accum) * base_norm
            loss, metrics, g = accum_grads_of(params, mbatch, norm,
                                              mb_w=mb_w)
            return None, (g, loss, metrics["ce"], metrics["aux"])

        _, (grads, losses, ces, auxs) = jax.lax.scan(body, None, (wb, mask))
        return grads, losses, ces, auxs

    def psum_grads_of(params, batch):
        mask = jnp.asarray(batch["mask"], jnp.float32)
        grads, losses, ces, auxs = worker_grads_of(params, batch)
        agg = collectives.masked_grad_mean(grads, mask)
        stale = None
        if stale_reuse:
            # the dropped workers' mean gradient, buffered by the Trainer
            # and folded into the NEXT step (Dutta et al.); stale reuse is
            # a 0/1-mask policy, so 1 - mask is the dropped bit array
            stale = (collectives.masked_grad_mean(grads, 1.0 - mask),
                     jnp.sum(1.0 - mask))
        c = jnp.maximum(jnp.sum(mask), 1.0)
        masked_mean = lambda x: jnp.sum(x * mask) / c
        return masked_mean(losses), {"ce": masked_mean(ces),
                                     "aux": masked_mean(auxs)}, agg, stale

    def train_step(state, batch):
        if mask_agg == "psum":
            loss, metrics, grads, stale = psum_grads_of(state["params"],
                                                        batch)
            if stale_reuse:
                # fold last step's dropped-worker mean in with its decayed
                # weight: g = (c * fresh + w * stale) / (c + w); w == 0
                # multiplies by exactly 1.0/0.0 => bit-identical discard
                c = jnp.maximum(
                    jnp.sum(jnp.asarray(batch["mask"], jnp.float32)), 1.0)
                w = jnp.asarray(batch["stale_w"], jnp.float32)
                denom = c + w
                grads = jax.tree.map(
                    lambda a, b: (a * (c / denom).astype(a.dtype)
                                  + b.astype(a.dtype)
                                  * (w / denom).astype(a.dtype)),
                    grads, batch["stale_g"])
        else:
            loss, metrics, grads = grads_of(state["params"], batch)
            stale = None
        if compress_pod_grads:
            grads, ef = optim.error_feedback_compress(grads,
                                                      state.get("ef"))
            new_ef = ef
        ups, opt = optimizer.update(grads, state["opt"], state["params"])
        params = optim.apply_updates(state["params"], ups)
        new_state = {"params": params, "opt": opt}
        if compress_pod_grads:
            new_state["ef"] = new_ef
        metrics = dict(metrics, loss=loss,
                       gnorm=optim.global_norm(grads))
        if stale_reuse:
            metrics["stale"] = stale
        return new_state, metrics

    return train_step


def jit_train_step(cfg, optimizer: optim.Optimizer, *, donate: bool = True,
                   **kwargs):
    """The one place train steps get jitted: donation-clean by default.

    ``donate=True`` donates argument 0 (the train state), so the params and
    optimizer moments update in place instead of doubling peak memory every
    step.  Callers must treat the state they pass in as CONSUMED — rebind to
    the returned state, never read the old one (the ``Trainer`` does this).
    ``**kwargs`` forward to :func:`make_train_step`.
    """
    return jax.jit(make_train_step(cfg, optimizer, **kwargs),
                   donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# Sharding trees for the train state.
# ---------------------------------------------------------------------------


def stacked_paths_for(cfg):
    segs = M.build_segments(M.layer_specs(cfg))
    paths = [f"segments/{i}" for i, s in enumerate(segs) if s.repeats > 1]
    if cfg.is_encoder_decoder:
        esegs = M.build_segments(M.encoder_layer_specs(cfg))
        paths += [f"encoder/segments/{i}" for i, s in enumerate(esegs)
                  if s.repeats > 1]
    return tuple(paths)


def state_shardings(cfg, params_tree, lay: shd.Layout, *,
                    zero1: bool = False, has_ef: bool = False):
    """NamedShardings for {"params", "opt"} given an (abstract) params tree.

    zero1: optimizer moments are additionally sharded over "data" on the dim
    the parameter is already "model"-sharded on (ZeRO-1 on top of ZeRO-3);
    XLA inserts the per-step weight-delta all-gather over "data".
    """
    sp = stacked_paths_for(cfg)
    pshard = shd.named_sharding(params_tree, lay, stacked_paths=sp)

    def widen(leaf, ns):
        if ns is None or lay.mesh is None:
            return ns
        dsize = 1
        for a in lay.dp:
            if a == "data":
                dsize = lay.mesh.shape[a]
        spec = list(ns.spec) + [None] * (leaf.ndim - len(ns.spec))
        for i, ax in enumerate(spec):
            if ax == lay.model_axis:
                tp = lay.mesh.shape[lay.model_axis]
                if leaf.shape[i] % (tp * dsize) == 0:
                    spec[i] = (lay.model_axis, "data")
                break
        return NamedSharding(lay.mesh, P(*spec))

    mom = (jax.tree.map(widen, params_tree, pshard) if zero1 else pshard)
    opt_shard = {"step": NamedSharding(lay.mesh, P()) if lay.mesh else None,
                 "m": mom, "v": mom, "mu": mom}
    out = {"params": pshard, "opt": opt_shard}
    if has_ef:
        out["ef"] = pshard
    return out


def abstract_state(cfg, optimizer: optim.Optimizer, key=None):
    """Shape-only train state via jax.eval_shape (no allocation)."""
    key = key if key is not None else jax.random.PRNGKey(0)

    def build():
        params = M.init_model(cfg, key)
        return {"params": params, "opt": optimizer.init(params)}

    return jax.eval_shape(build)


def filter_opt_shardings(opt_shard, opt_state_tree):
    """Keep only the sharding entries present in the actual opt state."""
    return {k: opt_shard[k] if k in opt_shard else None
            for k in opt_state_tree}


def clock_to_loss(history, target: float, window: int = 3):
    """Simulated wall-clock until the ``window``-step trailing mean loss
    reaches ``target``; None if the run never gets there.

    THE wall-clock-to-loss metric for Trainer trajectories — the
    acceptance tests, benches and demos all share this one
    implementation.  ``history`` is either a list of step records or the
    obs step stream (``repro.obs.StepStream`` — anything with a
    ``records`` attribute): benches that attach an ``ObsRun`` read the
    trajectory straight from the one recorder instead of re-threading
    their own ``(t, loss)`` lists.  Losses must already be drained
    floats, i.e. after ``run()`` returned.

    Only FULL windows are eligible: the first ``window - 1`` steps cannot
    trigger the target (a partial early window is a mean over fewer
    losses, so one lucky first step used to fire the target a true
    trailing mean would not).
    """
    records = getattr(history, "records", history)
    losses = [h["loss"] for h in records]
    for i in range(window - 1, len(losses)):
        if np.mean(losses[i - window + 1:i + 1]) <= target:
            return records[i]["clock"]
    return None


# ---------------------------------------------------------------------------
# Production Trainer (host-side driver).
# ---------------------------------------------------------------------------


@dataclass
class Trainer:
    """Cutoff-SGD trainer: controller + masked aggregation + fault tolerance.

    ``n_workers`` virtual workers map onto DP shards (one worker per shard on
    a real mesh; on CPU they are simulated).  ``timer`` provides per-worker
    step times each iteration: a ClusterSim / TraceReplay in this container,
    per-host wall-clock measurement on real hardware.

    ``mask_agg`` picks how the controller's bit array reaches the step
    (and must match the ``make_train_step`` the ``step_fn`` was built
    with): "weights" expands it to per-example loss weights (production),
    "psum" hands the bit array itself to the explicit per-worker gradient
    combine.

    The hot loop is asynchronous: the train step is dispatched (jax async
    dispatch) BEFORE the controller's observe/imputation runs, so the
    parameter server's inference for the next decision overlaps the
    device's gradient compute; per-step losses are kept as device scalars
    and only fetched in batches every ``metrics_every`` steps (and at eval
    / verbose / run-end boundaries).  ``metrics_every=1`` restores the
    blocking per-step loop (useful for benchmarking the overlap win);
    ``metrics_every=0`` drains only at boundaries.

    Elastic membership: when the timer exposes ``n_workers`` /
    ``active_ids`` (``ChurnSim``), the loop detects worker-set changes
    before each step and calls :meth:`resize` — the controller's lag
    window is remapped (survivors column-exact), the bit-array/weights
    plumbing is rebuilt at the new width, and ``B % W`` divisibility is
    re-checked for both ``mask_agg`` paths.  Checkpoints carry the
    controller window, step and membership (the ``"ctl"`` group), so a
    restart mid-churn resumes with a warm straggler predictor at the
    checkpoint's worker count.
    """
    cfg: Any
    step_fn: Callable
    data: Any
    controller: Any
    timer: Any = None
    n_workers: int = 8
    mask_agg: str = "weights"
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    metrics_every: int = 10

    # telemetry (optional): an ``repro.obs.ObsRun``.  Attaching one adds
    # one device metric-ring push per step and forwards drained history
    # records to the obs step stream (the step's spans are there either
    # way; an open ObsRun records them) — and NOTHING else: decisions,
    # RNG streams and parameters stay bit-identical with obs on or off
    # (tests/test_obs.py pins this).
    obs: Any = None
    name: Optional[str] = None                # job/run label for obs streams

    state: Dict = None
    step: int = 0
    sim_clock: float = 0.0
    members: Optional[np.ndarray] = None      # global worker ids
    history: list = field(default_factory=list)
    _pending_metrics: list = field(default_factory=list, repr=False)
    # stale-reuse buffer: last step's (dropped-mean tree, count) device pair
    _stale: Any = field(default=None, repr=False)

    def restore_or_init(self, init_state_fn):
        """Restore from the newest VALID checkpoint, else init cold.

        Steps are tried newest-first: a corrupt or truncated step
        (``store.CheckpointError`` — bad checksum, missing group, torn
        manifest) is skipped and the previous one is used, so a damaged
        latest checkpoint degrades to losing ``ckpt_every`` steps
        instead of killing the restart.  The controller group is
        restored from the SAME step as the train state.
        """
        from repro.checkpoint import store
        if self.members is None:
            self.members = np.arange(self.n_workers)
        steps = (list(reversed(store.list_steps(self.ckpt_dir)))
                 if self.ckpt_dir else [])
        example = init_state_fn()
        for step in steps:
            try:
                restored = store.restore(self.ckpt_dir,
                                         {"state": example, "meta": {
                                             "step": 0, "clock": 0.0}},
                                         step=step)
                self.state = restored["state"]
                self.step = int(restored["meta"]["step"])
                self.sim_clock = float(restored["meta"]["clock"])
                self._restore_controller(store, step)
                return self
            except store.CheckpointError as e:
                print(f"checkpoint step {step} unusable ({e}); "
                      f"falling back to the previous step")
        self.state = example
        return self

    def _restore_controller(self, store, step=None):
        """Warm-restore the straggler predictor from the ``ctl`` group."""
        grp = store.restore_group(self.ckpt_dir, "ctl", step=step)
        if grp is None:
            return
        n_saved = int(grp["n"])
        members = np.asarray(grp["members"], int)
        if (n_saved != self.n_workers
                or not np.array_equal(members, self.members)):
            # the checkpoint was taken mid-churn with a different worker
            # set: remap onto the SAVED membership (survivor columns by
            # global id, not by position — the set may not be a prefix)
            old = {wid: col for col, wid in enumerate(self.members)}
            col_map = np.array([old.get(wid, -1) for wid in members], int)
            self.resize(n_saved, col_map=col_map, members=members)
        ctl = self.controller
        if "window" in grp and hasattr(ctl, "seed_window"):
            ctl.seed_window(grp["window"])
        if hasattr(ctl, "_step"):
            ctl._step = int(grp["step"])

    def _controller_ckpt(self) -> Dict[str, np.ndarray]:
        members = (self.members if self.members is not None
                   else np.arange(self.n_workers))
        grp = {"n": np.int64(self.n_workers),
               "members": np.asarray(members, np.int64),
               "step": np.int64(getattr(self.controller, "_step",
                                        self.step))}
        if hasattr(self.controller, "window_array"):
            try:
                grp["window"] = np.asarray(self.controller.window_array(),
                                           np.float64)
            except ValueError:      # window still empty (cold controller)
                pass
        return grp

    # -- elastic membership --------------------------------------------
    def resize(self, n_workers: int, col_map=None, members=None):
        """Elastic worker-membership change, mid-run.

        Re-checks global-batch divisibility for the new width (both
        ``mask_agg`` paths slice the global batch into per-worker
        contiguous shards), remaps the controller's lag window
        (``col_map`` as in ``core.controller.remap_columns``), and
        records the new membership for the checkpoint meta.  The train
        step itself is width-agnostic — the next step's bit array simply
        has the new length (a new jit trace under ``mask_agg="psum"``).
        """
        n_new = int(n_workers)
        B = getattr(self.data, "global_batch", None)
        if B is not None and B % n_new != 0:
            raise ValueError(
                f"cannot resize to {n_new} workers: global batch {B} is "
                f"not divisible by the worker count (mask_agg="
                f"{self.mask_agg!r} slices the batch into B//W per-worker "
                f"shards — pick a worker count that divides {B})")
        if hasattr(self.controller, "resize"):
            # members: GLOBAL worker ids — part of the controller resize
            # protocol; width-only controllers ignore them, the
            # multi-tenant handle records them for restore-by-global-id
            self.controller.resize(n_new, col_map=col_map, members=members)
        elif getattr(self.controller, "n", n_new) != n_new:
            raise ValueError(
                f"controller {type(self.controller).__name__} cannot "
                f"resize to {n_new} workers")
        self.n_workers = n_new
        self.members = (np.asarray(members, int) if members is not None
                        else np.arange(n_new))
        return self

    def _sync_membership(self):
        """Follow the timer's worker set (ChurnSim) before each step."""
        if self.members is None:
            self.members = np.arange(self.n_workers)
        if self.timer is None:
            return
        ids = getattr(self.timer, "active_ids", None)
        w = int(getattr(self.timer, "n_workers", self.n_workers))
        if ids is None:
            if w != self.n_workers:
                self.resize(w)          # prefix survivors
            return
        ids = np.asarray(ids, int)
        if w == self.n_workers and np.array_equal(ids, self.members):
            return
        old = {wid: col for col, wid in enumerate(self.members)}
        col_map = np.array([old.get(wid, -1) for wid in ids], int)
        self.resize(w, col_map=col_map, members=ids)

    def _drain_metrics(self):
        """Fetch every pending device-side loss into its history record
        (and forward the now-host-resident records to the obs step
        stream — the one recorder every trajectory consumer reads)."""
        for rec in self._pending_metrics:
            rec["loss"] = float(rec["loss"])
            if self.obs is not None:
                self.obs.steps.on_step(rec, job=self.name)
        self._pending_metrics.clear()
        if self.obs is not None:
            # the obs drain rides the same boundary as the loss fetch:
            # decision scoring + device metric rings come back here, and
            # ONLY here — never inside the step
            with span("obs.drain", step=self.step):
                self.obs.drain()

    def run(self, n_steps: int, *, eval_fn=None, eval_every: int = 0,
            verbose: bool = False):
        from repro.checkpoint import store
        ckpt = (store.AsyncCheckpointer(self.ckpt_dir, self.keep)
                if self.ckpt_dir else None)
        ring = (self.obs.metrics.ring(
            "trainer" if self.name is None else f"trainer[{self.name}]",
            ("loss", "gnorm", "c", "iter_time"))
            if self.obs is not None else None)
        for _ in range(n_steps):
            with span("trainer.step", step=self.step + 1, job=self.name):
                self._sync_membership()  # elastic: follow the timer's width
                n = self.n_workers
                c = min(int(self.controller.predict_cutoff()), n)
                with span("trainer.timer"):
                    times = (self.timer.step() if self.timer is not None
                             else np.ones(n))
                    # fastest c workers participate (the PS's bit array)
                    order = np.argsort(times)
                    mask = np.zeros(n, np.float32)
                    mask[order[:c]] = 1.0
                    iter_time = float(times[order[c - 1]])
                    # the controller must see the SAME worker set the
                    # aggregation used: under ties, a times<=iter_time
                    # threshold marks MORE than c workers finished and
                    # the two views diverge
                    finished = mask.astype(bool)

                # anytime policy: stragglers contribute their completed
                # fraction instead of a zeroed bit; finishers stay 1.0
                contrib = mask
                if hasattr(self.controller, "contribution"):
                    contrib = np.asarray(
                        self.controller.contribution(times, c), np.float32)

                with span("trainer.batch"):
                    batch = dict(self.data.batch(self.step))
                    if self.mask_agg == "psum":
                        batch["mask"] = jnp.asarray(contrib)
                    else:
                        batch["weights"] = collectives.example_weights(
                            contrib, batch["tokens"].shape[0])
                decay = getattr(self.controller, "stale_decay", None)
                if decay is not None:
                    if self.mask_agg != "psum":
                        raise ValueError(
                            "StaleReuseController needs mask_agg='psum' "
                            "(the weights path never materializes a "
                            "dropped worker's gradient to buffer)")
                    if self._stale is None:
                        zeros = jax.tree.map(
                            lambda p: jnp.zeros(p.shape, jnp.float32),
                            self.state["params"])
                        self._stale = (zeros, jnp.float32(0))
                    stale_g, stale_d = self._stale
                    batch["stale_g"] = stale_g
                    # decayed weight of the buffered mean: decay per
                    # worker that contributed to it, kept lazy on device
                    batch["stale_w"] = jnp.float32(decay) * stale_d
                # dispatch the train step FIRST (async), then run the
                # PS's observe/imputation so controller inference
                # overlaps compute
                with span("train.dispatch"):
                    self.state, metrics = self.step_fn(self.state, batch)
                if decay is not None:
                    if "stale" not in metrics:
                        raise ValueError(
                            "StaleReuseController needs a step_fn built "
                            "with make_train_step(..., mask_agg='psum', "
                            "stale_reuse=True) — this one returned no "
                            "metrics['stale'] buffer")
                    self._stale = metrics.pop("stale")
                self.controller.observe(times, finished)
                self.step += 1
                self.sim_clock += iter_time
                rec = {"step": self.step, "clock": self.sim_clock, "c": c,
                       "n": n, "iter_time": iter_time,
                       "loss": metrics["loss"]}  # device scalar; drained
                self.history.append(rec)
                self._pending_metrics.append(rec)
                if ring is not None:
                    # ONE donated in-jit push; loss/gnorm stay lazy
                    ring.push((metrics["loss"], metrics["gnorm"],
                               float(c), iter_time))
                if (self.metrics_every
                        and self.step % self.metrics_every == 0):
                    self._drain_metrics()
                if eval_fn and eval_every and self.step % eval_every == 0:
                    self._drain_metrics()
                    rec["eval"] = float(eval_fn(self.state))
                if verbose and self.step % 20 == 0:
                    self._drain_metrics()
                    print(f"  step {self.step}: loss={rec['loss']:.4f} "
                          f"c={c}/{n} t={iter_time:.3f}s "
                          f"clock={self.sim_clock:.1f}s")
                if ckpt and self.step % self.ckpt_every == 0:
                    ckpt.save(self.step, {
                        "state": self.state,
                        "meta": {"step": self.step,
                                 "clock": self.sim_clock},
                        "ctl": self._controller_ckpt()})
        self._drain_metrics()
        if ckpt:
            ckpt.wait()
        return self.history
