"""Multi-tenant parameter server: batched device decisions for J jobs.

A production cluster runs many training jobs at once, and each one needs
the paper's cutoff decision every step.  Dispatching J separate fused
jits per tick pays the dispatch overhead J times for tiny per-job
compute; this module multiplexes every job through ONE vmapped decision:

  * :class:`JobRegistry` — admit/evict/resize bookkeeping.  Each job owns
    its :class:`~repro.core.runtime_model.api.RuntimeModel`, its worker
    membership, a priority, and a checkpoint-group name.
  * :class:`PSServer` — the decision plane.  Jobs of the same DMM
    architecture (lag, k_samples, z_dim, hidden) share a *bucket* even at
    MIXED worker widths: their lag windows live stacked in one
    ``(J_b, lag+1, n_pad)`` device ring, their params are zero-padded to
    the bucket width (``stack_models_padded``), and per-job TRACED width
    masks inside the jit (``controller._batched_observe_decide_ragged``)
    keep each job's decision exactly its own.  ``flush()`` therefore
    issues ONE vmapped dispatch per tick regardless of the job mix —
    observation rows, masks, predictive moments, PRNG keys and censor
    flags travel in one host-packed upload.
  * :class:`JobHandle` — a controller-protocol facade (`predict_cutoff` /
    `observe` / `resize` / `seed_window` / `window_array`), so one
    ``launch.train.Trainer`` per job drives the shared server unchanged,
    checkpointing included (the ``"ctl"`` group works verbatim).

Per-job elasticity follows the :class:`~repro.core.controller
.ElasticController` protocol: ``resize`` without a refit model remaps the
job's window (survivors column-exact), detaches it from the batched path
onto a warm-seeded Elfving fallback, and refits the DMM from the
surviving trace once ``refit_fresh`` fresh observations arrive — then the
job rejoins its (new) bucket.  With ``refit_async=True`` the ELBO refit
runs on a worker thread (``controller._spawn_refit`` — the exact task
shape :class:`~repro.core.controller.ElasticController` uses), so a tick
served during an active refit never blocks on ``model.fit``; results
stale by resize generation are discarded, never installed.

Semantics contract: a ``PSServer`` with J=1 produces the IDENTICAL cutoff
sequence as a bare ``CutoffController(backend="device")`` over a seeded
run (tests/test_ps_server.py), and J>1 jobs — mixed widths included —
match J looped single-job controllers to f32-window precision: batching
amortizes dispatch, it never changes the decision.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import controller as C
from repro.core.cutoff import order_stats
from repro.core.runtime_model.api import RuntimeModel, stack_models_padded
from repro.obs.trace import span


# ---------------------------------------------------------------------------
# Batched jit entries.  The flush path uploads ONE host-packed
# (4, m, n_pad) f32 block [times, mask, mu, std], ONE (m, 4) uint32 key
# block [decide key | impute base key], the (m,) impute steps and the (m,)
# censor flags; everything else (key folding, mask decode, gather/scatter)
# happens in-jit, so a tick costs a fixed number of transfers no matter
# how many jobs it serves.
# ---------------------------------------------------------------------------


def _unpack_obs(pack, keys, steps, cen):
    """Decode the packed observation block into the per-job obs pytree
    ``controller._ragged_append_core`` consumes.  The impute keys are
    folded in-jit (vmapped ``fold_in``), bit-identical to
    ``controller._impute_key(seed, step)`` per job."""
    return {"times": pack[0], "mask": pack[1] > 0.5,
            "mu": pack[2], "std": pack[3],
            "key": jax.vmap(jax.random.fold_in)(keys[:, 2:], steps),
            "cen": cen}


@functools.partial(jax.jit, static_argnames=("k_samples",))
def _full_observe_decide(params, rings, heads, pack, keys, steps, cen,
                         scales, widths, los, *, k_samples: int):
    """The steady-state tick: every bucket row is serviced, in slot
    order — no gather, no scatter, the whole stack updates in place."""
    obs = _unpack_obs(pack, keys, steps, cen)
    return C._batched_observe_decide_ragged(
        params, rings, heads, obs, keys[:, :2], scales, widths, los,
        k_samples=k_samples)


@functools.partial(jax.jit, static_argnames=("k_samples",))
def _subset_observe_decide(params, rings, heads, idx, pack, keys, steps,
                           cen, scales, widths, los, *, k_samples: int):
    """Service an arbitrary subset of a bucket in ONE dispatch (gather
    rows -> vmapped observe+decide -> scatter back)."""
    p = jax.tree.map(lambda x: x[idx], params)
    obs = _unpack_obs(pack, keys, steps, cen)
    r, h, cut, samp, mu, std, it = C._batched_observe_decide_ragged(
        p, rings[idx], heads[idx], obs, keys[:, :2], scales[idx],
        widths[idx], los[idx], k_samples=k_samples)
    return rings.at[idx].set(r), heads.at[idx].set(h), cut, samp, mu, std, it


@functools.partial(jax.jit, static_argnames=("k_samples",))
def _full_decide(params, rings, heads, keys, scales, widths, los, *,
                 k_samples: int):
    return C._batched_decide_ragged(params, rings, heads, keys, scales,
                                    widths, los, k_samples=k_samples)


@functools.partial(jax.jit, static_argnames=("k_samples",))
def _subset_decide(params, rings, heads, idx, keys, scales, widths, los,
                   *, k_samples: int):
    # decide-only never mutates the ring, so return just the decision —
    # scattering identical rows back would copy the whole bucket stack
    p = jax.tree.map(lambda x: x[idx], params)
    return C._batched_decide_ragged(p, rings[idx], heads[idx], keys,
                                    scales[idx], widths[idx], los[idx],
                                    k_samples=k_samples)


def _seed_ring(rows: np.ndarray, cap: int, n: int, n_pad: int):
    """Build the (cap, n_pad) f32 ring + head a fresh controller would
    reach by appending width-n ``rows`` with full masks — without cap
    device dispatches.  Plain appends write the f32 times verbatim, so
    the real columns are bit-exact; pad columns stay zero (the decision
    masks them out in-jit, it never reads them)."""
    rows = np.asarray(rows, np.float32)[-cap:]
    ring = np.zeros((cap, n_pad), np.float32)
    m = rows.shape[0]
    ring[:m, :n] = rows
    return ring, m % cap, min(m, cap)


# ---------------------------------------------------------------------------
# Job records + registry.
# ---------------------------------------------------------------------------


@dataclass
class PSJob:
    """One tenant of the shared parameter server (registry record)."""
    job_id: str
    model: Optional[RuntimeModel]
    members: np.ndarray                 # global worker ids
    priority: float
    admit_order: int
    k_samples: int
    min_frac: float
    seed: int
    ckpt_group: str

    width: int = 0                      # current worker count
    step: int = 0                       # controller step counter
    count: int = 0                      # rows in the lag window
    mode: str = "dmm"                   # "dmm" | "fallback"
    slot: int = -1                      # row in the bucket stack
    bucket_sig: Optional[tuple] = None
    fallback: Optional[C.ElfvingController] = None
    fresh: int = 0                      # observations since last (re)fit
    resize_count: int = 0
    refit_failures: int = 0             # consecutive failed async fits
    fallback_steps: int = 0
    trace: list = field(default_factory=list, repr=False)  # refit data
    # decision plumbing (device refs, fetched lazily)
    pending: Optional[tuple] = None     # (dstep, row, outputs dict)
    pending_pred: Optional[tuple] = None  # (mu row, std row, samples, row)
    last_iter: Optional[float] = None   # E[x_(c)] of the last decision
    queued: bool = False
    # async refit in flight: controller._spawn_refit triple
    refit_task: Optional[tuple] = None
    # architecture template for refits (widths change, shapes don't)
    lag: int = 20
    z_dim: int = 32
    hidden: int = 64

    @property
    def cap(self) -> int:
        return self.lag + 1

    @property
    def warmed_up(self) -> bool:
        return self.mode == "dmm" and self.count >= self.cap


class JobRegistry:
    """Admission bookkeeping for the multi-tenant server.

    Owns the job records: who is admitted, their RuntimeModel, worker
    membership, scheduling priority, and per-job checkpoint-group name
    (``ps/<job_id>``).  The decision-plane state (stacked rings, pending
    batched outputs) belongs to :class:`PSServer`.
    """

    def __init__(self):
        self._jobs: Dict[str, PSJob] = {}
        self._admitted = 0

    def admit(self, job_id: str, model: RuntimeModel, *,
              members=None, priority: float = 0.0, k_samples: int = 64,
              min_frac: float = 0.5, seed: int = 0) -> PSJob:
        if job_id in self._jobs:
            raise ValueError(f"job {job_id!r} already admitted")
        if model.params is None:
            raise ValueError(f"job {job_id!r}: admit a fitted RuntimeModel")
        members = (np.asarray(members, int) if members is not None
                   else np.arange(model.n_workers))
        if members.shape != (model.n_workers,):
            raise ValueError(
                f"job {job_id!r}: {members.shape[0]} members for a "
                f"width-{model.n_workers} model")
        job = PSJob(job_id=job_id, model=model, members=members,
                    priority=float(priority), admit_order=self._admitted,
                    k_samples=int(k_samples), min_frac=float(min_frac),
                    seed=int(seed), ckpt_group=f"ps/{job_id}",
                    width=model.n_workers, lag=model.lag,
                    z_dim=model.z_dim, hidden=model.hidden)
        self._jobs[job_id] = job
        self._admitted += 1
        return job

    def evict(self, job_id: str) -> PSJob:
        return self._jobs.pop(job_id)

    def __getitem__(self, job_id: str) -> PSJob:
        return self._jobs[job_id]

    def __contains__(self, job_id: str) -> bool:
        return job_id in self._jobs

    def __len__(self) -> int:
        return len(self._jobs)

    def ids(self) -> List[str]:
        """Admitted job ids in admission order."""
        return [j.job_id for j in
                sorted(self._jobs.values(), key=lambda j: j.admit_order)]

    def jobs(self) -> List[PSJob]:
        return [self._jobs[i] for i in self.ids()]

    def set_priority(self, job_id: str, priority: float):
        self._jobs[job_id].priority = float(priority)


# ---------------------------------------------------------------------------
# The decision plane.
# ---------------------------------------------------------------------------


class _Bucket:
    """Jobs of one DMM architecture, windows stacked in ONE device ring.

    ``n_pad`` is the bucket's pad width — the max worker width of its
    jobs.  It grows when a wider job joins (host repack, one upload) and
    shrinks when the widest leaves, so a bucket that happens to be
    same-width carries zero padding and its math is shape-identical to
    an unpadded stack."""

    def __init__(self, cap: int, k_samples: int):
        self.cap = cap
        self.k_samples = k_samples
        self.n_pad = 0
        self.jobs: List[PSJob] = []
        self.rings = jnp.zeros((0, cap, 0), jnp.float32)
        self.heads = jnp.zeros((0,), jnp.int32)
        self._stacked = None    # (params, scales, widths, los) cache

    def stacked(self):
        if self._stacked is None:
            params, scales = stack_models_padded(
                [j.model for j in self.jobs], self.n_pad)
            widths = jnp.asarray([j.width for j in self.jobs], jnp.int32)
            los = jnp.asarray(
                [order_stats.min_frac_floor(j.width, j.min_frac)
                 for j in self.jobs], jnp.int32)
            self._stacked = (params, scales, widths, los)
        return self._stacked

    def dirty(self):
        self._stacked = None

    def repack(self, n_pad_new: int):
        """Re-home every ring at a new pad width (host roundtrip, ONE
        upload).  Caller guarantees every job width fits ``n_pad_new``,
        so truncation only ever drops zero pad columns."""
        if self.jobs:
            old = np.asarray(self.rings)
            new = np.zeros((old.shape[0], self.cap, n_pad_new), np.float32)
            w = min(old.shape[2], n_pad_new)
            new[:, :, :w] = old[:, :, :w]
            self.rings = jnp.asarray(new)
        else:
            self.rings = jnp.zeros((0, self.cap, n_pad_new), jnp.float32)
        self.n_pad = n_pad_new
        self.dirty()


class PSServer:
    """The multi-tenant decision plane (see module docstring).

    Tick protocol (what ``launch.multi_job.MultiJobDriver`` runs)::

        server.prefetch(serviced)        # cold decisions, one dispatch
        for job_id in serviced:          # scheduler's order
            c = server.predict_cutoff(job_id)   # lazy host fetch
            ... run the job's train step with the bit array ...
            server.observe(job_id, times, mask)  # enqueues
        server.flush()                   # ONE vmapped dispatch per
                                         # architecture bucket — widths
                                         # and impute modes all ride it

    ``flush`` is also called implicitly whenever a job with a queued
    observation is asked to predict, so a ``JobHandle`` behaves like a
    plain controller even without a driver calling ``flush``.
    """

    def __init__(self, registry: Optional[JobRegistry] = None, *,
                 history: int = 512, refit_steps: int = 150,
                 refit_batch: int = 8, refit_fresh: int = 4,
                 refit_async: bool = False, fallback_warmup: int = 3,
                 refit_retries: int = 1, obs=None):
        self.registry = registry if registry is not None else JobRegistry()
        self.history = history
        self.refit_steps = refit_steps
        self.refit_batch = refit_batch
        self.refit_fresh = refit_fresh
        self.refit_async = refit_async
        self.fallback_warmup = fallback_warmup
        self.refit_retries = refit_retries
        # optional repro.obs.ObsRun: refit-gate activity lands on its
        # host counters — with obs attached the decision sequence is
        # bit-identical
        self.obs = obs
        self._buckets: Dict[tuple, _Bucket] = {}
        self._queue: List[dict] = []
        self.dispatches = 0             # fused decision dispatches issued
        self.ticks = 0                  # flush() calls that dispatched

    # -- admission ------------------------------------------------------
    def admit(self, job_id: str, model: RuntimeModel, *, window=None,
              members=None, priority: float = 0.0, k_samples: int = 64,
              min_frac: float = 0.5, seed: int = 0) -> "JobHandle":
        """Admit a job; ``window`` warm-starts its lag window (rows of
        raw runtimes, as ``CutoffController.seed_window``)."""
        self.flush()
        job = self.registry.admit(job_id, model, members=members,
                                  priority=priority, k_samples=k_samples,
                                  min_frac=min_frac, seed=seed)
        self._place(job, window)
        if window is not None:
            job.trace = [np.asarray(r, np.float64)
                         for r in np.asarray(window)][-self.history:]
        return JobHandle(self, job_id)

    def evict(self, job_id: str) -> dict:
        """Remove a job; returns its final window (or None) and trace."""
        self.flush()
        job = self.registry[job_id]
        window = None
        if job.mode == "dmm" and job.count > 0:
            window = self.window_array(job_id)
        if job.bucket_sig is not None:
            self._remove(job)
        job.refit_task = None
        self.registry.evict(job_id)
        return {"window": window, "trace": np.array(job.trace)}

    def handle(self, job_id: str) -> "JobHandle":
        if job_id not in self.registry:
            raise KeyError(job_id)
        return JobHandle(self, job_id)

    # -- bucket plumbing ------------------------------------------------
    def _sig(self, job: PSJob) -> tuple:
        """The decision ARCHITECTURE: window length, sampling count, and
        DMM shape.  Deliberately width-free — mixed worker widths share
        one bucket via pad-to-bucket ragged dispatch (the per-job width
        and argmax floor ride the jit as traced operands).  Two jobs with
        different (z_dim, hidden) still cannot share a param stack."""
        return (job.cap, job.k_samples, job.z_dim, job.hidden)

    def _place(self, job: PSJob, window=None):
        """Insert a dmm-mode job into its architecture bucket, growing
        the bucket pad width if this job is the widest, and seeding its
        ring slot."""
        sig = self._sig(job)
        b = self._buckets.get(sig)
        if b is None:
            b = self._buckets[sig] = _Bucket(job.cap, job.k_samples)
        if job.width > b.n_pad:
            b.repack(job.width)
        rows = np.asarray(window, np.float64) if window is not None else None
        if rows is not None and rows.ndim != 2:
            raise ValueError(f"seed window must be (T, n), got {rows.shape}")
        if rows is not None and rows.shape[1] != job.width:
            raise ValueError(f"seed window width {rows.shape[1]} != "
                             f"job width {job.width}")
        ring, head, count = _seed_ring(
            rows if rows is not None else np.zeros((0, job.width)),
            job.cap, job.width, b.n_pad)
        b.rings = jnp.concatenate([b.rings, jnp.asarray(ring)[None]])
        b.heads = jnp.concatenate(
            [b.heads, jnp.asarray([head], jnp.int32)])
        job.slot = len(b.jobs)
        b.jobs.append(job)
        b.dirty()
        job.bucket_sig = sig
        job.count = count
        job.mode = "dmm"

    def _remove(self, job: PSJob):
        b = self._buckets[job.bucket_sig]
        i = job.slot
        keep = np.array([k for k in range(len(b.jobs)) if k != i])
        if keep.size:
            ka = jnp.asarray(keep)
            b.rings = b.rings[ka]
            b.heads = b.heads[ka]
        else:
            b.rings = b.rings[:0]
            b.heads = b.heads[:0]
        b.jobs.pop(i)
        for k, other in enumerate(b.jobs):
            other.slot = k
        b.dirty()
        sig, job.bucket_sig = job.bucket_sig, None
        job.slot = -1
        if not b.jobs:
            del self._buckets[sig]
            return
        widest = max(j.width for j in b.jobs)
        if widest < b.n_pad:
            b.repack(widest)

    # -- window diagnostics / checkpointing -----------------------------
    def window_array(self, job_id: str) -> np.ndarray:
        """The job's lag window, oldest row first (host copy, pad
        columns stripped).

        Raises ValueError while empty — the Trainer's checkpoint path
        relies on this to skip cold controllers."""
        self.flush()
        job = self.registry[job_id]
        if job.mode != "dmm":
            if not job.trace:
                raise ValueError("window is empty")
            return np.stack(job.trace[-job.cap:])
        if job.count == 0:
            raise ValueError("window is empty")
        b = self._buckets[job.bucket_sig]
        head = int(b.heads[job.slot])
        w = np.asarray(jnp.roll(b.rings[job.slot], -head,
                                axis=0))[:, :job.width]
        return w[-job.count:] if job.count < job.cap else w

    def seed_window(self, job_id: str, rows: np.ndarray):
        """Warm-start the job's window from recorded traces (checkpoint
        restore path)."""
        self.flush()
        job = self.registry[job_id]
        rows = np.asarray(rows, np.float64)
        if rows.shape[1] != job.width:
            raise ValueError(f"seed rows have width {rows.shape[1]}, "
                             f"job width is {job.width}")
        job.trace = (job.trace + [r for r in rows])[-self.history:]
        if job.mode != "dmm":
            for r in rows[-50:]:
                job.fallback.buf.append(np.asarray(r, np.float64))
            return
        b = self._buckets[job.bucket_sig]
        old = (np.asarray(self.window_array(job_id), np.float32)
               if job.count else np.zeros((0, job.width), np.float32))
        merged = np.concatenate([old, np.asarray(rows, np.float32)])
        ring, head, count = _seed_ring(merged, job.cap, job.width, b.n_pad)
        b.rings = b.rings.at[job.slot].set(jnp.asarray(ring))
        b.heads = b.heads.at[job.slot].set(head)
        job.count = min(job.count + rows.shape[0], job.cap)
        job.pending = None
        job.pending_pred = None

    def checkpoint_group(self, job_id: str) -> Dict[str, np.ndarray]:
        """The job's persistable controller state (``"ctl"``-group shape:
        width, members, step, window), under its registry group name."""
        job = self.registry[job_id]
        grp = {"n": np.int64(job.width),
               "members": np.asarray(job.members, np.int64),
               "step": np.int64(job.step)}
        try:
            grp["window"] = np.asarray(self.window_array(job_id), np.float64)
        except ValueError:
            pass
        return grp

    def checkpoint_groups(self) -> Dict[str, Dict[str, np.ndarray]]:
        return {self.registry[i].ckpt_group: self.checkpoint_group(i)
                for i in self.registry.ids()}

    # -- the decision path ----------------------------------------------
    # reprolint: hot-path
    def predict_cutoff(self, job_id: str) -> int:
        with span("ps.predict_cutoff"):
            return self._predict_cutoff(self.registry[job_id])

    def _predict_cutoff(self, job: PSJob) -> int:
        if job.queued:
            self.flush()
        self._poll_refit(job)
        job.step += 1
        if job.mode == "fallback":
            job.fallback_steps += 1
            return min(job.fallback.predict_cutoff(), job.width)
        if not job.warmed_up:
            job.pending_pred = None
            return job.width
        if job.pending is None or job.pending[0] != job.step:
            # first decision after seeding/rejoin, or out-of-cadence
            # call: dispatch one now (prefetch() batches this for a
            # whole service set)
            self._decide_jobs([job], [job.step])
        _, row, out = job.pending
        job.pending = None
        host = self._out_host(out)
        # predictive moments come back as HOST rows (one shared fetch per
        # batched output, amortized over its jobs) so the next flush can
        # splice them straight into the packed upload
        job.pending_pred = (host["mu"][row], host["std"][row],
                            out["samples"], row)
        # reprolint: disable=host-sync-in-hot-path -- reads of the already-fetched host cache (the designated per-dispatch transfer lives in _out_host)
        job.last_iter = float(host["iter"][row])
        # reprolint: disable=host-sync-in-hot-path -- same host cache; int(cutoff) is the API's one designated sync
        return int(host["cutoff"][row])

    @staticmethod
    def _out_host(out: dict) -> dict:
        """Host view of one batched decision output, fetched ONCE per
        dispatch (cutoffs, moments and iter times for every job row in a
        single transfer) and cached on the output dict; the (K, n)
        sample clouds stay on device."""
        h = out.get("host")
        if h is None:
            with span("ps.fetch"):
                # reprolint: disable=host-sync-in-hot-path -- THE designated fetch: one device_get per batched dispatch, amortized over every job row it served
                cut, mu, std, it = jax.device_get(
                    (out["cutoff"], out["mu"], out["std"], out["iter"]))
            h = out["host"] = {"cutoff": np.asarray(cut),
                               "mu": np.asarray(mu),
                               "std": np.asarray(std),
                               "iter": np.asarray(it)}
        return h

    def prefetch(self, job_ids=None):
        """Batch the decide-only dispatch for every warmed job in
        ``job_ids`` (default: all) that has no decision in flight for its
        next step — one fused call per bucket instead of one per job."""
        ids = job_ids if job_ids is not None else self.registry.ids()
        jobs = [self.registry[i] for i in ids]
        need = [j for j in jobs
                if j.mode == "dmm" and j.warmed_up and not j.queued
                and (j.pending is None or j.pending[0] != j.step + 1)]
        by_bucket: Dict[tuple, list] = {}
        for j in need:
            by_bucket.setdefault(j.bucket_sig, []).append(j)
        for group in by_bucket.values():
            self._decide_jobs(group, [j.step + 1 for j in group])

    def _decide_jobs(self, jobs: List[PSJob], dsteps: List[int]):
        """Decide-only batched dispatch for same-bucket jobs.  ``dsteps``
        are the decision steps: the caller's current step when invoked
        from ``predict_cutoff`` (which already incremented), step+1 when
        prefetching."""
        b = self._buckets[jobs[0].bucket_sig]
        with span("ps.decide"):
            keys = jnp.asarray(C._prng_key_rows(
                [j.seed + d for j, d in zip(jobs, dsteps)]))
            params, scales, widths, los = b.stacked()
            slots = [j.slot for j in jobs]
            if slots == list(range(len(b.jobs))):
                cut, samp, mu, std, it = _full_decide(
                    params, b.rings, b.heads, keys, scales, widths, los,
                    k_samples=b.k_samples)
            else:
                idx = jnp.asarray(slots, jnp.int32)
                cut, samp, mu, std, it = _subset_decide(
                    params, b.rings, b.heads, idx, keys, scales, widths,
                    los, k_samples=b.k_samples)
        self.dispatches += 1
        out = {"cutoff": cut, "samples": samp, "mu": mu, "std": std,
               "iter": it}
        for row, (j, d) in enumerate(zip(jobs, dsteps)):
            j.pending = (d, row, out)

    def observe(self, job_id: str, times, finished_mask=None):
        with span("ps.observe"):
            self._observe(self.registry[job_id], times, finished_mask)

    def _observe(self, job: PSJob, times, finished_mask):
        job_id = job.job_id
        t = np.asarray(times, np.float64)
        if t.shape != (job.width,):
            raise ValueError(
                f"job {job_id!r}: observe got {t.shape[0]} runtimes at "
                f"width {job.width}; resize() before the resized step")
        mask = (np.ones(job.width, bool) if finished_mask is None
                else np.asarray(finished_mask, bool))
        if not mask.any():
            # no coherent cutoff time exists to impute anything at — the
            # old fall-through fed fully-censored times into the refit
            # trace as if observed; reject loudly instead (the
            # CutoffController/ElasticController convention)
            raise ValueError(
                f"job {job_id!r}: observe got an all-False finished_mask: "
                "a step with zero finished workers has no observed cutoff "
                "time to impute the censored entries at")
        # rolling imputed trace: refit training data (plain imputation at
        # the observed cutoff time, as ElasticController keeps it)
        row = np.where(mask, t, t[mask].max()) if not mask.all() else t
        job.trace = (job.trace + [row])[-self.history:]
        job.fresh += 1
        if job.mode == "fallback":
            job.fallback.observe(times, finished_mask)
            self._poll_refit(job)
            if job.refit_task is None:
                self._maybe_refit(job)
            return
        if job.queued:
            self.flush()        # one observation in flight per job, max
        t32 = t.astype(np.float32)
        # mirror CutoffController.observe's mode selection exactly: a
        # full-sync observation takes the plain append even when moments
        # are pending (cheaper, and equivalence-by-construction with the
        # single-job reference rather than by where-merge accident)
        cen = job.pending_pred is not None and not bool(mask.all())
        pred = (job.pending_pred[0], job.pending_pred[1]) if cen else None
        if job.pending_pred is not None:
            # moments stay valid for the queued imputation; the sample
            # cache does not survive the window change
            job.pending_pred = job.pending_pred[:2] + (None,
                                                       job.pending_pred[3])
        job.count = min(job.count + 1, job.cap)
        if job.warmed_up:
            self._queue.append({
                "job": job, "times": t32, "mask": mask, "cen": cen,
                "pred": pred, "dstep": job.step + 1, "istep": job.step})
            job.queued = True
        else:
            # warmup: plain append straight into the job's ring slot
            # (pad columns carry times 0 under a True mask, which the
            # plain imputation writes through as 0 — the decision never
            # reads them)
            b = self._buckets[job.bucket_sig]
            tp = np.zeros(b.n_pad, np.float32)
            tp[:job.width] = t32
            mp = np.ones(b.n_pad, bool)
            mp[:job.width] = mask
            obs = {"times": jnp.asarray(tp), "mask": jnp.asarray(mp)}
            ring, head = C._ring_append(b.rings[job.slot],
                                        b.heads[job.slot], obs, mode="plain")
            b.rings = b.rings.at[job.slot].set(ring)
            b.heads = b.heads.at[job.slot].set(head)

    def flush(self) -> int:
        """Dispatch every queued observation+decision: ONE vmapped fused
        call per architecture bucket — mixed widths AND mixed
        plain/censored modes all ride the same dispatch (traced width
        masks + traced censor flags).  Returns the dispatches issued."""
        if not self._queue:
            return 0
        # the spans time the host packing and the (async) dispatch: they
        # add no device sync here
        with span("ps.flush", tick=self.ticks, queued=len(self._queue)):
            queue, self._queue = self._queue, []
            groups: Dict[tuple, list] = {}
            for e in queue:
                groups.setdefault(e["job"].bucket_sig, []).append(e)
            issued = 0
            for sig, entries in groups.items():
                b = self._buckets[sig]
                m, npd = len(entries), b.n_pad
                slots = [e["job"].slot for e in entries]
                gather = slots != list(range(len(b.jobs)))
                with span("ps.pack"):
                    # one packed upload:
                    # [times, mask, mu, std] + keys/steps/cen
                    pack = np.zeros((4, m, npd), np.float32)
                    pack[1] = 1.0   # pad columns read mask=True (write 0.0)
                    keys = np.empty((m, 4), np.uint32)
                    steps = np.empty((m,), np.uint32)
                    cen = np.empty((m,), bool)
                    for r, e in enumerate(entries):
                        w = e["job"].width
                        pack[0, r, :w] = e["times"]
                        pack[1, r, :w] = e["mask"]
                        if e["cen"]:
                            pack[2, r, :w] = e["pred"][0][:w]
                            pack[3, r, :w] = e["pred"][1][:w]
                        steps[r] = e["istep"]
                        cen[r] = e["cen"]
                    keys[:, :2] = C._prng_key_rows(
                        [e["job"].seed + e["dstep"] for e in entries])
                    keys[:, 2:] = C._prng_key_rows(
                        [e["job"].seed + 1_000_003 for e in entries])
                with span("ps.dispatch", jobs=m, gather=gather):
                    params, scales, widths, los = b.stacked()
                    args = (jnp.asarray(pack), jnp.asarray(keys),
                            jnp.asarray(steps), jnp.asarray(cen),
                            scales, widths, los)
                    if not gather:
                        (b.rings, b.heads, cut, samp, mu, std, it) = (
                            _full_observe_decide(
                                params, b.rings, b.heads, *args,
                                k_samples=b.k_samples))
                    else:
                        idx = jnp.asarray(slots, jnp.int32)
                        (b.rings, b.heads, cut, samp, mu, std, it) = (
                            _subset_observe_decide(
                                params, b.rings, b.heads, idx, *args,
                                k_samples=b.k_samples))
                issued += 1
                out = {"cutoff": cut, "samples": samp, "mu": mu,
                       "std": std, "iter": it}
                for row, e in enumerate(entries):
                    e["job"].pending = (e["dstep"], row, out)
                    e["job"].queued = False
        self.dispatches += issued
        self.ticks += 1
        return issued

    # -- diagnostics -----------------------------------------------------
    def predicted_iter_time(self, job_id: str) -> Optional[float]:
        """Posterior-predictive E[x_(c)] of the job's latest decision (raw
        seconds) — the shortest-predicted-step-first scheduler's key.
        None before the first warmed-up decision (and in fallback mode,
        where the analytic controller has no sample cloud)."""
        return self.registry[job_id].last_iter

    def predicted_order_stats(self, job_id: str):
        job = self.registry[job_id]
        if job.pending_pred is None or job.pending_pred[2] is None:
            return None
        samples = np.asarray(
            job.pending_pred[2][job.pending_pred[3]])[:, :job.width]
        return order_stats.mc_order_stats(samples)

    def predicted_samples(self, job_id: str):
        """DEVICE view of the job's latest predictive sample cloud,
        ``(K, n)`` with the bucket's pad columns sliced off — a lazy
        array reference, never a host fetch, so the obs quality layer
        can buffer it on the hot path and materialize it only at drain
        boundaries.  None when no sampled decision is pending (cold,
        fallback mode, or already consumed by a censored observe)."""
        job = self.registry[job_id]
        if job.pending_pred is None or job.pending_pred[2] is None:
            return None
        return job.pending_pred[2][job.pending_pred[3], :, :job.width]

    # -- elasticity ------------------------------------------------------
    def resize(self, job_id: str, n_workers: int, col_map=None,
               model: Optional[RuntimeModel] = None, members=None):
        """Per-job worker-set change, ElasticController protocol: remap
        the window (survivors column-exact), then either swap in a
        ``model`` fitted at the new width (job stays on the batched DMM
        path) or degrade to a warm-seeded Elfving fallback until the
        refit lands (``_maybe_refit``)."""
        self.flush()
        job = self.registry[job_id]
        n_new = int(n_workers)
        if (n_new == job.width and col_map is None and model is None
                and members is None):
            return          # idempotent: re-asserting the current width
                            # must not degrade a healthy DMM job
        if model is not None and model.n_workers != n_new:
            raise ValueError(
                f"resize({n_new}) got a RuntimeModel of width "
                f"{model.n_workers}; refit it for the new width first")
        rows = None
        if job.mode == "dmm" and job.count > 0:
            rows = self.window_array(job_id)
        if job.bucket_sig is not None:
            self._remove(job)
        if job.trace:
            job.trace = [r for r in C.remap_columns(
                np.stack(job.trace), n_new, col_map)]
        if rows is not None:
            rows = C.remap_columns(np.asarray(rows, np.float64), n_new,
                                   col_map)
        elif job.trace:
            rows = np.stack(job.trace[-job.cap:])
        job.width = n_new
        job.members = self._resized_members(job.members, n_new, col_map,
                                            members)
        job.resize_count += 1
        job.fresh = 0
        job.pending = None
        job.pending_pred = None
        job.last_iter = None
        # abandon any in-flight refit WITHOUT blocking on its ELBO fit:
        # the daemon thread keeps filling its orphaned result box, and
        # _poll_refit_task would discard it by generation anyway
        job.refit_task = None
        if model is not None:
            job.model = model
            self._place(job, rows)
            return
        job.model = None
        job.mode = "fallback"
        job.count = 0
        job.fallback = C.ElfvingController(
            n_new, warmup=self.fallback_warmup, min_frac=job.min_frac)
        for r in job.trace[-50:]:
            job.fallback.buf.append(np.asarray(r, np.float64))

    @staticmethod
    def _resized_members(old: np.ndarray, n_new: int, col_map,
                         members) -> np.ndarray:
        """GLOBAL worker ids across a resize.  Survivors keep their ids
        (via ``col_map``, the same remap the window uses); workers whose
        global id the caller didn't supply are marked ``-1`` — never
        silently renumbered, so the per-job checkpoint group's
        restore-by-global-id protocol stays sound."""
        if members is not None:
            members = np.asarray(members, int)
            if members.shape != (n_new,):
                raise ValueError(f"members must be ({n_new},), got "
                                 f"{members.shape}")
            return members
        if old.size == 0:
            # np.clip(cm, 0, old.size - 1) on an empty member array would
            # clip to index -1 (the LAST element of a non-empty array) —
            # there are no surviving ids to carry over, so demand them
            # explicitly instead of crashing or aliasing
            raise ValueError(
                f"resize({n_new}) from a width-0 member set has no "
                "surviving global worker ids to remap; pass members= "
                "explicitly")
        if col_map is None:
            col_map = np.concatenate([
                np.arange(min(old.size, n_new)),
                np.full(max(0, n_new - old.size), -1, int)])
        cm = np.asarray(col_map, int)
        return np.where(cm >= 0, old[np.clip(cm, 0, old.size - 1)], -1)

    # -- refit plumbing (ElasticController's task shape, per job) --------
    def _fit_model(self, job: PSJob, rows: np.ndarray, n: int,
                   seed: int) -> RuntimeModel:
        model = RuntimeModel(n_workers=n, lag=job.lag,
                             z_dim=job.z_dim, hidden=job.hidden)
        model.fit(rows, steps=self.refit_steps, batch=self.refit_batch,
                  seed=seed)
        return model

    def _maybe_refit(self, job: PSJob):
        # failed attempts back off: each demands twice the fresh rows
        need = self.refit_fresh * (2 ** job.refit_failures)
        if (job.fresh < need
                or len(job.trace) < job.cap + self.refit_batch):
            return
        # freeze width/seed now: a resize mid-fit must not retarget the
        # running fit (its result is discarded by generation anyway)
        rows = np.stack(job.trace)
        n = job.width
        seed = job.seed + job.resize_count + 1000 * job.refit_failures
        if self.obs is not None:
            self.obs.metrics.counter("ps.refits_started").inc()
        if self.refit_async:
            job.refit_task = C._spawn_refit(
                lambda: self._fit_model(job, rows, n, seed),
                job.resize_count)
        else:
            with span("ps.refit", job=job.job_id, width=n):
                model = self._fit_model(job, rows, n, seed)
            self._install_refit(job, model)

    def _poll_refit(self, job: PSJob):
        if job.refit_task is None:
            return
        done, model, err = C._poll_refit_task(job.refit_task,
                                              job.resize_count, job.width)
        if not done:
            return
        job.refit_task = None
        if err is not None:
            job.refit_failures += 1
            if self.obs is not None:
                self.obs.metrics.counter("ps.refit_failures").inc()
            if job.refit_failures > self.refit_retries:
                raise C.RefitError(
                    f"job {job.job_id!r}: DMM refit failed "
                    f"{job.refit_failures} times at width {job.width} "
                    f"(retry budget {self.refit_retries} spent); last "
                    f"error: {err!r}") from err
            print(f"job {job.job_id!r}: DMM refit failed ({err!r}); "
                  f"retrying after "
                  f"{self.refit_fresh * 2 ** job.refit_failures} fresh "
                  f"observations")
            job.fresh = 0
            return
        if model is not None and job.mode == "fallback":
            job.refit_failures = 0
            self._install_refit(job, model)

    def _install_refit(self, job: PSJob, model: RuntimeModel):
        job.model = model
        job.mode = "dmm"
        job.fallback = None
        self._place(job, np.stack(job.trace[-job.cap:]))
        if self.obs is not None:
            # host counter increment — _poll_refit reaches here from the
            # hot predict path, so no spans/fetches, just bookkeeping
            self.obs.metrics.counter("ps.refits_installed").inc()

    def wait_refits(self, job_ids=None):
        """Block until every in-flight async refit for ``job_ids``
        (default: all) has finished and, if still current, been
        installed.  Deterministic sync point for tests and benches — the
        tick path itself never blocks on a fit."""
        ids = job_ids if job_ids is not None else self.registry.ids()
        for i in ids:
            job = self.registry[i]
            if job.refit_task is not None:
                job.refit_task[0].join()
                self._poll_refit(job)


# ---------------------------------------------------------------------------
# Controller-protocol facade.
# ---------------------------------------------------------------------------


class JobHandle:
    """One job's controller-shaped view of the shared server.

    Implements the full controller protocol (`predict_cutoff`, `observe`,
    `resize`, `seed_window`, `window_array`, `predicted_order_stats`,
    `_step`), so a ``launch.train.Trainer`` drives the multi-tenant
    server without knowing it — including the checkpoint ``"ctl"`` group
    and the elastic ``_sync_membership`` path.
    """

    def __init__(self, server: PSServer, job_id: str):
        self.server = server
        self.job_id = job_id

    @property
    def job(self) -> PSJob:
        return self.server.registry[self.job_id]

    @property
    def n(self) -> int:
        return self.job.width

    @property
    def warmed_up(self) -> bool:
        return self.job.warmed_up

    @property
    def mode(self) -> str:
        return self.job.mode

    @property
    def _step(self) -> int:
        return self.job.step

    @_step.setter
    def _step(self, value: int):
        self.job.step = int(value)

    def predict_cutoff(self) -> int:
        return self.server.predict_cutoff(self.job_id)

    def observe(self, times, finished_mask=None):
        return self.server.observe(self.job_id, times, finished_mask)

    def resize(self, n_workers: int, col_map=None, model=None,
               members=None):
        return self.server.resize(self.job_id, n_workers, col_map=col_map,
                                  model=model, members=members)

    def seed_window(self, traces):
        return self.server.seed_window(self.job_id, traces)

    def window_array(self) -> np.ndarray:
        return self.server.window_array(self.job_id)

    def predicted_order_stats(self):
        return self.server.predicted_order_stats(self.job_id)

    def predicted_samples(self):
        return self.server.predicted_samples(self.job_id)

    def predicted_iter_time(self) -> Optional[float]:
        return self.server.predicted_iter_time(self.job_id)
