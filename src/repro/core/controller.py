"""Cutoff controllers — the parameter-server decision logic (paper Alg. 1).

Each controller implements::

    c = ctl.predict_cutoff()            # before the step (line 23)
    ctl.observe(times, finished_mask)   # after the step (lines 25-26)

where ``times`` are per-worker runtimes for the finished workers (entries for
dropped workers are ignored) and ``finished_mask`` marks who reported.

Controllers:
  * CutoffController  — the paper's method: DMM + amortized inference,
    MC order statistics, censored imputation.  Two backends:
    ``backend="device"`` (default, production) keeps the lag window in a
    device-resident ring buffer; ``observe`` dispatches ONE fused jit
    (``_fused_observe_decide``: censored-imputation append + guide →
    transition → emission → sample → sort → argmax → predictive moments)
    that overlaps the workers' compute, and ``predict_cutoff`` only
    materializes the int32 — the single host/device sync per step.
    ``backend="numpy"`` is the float64 host reference the device path is
    checked against (tests/test_controller_device.py).
  * ElfvingController — the analytic iid-normal "order" baseline (Eq. 3).
  * StaticCutoffController — Chen et al. (2016) fixed cutoff.
  * FullSyncController — waits for everyone.
  * ElasticController — membership-elastic wrapper: DMM decisions while
    the cluster shape matches the fitted model; across a ``resize`` it
    remaps the window (``remap_columns``), falls back to Elfving, and
    refits the DMM on the surviving window (src/repro/core/README.md
    has the full elastic contract).

Every controller implements ``resize(n_workers, col_map=None, model=None,
members=None)`` for elastic worker membership; observation width is
strict after it.  ``members`` carries the GLOBAL worker ids of the new
set — width-only controllers ignore it, the multi-tenant ``ps.JobHandle``
records it in the job registry (its checkpoint groups restore by global
id).
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cutoff import censoring, elfving, order_stats
from repro.core.runtime_model.api import (RuntimeModel, colwise_uniform)
from repro.obs.trace import span


class FullSyncController:
    def __init__(self, n_workers: int):
        self.n = n_workers

    def predict_cutoff(self) -> int:
        return self.n

    def observe(self, times, finished_mask=None):
        pass

    def resize(self, n_workers: int, col_map=None, model=None,
               members=None):
        """Elastic membership change: track the new worker count
        (width-only controllers ignore the global ``members`` ids)."""
        self.n = int(n_workers)


class StaticCutoffController(FullSyncController):
    """Chen et al. (2016): fixed c < n for the whole run."""

    def __init__(self, n_workers: int, cutoff: Optional[int] = None,
                 drop_frac: float = 0.06):
        super().__init__(n_workers)
        self.drop_frac = drop_frac
        self._cutoff = cutoff        # the configured cutoff, never clamped
        self.c = cutoff if cutoff is not None else max(
            1, int(round(n_workers * (1 - drop_frac))))

    def predict_cutoff(self) -> int:
        return self.c

    def resize(self, n_workers: int, col_map=None, model=None,
               members=None):
        super().resize(n_workers, col_map, model, members)
        if self._cutoff is not None:
            # clamp to the live width but keep the configured value, so a
            # transient shrink doesn't permanently lower the baseline
            self.c = min(self._cutoff, self.n)
        else:
            self.c = max(1, int(round(self.n * (1 - self.drop_frac))))


class FirstKController(FullSyncController):
    """Chen et al. (2016) backup-workers baseline: accept the first
    ``n - b`` gradient arrivals BY COUNT, where ``b`` backup workers are
    provisioned to absorb stragglers.

    The distinction from :class:`StaticCutoffController` is the
    parameterization: the backup COUNT is fixed capacity (Chen et al.
    provision b extra machines), so a resize keeps ``b`` constant and the
    cutoff moves with the live width — shrink a 32-worker job to 24 and a
    4-backup config still accepts the first 20, not ``24 * (1 - 4/32)``.
    Count-based acceptance never consults the runtime distribution, which
    is exactly the error–runtime trade-off the paper's DMM controller
    beats (tests/test_controllers.py races it on wall-clock-to-loss).
    """

    def __init__(self, n_workers: int, backup: Optional[int] = None,
                 backup_frac: float = 0.04):
        super().__init__(n_workers)
        self.backup = (int(backup) if backup is not None
                       else max(1, int(round(n_workers * backup_frac))))

    def predict_cutoff(self) -> int:
        return max(1, self.n - self.backup)

    # resize: FullSyncController already tracks the live width; the backup
    # count deliberately stays fixed (it is provisioned capacity).


class ElfvingController(FullSyncController):
    """Analytic normality baseline: running (mu, sigma) -> Eq. 3 cutoff."""

    def __init__(self, n_workers: int, warmup: int = 5,
                 min_frac: float = 0.5):
        super().__init__(n_workers)
        self.buf: list = []
        self.warmup = warmup
        self.min_frac = min_frac

    def predict_cutoff(self) -> int:
        if len(self.buf) < self.warmup:
            return self.n
        data = np.concatenate(self.buf[-50:])
        return elfving.elfving_cutoff(self.n, float(data.mean()),
                                      float(data.std()), self.min_frac)

    def observe(self, times, finished_mask=None):
        t = np.asarray(times, np.float64)
        if finished_mask is not None:
            m = np.asarray(finished_mask, bool)
            if not m.any():
                raise ValueError(
                    "observe got an all-False finished_mask: a step with "
                    "zero finished workers has no observed cutoff time to "
                    "impute the censored entries at")
            if not m.all():
                # keeping only finished workers' times would give the
                # running (mu, sigma) survivorship bias once cutoffs
                # engage (the sample never contains a slow tail), drifting
                # the Eq. 3 cutoff optimistic.  Impute censored entries at
                # the observed cutoff time — a lower bound on their true
                # runtime, and the analytic analogue of §4.2's truncation.
                t = np.where(m, t, t[m].max())
        self.buf.append(t)


# ---------------------------------------------------------------------------
# Straggler-policy frontier: what a dropped worker contributes.
#
# The paper's controllers above all share ONE straggler policy — discard:
# a worker outside the cutoff contributes nothing and its mask bit is 0.
# The related work shows discard is one point on an error–runtime
# frontier; the two wrappers below implement the other two points the
# frontier bench races (benchmarks/frontier_bench.py), reusing any of the
# controllers above for the CUTOFF decision and changing only what the
# dropped workers contribute.  src/repro/core/README.md has the policy
# contract table.
# ---------------------------------------------------------------------------


class _PolicyWrapper:
    """Delegating base for straggler-policy wrappers: the inner controller
    owns the cutoff decision, the observe window, and the elastic resize
    protocol; the wrapper changes only the contribution semantics."""

    def __init__(self, inner):
        self.inner = inner

    @property
    def n(self) -> int:
        return self.inner.n

    def predict_cutoff(self) -> int:
        return self.inner.predict_cutoff()

    def observe(self, times, finished_mask=None):
        return self.inner.observe(times, finished_mask)

    def resize(self, n_workers: int, col_map=None, model=None,
               members=None):
        return self.inner.resize(n_workers, col_map=col_map, model=model,
                                 members=members)

    def predicted_order_stats(self):
        fn = getattr(self.inner, "predicted_order_stats", None)
        return fn() if fn is not None else None

    def predicted_samples(self):
        fn = getattr(self.inner, "predicted_samples", None)
        return fn() if fn is not None else None

    def window_array(self) -> np.ndarray:
        fn = getattr(self.inner, "window_array", None)
        if fn is None:
            # same contract as an empty CutoffController window: the
            # checkpoint path skips controllers with nothing to persist
            raise ValueError("inner controller keeps no window")
        return fn()

    def seed_window(self, traces: np.ndarray):
        fn = getattr(self.inner, "seed_window", None)
        if fn is not None:
            return fn(traces)


class AnytimeController(_PolicyWrapper):
    """Anytime SGD (Ferdinand & Draper): stragglers contribute PARTIAL
    gradient sums at the cutoff instead of being discarded.

    The inner controller still picks the cutoff c; the cutoff time is the
    c-th fastest worker's runtime as before.  But where the discard policy
    hands the aggregation a 0/1 bit array, :meth:`contribution` returns a
    per-worker f32 vector: a worker that completed ``k`` of its
    ``n_micro`` grad-accum microbatches by the cutoff time contributes its
    partial sum with weight ``k / n_micro``
    (``cluster.simulator.microbatch_progress``).  Finishers contribute
    exactly 1.0 (tie-consistent with the bit array), so with
    ``n_micro=1`` — or a cluster whose stragglers never complete a single
    microbatch by the cutoff — the vector reduces to the discard bit
    array bit-for-bit.

    The runtime model's view is unchanged: a straggler's full-step
    runtime is still censored at the cutoff time (it shipped a partial
    sum, not a completion time), so ``observe`` keeps the discard
    policy's finished mask.
    """

    def __init__(self, inner, n_micro: int = 1):
        super().__init__(inner)
        if n_micro < 1:
            raise ValueError(f"n_micro must be >= 1, got {n_micro}")
        self.n_micro = int(n_micro)

    def contribution(self, times, c: int) -> np.ndarray:
        """Per-worker f32 contribution vector for a step decided at
        cutoff ``c``: 1.0 for the c finishers, the completed-microbatch
        fraction at the cutoff time for everyone else."""
        from repro.cluster.simulator import microbatch_progress
        times = np.asarray(times, np.float64)
        order = np.argsort(times, kind="stable")
        cutoff_time = float(times[order[c - 1]])
        contrib = microbatch_progress(times, cutoff_time,
                                      self.n_micro).astype(np.float32)
        contrib[order[:c]] = 1.0       # finishers, exactly (tie-consistent)
        return contrib


class StaleReuseController(_PolicyWrapper):
    """Stale-gradient reuse (Dutta et al.): a dropped worker's LATE
    gradient is not thrown away — the Trainer buffers it and folds it
    into the NEXT step with a staleness-decayed weight.

    The wrapper itself only carries the policy knob: ``stale_decay`` is
    the weight a one-step-stale gradient enters the next step's masked
    mean with (relative to a fresh gradient's 1.0).  The Trainer detects
    the attribute, routes the step's dropped-gradient mean back into the
    next step's batch, and the ``stale_reuse=True`` train step does the
    fold in-jit (``launch.train.make_train_step``) — mask_agg="psum"
    only, since the fold needs per-worker gradients.  ``stale_decay=0``
    is exactly the discard policy (the fold multiplies by 0.0 and the
    parameters match bit-for-bit — tests/test_frontier.py).
    """

    def __init__(self, inner, decay: float = 0.5):
        super().__init__(inner)
        if not 0.0 <= decay <= 1.0:
            raise ValueError(f"decay must be in [0, 1], got {decay}")
        self.stale_decay = float(decay)


# ---------------------------------------------------------------------------
# Elastic membership: window remapping across worker-set changes.
# ---------------------------------------------------------------------------


def remap_columns(rows: np.ndarray, n_new: int,
                  col_map: Optional[np.ndarray] = None) -> np.ndarray:
    """Remap (T, n_old) worker-indexed rows onto a resized worker set.

    ``col_map`` is (n_new,) of old column indices — survivors carry their
    runtime series over column-exactly — with ``-1`` marking NEW workers,
    whose column is seeded row-by-row from the cluster mean of the
    surviving columns (the moment-matched prior before the new worker has
    reported anything).  Default: identity prefix (old worker i -> new
    column i, extra columns new).
    """
    rows = np.asarray(rows)
    if rows.ndim != 2:
        raise ValueError(f"rows must be (T, n), got {rows.shape}")
    n_old = rows.shape[1]
    if col_map is None:
        col_map = np.concatenate([
            np.arange(min(n_old, n_new)),
            np.full(max(0, n_new - n_old), -1, int)])
    col_map = np.asarray(col_map, int)
    if col_map.shape != (n_new,):
        raise ValueError(f"col_map must be ({n_new},), got {col_map.shape}")
    if np.any(col_map >= n_old):
        raise ValueError(f"col_map references old columns >= {n_old}")
    surv = col_map[col_map >= 0]
    fill = (rows[:, surv].mean(axis=1) if surv.size
            else rows.mean(axis=1))
    out = np.where((col_map >= 0)[None, :],
                   rows[:, np.clip(col_map, 0, n_old - 1)],
                   fill[:, None])
    return out.astype(rows.dtype)


# ---------------------------------------------------------------------------
# Device-resident ring-buffer primitives (jitted once, shared by every
# CutoffController instance — shapes key the jit cache).
# ---------------------------------------------------------------------------


def _append_core(ring, head, obs, mode: str):
    """Trace-level ring append; ``mode`` picks the imputation.

    "plain": censored entries take the observed cutoff time (warmup
    fallback, and the full-sync case).  "censored": fused truncated-normal
    imputation (paper §4.2) — the uniform draw, the inverse-CDF, the
    where-merge and the ring write all stay on device.
    """
    times, mask = obs["times"], obs["mask"]
    cutoff_time = jnp.max(jnp.where(mask, times, -jnp.inf))
    if mode == "censored":
        u = colwise_uniform(obs["key"], times.shape[0])
        row = censoring.impute_censored_jax(times, mask, obs["mu"],
                                            obs["std"], cutoff_time, u)
    else:
        row = jnp.where(mask, times, cutoff_time)
    return ring.at[head].set(row), (head + 1) % ring.shape[0]


def _ragged_append_core(ring, head, obs):
    """Ragged twin of :func:`_append_core` with the imputation mode
    TRACED: ``obs["cen"]`` (a per-job bool scalar) selects the censored or
    plain row in-jit, so a mixed plain/censored job set still shares one
    vmapped dispatch.  Both rows are computed — cheap elementwise work —
    and padded columns (mask False, garbage moments) land finite values
    that the decision's column mask never reads."""
    times, mask = obs["times"], obs["mask"]
    cutoff_time = jnp.max(jnp.where(mask, times, -jnp.inf))
    u = colwise_uniform(obs["key"], times.shape[0])
    crow = censoring.impute_censored_jax(times, mask, obs["mu"],
                                         obs["std"], cutoff_time, u)
    prow = jnp.where(mask, times, cutoff_time)
    row = jnp.where(obs["cen"], crow, prow)
    return ring.at[head].set(row), (head + 1) % ring.shape[0]


@functools.partial(jax.jit, static_argnames=("mode",))
def _ring_append(ring, head, obs, *, mode: str):
    return _append_core(ring, head, obs, mode)


def _observe_decide_core(params, ring, head, obs, key, norm_scale,
                         mode: str, k_samples: int, lo: int):
    """Trace-level body of one whole controller iteration: flush the
    deferred observation (imputation included) into the ring, then run the
    full decision (guide → transition → emission → sample → sort → argmax
    → predictive moments) on the updated window.  Jitted directly for the
    single-job hot path (:func:`_fused_observe_decide`) and vmapped over a
    leading JOB axis for the multi-tenant batched path
    (:func:`_batched_observe_decide`)."""
    if mode != "none":
        ring, head = _append_core(ring, head, obs, mode)
    (cutoff, samples, pred_mu, pred_std,
     pred_iter) = RuntimeModel._decide_core(
        params, ring, head, key, norm_scale, k_samples, lo)
    return ring, head, cutoff, samples, pred_mu, pred_std, pred_iter


# reprolint: disable=static-argnum-width -- `lo` is static by design on the single-job path: it changes only on resize (rare), and keeping it static lets XLA fold the cutoff floor; the ragged multi-job path traces it
@functools.partial(jax.jit, static_argnames=("mode", "k_samples", "lo"))
def _fused_observe_decide(params, ring, head, obs, key, norm_scale, *,
                          mode: str, k_samples: int, lo: int):
    """ONE jit call for a whole controller iteration on the hot path: the
    host uploads one (n,) row + mask and fetches one int32 per SGD step."""
    return _observe_decide_core(params, ring, head, obs, key, norm_scale,
                                mode, k_samples, lo)


def _ragged_observe_decide_core(params, ring, head, obs, key, norm_scale,
                                width, lo, k_samples: int):
    """One whole RAGGED controller iteration: traced-mode append
    (:func:`_ragged_append_core`), then the traced-width decision
    (``RuntimeModel._decide_core(width=...)``)."""
    ring, head = _ragged_append_core(ring, head, obs)
    (cutoff, samples, pred_mu, pred_std,
     pred_iter) = RuntimeModel._decide_core(
        params, ring, head, key, norm_scale, k_samples, lo, width=width)
    return ring, head, cutoff, samples, pred_mu, pred_std, pred_iter


@functools.partial(jax.jit, static_argnames=("k_samples",))
def _batched_observe_decide_ragged(params, rings, heads, obs, keys,
                                   norm_scales, widths, los, *,
                                   k_samples: int):
    """ONE jit call for J whole controller iterations (the multi-tenant
    parameter server's tick), jobs of MIXED widths included: every
    operand carries a leading (J,) job axis — zero-padded stacked params
    (``stack_models_padded``), the (J, lag+1, n_pad) ring stack, per-job
    heads, packed observation rows/masks/moments, per-job PRNG keys,
    norm scales, TRACED widths and argmax floors, and per-job traced
    censor flags inside ``obs``.  The only static is ``k_samples``, so
    one compiled program serves every job mix of a bucket and dispatch
    cost is paid once per tick instead of once per job (or per width
    group).  Per-job cutoffs come back as one (J,) int32 vector."""
    def one(p, r, h, o, k, s, w, lo):
        return _ragged_observe_decide_core(p, r, h, o, k, s, w, lo,
                                           k_samples)

    return jax.vmap(one)(params, rings, heads, obs, keys, norm_scales,
                         widths, los)


@functools.partial(jax.jit, static_argnames=("k_samples",))
def _batched_decide_ragged(params, rings, heads, keys, norm_scales,
                           widths, los, *, k_samples: int):
    """Decide-only twin of :func:`_batched_observe_decide_ragged`: used
    to prefetch the first post-seeding decision for a batch of jobs in
    one dispatch."""
    def one(p, r, h, k, s, w, lo):
        return RuntimeModel._decide_core(p, r, h, k, s, k_samples, lo,
                                         width=w)

    return jax.vmap(one)(params, rings, heads, keys, norm_scales, widths,
                         los)


# reprolint: disable=static-argnum-width -- `n` sizes the OUTPUT of a host-side helper for the numpy reference backend; it is not on the device hot path and must match the reference draw count exactly
@functools.partial(jax.jit, static_argnames=("n",))
def _impute_uniforms(key, n: int):
    # column-wise so the numpy reference backend draws the SAME uniforms
    # the device append path does at any padded width (api.colwise_uniform)
    return colwise_uniform(key, n)


def _impute_key(seed: int, step: int):
    """The per-step key both backends draw imputation uniforms from.

    Offset so it can never collide with the prediction keys
    (``PRNGKey(seed + step)``)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed + 1_000_003), step)


def _prng_key_rows(seeds) -> np.ndarray:
    """(J, 2) uint32 HOST array, row j bit-identical to
    ``jax.random.PRNGKey(seeds[j])`` under the default threefry impl.

    The numpy core of :func:`stacked_prng_keys`, kept host-side so the
    server's flush can splice decide and impute keys into one packed
    upload without touching the device."""
    seeds = np.asarray(list(seeds), np.uint64)
    out = np.empty((seeds.shape[0], 2), np.uint32)
    # with x64 disabled (this repo's default) PRNGKey truncates the seed
    # to its low 32 bits and the high word is 0
    if jax.config.jax_enable_x64:
        out[:, 0] = (seeds >> np.uint64(32)).astype(np.uint32)
    else:
        out[:, 0] = 0
    out[:, 1] = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return out


def stacked_prng_keys(seeds) -> jax.Array:
    """(J, 2) uint32 key stack, row j bit-identical to
    ``jax.random.PRNGKey(seeds[j])`` under the default threefry impl.

    Built host-side in one shot so a J-job tick costs ONE upload instead
    of J ``PRNGKey`` dispatches (the dispatch overhead the batched
    decision exists to amortize).  ``tests/test_ps_server.py`` pins the
    bit-level equivalence."""
    return jnp.asarray(_prng_key_rows(seeds))


@jax.jit
def _batched_impute_keys(base_keys, steps):
    """vmap of ``fold_in`` — row j equals ``_impute_key(seed_j, step_j)``
    when ``base_keys[j] == PRNGKey(seed_j + 1_000_003)``."""
    return jax.vmap(jax.random.fold_in)(base_keys, steps)


@dataclass
class CutoffController:
    """The paper's dynamic controller (DMM + amortized inference).

    Keeps the lag-l window of (imputed) runtime vectors; each iteration:
      1. predict K samples of the next joint runtime vector (Eq. 5),
      2. c* = argmax_c E[c / x_(c)]  (throughput-optimal cutoff),
      3. after the step, impute censored runtimes from the predictive
         distribution left-truncated at the observed cutoff time (§4.2).

    ``backend="device"`` (default): the window lives in a (lag+1, n) f32
    device ring buffer; ``observe`` uploads one (n,) row and dispatches
    the fused append+decide jit for the next step, and ``predict_cutoff``
    materializes a single int32.  ``backend="numpy"``: the float64 host
    reference.  Both
    consume the same jax-derived uniform stream for imputation, so their
    cutoff sequences are identical and their windows agree to f32 precision
    on seeded runs.
    """
    model: RuntimeModel
    k_samples: int = 64
    min_frac: float = 0.5
    seed: int = 0
    backend: str = "device"

    _window: list = field(default_factory=list)       # numpy backend
    _ring: Optional[jax.Array] = None                 # device backend
    _head: Optional[jax.Array] = None
    _count: int = 0
    _pending_pred: Optional[tuple] = None
    _pending_decision: Optional[tuple] = None   # (step, c, s, mu, std, it)
    _last_iter: Optional[object] = None         # E[x_(c)] of last decision
    _step: int = 0

    def __post_init__(self):
        if self.backend not in ("device", "numpy"):
            raise ValueError(f"unknown backend {self.backend!r}")

    @property
    def n(self) -> int:
        return self.model.n_workers

    @property
    def _cap(self) -> int:
        return self.model.lag + 1

    @property
    def warmed_up(self) -> bool:
        if self.backend == "numpy":
            return len(self._window) >= self._cap
        return self._count >= self._cap

    # -- window plumbing ------------------------------------------------
    def _ensure_ring(self):
        if self._ring is None:
            self._ring = jnp.zeros((self._cap, self.n), jnp.float32)
            self._head = jnp.zeros((), jnp.int32)

    def window_array(self) -> np.ndarray:
        """The current lag window, oldest row first, as a numpy array.

        Raises ValueError while the window is empty (both backends — the
        checkpoint path relies on this to skip cold controllers rather
        than persist an all-zeros ring).
        """
        if self.backend == "numpy":
            if not self._window:
                raise ValueError("window is empty")
            return np.stack(self._window[-self._cap:])
        self._ensure_ring()
        if self._count == 0:
            raise ValueError("window is empty")
        w = np.asarray(jnp.roll(self._ring, -self._head, axis=0))
        return w[-self._count:] if self._count < self._cap else w

    def seed_window(self, traces: np.ndarray):
        """Warm-start the lag window from recorded traces.

        Device backend: built host-side and uploaded in ONE transfer —
        bit-identical to ``_ring_append`` with ``mode="plain"`` and a
        full mask (which writes the f32 rows verbatim), without paying
        up to lag+1 tiny dispatches per seeded controller (the cost that
        dominates large-J benchmark setup)."""
        rows = np.asarray(traces)[-self._cap:]
        if self.backend == "numpy":
            for row in rows:
                self._window.append(np.asarray(row, np.float64))
            return
        self._ensure_ring()
        self._pending_decision = None
        merged = np.asarray(rows, np.float32)
        if self._count:
            merged = np.concatenate(
                [np.asarray(self.window_array(), np.float32), merged])
        merged = merged[-self._cap:]
        m = merged.shape[0]
        ring = np.zeros((self._cap, self.n), np.float32)
        ring[:m] = merged
        self._ring = jnp.asarray(ring)
        self._head = jnp.asarray(m % self._cap, jnp.int32)
        self._count = min(self._count + rows.shape[0], self._cap)

    def resize(self, n_workers: int, col_map=None,
               model: Optional[RuntimeModel] = None, members=None):
        """Remap the lag window across a worker-set change.

        Survivor columns (``col_map`` entries >= 0) move column-exactly
        into the resized ring; NEW workers' columns are seeded from the
        per-row cluster mean of the survivors (:func:`remap_columns`).
        ``model`` must be a :class:`RuntimeModel` of the NEW width — the
        DMM's emission layer is shaped by n_workers, so a resize without a
        refit model cannot decide.  Callers that need a degraded mode
        while the refit runs should drive the resize through
        :class:`ElasticController` instead.
        """
        n_new = int(n_workers)
        model = model if model is not None else self.model
        if model.n_workers != n_new:
            raise ValueError(
                f"resize({n_new}) needs a RuntimeModel of that width, got "
                f"n_workers={model.n_workers}; refit first or drive the "
                f"resize through ElasticController")
        have_rows = (len(self._window) > 0 if self.backend == "numpy"
                     else self._count > 0)
        rows = self.window_array() if have_rows else None
        self.model = model
        self._pending_decision = None
        self._pending_pred = None
        self._last_iter = None
        if self.backend == "numpy":
            self._window = []
            if rows is not None:
                remapped = remap_columns(np.asarray(rows, np.float64), n_new,
                                         col_map)
                self._window = [row for row in remapped]
            return
        self._ring = None
        self._head = None
        self._count = 0
        self._ensure_ring()
        if rows is not None:
            self.seed_window(remap_columns(rows, n_new, col_map))

    def _dispatch_decision(self, obs, mode: str, step: int):
        """Issue the fused observe+decide for ``step`` (async dispatch —
        nothing blocks until the cutoff scalar is read)."""
        lo = order_stats.min_frac_floor(self.n, self.min_frac)
        (self._ring, self._head, cutoff, samples, pred_mu, pred_std,
         pred_iter) = _fused_observe_decide(
            self.model.params, self._ring, self._head, obs,
            jax.random.PRNGKey(self.seed + step),
            jnp.float32(self.model.norm_scale), mode=mode,
            k_samples=self.k_samples, lo=lo)
        self._pending_decision = (step, cutoff, samples, pred_mu, pred_std,
                                  pred_iter)

    # -- decision -------------------------------------------------------
    def predict_cutoff(self) -> int:
        with span("controller.predict_cutoff"):
            return self._predict_cutoff()

    def _predict_cutoff(self) -> int:
        self._step += 1
        if not self.warmed_up:
            self._pending_pred = None
            return self.n
        if self.backend == "numpy":
            w = np.stack(self._window[-self._cap:])
            samples, mu, std = self.model.predict_next(
                w, self.k_samples, seed=self.seed + self._step)
            # per-worker predictive moments (for censoring) from MC samples:
            # the K draws form a Gaussian mixture, so the variance is
            # E[std^2] + Var[mu] (mixture-variance law) — NOT E[std]^2,
            # which under-disperses the censored imputation
            self._pending_pred = (
                mu.mean(axis=0),
                np.sqrt(np.mean(std ** 2, axis=0) + mu.var(axis=0)),
                samples)
            c = order_stats.optimal_cutoff(samples, self.min_frac)
            # lazy: the extra sort only runs if a scheduler actually asks
            self._last_iter = ("lazy", samples, c)
            return c
        if (self._pending_decision is None
                or self._pending_decision[0] != self._step):
            # no decision in flight for this step (first decision after
            # warmup/seeding, or out-of-cadence call): dispatch one now
            self._dispatch_decision(None, "none", self._step)
        (_, cutoff, samples, pred_mu, pred_std,
         pred_iter) = self._pending_decision
        self._pending_decision = None
        self._pending_pred = (pred_mu, pred_std, samples)
        self._last_iter = pred_iter          # device scalar, fetched lazily
        # the ONLY host/device sync on the decision path: one int32, which
        # waits for the decision queued behind the train step
        with span("controller.fetch"):
            return int(cutoff)

    def predicted_samples(self):
        """The predictive sample cloud (K, n) behind the decision just
        made — a LAZY peek for the obs decision-quality layer: the device
        backend returns the device array unfetched (the obs drain
        materializes it in batch), the numpy backend its host samples.
        None before warmup and after ``observe`` consumed the cache."""
        if self._pending_pred is None:
            return None
        return self._pending_pred[2]

    def predicted_iter_time(self):
        """Posterior-predictive E[x_(c)] of the step just decided (raw
        seconds) — what the multi-tenant scheduler ranks jobs by; None
        before the first warmed-up decision.  The device backend gets it
        free out of the fused decision's shared sort; the numpy backend
        computes it here, on demand."""
        if self._last_iter is None:
            return None
        if isinstance(self._last_iter, tuple):
            _, samples, c = self._last_iter
            self._last_iter = float(
                np.sort(samples, axis=1)[:, c - 1].mean())
        return float(self._last_iter)

    def predicted_order_stats(self):
        """(mean, std) of predicted order statistics for the next step.

        Reuses the samples already drawn by the preceding
        ``predict_cutoff`` (cached on ``_pending_pred``) so diagnostics
        never double the inference cost.  ``observe`` invalidates the
        sample cache (the window changed), so a call after it falls back
        to a fresh prediction over the updated window — the pre-cache
        behavior.
        """
        if not self.warmed_up:
            return None
        if self._pending_pred is not None and self._pending_pred[2] is not None:
            samples = np.asarray(self._pending_pred[2])
        else:
            w = self.window_array()
            samples, _, _ = self.model.predict_next(
                w, self.k_samples, seed=self.seed + self._step)
        return order_stats.mc_order_stats(samples)

    # -- observation ----------------------------------------------------
    def observe(self, times, finished_mask=None):
        with span("controller.observe"):
            self._observe(times, finished_mask)

    def _observe(self, times, finished_mask):
        if finished_mask is not None and not bool(np.any(finished_mask)):
            # no coherent cutoff time exists: the device path would
            # silently impute at max(where(False, ..)) = -inf and poison
            # the ring — reject loudly on both backends instead
            raise ValueError(
                "observe got an all-False finished_mask: a step with zero "
                "finished workers has no observed cutoff time to impute "
                "the censored entries at")
        if self.backend == "numpy":
            return self._observe_numpy(times, finished_mask)
        self._ensure_ring()
        t = jnp.asarray(np.asarray(times, np.float32))
        mask = (jnp.ones(t.shape, bool) if finished_mask is None
                else jnp.asarray(np.asarray(finished_mask, bool)))
        all_finished = finished_mask is None or bool(np.all(finished_mask))
        if self._pending_pred is None or all_finished:
            # full sync, or warmup before any prediction exists
            obs, mode = {"times": t, "mask": mask}, "plain"
        else:
            pred_mu, pred_std, _ = self._pending_pred
            obs = {"times": t, "mask": mask, "mu": pred_mu, "std": pred_std,
                   "key": _impute_key(self.seed, self._step)}
            mode = "censored"
        if self._pending_pred is not None:
            # the moments stay valid for a repeated observe; the sample
            # cache does not survive a window change
            self._pending_pred = self._pending_pred[:2] + (None,)
        self._count = min(self._count + 1, self._cap)
        if self.warmed_up:
            # pipeline: fuse this append (imputation included) with the
            # NEXT step's decision and dispatch it now — the PS inference
            # runs while the workers compute, so the next predict_cutoff
            # only fetches a scalar (paper §1: the controller must decide
            # faster than the workers step)
            self._dispatch_decision(obs, mode, self._step + 1)
        else:
            self._ring, self._head = _ring_append(self._ring, self._head,
                                                  obs, mode=mode)

    def _observe_numpy(self, times, finished_mask=None):
        t = np.asarray(times, np.float64)
        if self._pending_pred is not None:
            # moments stay valid for a repeated observe; the sample cache
            # does not survive a window change
            self._pending_pred = self._pending_pred[:2] + (None,)
        # every read uses only the last lag+1 rows; drop the dead history
        # (the device backend's ring is O(lag+1) by construction)
        del self._window[:-self._cap]
        if finished_mask is None or bool(np.all(finished_mask)):
            self._window.append(t)
            return
        mask = np.asarray(finished_mask, bool)
        cutoff_time = float(t[mask].max())
        if self._pending_pred is None:
            # warmup fallback: impute with the max observed time
            imputed = np.where(mask, t, cutoff_time)
        else:
            mu, std = self._pending_pred[0], self._pending_pred[1]
            # reprolint: disable=host-sync-in-hot-path -- numpy REFERENCE backend: this whole method is the host-side equivalence twin, not the device dispatch path
            u = np.asarray(_impute_uniforms(
                _impute_key(self.seed, self._step), t.shape[0]), np.float64)
            imputed = censoring.impute_censored(t, mask, mu, std,
                                                cutoff_time, u=u)
        self._window.append(imputed)


# ---------------------------------------------------------------------------
# Elastic membership: DMM controller + analytic fallback + refit.
# ---------------------------------------------------------------------------


class RefitError(RuntimeError):
    """An async DMM refit raised, and the retry budget is spent.

    Raised from the POLL (``predict_cutoff`` / ``observe``), not lost on
    the worker thread: the owner keeps serving decisions through its
    fallback while one seeded retry is in flight, and only escalates
    when the retry fails too — a silently-dead refit would pin the
    controller on the fallback forever and nobody would know why.
    """


def _spawn_refit(fit_fn, gen: int) -> tuple:
    """Start a DMM refit on a daemon thread.

    Returns the ``(thread, result_box, generation)`` refit-task triple
    shared by :class:`ElasticController` and the multi-tenant
    ``ps.PSServer``: the thread fills ``result_box["model"]`` when the
    ELBO fit finishes — or ``result_box["error"]`` when it RAISES (the
    exception is captured, never swallowed; :func:`_poll_refit_task`
    hands it back to the owner's poll) — and the generation tag (the
    owner's resize count at spawn time) lets :func:`_poll_refit_task`
    discard results that a later resize made stale.  Dropping the triple
    abandons the fit without ever blocking a decision tick on
    ``model.fit``.
    """
    box: dict = {}

    def work():
        try:
            box["model"] = fit_fn()
        except BaseException as e:         # surfaced by the poll
            box["error"] = e

    thread = threading.Thread(target=work, daemon=True)
    task = (thread, box, gen)
    thread.start()
    return task


def _poll_refit_task(task: tuple, gen: int, width: int):
    """Non-blocking poll of a :func:`_spawn_refit` triple.

    Returns ``(done, model, error)``: ``(False, None, None)`` while the
    fit thread is still running; ``(True, model, None)`` once it
    finished AND the result is still current (generation matches and the
    fitted width is the owner's width); ``(True, None, exc)`` when the
    fit RAISED and the failure is still current (a stale failure is as
    dead as a stale result); ``(True, None, None)`` for a
    finished-but-stale fit, which is discarded, never installed.
    """
    thread, box, task_gen = task
    if thread.is_alive():
        return False, None, None
    thread.join()
    if task_gen != gen:
        return True, None, None
    error = box.get("error")
    if error is not None:
        return True, None, error
    model = box.get("model")
    if model is None or model.n_workers != width:
        return True, None, None
    return True, model, None


class ElasticController:
    """Membership-elastic cutoff controller (DMM + Elfving fallback + refit).

    Wraps the paper's :class:`CutoffController` for clusters whose worker
    set changes mid-run (rack loss, preemption, node return).  While the
    cluster shape matches the fitted :class:`RuntimeModel` it delegates
    every decision to the DMM controller.  Across a :meth:`resize` it:

      1. remaps its window/trace onto the new worker set — survivors
         column-exact, new workers seeded from the cluster-mean moments
         (:func:`remap_columns`);
      2. falls back to the analytic :class:`ElfvingController`
         (warm-seeded from the remapped window, so Eq. 3 decisions start
         immediately) — the degraded mode the elastic launch story
         narrates (``launch/elastic.py``);
      3. refits the DMM at the new width from the surviving window once
         ``refit_fresh`` post-resize observations have arrived
         (synchronously by default; ``refit_async=True`` runs the ELBO
         fit on a worker thread and swaps the DMM back in on completion),
         then resumes DMM decisions with the window it kept warm.

    The controller also keeps a rolling imputed trace (plain imputation at
    the observed cutoff time) as refit training data; ``window_array`` /
    ``seed_window`` expose its lag-window tail so checkpoints can persist
    and warm-restore straggler prediction across restarts and resizes.
    """

    def __init__(self, model: RuntimeModel, *, k_samples: int = 64,
                 min_frac: float = 0.5, seed: int = 0,
                 backend: str = "device", history: int = 512,
                 refit_steps: int = 150, refit_batch: int = 8,
                 refit_fresh: int = 4, refit_async: bool = False,
                 fallback_warmup: int = 3, refit_retries: int = 1):
        self.k_samples = k_samples
        self.min_frac = min_frac
        self.seed = seed
        self.backend = backend
        self.history = history
        self.refit_steps = refit_steps
        self.refit_batch = refit_batch
        self.refit_fresh = refit_fresh
        self.refit_async = refit_async
        self.fallback_warmup = fallback_warmup
        self.refit_retries = refit_retries
        self._refit_failures = 0          # consecutive failed async fits
        # architecture template for refits (widths change, shapes don't)
        self._lag = model.lag
        self._z_dim = model.z_dim
        self._hidden = model.hidden
        self._n = model.n_workers
        self._trace: list = []            # imputed full rows, rolling
        self._fresh = 0                   # post-resize observations
        self._resize_count = 0
        # async refit in flight: (thread, result_box, resize generation)
        self._refit_job: Optional[tuple] = None
        self.fallback_steps = 0           # observes served by the fallback
        self._dmm: Optional[CutoffController] = None
        self._fallback = ElfvingController(self._n,
                                           warmup=fallback_warmup,
                                           min_frac=min_frac)
        self._install_dmm(model)

    # -- bookkeeping ----------------------------------------------------
    @property
    def n(self) -> int:
        return self._n

    @property
    def mode(self) -> str:
        """"dmm" when the fitted controller decides, "fallback" while a
        resize awaits its refit."""
        return "dmm" if self._dmm is not None else "fallback"

    @property
    def warmed_up(self) -> bool:
        return len(self._trace) >= self._lag + 1

    def _install_dmm(self, model: RuntimeModel):
        assert model.n_workers == self._n, (model.n_workers, self._n)
        ctl = CutoffController(
            model, k_samples=self.k_samples, min_frac=self.min_frac,
            seed=self.seed + 101 * self._resize_count, backend=self.backend)
        rows = self._trace[-(self._lag + 1):]
        if rows:
            ctl.seed_window(np.stack(rows))
        self._dmm = ctl

    def _active(self):
        return self._dmm if self._dmm is not None else self._fallback

    # -- window persistence (checkpoint contract) -----------------------
    def window_array(self) -> np.ndarray:
        """The lag-window tail of the imputed trace, oldest row first."""
        return np.stack(self._trace[-(self._lag + 1):])

    def seed_window(self, traces: np.ndarray):
        """Warm-start from recorded rows at the CURRENT width."""
        rows = [np.asarray(r, np.float64) for r in np.asarray(traces)]
        if rows and rows[0].shape != (self._n,):
            raise ValueError(f"seed rows have width {rows[0].shape}, "
                             f"controller width is {self._n}")
        self._trace = (self._trace + rows)[-self.history:]
        for r in rows[-50:]:
            self._fallback.buf.append(r)
        if self._dmm is not None:
            self._dmm.seed_window(np.stack(self._trace[-(self._lag + 1):]))

    # -- decision / observation -----------------------------------------
    def predict_cutoff(self) -> int:
        self._poll_refit()
        return self._active().predict_cutoff()

    def predicted_order_stats(self):
        if self._dmm is not None:
            return self._dmm.predicted_order_stats()
        return None

    def predicted_samples(self):
        if self._dmm is not None:
            return self._dmm.predicted_samples()
        return None

    def observe(self, times, finished_mask=None):
        t = np.asarray(times, np.float64)
        if t.shape != (self._n,):
            raise ValueError(
                f"observe got {t.shape[0]} runtimes at width {self._n}; "
                f"call resize() before observing the resized step")
        row = t
        if finished_mask is not None:
            m = np.asarray(finished_mask, bool)
            if not m.any():
                raise ValueError(
                    "observe got an all-False finished_mask: a step with "
                    "zero finished workers has no observed cutoff time to "
                    "impute the trace row at")
            if not m.all():
                # plain imputation at the observed cutoff time is enough
                # for refit TRAINING data; the active DMM still runs the
                # truncated-normal imputation for its own window
                row = np.where(m, t, t[m].max())
        self._trace = (self._trace + [row])[-self.history:]
        if self._dmm is None:
            self.fallback_steps += 1
        self._active().observe(times, finished_mask)
        self._fresh += 1
        self._poll_refit()
        if self._dmm is None and self._refit_job is None:
            self._maybe_refit()

    # -- resize protocol -------------------------------------------------
    def resize(self, n_workers: int, col_map=None,
               model: Optional[RuntimeModel] = None, members=None):
        """Worker-set change: remap, fall back, schedule the refit.

        ``col_map`` as in :func:`remap_columns`.  If ``model`` (already
        fitted at the new width) is supplied, the DMM controller resumes
        immediately; otherwise decisions route through the Elfving
        fallback until the refit lands.
        """
        n_new = int(n_workers)
        if model is not None and model.n_workers != n_new:
            raise ValueError(
                f"resize({n_new}) got a RuntimeModel of width "
                f"{model.n_workers}; refit it for the new width first")
        if n_new == self._n and col_map is None and model is None:
            return
        # abandon any in-flight refit WITHOUT blocking on its ELBO fit:
        # the daemon thread keeps filling its orphaned result box, and
        # _poll_refit discards it by generation
        self._refit_job = None
        if self._trace:
            rows = remap_columns(np.stack(self._trace), n_new, col_map)
            self._trace = [row for row in rows]
        self._n = n_new
        self._resize_count += 1
        self._fresh = 0
        self._dmm = None
        self._fallback = ElfvingController(n_new,
                                           warmup=self.fallback_warmup,
                                           min_frac=self.min_frac)
        for r in self._trace[-50:]:
            self._fallback.buf.append(r)
        if model is not None:
            self._install_dmm(model)

    # -- refit plumbing --------------------------------------------------
    def _enough_rows(self) -> bool:
        # RuntimeModel.fit needs strictly more than lag+1 rows; demand a
        # small margin so the first refit windows aren't degenerate
        return len(self._trace) >= self._lag + 1 + self.refit_batch

    def _maybe_refit(self):
        # failed attempts back the respawn off exponentially: each one
        # demands twice the fresh observations before the next try
        need = self.refit_fresh * (2 ** self._refit_failures)
        if self._fresh < need or not self._enough_rows():
            return
        # freeze width/seed now: a resize mid-fit must not retarget the
        # running fit (its result is discarded by generation anyway)
        rows = np.stack(self._trace)
        n = self._n
        seed = self.seed + self._resize_count + 1000 * self._refit_failures
        if self.refit_async:
            self._refit_job = _spawn_refit(
                lambda: self._fit_model(rows, n, seed), self._resize_count)
        else:
            self._install_dmm(self._fit_model(rows, n, seed))

    def _poll_refit(self):
        if self._refit_job is None:
            return
        # a resize since the fit started makes the result stale (wrong
        # membership, possibly even the wrong width) — _poll_refit_task
        # drops it by generation/width
        done, model, err = _poll_refit_task(self._refit_job,
                                            self._resize_count, self._n)
        if not done:
            return
        self._refit_job = None
        if err is not None:
            self._refit_failures += 1
            if self._refit_failures > self.refit_retries:
                raise RefitError(
                    f"DMM refit failed {self._refit_failures} times at "
                    f"width {self._n} (retry budget {self.refit_retries} "
                    f"spent); last error: {err!r}") from err
            # log + retry: stay on the fallback, reschedule with backoff
            print(f"DMM refit failed ({err!r}); retrying after "
                  f"{self.refit_fresh * 2 ** self._refit_failures} fresh "
                  f"observations")
            self._fresh = 0
            return
        if model is not None:
            self._refit_failures = 0
            self._install_dmm(model)

    def _fit_model(self, rows: np.ndarray, n: int,
                   seed: int) -> RuntimeModel:
        model = RuntimeModel(n_workers=n, lag=self._lag,
                             z_dim=self._z_dim, hidden=self._hidden)
        model.fit(rows, steps=self.refit_steps, batch=self.refit_batch,
                  seed=seed)
        return model
