#!/usr/bin/env python3
"""Smoke run of the cutoff-SGD system on a TPU, through its normal entry
points, at qwen2-0.5b's published widths.

  python chip_smoke.py             # one chip: trainer, parameter server,
                                   # Pallas kernels
  python chip_smoke.py --chips 4   # four chips: masked data-parallel steps
                                   # on a (4,) "data" mesh vs one chip

Every phase runs in this one process (a chip belongs to one process).  The
script exits non-zero, printing no result line, unless JAX's first device
is a TPU.  On success the last line of stdout is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from repro import optim  # noqa: E402
from repro.cluster.simulator import ClusterSim  # noqa: E402
from repro.cluster.trace import TraceReplay  # noqa: E402
from repro.configs.base import SHAPES, get_config  # noqa: E402
from repro.core import aggregation  # noqa: E402
from repro.core.controller import CutoffController  # noqa: E402
from repro.core.runtime_model.api import RuntimeModel  # noqa: E402
from repro.data.pipeline import SyntheticTokens  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.launch.multi_job import build_multi_job, run_ticks  # noqa: E402
from repro.launch.train import (Trainer, jit_train_step,  # noqa: E402
                                make_train_step)
from repro.models import model as M  # noqa: E402
from repro.perf import hlo_stats  # noqa: E402
from repro.perf.knobs import use_knobs  # noqa: E402
from repro.ps import make_scheduler  # noqa: E402

ARCH = "qwen2-0.5b"
SEQ = SHAPES["train_4k"].seq_len
BATCH = 8            # largest multiple of 8 whose step fits one v5e's 16 GiB
LR = 1e-3
SEED = 0
K_SAMPLES = 48       # DMM predictive samples per decision
CE_CHUNK = 8192      # vocab chunk of the fused CE: (B*S, V) f32 logits
                     # would not fit (8 x 4096 x 151936 x 4 B ~ 20 GB)

# bf16 carries 8 significand bits: two paths that differ only in reduction
# order agree to about one ulp (2^-8) relative
LOSS_RTOL = 2.0 ** -8
GRAD_RTOL = 2.0 ** -3        # aggregated gradient: norm and relative RMS
GRAD_SIGNAL_FRAC = 2.0 ** -5  # |m| below this share of its leaf's max is
                              # within reach of the bf16 gradient noise
PARAM_RTOL = 2.0 ** -7        # two bf16 ulps
PARAM_ATOL = LR / 8           # an eighth of one Adam step
PARAM_BAD_FRAC = 1e-2


class CompileClock:
    """Sums XLA compile time while active (a persistent-cache read counts
    as a compile of its read time) and counts compiles and cache hits."""

    def __init__(self):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Trainer phase: the main path.
# ---------------------------------------------------------------------------


def f32_example_ce(cfg, vchunk: int):
    """jit: (params, batch) -> per-example summed token CE, evaluated in
    float32 at the highest matmul precision, one example at a time."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    def per_example(params, batch):
        p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)

        def one(ex):
            b = {k: v[None] for k, v in ex.items()}
            x, _, _ = M.forward(cfg32, p32, b, mode="train", head=False,
                                remat=False)
            return M.chunked_ce_sum(cfg32, p32, x, b["labels"], None, vchunk)

        with jax.default_matmul_precision("highest"):
            return jax.lax.map(one, batch)

    return jax.jit(per_example)


def replay_decisions(rm, trace, times, cutoffs):
    """Replay the Trainer's observations through a ``backend="numpy"``
    controller on the host CPU (the default device where JAX was started
    without one); returns (its cutoff for every step, that device)."""
    try:
        dev = jax.local_devices(backend="cpu")[0]
    except RuntimeError:        # JAX_PLATFORMS names no host CPU
        dev = jax.devices()[0]
    rm_ref = dataclasses.replace(rm, params=jax.device_put(rm.params, dev))
    out = []
    with jax.default_device(dev):
        ctl = CutoffController(rm_ref, k_samples=K_SAMPLES, seed=SEED,
                               backend="numpy")
        ctl.seed_window(trace)
        for t, c in zip(times, cutoffs):
            out.append(ctl.predict_cutoff())
            # the finished set the Trainer built from ITS cutoff
            finished = np.zeros(t.shape[0], bool)
            finished[np.argsort(t)[:c]] = True
            ctl.observe(t, finished)
    return out, dev.platform


def trainer_phase(cfg, *, seq: int, batch: int, workers: int, steps: int,
                  ce_chunk: int, lr: float = LR, fit_steps: int = 300):
    """Warm-up step + ``steps`` timed steps of ``Trainer`` with a
    device-backend DMM controller.  Returns (report, failures)."""
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=seq,
                           global_batch=batch, seed=SEED)
    trace = ClusterSim(n_workers=workers, n_nodes=4, seed=SEED).run(200)
    t0 = time.perf_counter()
    rm = RuntimeModel(n_workers=workers, lag=20).init(SEED)
    rm.fit(trace, steps=fit_steps, batch=8, seed=SEED)
    fit_s = time.perf_counter() - t0
    ctl = CutoffController(rm, k_samples=K_SAMPLES, seed=SEED,
                           backend="device")
    ctl.seed_window(trace)
    times = ClusterSim(n_workers=workers, n_nodes=4,
                       seed=SEED + 9).run(steps + 1)
    opt = optim.adamw(lr)
    tr = Trainer(cfg=cfg, step_fn=jit_train_step(cfg, opt, mask_agg="weights"),
                 data=data, controller=ctl,
                 timer=TraceReplay(times, loop=False), n_workers=workers,
                 mask_agg="weights", metrics_every=0)
    params = M.init_model(cfg, jax.random.PRNGKey(SEED))
    # the reference reads the initial params before step 0 donates them
    ce0 = np.asarray(f32_example_ce(cfg, ce_chunk)(params, data.batch(0)),
                     np.float64)
    tr.state = {"params": params, "opt": opt.init(params)}
    del params

    step_s = []
    with use_knobs(ce_chunk=ce_chunk), CompileClock() as clock:
        t0 = time.perf_counter()
        tr.run(1)
        jax.block_until_ready(tr.state)
        warmup_s = time.perf_counter() - t0
        compile_s, n_compiles = clock.seconds, clock.count
        for _ in range(steps):
            t0 = time.perf_counter()
            tr.run(1)
            jax.block_until_ready(tr.state)
            step_s.append(time.perf_counter() - t0)
    hist = tr.history
    losses = [float(h["loss"]) for h in hist]
    cutoffs = [int(h["c"]) for h in hist]
    w = aggregation.example_weights(
        np.isin(np.arange(workers), np.argsort(times[0])[:cutoffs[0]]),
        batch)
    ref0 = float(np.sum(w * ce0) / (np.sum(w) * seq))
    ref_cuts, ref_dev = replay_decisions(rm, trace, times, cutoffs)
    agree = sum(a == b for a, b in zip(cutoffs, ref_cuts))
    med = float(np.median(step_s))
    report = {
        "arch": cfg.name, "params": cfg.n_params(), "seq": seq,
        "batch": batch, "workers": workers, "ce_chunk": ce_chunk,
        "dmm_fit_s": fit_s, "warmup_step_s": warmup_s,
        "compile_s": compile_s, "compiles": n_compiles,
        "persistent_cache_hits": clock.cache_hits,
        "compiles_in_timed_steps": clock.count - n_compiles,
        "step_s": step_s, "step_s_median": med,
        "tokens_per_s": batch * seq / med,
        "losses": losses, "loss0_f32_reference": ref0,
        "loss0_rel_err": abs(losses[0] - ref0) / abs(ref0),
        "cutoffs": cutoffs, "numpy_cutoffs": ref_cuts,
        "decision_agreement": f"{agree}/{len(cutoffs)}",
        "numpy_replay_device": ref_dev,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
    }
    fails = []
    if not all(np.isfinite(losses)):
        fails.append(f"non-finite loss in {losses}")
    if report["loss0_rel_err"] > 0.02:
        fails.append(f"step-0 loss {losses[0]} is not within 2% of its "
                     f"float32 evaluation {ref0}")
    if not np.mean(losses[-3:]) < losses[0]:
        fails.append(f"losses did not fall: {losses}")
    if not all(1 <= c <= workers for c in cutoffs):
        fails.append(f"cutoff outside [1, {workers}]: {cutoffs}")
    return report, fails


# ---------------------------------------------------------------------------
# Parameter-server phase: J jobs through one PSServer.
# ---------------------------------------------------------------------------


def ps_phase(*, n_jobs: int, n_per_job: int, ticks: int,
             fit_steps: int = 120):
    """``ticks`` scheduler ticks of J seeded jobs through one PSServer.
    Returns (report, failures)."""
    t0 = time.perf_counter()
    server, jobs, _ = build_multi_job(n_jobs, n_per_job, seed=SEED,
                                      fit_steps=fit_steps,
                                      global_batch=n_per_job,
                                      metrics_every=50)
    build_s = time.perf_counter() - t0
    # host wall time of every flush call (the fused decision's dispatch)
    flush_s, flush = [], server.flush

    def timed_flush():
        t = time.perf_counter()
        issued = flush()
        flush_s.append(time.perf_counter() - t)
        return issued

    server.flush = timed_flush
    sched = make_scheduler("rr")
    per_tick, tick_s = [], []
    for _ in range(ticks):
        t0 = time.perf_counter()
        per_tick.append(run_ticks(server, jobs, sched, 1)["dispatches"])
        tick_s.append(time.perf_counter() - t0)
    cuts = {j: [h["c"] for h in run.trainer.history]
            for j, run in jobs.items()}
    # tick 0 also dispatches the decide-only prefetch of every job's first
    # decision (no observation exists yet) and compiles; later ticks are
    # the steady state
    report = {"jobs": n_jobs, "workers_per_job": n_per_job, "ticks": ticks,
              "build_s": build_s, "dispatches_per_tick": per_tick,
              "flush_s": flush_s,
              "flush_s_median": float(np.median(flush_s[1:])),
              "tick_s_median": float(np.median(tick_s[1:])),
              "cutoff_min": min(min(c) for c in cuts.values()),
              "cutoff_max": max(max(c) for c in cuts.values())}
    fails = []
    if per_tick[0] != 2 or per_tick[1:] != [1] * (ticks - 1):
        fails.append(f"dispatches per tick {per_tick}, want 2 (prefetch + "
                     f"flush) then 1 each")
    bad = {j: c for j, c in cuts.items()
           if len(c) != ticks or not all(1 <= x <= n_per_job for x in c)}
    if bad:
        fails.append(f"cutoffs out of [1, {n_per_job}] or steps missing: "
                     f"{bad}")
    return report, fails


# ---------------------------------------------------------------------------
# Kernel phase: both wired Pallas kernels against their references.
# ---------------------------------------------------------------------------


def kernel_phase(cfg, *, workers: int):
    """``masked_grad_agg`` on a (workers, layer-leaf) gradient stack and
    ``fused_adam`` on the embedding leaf, through ``kernels.ops``, each
    against its ``kernels.ref`` oracle.  Returns (report, failures)."""
    shapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    w_down = shapes["segments"][0][0]["mlp"]["w_down"].shape[1:]
    emb = shapes["embed"]["table"]
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    fails, report = [], {"backend": ops._mode(), "agg_leaf": w_down,
                         "adam_leaf": emb.shape}
    if report["backend"] != "pallas":
        fails.append(f"kernel backend {report['backend']!r}, want "
                     f"'pallas'")

    n = int(np.prod(w_down))
    g = jax.random.normal(ks[0], (workers, n), jnp.float32)
    mask = (jax.random.uniform(ks[1], (workers,)) < 0.7).astype(jnp.float32)
    got = ops.masked_aggregate(g, mask)
    want = ref.reference_masked_agg(g, mask[:, None])[0]
    report["agg_max_abs_err"] = float(jnp.max(jnp.abs(got - want)))
    if not bool(jnp.allclose(got, want, rtol=1e-5, atol=1e-6)):
        fails.append(f"masked_grad_agg != reference "
                     f"(max abs err {report['agg_max_abs_err']})")
    del g, got, want

    p = (jax.random.normal(ks[2], emb.shape) * 0.02).astype(emb.dtype)
    gr = (jax.random.normal(ks[3], emb.shape) * 1e-3).astype(emb.dtype)
    m = jax.random.normal(ks[4], emb.shape) * 1e-3
    v = jnp.abs(jax.random.normal(ks[5], emb.shape)) * 1e-6
    step, lr, wd = jnp.int32(3), 1e-3, 0.01
    po, mo, vo = ops.adam_update_tree({"e": p}, {"e": gr}, {"e": m},
                                      {"e": v}, step, lr, wd=wd)
    t = 4.0
    scalars = jnp.asarray([lr, 1 - 0.9 ** t, 1 - 0.999 ** t], jnp.float32)
    rp, rm_, rv = ref.reference_adam(p, gr, m, v, scalars, wd=wd)
    for name, a, b, tol in (("p", po["e"], rp, (1e-2, 1e-4)),
                            ("m", mo["e"], rm_, (1e-5, 1e-8)),
                            ("v", vo["e"], rv, (1e-5, 1e-12))):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        report[f"adam_{name}_max_abs_err"] = err
        if not bool(jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                                 rtol=tol[0], atol=tol[1])):
            fails.append(f"fused_adam {name} != reference (max abs err "
                         f"{err})")
    return report, fails


# ---------------------------------------------------------------------------
# Four-chip phase: masked data-parallel steps across chips vs one chip.
# ---------------------------------------------------------------------------


def fsdp_step(cfg, opt, lay, mask_agg: str, ce_chunk: int):
    """jit of one masked train step under layout ``lay`` (state donated)."""
    step = make_train_step(cfg, opt, mask_agg=mask_agg)

    def run(state, batch):
        with shd.use_layout(lay), use_knobs(ce_chunk=ce_chunk):
            return step(state, batch)

    return jax.jit(run, donate_argnums=(0,))


def _run_path(cfg, opt, devices, mask_agg, batches, masks, ce_chunk):
    """Init, compile and run the masked steps of one aggregation path.
    ``mask_agg="local"`` is the one-chip weights path on devices[0]."""
    if mask_agg == "local":
        rep = dp = SingleDeviceSharding(devices[0])
        lay, kind = shd.LOCAL, "weights"
    else:
        mesh = Mesh(np.asarray(devices), ("data",))
        rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        lay, kind = shd.Layout(mesh=mesh, mode="train_fsdp",
                               dp=("data",)), mask_agg

    def init():
        params = M.init_model(cfg, jax.random.PRNGKey(SEED))
        return {"params": params, "opt": opt.init(params)}

    state = jax.jit(init, out_shardings=rep)()
    ins = []
    for b, mask in zip(batches, masks):
        b = {k: jax.device_put(v, dp) for k, v in b.items()}
        if kind == "psum":
            b["mask"] = jax.device_put(mask, rep)
        else:
            b["weights"] = jax.device_put(
                aggregation.example_weights(mask, b["tokens"].shape[0]), dp)
        ins.append(b)
    t0 = time.perf_counter()
    compiled = fsdp_step(cfg, opt, lay, kind, ce_chunk).lower(
        state, ins[0]).compile()
    run = {"compiled": compiled, "compile_s": time.perf_counter() - t0,
           "losses": [], "gnorms": [], "step_s": []}
    for b in ins:
        t0 = time.perf_counter()
        state, metrics = compiled(state, b)
        jax.block_until_ready(state)
        run["step_s"].append(time.perf_counter() - t0)
        run["losses"].append(float(metrics["loss"]))
        run["gnorms"].append(float(metrics["gnorm"]))
        if "grad0" not in run:
            # Adam's first moment after one step: (1 - b1) x the step-0
            # aggregated gradient, taken at the initial params
            run["grad0"] = jax.device_get(state["opt"]["m"])
    run["params"] = jax.device_get(state["params"])
    run["m"] = jax.device_get(state["opt"]["m"])
    return run


def _max_rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _compare_runs(a, b):
    """How far run ``a`` is from run ``b``.

    The step-0 aggregated gradient (both runs at the initial params) is
    compared per leaf, normwise (relative RMS): bf16 forward and backward
    passes that group the batch differently put noise of about 1% RMS on
    it, while an aggregation fault (a dropped worker counted, a wrong
    normalizer) moves it by 25% or more.  Params after the last step are
    compared elementwise only where the gradient stands above that noise:
    Adam scales every coordinate to a step of about ``LR`` whatever its
    size, so where the gradient is noise-level (a tied-embedding row of a
    token absent from the batch is a sum of ~V*T tiny softmax terms) two
    runs step in opposite directions, and the next batches read those
    rows.
    """
    g_rel, noise, off, total, worst = 0.0, 0, 0, 0, 0.0
    for pa, pb, ga, gb, mb in zip(
            *(jax.tree.leaves(r[k]) for k in ("params", "grad0")
              for r in (a, b)), jax.tree.leaves(b["m"])):
        ga, gb = np.asarray(ga, np.float32), np.asarray(gb, np.float32)
        mb = np.asarray(mb, np.float32)
        scale = float(np.max(np.abs(mb)))
        if scale == 0.0:
            continue
        g_rel = max(g_rel, float(np.sqrt(np.mean(np.square(ga - gb))
                                         / np.mean(np.square(gb)))))
        signal = np.abs(mb) > GRAD_SIGNAL_FRAC * scale
        pa, pb = np.asarray(pa, np.float32), np.asarray(pb, np.float32)
        d = np.abs(pa - pb)
        off += int(np.count_nonzero(
            signal & (d > PARAM_RTOL * np.abs(pb) + PARAM_ATOL)))
        noise += int(signal.size - np.count_nonzero(signal))
        total += d.size
        worst = max(worst, float(d.max()))
    return {"loss_max_rel": _max_rel(a["losses"], b["losses"]),
            "gnorm_max_rel": _max_rel(a["gnorms"], b["gnorms"]),
            "grad0_rel_rms": g_rel, "param_frac_off": off / total,
            "param_frac_noise_level": noise / total,
            "param_max_abs": worst}


def four_chip_phase(cfg, devices, *, seq: int, per_worker: int, steps: int,
                    ce_chunk: int):
    """``steps`` masked steps, one worker per device, on the weights and
    psum paths over a (W,) "data" mesh, against the same steps on one
    device under LOCAL.  Returns (report, failures)."""
    W = len(devices)
    B = W * per_worker
    opt = optim.adamw(LR)
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=seq,
                           global_batch=B, seed=SEED)
    rng = np.random.default_rng(SEED)
    masks = []
    for _ in range(steps):          # one straggler dropped per step
        m = np.ones(W, np.float32)
        m[rng.integers(W)] = 0.0
        masks.append(m)
    batches = [data.batch(t) for t in range(steps)]
    runs = {name: _run_path(cfg, opt, devices, name, batches, masks,
                            ce_chunk)
            for name in ("local", "weights", "psum")}
    report = {"devices": W, "seq": seq, "global_batch": B, "masks":
              [m.tolist() for m in masks]}
    fails = []
    for name, run in runs.items():
        for k in ("losses", "gnorms", "compile_s", "step_s"):
            report[f"{name}_{k}"] = run[k]
    for a, b in (("weights", "local"), ("psum", "local"),
                 ("psum", "weights")):
        cmp = report[f"{a}_vs_{b}"] = _compare_runs(runs[a], runs[b])
        if cmp["loss_max_rel"] > LOSS_RTOL:
            fails.append(f"{a} losses {runs[a]['losses']} != {b} losses "
                         f"{runs[b]['losses']}")
        if cmp["gnorm_max_rel"] > GRAD_RTOL or cmp["grad0_rel_rms"] > GRAD_RTOL:
            fails.append(f"{a} gradients != {b} gradients: {cmp}")
        if cmp["param_frac_off"] > PARAM_BAD_FRAC:
            fails.append(f"{a} params != {b} params: {cmp}")
    hlo = runs["psum"]["compiled"].as_text()
    coll = hlo_stats.collective_bytes(hlo)
    lines = hlo.splitlines()
    ar_lines = [ln for ln in lines if _is_op(ln, "all-reduce")]
    report["psum_hlo"] = {
        "all_reduce_ops": len(ar_lines),
        "all_reduce_bytes": coll["all-reduce"],
        "replica_groups": sorted({m.group(1) for m in (
            re.search(r"replica_groups=(\S+?)(?:,\s|$)", ln)
            for ln in ar_lines) if m}),
        "other_collective_bytes": {k: coll[k] for k in hlo_stats.COLLECTIVES
                                   if k != "all-reduce" and coll[k]},
        # an all-gather inside the worker scan's loop body fetches other
        # workers' examples: every chip then runs every worker's gradient
        "all_gathers_in_worker_loop": sum(
            1 for ln in lines
            if _is_op(ln, "all-gather") and "/while/body/" in ln)}
    groups = report["psum_hlo"]["replica_groups"]
    if not groups or not all(_spans(g, W) for g in groups):
        fails.append(f"psum path all-reduces {groups}, want every one over "
                     f"all {W} devices of 'data'")
    report["psum_each_chip_only_own_worker"] = (
        report["psum_hlo"]["all_gathers_in_worker_loop"] == 0)
    # about 1 when each chip computes only its own worker's gradient, about
    # W when every chip runs all W iterations of the worker scan
    report["psum_over_weights_step_s"] = (
        float(np.median(report["psum_step_s"]))
        / float(np.median(report["weights_step_s"])))
    return report, fails


def _spans(groups: str, n: int) -> bool:
    """Whether an HLO ``replica_groups`` value is one group of all ``n``
    devices: ``{{0,1,2,3}}`` or the iota form ``[1,4]<=[4]``."""
    if groups.startswith("{{"):
        ids = groups[2:-2].split("},{")
        return len(ids) == 1 and len(ids[0].split(",")) == n
    return groups.startswith(f"[1,{n}]")


def _is_op(line: str, op: str) -> bool:
    """Whether an HLO text line is an ``op`` instruction (sync or async)."""
    return f" {op}(" in line or f" {op}-start(" in line


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX's first device is {devices[0].platform!r}, "
              f"not a TPU; refusing to run on it", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache = place_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"compile cache: {cache}", flush=True)
    cfg = get_config(ARCH)
    if args.chips == 4:
        phases = [("four_chip", lambda: four_chip_phase(
            cfg, devices[:4], seq=SEQ, per_worker=1, steps=3,
            ce_chunk=CE_CHUNK))]
    else:
        phases = [
            ("trainer", lambda: trainer_phase(
                cfg, seq=SEQ, batch=BATCH, workers=8, steps=8,
                ce_chunk=CE_CHUNK)),
            ("parameter_server", lambda: ps_phase(
                n_jobs=16, n_per_job=158, ticks=20)),
            ("kernels", lambda: kernel_phase(cfg, workers=16)),
        ]
    failures = []
    t_all = time.perf_counter()
    for name, run in phases:
        t0 = time.perf_counter()
        report, fails = run()
        report["phase_s"] = time.perf_counter() - t0
        print(f"{name}: {json.dumps(report, default=str)}", flush=True)
        failures += [f"{name}: {f}" for f in fails]
    print(f"total_s: {time.perf_counter() - t_all}", flush=True)
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
