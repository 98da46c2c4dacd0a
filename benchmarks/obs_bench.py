"""Obs overhead benchmark: what the telemetry spine costs, measured.

Four sections, all seeded, emitted as CSV rows AND into
``BENCH_obs.json`` (schema ``bench_obs/v1``):

  * ``step`` — the headline gate: end-to-end Trainer step latency with a
    full ``ObsRun`` attached (the step's spans recorded, one
    donated metric-ring push per step, the decision-quality wrapper)
    vs the identical bare trainer, at n ∈ {8, 158}.  Min-of-repeats on
    both sides; ``scripts/ci.sh --bench`` pins ``overhead_frac`` at
    n=158 to <= 5% — the "zero-sync" claim, priced.
  * ``ring`` — the device collector path in isolation: µs per
    ``MetricRing.push`` (one donated jit dispatch, nothing fetched) and
    per ``MetricsRegistry.drain`` of a full 256-row ring (the ONLY
    device read the spine ever does).
  * ``span`` — µs per span recorded by an open tracer (two
    ``perf_counter`` stamps + one in-memory record), that cost multiplied
    by the 7 spans a Trainer step emits, and ns per span with nobody
    listening (the shared no-op object).
  * ``calibration`` — a seeded controller-level mini-race (sync /
    static / firstk / dmm over the same paper-cluster draws) recorded
    through ``--obs-dir`` artifacts, then summarized with
    ``repro.obs.report.calibration_report`` — the frontier story
    (regret / idle / discard / DMM quantile coverage) reproduced from
    JSONL alone, exactly what ``python -m repro.obs`` renders.
"""
from __future__ import annotations

import json
import tempfile
import time

from benchmarks.common import emit

STEP_NS = (8, 158)
RING_CAP = 256


# ---------------------------------------------------------------------------
# step: instrumented vs bare Trainer.
# ---------------------------------------------------------------------------


def _step_bench(n_list, steps: int, repeats: int = 3):
    import jax

    from repro import optim
    from repro.cluster.simulator import paper_cluster_158
    from repro.configs.base import bench_tiny_config
    from repro.core.controller import CutoffController
    from repro.core.runtime_model.api import RuntimeModel
    from repro.data.pipeline import SyntheticTokens
    from repro.launch.train import Trainer, jit_train_step
    from repro.models import model as M
    from repro.obs import ObsRun

    cfg = bench_tiny_config()
    opt = optim.adamw(3e-3)
    step_fn = jit_train_step(cfg, opt)

    def init_fn():
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": opt.init(params)}

    rows = []
    for n in n_list:
        trace = paper_cluster_158(seed=0, n_workers=n).run(40)

        def make_ctl():
            # analytic-scale model (no fit): decisions are deterministic
            # and identical across the bare/instrumented runs, which is
            # all a latency comparison needs
            rm = RuntimeModel(n_workers=n, lag=20).init(0)
            rm.norm_scale = float(2.0 * trace[:21].mean())
            ctl = CutoffController(rm, k_samples=16, seed=0)
            ctl.seed_window(trace)
            return ctl

        def run_once(instrument: bool) -> float:
            obs = ObsRun() if instrument else None
            ctl = make_ctl()
            data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=8,
                                   global_batch=n, seed=0)
            tr = Trainer(cfg=cfg, step_fn=step_fn, data=data,
                         controller=obs.wrap(ctl, policy="dmm")
                         if instrument else ctl,
                         timer=paper_cluster_158(seed=9, n_workers=n),
                         n_workers=n, metrics_every=0, obs=obs,
                         name="dmm" if instrument else None)
            tr.restore_or_init(init_fn)
            tr.run(3)                       # warm the compile caches
            t0 = time.perf_counter()
            tr.run(steps)
            us = (time.perf_counter() - t0) / steps * 1e6
            if obs is not None:
                obs.close()
            return us

        bare = min(run_once(False) for _ in range(repeats))
        inst = min(run_once(True) for _ in range(repeats))
        frac = inst / bare - 1.0
        rows.append({"n_workers": n, "steps": steps, "repeats": repeats,
                     "bare_us": bare, "instrumented_us": inst,
                     "overhead_frac": frac})
        emit(f"obs/step_overhead_n{n}", inst,
             f"bare={bare:.1f}us;frac={frac * 100:+.1f}%")
    return rows


# ---------------------------------------------------------------------------
# ring + span micro-costs.
# ---------------------------------------------------------------------------


def _ring_bench(n_push: int = 512):
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    ring = reg.ring("bench", ("a", "b", "c", "d"), cap=RING_CAP)
    ring.push((0.0, 1.0, 2.0, 3.0))         # warm the donated jit
    reg.drain()
    t0 = time.perf_counter()
    for i in range(n_push):
        ring.push((float(i), 1.0, 2.0, 3.0))
    push_us = (time.perf_counter() - t0) / n_push * 1e6
    t0 = time.perf_counter()
    payloads = reg.drain()
    drain_us = (time.perf_counter() - t0) * 1e6
    p = payloads[0]
    out = {"cap": RING_CAP, "pushes": n_push, "push_us": push_us,
           "drain_us": drain_us, "rows_drained": len(p["rows"]),
           "dropped": p["dropped"]}
    emit("obs/ring_push", push_us, f"cap={RING_CAP}")
    emit("obs/ring_drain", drain_us,
         f"rows={out['rows_drained']};dropped={out['dropped']}")
    return out


def _span_bench(n_spans: int = 4000):
    from repro.obs.trace import ObsLog, Tracer, span

    # nobody listening: the shared no-op object
    t0 = time.perf_counter()
    for _ in range(n_spans):
        with span("bench.span"):
            pass
    ns_off = (time.perf_counter() - t0) / n_spans * 1e9
    tracer = Tracer(log=ObsLog(None))
    t0 = time.perf_counter()
    for i in range(n_spans):
        with span("bench.span", step=i):
            pass
    us = (time.perf_counter() - t0) / n_spans * 1e6
    tracer.close()
    # a DMM Trainer step opens 7 spans: trainer.step, trainer.timer,
    # trainer.batch, train.dispatch, controller.predict_cutoff (+ its
    # controller.fetch) and controller.observe
    per_step = 7
    out = {"n_spans": n_spans, "us_per_span": us, "ns_per_span_off": ns_off,
           "spans_per_trainer_step": per_step,
           "us_per_trainer_step": per_step * us}
    emit("obs/span", us, f"{per_step * us:.1f}us/trainer-step;"
         f"off={ns_off:.0f}ns")
    return out


# ---------------------------------------------------------------------------
# calibration: the frontier story from artifacts alone.
# ---------------------------------------------------------------------------


def _calibration_bench(steps: int, n: int = 8, seed: int = 0):
    from repro.cluster.simulator import paper_cluster_158
    from repro.core.controller import (CutoffController, FirstKController,
                                       FullSyncController,
                                       StaticCutoffController)
    from repro.core.cutoff import order_stats
    from repro.core.runtime_model.api import RuntimeModel
    from repro.obs import ObsRun
    from repro.obs import report as R

    trace = paper_cluster_158(seed=seed, n_workers=n).run(120)
    rm = RuntimeModel(n_workers=n, lag=10).init(seed)
    rm.fit(trace, steps=80, batch=8, seed=seed)
    dmm = CutoffController(rm, k_samples=32, seed=seed)
    dmm.seed_window(trace[-40:])
    policies = [("sync", FullSyncController(n)),
                ("static", StaticCutoffController(n, cutoff=n - 1)),
                ("firstk", FirstKController(n, backup=1)),
                ("dmm", dmm)]

    obs_dir = tempfile.mkdtemp(prefix="obs_bench_")
    with ObsRun(obs_dir) as obs:
        for name, bare in policies:
            ctl = obs.wrap(bare, policy=name)
            sim = paper_cluster_158(seed=seed + 9, n_workers=n)
            for _ in range(steps):
                c = ctl.predict_cutoff()
                times = sim.step()
                it = order_stats.iter_time(times, c)
                ctl.observe(times, times <= it + 1e-12)
            obs.drain()

    # round-trip THROUGH the artifacts: what the CLI renders, the bench
    # reports — no live objects survive to this point
    run = R.load_run(obs_dir)
    cal = R.calibration_report(run["decisions"])
    for name, r in cal.items():
        fmt = lambda v: "-" if v is None else f"{v:.3f}"
        emit(f"obs/calibration_{name}", 0.0,
             f"regret={fmt(r['mean_regret'])};"
             f"idle={fmt(r['mean_idle_frac'])};"
             f"cov50={fmt(r['coverage50'])};cov90={fmt(r['coverage90'])}")
    return {"n_workers": n, "steps": steps, "obs_dir": obs_dir,
            "policies": cal}


def bench_obs(quick: bool = False, out_path: str = "BENCH_obs.json",
              n_list=STEP_NS, steps: int = None):
    steps = steps if steps is not None else (25 if quick else 50)
    results = {
        "schema": "bench_obs/v1",
        "quick": quick,
        "step": _step_bench(n_list, steps, repeats=3),
        "ring": _ring_bench(),
        "span": _span_bench(),
        "calibration": _calibration_bench(30 if quick else 60),
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("obs/json_written", 0.0, out_path)
    return results
