"""Multi-job training driver: J Trainers through ONE multi-tenant PS.

Builds J seeded tiny training jobs over disjoint partitions of one
simulated cluster (``cluster.simulator.PartitionedSim``), admits each to
a shared :class:`repro.ps.PSServer`, and runs a scheduler-driven tick
loop: every tick the policy picks which jobs the cluster services, each
serviced job runs one Trainer step (its cutoff fetched lazily from the
batched decision), and ``server.flush()`` dispatches ONE vmapped fused
observe+decide for the whole service set.

Per-job elasticity rides the existing protocol end-to-end: a ChurnEvent
killing workers inside partition p shrinks job p's timer view, its
Trainer resizes through ``JobHandle.resize``, the server degrades that
job to the warm Elfving fallback and refits its DMM from the surviving
window — the other J-1 jobs never leave the batched path.

  PYTHONPATH=src python -m repro.launch.multi_job [--jobs 3] [--ticks 40]
                                                  [--policy rr|priority|spsf]
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from repro.obs.trace import span


@dataclass
class JobRun:
    """One tenant: its Trainer, its server handle, its timer view."""
    job_id: str
    trainer: object
    handle: object
    view: object
    serviced: int = 0


def build_multi_job(n_jobs: int = 3, n_per_job: int = 8, *,
                    seed: int = 0, k_samples: int = 32,
                    fit_steps: int = 120, churn_events=(),
                    priorities=None, global_batch: int = 24,
                    refit_steps: int = 100, refit_fresh: int = 3,
                    refit_async: bool = False, metrics_every: int = 10,
                    obs=None):
    """J seeded tiny Trainers over a partitioned paper cluster, one
    shared PSServer.  Returns (server, jobs dict, sim).

    ``obs`` (a :class:`repro.obs.ObsRun`) instruments the server's flush
    dispatches, every trainer's step loop (``Trainer.name`` = job id, so
    the interleaved step stream stays attributable), and wraps each
    job's handle in the decision-quality recorder — decisions are
    bit-identical with it attached."""
    import jax

    from repro import optim
    from repro.cluster.simulator import (PartitionedSim, paper_cluster_158,
                                         partition_ids)
    from repro.configs.base import bench_tiny_config
    from repro.core.runtime_model.api import RuntimeModel
    from repro.data.pipeline import SyntheticTokens
    from repro.launch.train import Trainer, jit_train_step
    from repro.models import model as M
    from repro.ps import PSServer

    n_total = n_jobs * n_per_job
    cfg = bench_tiny_config()
    opt = optim.adamw(3e-3)
    step_fn = jit_train_step(cfg, opt)      # ONE jit, shared by every job
    base = paper_cluster_158(seed=seed + 1, n_workers=n_total)
    sim = PartitionedSim(base, partition_ids(n_total, n_jobs),
                         events=list(churn_events))
    server = PSServer(refit_steps=refit_steps, refit_fresh=refit_fresh,
                      refit_async=refit_async, obs=obs)
    jobs: Dict[str, JobRun] = {}
    for j in range(n_jobs):
        job_id = f"job{j}"
        ids = sim.partitions[j]
        # per-job DMM fit on a seeded same-phenomenology trace at the
        # partition width (the per-job instrumentation run)
        trace = paper_cluster_158(seed=seed + 10 + j,
                                  n_workers=n_per_job).run(
            max(40, fit_steps // 3))
        rm = RuntimeModel(n_workers=n_per_job, lag=10).init(seed + j)
        rm.fit(trace, steps=fit_steps, batch=8, seed=seed + j)
        handle = server.admit(
            job_id, rm, window=trace[-(rm.lag + 1):], members=ids,
            priority=(priorities[j] if priorities is not None else 0.0),
            k_samples=k_samples, seed=seed + 100 * j)
        view = sim.view(j)
        data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=8,
                               global_batch=global_batch, seed=seed + j)
        ctl = obs.wrap(handle, policy=job_id) if obs is not None else handle
        tr = Trainer(cfg=cfg, step_fn=step_fn, data=data, controller=ctl,
                     timer=view, n_workers=n_per_job, members=ids,
                     metrics_every=metrics_every, obs=obs, name=job_id)

        def init_fn(jj=j):
            params = M.init_model(cfg, jax.random.PRNGKey(seed + jj))
            return {"params": params, "opt": opt.init(params)}

        tr.restore_or_init(init_fn)
        jobs[job_id] = JobRun(job_id=job_id, trainer=tr, handle=handle,
                              view=view)
    return server, jobs, sim


def run_ticks(server, jobs: Dict[str, JobRun], scheduler, ticks: int, *,
              capacity: Optional[int] = None, verbose: bool = False):
    """The multi-tenant hot loop: schedule -> prefetch -> serve -> flush.

    Returns per-tick service lists plus aggregate counters."""
    from repro.ps.scheduler import job_views

    obs = getattr(server, "obs", None)
    schedule_log: List[List[str]] = []
    serviced = {job_id: 0 for job_id in jobs}
    d0 = server.dispatches
    for tick in range(ticks):
        with span("multi_job.tick", tick=tick):
            order = scheduler.order(job_views(server), capacity)
            server.prefetch(order)
            for job_id in order:
                jobs[job_id].trainer.run(1)
                jobs[job_id].serviced += 1
                serviced[job_id] += 1
            server.flush()
        schedule_log.append(order)
        if verbose and (tick + 1) % 10 == 0:
            modes = {j.job_id: j.handle.mode for j in jobs.values()}
            print(f"  tick {tick + 1}: serviced={order} modes={modes}")
    if obs is not None:
        obs.drain()
    return {"schedule": schedule_log,
            "dispatches": server.dispatches - d0,
            "serviced": serviced}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--workers-per-job", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=40)
    ap.add_argument("--capacity", type=int, default=None,
                    help="jobs serviced per tick (default: all)")
    ap.add_argument("--policy", default="rr",
                    choices=["rr", "priority", "spsf"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-dir", default=None,
                    help="write obs telemetry streams (spans/steps/"
                         "decisions/metrics JSONL) under this directory")
    args = ap.parse_args()

    from repro.cluster.simulator import ChurnEvent
    from repro.obs import ObsRun
    from repro.ps import make_scheduler

    kill_at = args.ticks // 3
    back_at = 2 * args.ticks // 3
    # kill two workers of job1's partition mid-run, restore later
    victim = [args.workers_per_job + 0, args.workers_per_job + 1]
    events = [ChurnEvent(step=kill_at, kill=tuple(victim)),
              ChurnEvent(step=back_at, restore=tuple(victim))]
    print(f"=== building {args.jobs} jobs x {args.workers_per_job} workers, "
          f"churn kills {victim} at tick {kill_at} ===")
    obs = ObsRun(args.obs_dir) if args.obs_dir else None
    server, jobs, _ = build_multi_job(
        args.jobs, args.workers_per_job, seed=args.seed,
        churn_events=events if args.jobs > 1 else (), obs=obs)
    sched = make_scheduler(args.policy)
    out = run_ticks(server, jobs, sched, args.ticks,
                    capacity=args.capacity, verbose=True)
    if obs is not None:
        obs.close()
        print(f"obs streams -> {args.obs_dir} "
              f"(render: python -m repro.obs {args.obs_dir})")
    print(f"=== {args.ticks} ticks, {out['dispatches']} fused dispatches "
          f"({out['dispatches'] / max(1, args.ticks):.2f}/tick) ===")
    for job_id, run in jobs.items():
        hist = run.trainer.history
        losses = [h["loss"] for h in hist[-3:]]
        print(f"  {job_id}: serviced={run.serviced} steps={len(hist)} "
              f"width={run.handle.n} mode={run.handle.mode} "
              f"last3loss={np.mean(losses):.4f}")


if __name__ == "__main__":
    main()
