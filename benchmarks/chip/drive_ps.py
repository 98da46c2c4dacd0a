"""Parameter-server cells: J jobs behind one ``PSServer``, every job
served every tick, in a closed loop over a simulated cluster.

A tick: for each job in the scheduler's order, take its cutoff
(``JobHandle.predict_cutoff``; the first fetches the batched decision to
the host), take its workers' step times from the cluster, mark the
fastest ``c`` finished (the Trainer's own rule) and observe; then one
``PSServer.flush`` dispatches the fused observe+decide of every job.  No
train step runs: the jobs' workers compute their gradients elsewhere.

Set-up admits the jobs (runtime-model weights made from the seed, each
job's window seeded from its own trace of the same cluster) and runs the
first ticks, which compile.  After the window, the reference replays the
decisions of a sample of jobs drawn from the seed, from the first tick.
"""
from __future__ import annotations

import os
import shutil
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

import decision_check
import dmm_weights
import harness
import lm_weights
import trace_reduce
from traffic.cluster_times import ClusterTimes, Partitioned

from repro.core.runtime_model.api import RuntimeModel
from repro.ps import PSServer, make_scheduler
from repro.ps.scheduler import job_views


def build(c: dict, tr: dict, seed: int):
    J, n, lag = tr["jobs"], c["n_workers"], c["lag"]
    key = lm_weights.seed_key(seed)
    part = Partitioned(ClusterTimes.preset(tr["cluster"], J * n, seed + 1),
                       J)
    server = PSServer()
    jobs = []
    for j in range(J):
        window = ClusterTimes.preset(tr["cluster"], n,
                                     seed + 10 + j).run(lag + 1)
        params, scale = dmm_weights.make(jax.random.fold_in(key, j), n,
                                         c["z_dim"], c["hidden"], window)
        params = jax.device_get(params)
        rm = RuntimeModel(n_workers=n, lag=lag, z_dim=c["z_dim"],
                          hidden=c["hidden"], params=params,
                          norm_scale=float(scale))
        job_seed = (seed % 2 ** 29) + 1000 * j
        handle = server.admit(f"job{j}", rm, window=window,
                              members=np.arange(j * n, (j + 1) * n),
                              k_samples=c["k_samples"],
                              min_frac=c["min_frac"], seed=job_seed)
        jobs.append({"id": f"job{j}", "handle": handle, "params": params,
                     "scale": float(scale), "window": window,
                     "seed": job_seed})
    return server, jobs, part


class Ticks:
    """The tick loop, recording what each job observed and decided."""

    def __init__(self, server, jobs, part, scheduler):
        self.server, self.jobs, self.part = server, jobs, part
        self.sched = make_scheduler(scheduler)
        self.index = {j["id"]: i for i, j in enumerate(jobs)}
        self.times, self.cuts, self.iters, self.tick_s = [], [], [], []

    def tick(self):
        t0 = time.perf_counter()
        i = len(self.times)
        J, n = len(self.jobs), self.jobs[0]["handle"].n
        times = np.empty((J, n))
        cuts = np.empty(J, int)
        iters = np.empty(J)
        order = self.sched.order(job_views(self.server), None)
        self.server.prefetch(order)
        for k, job_id in enumerate(order):
            j = self.index[job_id]
            h = self.jobs[j]["handle"]
            if k == 0:
                with TraceAnnotation("bench.fetch"):
                    c = h.predict_cutoff()
            else:
                c = h.predict_cutoff()
            iters[j] = self.server.predicted_iter_time(job_id)
            t = self.part.times(j, i)
            finished = np.zeros(n, bool)
            finished[np.argsort(t)[:c]] = True
            h.observe(t, finished)
            times[j], cuts[j] = t, c
        with TraceAnnotation("bench.flush"):
            self.server.flush()
        self.times.append(times)
        self.cuts.append(cuts)
        self.iters.append(iters)
        self.tick_s.append(time.perf_counter() - t0)


def record(ticks: Ticks, jobs, sample) -> dict:
    """The sampled jobs' record (decision_check.py)."""
    return {"params": [jobs[j]["params"] for j in sample],
            "windows": np.stack([jobs[j]["window"] for j in sample]),
            "scales": [jobs[j]["scale"] for j in sample],
            "seeds": [jobs[j]["seed"] for j in sample],
            "times": np.stack(ticks.times)[:, sample],
            "cuts": np.stack(ticks.cuts)[:, sample]}


def compare(ticks: Ticks, jobs, sample, finals, c: dict) -> dict:
    rec = record(ticks, jobs, sample)
    ref = decision_check.replay(**rec, k_samples=c["k_samples"],
                                min_frac=c["min_frac"])
    out = decision_check.gaps(rec["cuts"], np.stack(ticks.iters)[:, sample],
                              finals, ref)
    print(f"cutoffs equal to the reference's: "
          f"{float(np.mean(rec['cuts'] == ref['cuts']))!r}", flush=True)
    return out


def sample_jobs(seed: int, J: int, k: int) -> list:
    return sorted(np.random.default_rng((seed, 7)).choice(J, k,
                                                          replace=False))


def run(*, workload: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, t_start: float, devices):
    c, tr = config, traffic
    split = {}
    with harness.CompileClock() as clock:
        t0 = time.perf_counter()
        server, jobs, part = build(c, tr, seed)
        split["admit_s"] = time.perf_counter() - t0
        ticks = Ticks(server, jobs, part, tr["scheduler"])
        t0 = time.perf_counter()
        for _ in range(tr["warmup_ticks"]):
            ticks.tick()
        jax.effects_barrier()
        split["first_ticks_s"] = time.perf_counter() - t0
        split["compile_s"] = clock.seconds
        split["compiles"], split["cache_hits"] = clock.count, clock.cache_hits
        setup_s = time.perf_counter() - t_start
        print(f"setup: {setup_s!r} s {split}", flush=True)
        n_setup, first = clock.count, len(ticks.tick_s)
        log_dir = os.path.join(harness.ROOT, ".bench", "trace",
                               f"{workload['name']}-{seed}")
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        with TraceAnnotation(trace_reduce.WINDOW):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                ticks.tick()
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        compiles_in_window = clock.count - n_setup
    n_ticks = len(ticks.tick_s) - first
    print(f"window: {window_s!r} s, {n_ticks} ticks, "
          f"{compiles_in_window} compiles", flush=True)
    peak = harness.memory_peak(devices)
    summary = None
    if trace:
        summary = trace_reduce.summarize(trace_reduce.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    sample = sample_jobs(seed, len(jobs), tr["reference_jobs"])
    finals = np.stack([server.window_array(jobs[j]["id"]) for j in sample])
    t0 = time.perf_counter()
    values = compare(ticks, jobs, sample, finals, c)
    print(f"reference: {time.perf_counter() - t0!r} s over jobs {sample}, "
          f"{len(ticks.tick_s)} ticks; {values}", flush=True)
    lim = harness.limits(workload["name"])
    checks = [harness.Check(k, values[k], lim[k]) for k in lim]
    r = harness.Run(config=c, traffic=tr, chips=len(devices),
                    device_kind=devices[0].device_kind, setup_s=setup_s,
                    window_s=window_s,
                    counts={"ticks": n_ticks,
                            "decisions": n_ticks * len(jobs)},
                    samples={"tick_s": ticks.tick_s[first:]}, trace=summary)
    return r, checks, n_ticks, peak


def control(*, config: dict, traffic: dict, seed: int, seconds: float,
            devices, ticks: int = 0, **_):
    """Readings of the program, the control and the faults a PS cell can
    have, at the cell's own size, on the ticks of a short program run
    (decision_check.faults): the reference with float8 matmul operands in
    the program's place; a window that never changes; every answer
    altered.  The run lasts ``seconds``, or ``ticks`` ticks where that is
    given, so that its record does not depend on the host's speed."""
    c, tr = config, traffic
    server, jobs, part = build(c, tr, seed)
    loop = Ticks(server, jobs, part, tr["scheduler"])
    t0 = time.perf_counter()
    while (len(loop.tick_s) < ticks if ticks
           else time.perf_counter() - t0 < seconds):
        loop.tick()
    ticks = loop
    sample = sample_jobs(seed, len(jobs), tr["reference_jobs"])
    finals = np.stack([server.window_array(jobs[j]["id"]) for j in sample])
    out = decision_check.faults(
        **record(ticks, jobs, sample), iters=np.stack(ticks.iters)[:, sample],
        finals=finals, k_samples=c["k_samples"], min_frac=c["min_frac"],
        n=c["n_workers"])
    out["ticks"] = len(ticks.tick_s)
    return out
