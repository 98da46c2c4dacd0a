"""Multi-tenant parameter-server demo: J jobs, one batched decision path.

Three tiny training jobs share one simulated 24-worker cluster (8 workers
each).  A single PSServer multiplexes all three cutoff controllers
through ONE vmapped fused decision per tick; mid-run a churn event kills
two of job1's workers and the per-job elastic protocol (Elfving fallback
+ DMM refit) absorbs it while the other jobs stay on the batched path.
Then the same jobs re-run under capacity pressure (2 of 3 serviced per
tick) to show the scheduler policies' throughput trade-offs.

  PYTHONPATH=src python examples/multi_job_demo.py
"""
import numpy as np

from repro.cluster.simulator import ChurnEvent
from repro.launch.compile_cache import place_compile_cache
from repro.launch.multi_job import build_multi_job, run_ticks
from repro.ps import make_scheduler


def main():
    place_compile_cache()
    ticks = 36
    kill_at, back_at = ticks // 3, 2 * ticks // 3

    print("=== phase 1: 3 jobs x 8 workers, one PSServer, round-robin ===")
    events = [ChurnEvent(step=kill_at, kill=(8, 9)),
              ChurnEvent(step=back_at, restore=(8, 9))]
    server, jobs, _ = build_multi_job(3, 8, seed=0, churn_events=events,
                                      refit_steps=60,
                                      priorities=[0.0, 1.0, 2.0])
    out = run_ticks(server, jobs, make_scheduler("rr"), ticks, verbose=True)
    print(f"  {ticks} ticks -> {out['dispatches']} fused dispatches "
          f"({out['dispatches'] / ticks:.2f}/tick for 3 jobs; a looped "
          f"design pays 3/tick)")
    for job_id, run in jobs.items():
        losses = [h["loss"] for h in run.trainer.history[-3:]]
        print(f"  {job_id}: steps={len(run.trainer.history)} "
              f"width={run.handle.n} mode={run.handle.mode} "
              f"loss={np.mean(losses):.4f}")
    assert jobs["job1"].handle.n == 8, "job1 should have recovered"

    print("\n=== phase 2: capacity 2 of 3 — scheduler policy spread ===")
    for policy in ("rr", "priority", "spsf"):
        server, jobs, _ = build_multi_job(3, 8, seed=0,
                                          priorities=[0.0, 1.0, 2.0])
        out = run_ticks(server, jobs, make_scheduler(policy), ticks,
                        capacity=2)
        total = sum(out["serviced"].values())
        clock = {j: round(r.trainer.sim_clock, 1) for j, r in jobs.items()}
        print(f"  {policy:8s}: serviced={out['serviced']} "
              f"(total {total}), per-job sim clock={clock}")
    print("\nround-robin spreads service evenly; priority starves job0; "
          "spsf packs the most total steps into predicted-fast jobs.")


if __name__ == "__main__":
    main()
