"""Fault-tolerance walkthrough: crash/restart + permanent node failure +
elastic resize.

1. Train 30 steps with async checkpointing.
2. Simulate a crash; restart from the latest checkpoint (exact resume —
   the controller window and data cursor come back too).
3. Kill one worker permanently: the cutoff controller routes around it
   within one step (the paper's mechanism doubling as fault tolerance).
4. Elastic resize mid-run, 8 -> 6 -> 8 workers (``ChurnSim``): the SAME
   trainer keeps stepping across both membership changes — the controller
   window is remapped (survivors column-exact), the checkpoint records the
   degraded membership, and the restored run resumes at the checkpoint's
   worker count.
5. Real DETECTION: phases 3-4 were told who died.  Here a
   ``controlplane.Supervisor`` finds out from missed heartbeats — a
   seeded crash + hang storm is detected within the deadline, the
   membership shrinks, restarts bring the workers back, and the trainer
   rides the detected schedule end to end.

  PYTHONPATH=src python examples/fault_tolerance_demo.py
"""
import shutil

import jax
import numpy as np

from repro import optim
from repro.cluster.simulator import ChurnEvent, ChurnSim, ClusterSim
from repro.configs.base import get_config
from repro.core.controller import ElfvingController
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import place_compile_cache
from repro.launch.train import Trainer, jit_train_step
from repro.models import model as M

CKPT = "/tmp/repro_ft_demo"


class FailingCluster(ClusterSim):
    """Worker `dead` becomes a permanent straggler after step `at`."""

    def __init__(self, dead: int, at: int, **kw):
        super().__init__(**kw)
        self.dead, self.at = dead, at

    def step(self):
        t = super().step()
        if self.t >= self.at:
            t[self.dead] = 1e6  # never finishes
        return t


def make_trainer(cfg, n_workers, timer):
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=32,
                           global_batch=24, seed=0)
    opt = optim.adamw(3e-3)
    step = jit_train_step(cfg, opt)
    tr = Trainer(cfg=cfg, step_fn=step, data=data,
                 controller=ElfvingController(n_workers, warmup=3),
                 timer=timer, n_workers=n_workers, ckpt_dir=CKPT,
                 ckpt_every=10)

    def init_fn():
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": opt.init(params)}

    return tr.restore_or_init(init_fn)


def main():
    place_compile_cache()
    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = get_config("qwen2-0.5b").reduced()

    print("=== phase 1: train 30 steps with checkpoints ===")
    tr = make_trainer(cfg, 8, ClusterSim(n_workers=8, n_nodes=2, seed=1))
    tr.run(30, verbose=True)
    loss_before = tr.history[-1]["loss"]

    print("\n=== phase 2: simulated crash; restart from checkpoint ===")
    tr2 = make_trainer(cfg, 8, ClusterSim(n_workers=8, n_nodes=2, seed=1))
    print(f"resumed at step {tr2.step} (clock {tr2.sim_clock:.1f}s)")
    assert tr2.step == 30
    tr2.run(10, verbose=True)
    assert tr2.history[-1]["loss"] < loss_before * 1.5

    print("\n=== phase 3: permanent worker failure at step 45 ===")
    tr3 = make_trainer(cfg, 8, FailingCluster(
        dead=3, at=5, n_workers=8, n_nodes=2, seed=1))
    tr3.run(15, verbose=True)
    cs = [h["c"] for h in tr3.history[-8:]]
    print(f"cutoffs after failure: {cs} (controller routes around the "
          f"dead worker; iteration time stays bounded)")
    assert max(h["iter_time"] for h in tr3.history[-5:]) < 100

    print("\n=== phase 4: elastic resize 8 -> 6 -> 8 workers, mid-run ===")
    shutil.rmtree(CKPT, ignore_errors=True)
    churn = ChurnSim(ClusterSim(n_workers=8, n_nodes=2, seed=2),
                     [ChurnEvent(step=6, kill=(6, 7)),
                      ChurnEvent(step=14, restore=(6, 7))])
    tr4 = make_trainer(cfg, 8, churn)
    tr4.run(20, verbose=True)
    widths = [h["n"] for h in tr4.history]
    print(f"worker counts over the run: {widths}")
    assert 6 in widths and widths[-1] == 8
    # the checkpoint written while degraded carries the 6-wide membership
    from repro.checkpoint import store
    grp = store.restore_group(CKPT, "ctl", step=10)
    print(f"step-10 checkpoint membership: n={int(grp['n'])} "
          f"members={grp['members'].tolist()}")
    tr5 = make_trainer(cfg, 8, ChurnSim(ClusterSim(n_workers=8, n_nodes=2,
                                                   seed=3),
                                        [ChurnEvent(step=0, kill=(6, 7))]))
    print(f"restart from the latest checkpoint: step {tr5.step}, "
          f"n_workers {tr5.n_workers}")
    tr5.run(5, verbose=True)

    print("\n=== phase 5: detected (not scripted) failures, supervised ===")
    from repro.controlplane import drill_report
    from repro.launch.supervised import (build_supervised, default_plan,
                                         run_supervised_trainer)
    shutil.rmtree(CKPT, ignore_errors=True)
    overlay, sup, timer = build_supervised(8, default_plan(8), seed=4)
    # every transient width (8 full, 7 during a detection window) must
    # divide the global batch
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=32,
                           global_batch=56, seed=0)
    opt = optim.adamw(3e-3)
    tr6 = Trainer(cfg=cfg, step_fn=jit_train_step(cfg, opt), data=data,
                  controller=ElfvingController(8, warmup=3), timer=timer,
                  n_workers=8)
    tr6.restore_or_init(lambda: {
        "params": (p := M.init_model(cfg, jax.random.PRNGKey(0))),
        "opt": opt.init(p)})
    run_supervised_trainer(tr6, sup, 36)
    rep = drill_report(sup.log.events)
    for i in rep["incidents"]:
        print(f"  {i['kind']} on worker {i['worker']} at tick "
              f"{i['fault_tick']}: detected +{i['detection_ticks']} "
              f"ticks, rejoined at {i['rejoin_tick']}")
    widths6 = sorted({h["n"] for h in tr6.history})
    print(f"widths ridden off detection alone: {widths6}")
    assert rep["n_detected"] == 2 and rep["max_detection_ticks"] <= 5
    assert widths6 == [7, 8] and tr6.history[-1]["n"] == 8
    print("\nall phases OK")


if __name__ == "__main__":
    main()
