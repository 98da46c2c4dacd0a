"""Quickstart: cutoff SGD end to end in ~a minute on CPU.

Trains a reduced qwen2-0.5b on synthetic tokens with 8 simulated workers:
the DMM runtime model predicts each step's joint worker runtimes, the
controller picks the throughput-optimal cutoff, stragglers' gradients are
masked out of the aggregation, and censored runtimes are imputed.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro import optim
from repro.cluster.simulator import ClusterSim
from repro.configs.base import get_config
from repro.core.controller import CutoffController
from repro.core.runtime_model.api import RuntimeModel
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import place_compile_cache
from repro.launch.train import Trainer, jit_train_step
from repro.models import model as M


def main():
    place_compile_cache()
    n_workers = 8
    cfg = get_config("qwen2-0.5b").reduced()

    # 1. instrument the cluster once, fit the runtime model (paper §3.1)
    sim = ClusterSim(n_workers=n_workers, n_nodes=2, seed=0)
    trace = sim.run(200)
    print(f"recorded trace: mean={trace.mean():.3f}s std={trace.std():.3f}s")
    rm = RuntimeModel(n_workers=n_workers, lag=20).init(0)
    rm.fit(trace, steps=200, batch=8, verbose=True)

    # 2. dynamic-cutoff controller (paper Alg. 1)
    ctl = CutoffController(rm, k_samples=48)
    ctl.seed_window(trace)

    # 3. train with masked gradient aggregation
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=32,
                           global_batch=16, seed=0)
    opt = optim.adamw(optim.cosine_schedule(3e-3, 10, 200))
    step = jit_train_step(cfg, opt)
    tr = Trainer(cfg=cfg, step_fn=step, data=data, controller=ctl,
                 timer=ClusterSim(n_workers=n_workers, n_nodes=2, seed=7),
                 n_workers=n_workers)

    def init_fn():
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": opt.init(params)}

    tr.restore_or_init(init_fn)
    hist = tr.run(60, verbose=True)

    cs = [h["c"] for h in hist]
    print(f"\nloss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    print(f"cutoffs: min={min(cs)} max={max(cs)} mean={np.mean(cs):.1f} "
          f"of {n_workers} workers")
    print(f"simulated wall-clock: {tr.sim_clock:.1f}s "
          f"(full sync would have paid the max worker every step)")


if __name__ == "__main__":
    main()
