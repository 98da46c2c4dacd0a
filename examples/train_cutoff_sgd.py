"""End-to-end driver: train a ~100M-parameter LM with cutoff SGD.

The full production loop: synthetic-token pipeline with per-worker
sampling-with-replacement, DMM-driven dynamic cutoff, masked gradient
aggregation, async checkpointing, and a comparison against full-sync on the
same simulated cluster clock.

  PYTHONPATH=src python examples/train_cutoff_sgd.py --steps 300
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import optim
from repro.cluster.simulator import ClusterSim
from repro.configs.base import ArchConfig, get_config
from repro.core.controller import CutoffController, FullSyncController
from repro.core.runtime_model.api import RuntimeModel
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import place_compile_cache
from repro.launch.train import Trainer, jit_train_step
from repro.models import model as M


def model_100m() -> ArchConfig:
    """~100M-parameter dense LM (qwen2-family structure)."""
    return dataclasses.replace(
        get_config("qwen2-0.5b"), name="repro-100m",
        n_layers=10, d_model=640, n_heads=10, n_kv_heads=2, head_dim=64,
        d_ff=1792, vocab_size=32_000, dtype="float32", tie_embeddings=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt_100m")
    ap.add_argument("--method", default="cutoff",
                    choices=["cutoff", "sync"])
    ap.add_argument("--mask-agg", default="weights",
                    choices=["weights", "psum"],
                    help="how the bit array meets the gradients: folded "
                         "per-example weights (production) or the explicit "
                         "per-worker gradient psum")
    ap.add_argument("--obs-dir", default=None,
                    help="write obs telemetry (spans/steps/decisions/"
                         "metrics JSONL) under this directory; render "
                         "with: python -m repro.obs <dir>")
    args = ap.parse_args()
    place_compile_cache()

    cfg = model_100m()
    n_params = cfg.n_params()
    print(f"model: {cfg.name} ({n_params/1e6:.0f}M params)")

    sim = ClusterSim(n_workers=args.workers, n_nodes=4, seed=0)
    trace = sim.run(200)
    if args.method == "cutoff":
        rm = RuntimeModel(n_workers=args.workers, lag=20).init(0)
        t0 = time.time()
        rm.fit(trace, steps=300, batch=8)
        print(f"runtime model fitted in {time.time()-t0:.1f}s")
        ctl = CutoffController(rm, k_samples=48)
        ctl.seed_window(trace)
    else:
        ctl = FullSyncController(args.workers)

    obs = None
    if args.obs_dir:
        from repro.obs import ObsRun
        obs = ObsRun(args.obs_dir)
        ctl = obs.wrap(ctl, policy=args.method)

    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch, seed=0)
    opt = optim.clip_by_global_norm(
        optim.adamw(optim.cosine_schedule(3e-4, 50, args.steps)), 1.0)
    step = jit_train_step(cfg, opt, mask_agg=args.mask_agg)
    tr = Trainer(cfg=cfg, step_fn=step, data=data, controller=ctl,
                 timer=ClusterSim(n_workers=args.workers, n_nodes=4, seed=9),
                 n_workers=args.workers, mask_agg=args.mask_agg,
                 ckpt_dir=args.ckpt, ckpt_every=100, obs=obs,
                 name=args.method)

    def init_fn():
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": opt.init(params)}

    tr.restore_or_init(init_fn)
    t0 = time.time()
    hist = tr.run(args.steps, verbose=True)
    dt = time.time() - t0

    cs = [h["c"] for h in hist]
    print(f"\n=== {args.method} ===")
    print(f"loss: {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    print(f"simulated cluster wall-clock: {tr.sim_clock:.1f}s "
          f"({tr.sim_clock/len(hist):.3f}s/step)")
    print(f"mean cutoff: {np.mean(cs):.1f}/{args.workers}")
    print(f"host compute time: {dt:.1f}s ({dt/args.steps:.2f}s/step)")
    if obs is not None:
        obs.close()
        print(f"obs streams -> {args.obs_dir} "
              f"(render: python -m repro.obs {args.obs_dir})")


if __name__ == "__main__":
    main()
