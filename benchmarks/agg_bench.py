"""Aggregation-path benchmark: weights vs psum train steps + the combine.

Times the three ways the cutoff bit array can meet the gradients:

  * the production example-weights train step (``mask_agg="weights"``),
  * the explicit per-worker psum train step (``mask_agg="psum"``),
  * the stacked host combine itself — pure-jnp reference vs the Pallas
    masked_grad_agg kernel (interpret mode on CPU, so that number is
    Python overhead; the derived TPU roofline bound is what matters).

Emits the usual CSV rows AND a machine-readable ``BENCH_agg.json`` so the
perf trajectory of the aggregation path accumulates across PRs.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.perf.hlo_stats import peaks

HBM_BW = peaks("TPU v5 lite")["hbm_bw"]


def _combine_bench(quick: bool):
    from repro.core import aggregation
    from repro.kernels import ops

    W = 8
    N = 2**18 if quick else 2**21
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (W, N))
    mask = (jnp.arange(W) % 3 != 0).astype(jnp.float32)

    fn = jax.jit(lambda a, m: aggregation.masked_mean_local({"g": a}, m)["g"])
    jnp_us = timeit(fn, g, mask, iters=5)
    stream = g.size * 4
    bound_us = stream / HBM_BW * 1e6
    emit("agg/combine_jnp_local", jnp_us, f"tpu_mem_bound_us={bound_us:.1f}")

    # interpret mode measures the Pallas interpreter, not silicon — keep N
    # small enough that the grid stays a few dozen steps.
    Nk = 2**14 if quick else 2**15
    gk = g[:, :Nk]
    saved = ops.KERNEL_BACKEND
    ops.KERNEL_BACKEND = "interpret"
    try:
        kfn = jax.jit(lambda a, m: ops.masked_aggregate_tree({"g": a}, m)["g"])
        kernel_us = timeit(kfn, gk, mask, iters=2)
    finally:
        ops.KERNEL_BACKEND = saved
    emit("agg/combine_kernel_interpret", kernel_us,
         f"n={Nk};tpu_mem_bound_us={Nk * W * 4 / HBM_BW * 1e6:.1f}")

    return {"W": W, "N": N, "jnp_local_us": jnp_us,
            "kernel_interpret_us": kernel_us, "kernel_interpret_n": Nk,
            "tpu_mem_bound_us": bound_us}


def _train_step_bench(quick: bool):
    from repro import optim
    from repro.configs.base import get_config
    from repro.core import aggregation
    from repro.launch.train import make_train_step
    from repro.models import model as M
    import numpy as np

    cfg = get_config("qwen2-0.5b").reduced()
    opt = optim.adamw(3e-3)
    W, per, S = 8, 2, 16
    B = W * per
    key = jax.random.PRNGKey(0)
    params = M.init_model(cfg, key)
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    mask = np.asarray([1, 0, 1, 1, 1, 0, 1, 1], np.float32)
    iters = 3 if quick else 10
    out = {"arch": f"{cfg.name}/reduced", "B": B, "S": S, "W": W}
    for mode in ("weights", "psum"):
        step = jax.jit(make_train_step(cfg, opt, mask_agg=mode))
        state = {"params": params, "opt": opt.init(params)}
        if mode == "psum":
            b = dict(batch, mask=jnp.asarray(mask))
        else:
            b = dict(batch, weights=jnp.asarray(
                aggregation.example_weights(mask, B)))

        def one(s, bb):
            s2, m = step(s, bb)
            return m["loss"]

        us = timeit(one, state, b, iters=iters)
        out[f"{mode}_us"] = us
        emit(f"agg/train_step_{mode}", us, f"arch={cfg.name};W={W}")
    out["psum_over_weights"] = out["psum_us"] / out["weights_us"]
    return out


def bench_agg(quick: bool = False, out_path: str = "BENCH_agg.json"):
    results = {
        "schema": "bench_agg/v1",
        "quick": quick,
        "combine": _combine_bench(quick),
        "train_step": _train_step_bench(quick),
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("agg/json_written", 0.0, out_path)
    return results
