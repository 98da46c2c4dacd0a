"""Per-kernel microbenchmarks.

CPU wall-times here are for the XLA-reference path (the Pallas kernels only
execute on TPU or under interpret mode, which measures Python, not silicon);
the 'derived' column therefore reports the TPU roofline bound for each
kernel instead: bytes-streamed / HBM_BW and FLOPs / peak.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.kernels import ref
from repro.perf.hlo_stats import peaks

_V5E = peaks("TPU v5 lite")
HBM_BW, PEAK_FLOPS_BF16 = _V5E["hbm_bw"], _V5E["bf16_flops"]


def bench_kernels():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)

    # flash attention: B=4, S=2048, H=16, hd=128 bf16
    B, S, H, hd = 4, 2048, 16, 128
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.bfloat16)
    fn = jax.jit(lambda a, b, c: ref.reference_attention(a, b, c))
    us = timeit(fn, q, k, v, iters=3)
    flops = 4 * B * H * S * S * hd  # qk + pv
    stream = 4 * B * S * H * hd * 2
    emit("kernel/flash_attention_cpu_ref", us,
         f"tpu_compute_bound_us={flops / PEAK_FLOPS_BF16 * 1e6:.1f};"
         f"tpu_mem_bound_us={stream / HBM_BW * 1e6:.1f}")

    # mlstm chunk: B=4, S=2048, H=4, hd=256
    B, S, H, hd = 4, 2048, 4, 256
    q = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    kk = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    vv = jax.random.normal(ks[2], (B, S, H, hd))
    g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, S, H)) + 3.0)
    i = jax.random.normal(ks[4], (B, S, H)) * 0.5
    from repro.models.ssm import linear_recurrence
    fn = jax.jit(lambda *a: linear_recurrence(*a, chunk=128,
                                              normalize=True)[0])
    us = timeit(fn, q, kk, vv, g, i, iters=2)
    c = 128
    flops = B * H * (S // c) * (2 * c * c * hd * 2 + 4 * c * hd * hd)
    emit("kernel/mlstm_chunk_cpu_ref", us,
         f"tpu_compute_bound_us={flops / PEAK_FLOPS_BF16 * 1e6:.1f}")

    # fused adam: 16M params
    n = 16 * 2**20
    p = jax.random.normal(ks[0], (n // 1024, 1024))
    g2 = jax.random.normal(ks[1], (n // 1024, 1024))
    m = jnp.zeros_like(p)
    v2 = jnp.zeros_like(p)
    sc = jnp.array([1e-3, 0.1, 0.001], jnp.float32)
    fn = jax.jit(lambda *a: ref.reference_adam(*a)[0])
    us = timeit(fn, p, g2, m, v2, sc, iters=3)
    stream = n * 4 * 7  # 4 reads + 3 writes, fp32
    emit("kernel/fused_adam_cpu_ref", us,
         f"tpu_mem_bound_us={stream / HBM_BW * 1e6:.1f}")

    # the optim-level fused backend (optim.adam(fused=True) ->
    # ops.adam_update_tree) vs the unfused tree-map optimizer, same tree
    from repro import optim
    tree_p = {"a": p[: n // 2048], "b": p[n // 2048:]}
    tree_g = {"a": g2[: n // 2048], "b": g2[n // 2048:]}
    for label, opt in (("unfused", optim.adam(1e-3)),
                       ("fused_xla", optim.adam(1e-3, fused=True))):
        state = opt.init(tree_p)

        def step(pp, st, gg, _opt=opt):
            ups, st = _opt.update(gg, st, pp)
            return optim.apply_updates(pp, ups)["a"]

        us = timeit(jax.jit(step), tree_p, state, tree_g, iters=3)
        emit(f"kernel/adam_tree_{label}", us,
             f"tpu_mem_bound_us={stream / HBM_BW * 1e6:.1f}")

    # masked grad agg: 16 workers x 4M
    g3 = jax.random.normal(ks[2], (16, 4 * 2**20))
    mask = (jnp.arange(16) % 3 != 0).astype(jnp.float32).reshape(16, 1)
    fn = jax.jit(ref.reference_masked_agg)
    us = timeit(fn, g3, mask, iters=3)
    stream = g3.size * 4
    emit("kernel/masked_grad_agg_cpu_ref", us,
         f"tpu_mem_bound_us={stream / HBM_BW * 1e6:.1f}")
