import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything else follows.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the right entry point is AOT-compiled against the production
mesh with ShapeDtypeStruct inputs (no allocation):

  train_4k    -> train_step (FSDP + sequence-parallel layout, grad accum)
  prefill_32k -> prefill     (same layout)
  decode_*    -> decode_step (feature-TP + sequence-sharded KV cache)

Outputs per cell: memory_analysis, cost_analysis, collective-bytes by kind,
roofline terms -> experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import base as cfgbase
from repro.dist import sharding as shd
from repro.launch import inputs as I
from repro.launch import train as T
from repro.launch.mesh import make_production_mesh
from repro.models import model as M

from repro.perf import hlo_stats

# the chip of the v5e pods the production meshes describe
TARGET_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def grad_accum_for(cfg, shape) -> int:
    """Microbatching so activations fit 16 GB/chip (hillclimb knob)."""
    n = cfg.n_params()
    if shape.name != "train_4k":
        return 1
    if n > 20e9:
        return 8
    if n > 8e9:
        return 4
    if n > 3e9:
        return 2
    return 1


def zero1_for(cfg) -> bool:
    return cfg.n_params() > 5e9


def lower_cell(cfg, shape, mesh, *, grad_accum=None, zero1=None,
               overrides=None, grads_only=False, layout="sp"):
    """Returns the jax ``Lowered`` for one cell.

    layout="fsdp": pure batch-parallel ZeRO-3 for train shapes whose
    global_batch divides the device count (single-pod train_4k); no
    sequence sharding, no KV gathers.  MoE archs keep "sp" (EP owns the
    model axis)."""
    overrides = overrides or {}
    kind = shape.kind
    if kind == "decode":
        mode = "decode_tp"
    elif layout == "fsdp" and kind == "train":
        assert cfg.family != "moe", "train_fsdp incompatible with EP"
        assert shape.global_batch % mesh.size == 0, (shape.global_batch,
                                                     mesh.size)
        mode = "train_fsdp"
    else:
        mode = "train_sp"
    lay = shd.make_layout(mesh, mode)
    key = jax.random.PRNGKey(0)

    with shd.use_layout(lay), jax.set_mesh(mesh):
        if kind == "train":
            ga = grad_accum if grad_accum is not None else grad_accum_for(
                cfg, shape)
            z1 = zero1 if zero1 is not None else zero1_for(cfg)
            opt = optim.adamw(optim.cosine_schedule(3e-4, 200, 10_000))
            params_abs = jax.eval_shape(lambda: M.init_model(cfg, key))
            pshard = shd.named_sharding(
                params_abs, lay, stacked_paths=T.stacked_paths_for(cfg))
            batch, bshard = I.input_specs(cfg, shape, lay)
            if grads_only:
                loss_fn = T.make_loss_fn(cfg)

                def gfn(params, batch):
                    B, S = batch["tokens"].shape
                    norm = jnp.asarray(B * S, jnp.float32)
                    return jax.grad(loss_fn, has_aux=True)(
                        params, batch, norm)

                jitted = jax.jit(gfn, in_shardings=(pshard, bshard),
                                 out_shardings=(pshard, None))
                return jitted.lower(params_abs, batch), {}
            step = T.make_train_step(cfg, opt, grad_accum=ga, **overrides)
            state_abs = T.abstract_state(cfg, opt, key)
            sshard = T.state_shardings(cfg, state_abs["params"], lay,
                                       zero1=z1)
            sshard["opt"] = {k: sshard["opt"][k]
                             for k in state_abs["opt"]}
            jitted = jax.jit(step, in_shardings=(sshard, bshard),
                             out_shardings=(sshard, None),
                             donate_argnums=(0,))
            return jitted.lower(state_abs, batch), {"grad_accum": ga,
                                                    "zero1": z1}
        if kind == "prefill":
            params_abs = jax.eval_shape(lambda: M.init_model(cfg, key))
            pshard = shd.named_sharding(
                params_abs, lay, stacked_paths=T.stacked_paths_for(cfg))
            batch, bshard = I.input_specs(cfg, shape, lay)

            def fn(params, batch):
                return M.prefill(cfg, params, batch)

            jitted = jax.jit(fn, in_shardings=(pshard, bshard))
            return jitted.lower(params_abs, batch), {}
        # decode
        params_abs = jax.eval_shape(lambda: M.init_model(cfg, key))
        pshard = shd.named_sharding(
            params_abs, lay, stacked_paths=T.stacked_paths_for(cfg))
        (batch, caches), (bshard, cshard) = I.input_specs(cfg, shape, lay)

        def fn(params, tokens, pos, caches, positions):
            return M.decode_step(cfg, params, tokens, pos, caches,
                                 positions=positions)

        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(
            fn,
            in_shardings=(pshard, bshard["tokens"], None, cshard,
                          bshard["positions"]),
            out_shardings=(None, cshard),
            donate_argnums=(3,))
        return jitted.lower(params_abs, batch["tokens"], pos_sds, caches,
                            batch["positions"]), {}


# ---------------------------------------------------------------------------
# Layer-wise cost accounting.
#
# XLA's cost_analysis counts while-loop (scan) bodies ONCE, not x trip-count,
# so the production graph (layers under lax.scan, q-chunks under lax.map)
# under-reports FLOPs/bytes/collectives.  We therefore measure, per distinct
# LayerSpec, a 1-layer fully-unrolled graph and a 0-layer base graph and
# combine:  total = ga * [grads(0L) + sum_spec count * (grads(1L)-grads(0L))]
#                 + [opt(0L) + sum_spec count * opt_delta(1L)]
# which is exact for homogeneous repeats.  sLSTM's sequential time scan gets
# an analytic FLOPs add-on (its recurrent matmuls live inside a length-S
# scan that cannot be unrolled).
# ---------------------------------------------------------------------------

import dataclasses

from repro.models.blocks import LayerSpec


def _single_layer_cfg(cfg, spec: LayerSpec, n: int = 1):
    ch = dict(n_layers=n, layer_pattern="", global_layer_ids=(),
              first_dense_layers=0, slstm_every=0, n_encoder_layers=0,
              sliding_window=0)
    if spec.kind == "attn_dense":
        ch.update(first_dense_layers=n)
    if spec.kind == "slstm":
        ch.update(slstm_every=1)
    if spec.kind == "enc":
        ch.update(n_layers=0, n_encoder_layers=n)
    if spec.window > 0:
        ch.update(sliding_window=spec.window)
    elif spec.kind == "hybrid":
        ch.update(global_layer_ids=tuple(range(n)),
                  sliding_window=cfg.sliding_window)
    return dataclasses.replace(cfg, **ch)


def _base_cfg(cfg):
    return dataclasses.replace(
        cfg, n_layers=0, n_encoder_layers=0, layer_pattern="",
        global_layer_ids=(), first_dense_layers=0, slstm_every=0)


def _cost_of(cfg, shape, mesh, **kw):
    with shd.unroll_loops():
        lowered, _ = lower_cell(cfg, shape, mesh, **kw)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = hlo_stats.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]),
            "coll_by_kind": {k: coll[k] for k in hlo_stats.COLLECTIVES}}


def _combine(a, scale_a, b=None, scale_b=0.0):
    out = {}
    for k in ("flops", "bytes", "coll"):
        out[k] = scale_a * a[k] + (scale_b * b[k] if b else 0.0)
    out["coll_by_kind"] = {
        k: scale_a * a["coll_by_kind"][k]
        + (scale_b * b["coll_by_kind"][k] if b else 0.0)
        for k in a["coll_by_kind"]}
    return out


def _slstm_extra_flops(cfg, shape, n_slstm: int, lay) -> float:
    """Analytic recurrent-matmul FLOPs hidden in the length-S sLSTM scan.

    Per step per sequence: 4 gates x (nh x hd x hd) matmul = 8*d*hd MACs.
    Train: fwd + remat fwd + bwd ~= 4x fwd.  Per-device: the scan is
    replicated over "model" (documented), sharded over batch only.
    """
    if n_slstm == 0:
        return 0.0
    d = cfg.d_model
    hd = d // cfg.n_heads
    S = shape.seq_len if shape.kind != "decode" else 1
    B = shape.global_batch
    fwd = 2.0 * S * B * 4 * d * hd
    mult = 4.0 if shape.kind == "train" else 1.0
    per_dev = fwd * mult * n_slstm / max(lay.dp_size, 1)
    return per_dev


def account_cell(cfg, shape, mesh, *, grad_accum=None, zero1=None,
                 layout="sp"):
    """Layer-wise accounted per-device costs for one cell."""
    kind = shape.kind
    ga = (grad_accum if grad_accum is not None
          else grad_accum_for(cfg, shape)) if kind == "train" else 1
    z1 = zero1 if zero1 is not None else zero1_for(cfg)
    # microbatch shape for the per-layer graphs
    mshape = dataclasses.replace(shape, global_batch=shape.global_batch // ga)

    specs = M.layer_specs(cfg)
    counts = {}
    for s in specs:
        counts[s] = counts.get(s, 0) + 1
    if cfg.is_encoder_decoder:
        for s in M.encoder_layer_specs(cfg):
            counts[s] = counts.get(s, 0) + 1

    base_cfg = _base_cfg(cfg)
    kw = dict(grad_accum=1, zero1=z1, layout=layout)
    if kind == "train":
        g0 = _cost_of(base_cfg, mshape, mesh, grads_only=True, **kw)
        t0 = _cost_of(base_cfg, mshape, mesh, **kw)
        opt0 = {k: (t0[k] - g0[k]) if k != "coll_by_kind" else {
            kk: t0["coll_by_kind"][kk] - g0["coll_by_kind"][kk]
            for kk in t0["coll_by_kind"]} for k in t0}
        total = _combine(g0, float(ga))
        total = _combine(total, 1.0, opt0, 1.0)
        for s, cnt in counts.items():
            c1 = _single_layer_cfg(cfg, s)
            g1 = _cost_of(c1, mshape, mesh, grads_only=True, **kw)
            t1 = _cost_of(c1, mshape, mesh, **kw)
            dg = {k: (g1[k] - g0[k]) if k != "coll_by_kind" else {
                kk: g1["coll_by_kind"][kk] - g0["coll_by_kind"][kk]
                for kk in g1["coll_by_kind"]} for k in g1}
            dopt = {k: ((t1[k] - g1[k]) - opt0[k]) if k != "coll_by_kind"
                    else {kk: (t1["coll_by_kind"][kk] - g1["coll_by_kind"][kk]
                               - opt0["coll_by_kind"][kk])
                          for kk in t1["coll_by_kind"]} for k in t1}
            total = _combine(total, 1.0, dg, float(ga * cnt))
            total = _combine(total, 1.0, dopt, float(cnt))
    else:
        b0 = _cost_of(base_cfg, mshape, mesh)
        total = _combine(b0, 1.0)
        for s, cnt in counts.items():
            c1 = _single_layer_cfg(cfg, s)
            b1 = _cost_of(c1, mshape, mesh)
            ds = {k: (b1[k] - b0[k]) if k != "coll_by_kind" else {
                kk: b1["coll_by_kind"][kk] - b0["coll_by_kind"][kk]
                for kk in b1["coll_by_kind"]} for k in b1}
            total = _combine(total, 1.0, ds, float(cnt))

    lay = shd.make_layout(mesh, "decode_tp" if kind == "decode"
                          else "train_sp")
    n_slstm = sum(cnt for s, cnt in counts.items() if s.kind == "slstm")
    total["flops"] += _slstm_extra_flops(cfg, shape, n_slstm, lay)
    total["grad_accum"] = ga
    return total


def run_cell(cfg, shape, mesh, mesh_name: str, out_dir: str,
             force: bool = False, **kw):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{cfg.name}__{shape.name}__{mesh_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
           "n_devices": mesh.size}
    try:
        lowered, meta = lower_cell(cfg, shape, mesh, **kw)
        rec.update(meta)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # the assignment requires these printed: proves fit + feeds §Roofline
        print(f"--- {cfg.name} x {shape.name} x {mesh_name} ---")
        print("memory_analysis:", mem)
        print("cost_analysis:", {k: v for k, v in sorted(cost.items())
                                 if "bytes accessed" == k or k == "flops"
                                 or k == "optimal_seconds"})
        coll = hlo_stats.collective_bytes(compiled.as_text())
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        rec.update({
            "ok": True,
            "trace_s": round(t1 - t0, 1),
            "compile_s": round(t2 - t1, 1),
            # raw = production graph as XLA reports it (scan bodies counted
            # once -- kept for reference only)
            "raw_flops_per_device": flops,
            "raw_bytes_per_device": bytes_acc,
            "raw_collectives": coll,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_live_est": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
            },
        })
        t3 = time.time()
        acc = account_cell(cfg, shape, mesh, **{
            k: v for k, v in kw.items() if k in ("grad_accum", "zero1")})
        rec.update({
            "accounting_s": round(time.time() - t3, 1),
            "flops_per_device": acc["flops"],
            "bytes_per_device": acc["bytes"],
            "collective_bytes_per_device": acc["coll"],
            "collectives_by_kind": acc["coll_by_kind"],
            "roofline": hlo_stats.roofline_terms(
                acc["flops"], acc["bytes"], acc["coll"],
                device_kind=TARGET_KIND),
        })
        print(f"[OK]   {cfg.name:24s} {shape.name:12s} {mesh_name:10s} "
              f"compile={t2-t1:6.1f}s flops/dev={acc['flops']:.3e} "
              f"bound={rec['roofline']['bound']}")
    except Exception as e:  # noqa: BLE001
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        print(f"[FAIL] {cfg.name:24s} {shape.name:12s} {mesh_name}: "
              f"{type(e).__name__}: {str(e)[:200]}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--zero1", type=int, default=None)
    args = ap.parse_args()
    out_dir = args.out or os.path.abspath(OUT_DIR)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    n_ok = n_fail = n_skip = 0
    for cfg, shape, skip in cfgbase.cells():
        if args.arch and cfg.name != args.arch:
            continue
        if args.shape and shape.name != args.shape:
            continue
        if skip:
            print(f"[SKIP] {cfg.name:24s} {shape.name:12s} -- {skip}")
            n_skip += 1
            continue
        for mesh_name, mesh in meshes:
            kw = {}
            if args.grad_accum is not None:
                kw["grad_accum"] = args.grad_accum
            if args.zero1 is not None:
                kw["zero1"] = bool(args.zero1)
            rec = run_cell(cfg, shape, mesh, mesh_name, out_dir,
                           force=args.force, **kw)
            if rec.get("ok"):
                n_ok += 1
            else:
                n_fail += 1
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed, "
          f"{n_skip} skipped (documented)")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
