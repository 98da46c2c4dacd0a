import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""§Perf hillclimb harness: measure one (arch x shape) cell under a knob
setting, via the layer-wise accounting (same machinery as the dry-run).

  PYTHONPATH=src python -m repro.launch.hillclimb --arch gemma3-12b \
      --shape train_4k --tag ring_ce --set ce_impl=ring --set q_chunk=512

Writes experiments/perf/<arch>__<shape>__<tag>.json with the roofline terms
so before/after deltas land in EXPERIMENTS.md §Perf.
"""
import argparse
import json
import time

from repro.configs.base import SHAPES, get_config
from repro.launch.dryrun import (TARGET_KIND, account_cell, grad_accum_for,
                                 lower_cell, zero1_for)
from repro.launch.mesh import make_production_mesh
from repro.perf import hlo_stats
from repro.perf.knobs import Knobs, use_knobs

OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "experiments", "perf")


def parse_sets(pairs):
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        field = Knobs.__dataclass_fields__[k]
        if field.type in ("int", int):
            v = int(v)
        elif field.type in ("float", float):
            v = float(v)
        elif field.type in ("bool", bool):
            v = v.lower() in ("1", "true", "yes")
        out[k] = v
    return out


def measure(arch, shape_name, *, mesh=None, knob_kw=None, grad_accum=None,
            zero1=None, with_memory=False, layout="sp"):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = mesh or make_production_mesh()
    knob_kw = knob_kw or {}
    t0 = time.time()
    with use_knobs(**knob_kw):
        acc = account_cell(cfg, shape, mesh, grad_accum=grad_accum,
                           zero1=zero1, layout=layout)
        rec = {
            "arch": arch, "shape": shape_name, "knobs": knob_kw,
            "layout": layout,
            "grad_accum": acc["grad_accum"],
            "flops_per_device": acc["flops"],
            "bytes_per_device": acc["bytes"],
            "collective_bytes_per_device": acc["coll"],
            "collectives_by_kind": acc["coll_by_kind"],
            "roofline": hlo_stats.roofline_terms(
                acc["flops"], acc["bytes"], acc["coll"],
                device_kind=TARGET_KIND),
            "measure_s": round(time.time() - t0, 1),
        }
        if with_memory:
            lowered, _ = lower_cell(cfg, shape, mesh, grad_accum=grad_accum,
                                    zero1=zero1, layout=layout)
            mem = lowered.compile().memory_analysis()
            rec["memory_peak_gb"] = round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
                / 2**30, 2)
    return rec


def kernel_adjusted(arch, shape_name, *, mesh=None, knob_kw=None,
                    grad_accum=None, zero1=None, layout="sp"):
    """Kernel-adjusted memory term.

    The XLA (CPU-lowered) graph materializes attention score tiles in HBM;
    the Pallas flash kernel (validated in interpret mode) keeps them in
    VMEM.  Because Mosaic cannot lower for the CPU dry-run target, the
    kernel's effect on the memory term is measured DIFFERENTIALLY:

      1. cost a 1-layer graph with full attention  (score elems P_full)
      2. cost the same layer with a small sliding window (score elems P_win)
      3. bytes-per-score-element  k = dBytes / dP  (linear model)
      4. adjusted = total - sum_layers k*P(layer) + sum_layers kernel_streams

    kernel_streams = (q,k,v,o) HBM traffic of the flash kernel itself
    (x ~3.5 for train: fwd + remat-fwd + bwd read/write).
    """
    import dataclasses as _dc

    from repro.launch import dryrun as DR
    from repro.models import model as M

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = mesh or make_production_mesh()
    knob_kw = knob_kw or {}
    kind = shape.kind
    assert kind in ("train", "prefill"), "decode bytes are real HBM traffic"
    ga = (grad_accum if grad_accum is not None
          else DR.grad_accum_for(cfg, shape)) if kind == "train" else 1
    z1 = zero1 if zero1 is not None else DR.zero1_for(cfg)
    mshape = _dc.replace(shape, global_batch=shape.global_batch // ga)

    with use_knobs(**knob_kw):
        base = measure(arch, shape_name, mesh=mesh, knob_kw=knob_kw,
                       grad_accum=grad_accum, zero1=zero1, layout=layout)
        # differential attention-byte measurement on a full-attn layer
        from repro.models.blocks import LayerSpec
        attn_kinds = {"attn_mlp": None, "attn_moe": None, "attn_dense": None,
                      "hybrid": None, "enc": None, "dec": None}
        probe_kind = next(s.kind for s in M.layer_specs(cfg)
                          if s.kind in attn_kinds)
        kw = dict(grad_accum=1, zero1=z1, grads_only=(kind == "train"),
                  layout=layout)
        c_full = DR._cost_of(DR._single_layer_cfg(
            cfg, LayerSpec(kind=probe_kind, window=0)), mshape, mesh, **kw)
        W = 1024
        c_win = DR._cost_of(DR._single_layer_cfg(
            cfg, LayerSpec(kind=probe_kind, window=W)), mshape, mesh, **kw)

        tp = mesh.shape.get("model", 1)
        if layout == "fsdp":
            lay_dp = mesh.size
            tp_seq = 1
        else:
            lay_dp = max(mesh.shape.get("data", 1)
                         * mesh.shape.get("pod", 1), 1)
            tp_seq = tp
        B_l = max(mshape.global_batch // lay_dp, 1)
        S = shape.seq_len
        S_loc = S // tp_seq
        qc = 256
        span_win = min(W + qc, S)
        H = cfg.n_heads
        p_full = B_l * H * S_loc * S
        p_win = B_l * H * S_loc * span_win
        k_per = max((c_full["bytes"] - c_win["bytes"]) / (p_full - p_win), 0)

        # subtract XLA attention bytes / add kernel streams, per layer
        adj = base["bytes_per_device"]
        kern_total = 0.0
        for s in (M.layer_specs(cfg)
                  + (M.encoder_layer_specs(cfg) if cfg.is_encoder_decoder
                     else [])):
            if s.kind not in attn_kinds:
                continue
            span = S if s.window == 0 else min(s.window + qc, S)
            p = B_l * H * S_loc * span
            adj -= ga * k_per * p
            streams = (2 * B_l * S_loc * (cfg.qkv_dim + cfg.kv_dim)
                       + 2 * B_l * S * 2 * cfg.kv_dim)  # q,o local + k,v full
            passes = 3.5 if kind == "train" else 1.0
            kern_total += ga * passes * streams
        adj = max(adj + kern_total, 0.0)
    rec = dict(base)
    rec["bytes_per_device_kernel_adjusted"] = adj
    rec["xla_attn_bytes_per_score_elem"] = k_per
    rec["roofline_kernel_adjusted"] = hlo_stats.roofline_terms(
        base["flops_per_device"], adj, base["collective_bytes_per_device"],
        device_kind=TARGET_KIND)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--set", action="append", dest="sets", default=[])
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--zero1", type=int, default=None)
    ap.add_argument("--memory", action="store_true")
    ap.add_argument("--kernel-adjust", action="store_true")
    ap.add_argument("--layout", default="sp", choices=["sp", "fsdp"])
    args = ap.parse_args()

    fn = kernel_adjusted if args.kernel_adjust else measure
    kw = ({"layout": args.layout} if args.kernel_adjust
          else {"with_memory": args.memory, "layout": args.layout})
    rec = fn(args.arch, args.shape, knob_kw=parse_sets(args.sets),
             grad_accum=args.grad_accum,
             zero1=None if args.zero1 is None else bool(args.zero1), **kw)
    os.makedirs(os.path.abspath(OUT), exist_ok=True)
    path = os.path.join(os.path.abspath(OUT),
                        f"{args.arch}__{args.shape}__{args.tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    rl = rec["roofline"]
    print(f"{args.tag}: compute={rl['compute_s']:.4f}s "
          f"memory={rl['memory_s']:.4f}s coll={rl['collective_s']:.4f}s "
          f"bound={rl['bound']}  ({rec['measure_s']}s to measure)")
    if "roofline_kernel_adjusted" in rec:
        ra = rec["roofline_kernel_adjusted"]
        print(f"  kernel-adjusted: memory={ra['memory_s']:.4f}s "
              f"bound={ra['bound']} "
              f"(attn bytes/elem={rec['xla_attn_bytes_per_score_elem']:.1f})")
    if "memory_peak_gb" in rec:
        print(f"  peak HBM: {rec['memory_peak_gb']} GB/device")


if __name__ == "__main__":
    main()
