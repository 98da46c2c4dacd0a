"""Parse collective traffic + roofline terms from compiled HLO.

``cost_analysis`` gives FLOPs and HBM bytes but not collective bytes; those
are summed from the optimized (post-SPMD) HLO text: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute result size.
Async pairs (-start/-done) are counted once via the -start op.
"""
from __future__ import annotations

import math
import re
from typing import Dict

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind from HLO text."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(1)
        for kind in COLLECTIVES:
            # match "<type> <kind>(" or "<type> <kind>-start("
            km = re.match(r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s+"
                          + kind + r"(-start)?\(", rhs)
            if km:
                out[kind] += _shape_bytes(km.group(1))
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# Roofline terms, from published per-chip peaks.
# ---------------------------------------------------------------------------

# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" (per-chip specifications): 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect over 4 links.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 1600e9 / 8 / 4},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float, *,
                   device_kind: str) -> Dict[str, float]:
    pk = peaks(device_kind)
    t_c = flops_per_dev / pk["bf16_flops"]
    t_m = bytes_per_dev / pk["hbm_bw"]
    t_n = coll_bytes_per_dev / pk["ici_link_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])[0]
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
            "bound": dom, "step_s_lower_bound": max(t_c, t_m, t_n)}
