"""Model FLOPs of one training token of a dense decoder LM.

6 x the matmul parameters a token passes through (forward 2, backward 4):
every layer's projections and MLP, and the output head (the embedding
matrix itself when tied; the lookup is no matmul), plus causal attention,
QK^T and AV: 2 x 2 x S x H x hd per token and layer forward, halved by the
causal mask, times 3 for forward and backward = 6 x L x S x H x hd.
Recomputation (remat) is not counted: these are the FLOPs the step needs."""
from __future__ import annotations

import lm_weights


def matmul_params(c: dict) -> int:
    d = lm_weights.dims(c)
    D, F = d["D"], d["F"]
    qd, kvd = d["H"] * d["hd"], d["KV"] * d["hd"]
    mlp = (3 if lm_weights.gated(c) else 2) * D * F
    per_layer = D * qd + 2 * D * kvd + qd * D + mlp
    return d["L"] * per_layer + d["V"] * D


def per_token(c: dict, seq_len: int) -> float:
    d = lm_weights.dims(c)
    attn = 6.0 * d["L"] * seq_len * d["H"] * d["hd"]
    return 6.0 * matmul_params(c) + attn
