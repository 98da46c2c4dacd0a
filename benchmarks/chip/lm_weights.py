"""Weights of a dense decoder LM, made by the benchmark from the seed.

The benchmark, not the program, makes the weights, so that the reference
can start from the same numbers without taking anything the program made.
One nested dict holds them; each layer's tensors are stacked on a leading
layer axis under ``"layers"``:

  embed.table (V, D) | lm_head.w (D, V), untied only | final_norm.{scale,bias}
  layers.norm1 / layers.norm2: {scale[, bias]}            (L, D)
  layers.attn: wq (L, D, H*hd), wk/wv (L, D, KV*hd), wo (L, H*hd, D)
               [bq, bk, bv] [bo]
  layers.mlp:  [w_gate] w_up (L, D, F) [b_up], w_down (L, F, D) [b_down]

Scales: embedding N(0, 0.02), projections N(0, 1/d_in), biases N(0, 0.02),
norm scales 1 + N(0, 0.05) and norm biases N(0, 0.02): every tensor is
non-trivial, so a path that drops a bias or a norm shows in the loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    """The sizes the equations use, read from a configuration file."""
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "hd": c["head_dim"], "F": c["intermediate_size"],
            "V": c["vocab_size"]}


def shapes(c: dict) -> dict:
    """{path: (shape, init kind)} of every tensor, in a fixed order."""
    d = dims(c)
    L, D, F, V = d["L"], d["D"], d["F"], d["V"]
    qd, kvd = d["H"] * d["hd"], d["KV"] * d["hd"]
    layer_norm = c["norm"] == "layernorm"
    out = {"embed.table": ((V, D), "embed")}
    if not c["tie_word_embeddings"]:
        out["lm_head.w"] = ((D, V), "dense")
    out["final_norm.scale"] = ((D,), "scale")
    if layer_norm:
        out["final_norm.bias"] = ((D,), "bias")
    for n in ("norm1", "norm2"):
        out[f"layers.{n}.scale"] = ((L, D), "scale")
        if layer_norm:
            out[f"layers.{n}.bias"] = ((L, D), "bias")
    out["layers.attn.wq"] = ((L, D, qd), "dense")
    out["layers.attn.wk"] = ((L, D, kvd), "dense")
    out["layers.attn.wv"] = ((L, D, kvd), "dense")
    out["layers.attn.wo"] = ((L, qd, D), "dense")
    if c["qkv_bias"]:
        out["layers.attn.bq"] = ((L, qd), "bias")
        out["layers.attn.bk"] = ((L, kvd), "bias")
        out["layers.attn.bv"] = ((L, kvd), "bias")
    if c["attention_out_bias"]:
        out["layers.attn.bo"] = ((L, D), "bias")
    if gated(c):
        out["layers.mlp.w_gate"] = ((L, D, F), "dense")
    out["layers.mlp.w_up"] = ((L, D, F), "dense")
    if c["mlp_bias"]:
        out["layers.mlp.b_up"] = ((L, F), "bias")
    out["layers.mlp.w_down"] = ((L, F, D), "dense")
    if c["mlp_bias"]:
        out["layers.mlp.b_down"] = ((L, D), "bias")
    return out


def gated(c: dict) -> bool:
    """SiLU MLPs are gated (SwiGLU); GELU MLPs are plain."""
    return c["hidden_act"] == "silu"


def _init(key, shape, kind, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "dense":
        z = z / math.sqrt(shape[-2])
    elif kind == "scale":
        z = 1.0 + 0.05 * z
    else:                       # embed, bias
        z = 0.02 * z
    return z.astype(dtype)


def make(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """The nested weight dict (trace-safe: call it inside one jit)."""
    out: dict = {}
    for i, (path, (shape, kind)) in enumerate(shapes(c).items()):
        node = out
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = _init(jax.random.fold_in(key, i), shape, kind, dtype)
    return out


def flat(tree: dict, prefix: str = "") -> dict:
    """{dotted path: leaf} of a nested dict."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = v
    return out


def seed_key(seed: int):
    """A PRNG key from a seed of any size (more bits than 32 are folded
    in, so seeds past 2**32 stay distinct)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
