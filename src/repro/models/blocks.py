"""Layer blocks for every assigned architecture family.

Contract: ``apply_block(cfg, spec, params, x, ctx, cache) -> (x, cache', aux)``
  * train:   cache None -> None
  * prefill: cache None -> freshly built cache
  * decode:  cache in   -> updated cache
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


@dataclass(frozen=True)
class LayerSpec:
    kind: str      # attn_mlp | attn_moe | mlstm | slstm | hybrid | enc | dec
    window: int = 0  # 0 = full attention


class Ctx(NamedTuple):
    mode: str                      # train | prefill | decode
    positions: Any                 # (B,S) or (3,B,S) int32
    pos: Any = None                # decode: scalar cache write position
    encoder_out: Any = None        # whisper cross-attention source (B,Se,D)


def _round128(x: float) -> int:
    return max(16, int(-(-x // 16) * 16)) if x < 128 else int(-(-x // 128) * 128)


def slstm_ff_dim(cfg) -> int:
    return _round128(cfg.d_model * 4 / 3)


# ---------------------------------------------------------------------------
# Attention sublayer (shared).
# ---------------------------------------------------------------------------


def _attn_sublayer(cfg, p, x, ctx, cache, *, window: int, causal: bool = True,
                   rope: bool = True):
    B, Sx, _ = x.shape
    if ctx.mode == "decode":
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        # gather feature-sharded projections to full heads (tiny at S=1)
        q = shd.act(q, "dp", None, None)
        k = shd.act(k, "dp", None, None)
        v = shd.act(v, "dp", None, None)
        q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = L.rms_head_norm(q, p["q_norm"], cfg.norm_eps)
            k = L.rms_head_norm(k, p["k_norm"], cfg.norm_eps)
        if rope:
            q, k = L.apply_rope(cfg, q, k, ctx.positions)
        y, ck, cv = A.attn_decode(q, k, v, cache["k"], cache["v"], ctx.pos,
                                  window=window,
                                  softcap=cfg.attn_logit_softcap)
        cache = dict(cache, k=ck, v=cv)
    else:
        q, k, v = A.project_qkv(cfg, p, x, ctx.positions, rope=rope)
        y = A.attention_sp(q, k, v, ctx.positions, causal=causal,
                           window=window, softcap=cfg.attn_logit_softcap)
        if ctx.mode == "prefill":
            cache = {"k": k, "v": v}
    y = y.reshape(B, Sx, cfg.qkv_dim)
    y = y @ p["wo"]
    if "bo" in p:
        y = y + p["bo"]
    return y, cache


def _cross_attn_sublayer(cfg, p, x, ctx, cache):
    """Whisper cross-attention: keys/values from the encoder output."""
    B, Sx, _ = x.shape
    if ctx.mode == "decode":
        ck, cv = cache["ck"], cache["cv"]
        q = (x @ p["wq"] + p.get("bq", 0.0))
        q = shd.act(q, "dp", None, None)
        q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        kpos = jnp.arange(ck.shape[1])
        y = A.attn_core(q, ck, cv, jnp.full((B, 1), ck.shape[1] - 1), kpos,
                        causal=False, window=0)
    else:
        enc = ctx.encoder_out
        # project q from x, k/v from encoder output
        q = (x @ p["wq"] + (p["bq"] if "bq" in p else 0.0)).reshape(
            B, Sx, cfg.n_heads, cfg.head_dim)
        k = (enc @ p["wk"] + (p["bk"] if "bk" in p else 0.0)).reshape(
            B, enc.shape[1], cfg.n_kv_heads, cfg.head_dim)
        v = (enc @ p["wv"] + (p["bv"] if "bv" in p else 0.0)).reshape(
            B, enc.shape[1], cfg.n_kv_heads, cfg.head_dim)
        qpos = ctx.positions[0] if ctx.positions.ndim == 3 else ctx.positions
        y = A.attention_sp(q, k, v, qpos, causal=False, window=0)
        if ctx.mode == "prefill":
            cache = dict(cache or {}, ck=k, cv=v)
    y = y.reshape(B, Sx, cfg.qkv_dim) @ p["wo"]
    if "bo" in p:
        y = y + p["bo"]
    return y, cache


# ---------------------------------------------------------------------------
# Mamba sublayer (hymba) — Mamba-2/SSD form, per-head scalar decay.
# ---------------------------------------------------------------------------


def mamba_init(cfg, key, dtype):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    h = cfg.n_heads
    n = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        "w_in": L.dense_init(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv_width, di),
                                     dtype=jnp.float32) * 0.2).astype(dtype),
        "conv_b": L.zeros((di,), dtype),
        "w_bc": L.dense_init(ks[2], di, 2 * n, dtype),
        "w_dt": L.dense_init(ks[3], di, h, dtype),
        "dt_bias": jnp.full((h,), -2.0, dtype),
        "a_log": jnp.zeros((h,), dtype),
        "d_skip": L.ones((h,), dtype),
        "w_out_m": L.dense_init(ks[4], di, d, dtype),
    }


def mamba_apply(cfg, p, x, ctx, cache):
    B, Sx, d = x.shape
    di = cfg.ssm_expand * d
    h = cfg.n_heads
    hd = di // h
    n = cfg.ssm_state
    xz = x @ p["w_in"]
    xs, z = jnp.split(xz, 2, axis=-1)
    if ctx.mode == "decode":
        xs = shd.act(xs, "dp", None, None)
        z = shd.act(z, "dp", None, None)
        conv_in = jnp.concatenate([cache["conv"], xs], axis=1)
        xc = sum(conv_in[:, j:j + 1] * p["conv_w"][j]
                 for j in range(cfg.ssm_conv_width)) + p["conv_b"]
        new_conv = conv_in[:, 1:]
    else:
        xc = S.causal_conv1d(xs, p["conv_w"], p["conv_b"])
        new_conv = None
    xc = jax.nn.silu(xc)
    bc = xc @ p["w_bc"]
    b_, c_ = jnp.split(bc, 2, axis=-1)                    # (B,S,N) each
    dt = jax.nn.softplus(xc @ p["w_dt"] + p["dt_bias"])   # (B,S,h)
    g = (-dt * jnp.exp(p["a_log"].astype(jnp.float32))[None, None, :])
    i = jnp.log(dt + 1e-9)
    v = xs.reshape(B, Sx, h, hd)
    k = jnp.broadcast_to(b_[:, :, None, :], (B, Sx, h, n))
    q = jnp.broadcast_to(c_[:, :, None, :], (B, Sx, h, n))
    if ctx.mode == "decode":
        y, st = S.recurrence_step(cache["state"], q[:, 0], k[:, 0], v[:, 0],
                                  g[:, 0], i[:, 0], normalize=False,
                                  scale=1.0)
        y = y[:, None]
        cache = dict(cache, state=st, conv=new_conv)
    else:
        y, st = S.linear_recurrence(q, k, v, g, i, normalize=False,
                                    scale=1.0)
        if ctx.mode == "prefill":
            tail = shd.act(xs, "dp", None, None)[:, -(cfg.ssm_conv_width - 1):]
            cache = {"state": st, "conv": tail}
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * v.astype(jnp.float32)
    y = y.reshape(B, Sx, di).astype(x.dtype) * jax.nn.silu(z)
    return y @ p["w_out_m"], cache


# ---------------------------------------------------------------------------
# Block kinds.
# ---------------------------------------------------------------------------


def init_block(cfg, key, spec: LayerSpec, dtype):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: dict = {}
    if spec.kind in ("attn_mlp", "attn_moe", "enc", "dec", "hybrid"):
        p["norm1"] = L.norm_init(cfg, d, dtype)
        p["attn"] = A.attn_init(cfg, ks[0], dtype)
        p["norm2"] = L.norm_init(cfg, d, dtype)
    if spec.kind == "attn_mlp" or spec.kind == "enc" or spec.kind == "hybrid":
        dff = cfg.d_ff
        p["mlp"] = L.mlp_init(cfg, ks[1], d, dff, dtype)
    if spec.kind == "attn_moe":
        p["moe"] = M.moe_init(cfg, ks[1], dtype)
    if spec.kind == "dec":
        p["norm_cross"] = L.norm_init(cfg, d, dtype)
        p["cross"] = A.attn_init(cfg, ks[2], dtype)
        p["mlp"] = L.mlp_init(cfg, ks[3], d, cfg.d_ff, dtype)
    if spec.kind == "hybrid":
        p["mamba"] = mamba_init(cfg, ks[4], dtype)
        p["branch_norm_attn"] = {"scale": L.ones((d,), dtype)}
        p["branch_norm_ssm"] = {"scale": L.ones((d,), dtype)}
    if spec.kind == "mlstm":
        di = cfg.ssm_expand * d
        kk = jax.random.split(ks[5], 7)
        p["norm1"] = L.norm_init(cfg, d, dtype)
        p["w_in"] = L.dense_init(kk[0], d, 2 * di, dtype)
        p["conv_w"] = (jax.random.normal(kk[1], (cfg.ssm_conv_width, di),
                                         dtype=jnp.float32) * 0.2).astype(dtype)
        p["conv_b"] = L.zeros((di,), dtype)
        p["wq"] = L.dense_init(kk[2], di, di, dtype)
        p["wk"] = L.dense_init(kk[3], di, di, dtype)
        p["wv"] = L.dense_init(kk[4], di, di, dtype)
        p["w_gates"] = L.dense_init(kk[5], di, 2 * cfg.n_heads, dtype)
        p["b_gates"] = jnp.concatenate([
            jnp.zeros((cfg.n_heads,), dtype),
            jnp.full((cfg.n_heads,), 3.0, dtype)])  # forget-gate bias high
        p["head_norm"] = {"scale": L.ones((di,), dtype)}
        p["w_out"] = L.dense_init(kk[6], di, d, dtype)
    if spec.kind == "slstm":
        p["norm1"] = L.norm_init(cfg, d, dtype)
        p["slstm"] = S.slstm_init(ks[6], d, cfg.n_heads, dtype)
        p["w_out"] = L.dense_init(ks[7], d, d, dtype)
        p["norm2"] = L.norm_init(cfg, d, dtype)
        p["mlp"] = L.mlp_init(cfg, ks[1], d, slstm_ff_dim(cfg), dtype)
    # deepseek first dense layer: attn + dense mlp with dense_d_ff
    if spec.kind == "attn_dense":
        p["norm1"] = L.norm_init(cfg, d, dtype)
        p["attn"] = A.attn_init(cfg, ks[0], dtype)
        p["norm2"] = L.norm_init(cfg, d, dtype)
        p["mlp"] = L.mlp_init(cfg, ks[1], d, cfg.dense_d_ff or cfg.d_ff, dtype)
    return p


def apply_block(cfg, spec: LayerSpec, p, x, ctx: Ctx, cache):
    aux = jnp.float32(0.0)
    kind = spec.kind
    if kind in ("attn_mlp", "attn_moe", "attn_dense", "enc", "dec"):
        pa = shd.use_weight(p["attn"])
        h = L.apply_norm(cfg, p["norm1"], x)
        rope = cfg.rope_theta != 0.0
        causal = kind != "enc"
        attn_cache = cache.get("attn") if cache else None
        y, attn_cache = _attn_sublayer(cfg, pa, h, ctx, attn_cache,
                                       window=spec.window, causal=causal,
                                       rope=rope)
        x = x + shd.act(y, "dp", "sp", None)
        new_cache = {"attn": attn_cache} if attn_cache is not None else None
        if kind == "dec":
            pc = shd.use_weight(p["cross"])
            h = L.apply_norm(cfg, p["norm_cross"], x)
            cross_cache = cache.get("cross") if cache else None
            y, cross_cache = _cross_attn_sublayer(cfg, pc, h, ctx, cross_cache)
            x = x + shd.act(y, "dp", "sp", None)
            if cross_cache is not None:
                new_cache = dict(new_cache or {}, cross=cross_cache)
        h = L.apply_norm(cfg, p["norm2"], x)
        if kind == "attn_moe":
            y, aux = M.moe_apply(cfg, p["moe"], h)
        else:
            y = L.apply_mlp(cfg, p["mlp"], h)
        x = x + shd.act(y, "dp", "sp", None)
        return x, new_cache, aux

    if kind == "hybrid":
        h = L.apply_norm(cfg, p["norm1"], x)
        pa = shd.use_weight(p["attn"])
        attn_cache = cache.get("attn") if cache else None
        ya, attn_cache = _attn_sublayer(cfg, pa, h, ctx, attn_cache,
                                        window=spec.window)
        pm = shd.use_weight(p["mamba"])
        mamba_cache = cache.get("mamba") if cache else None
        ym, mamba_cache = mamba_apply(cfg, pm, h, ctx, mamba_cache)
        ya = L.apply_norm(cfg, p["branch_norm_attn"], ya)
        ym = L.apply_norm(cfg, p["branch_norm_ssm"], ym)
        x = x + shd.act(0.5 * (ya + ym), "dp", "sp", None)
        h = L.apply_norm(cfg, p["norm2"], x)
        x = x + shd.act(L.apply_mlp(cfg, p["mlp"], h), "dp", "sp", None)
        new_cache = None
        if attn_cache is not None or mamba_cache is not None:
            new_cache = {"attn": attn_cache, "mamba": mamba_cache}
        return x, new_cache, aux

    if kind == "mlstm":
        pu = shd.use_weight(p)
        B, Sx, d = x.shape
        di = cfg.ssm_expand * d
        h0 = L.apply_norm(cfg, pu["norm1"], x)
        xz = h0 @ pu["w_in"]
        xs, z = jnp.split(xz, 2, axis=-1)
        if ctx.mode == "decode":
            xs = shd.act(xs, "dp", None, None)
            z = shd.act(z, "dp", None, None)
            conv_in = jnp.concatenate([cache["conv"], xs], axis=1)
            xc = sum(conv_in[:, j:j + 1] * pu["conv_w"][j]
                     for j in range(cfg.ssm_conv_width)) + pu["conv_b"]
            new_conv = conv_in[:, 1:]
        else:
            xc = S.causal_conv1d(xs, pu["conv_w"], pu["conv_b"])
            new_conv = None
        xc = jax.nn.silu(xc)
        nh = cfg.n_heads
        hd = di // nh
        q = (xc @ pu["wq"]).reshape(B, Sx, nh, hd)
        k = (xc @ pu["wk"]).reshape(B, Sx, nh, hd)
        v = (xs @ pu["wv"]).reshape(B, Sx, nh, hd)
        gates = xc @ pu["w_gates"] + pu["b_gates"]
        i_pre, f_pre = jnp.split(gates, 2, axis=-1)        # (B,S,nh)
        g = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
        ig = i_pre.astype(jnp.float32)
        if ctx.mode == "decode":
            y, st = S.recurrence_step(cache["state"], q[:, 0], k[:, 0],
                                      v[:, 0], g[:, 0], ig[:, 0],
                                      normalize=True)
            y = y[:, None]
            cache = dict(cache, state=st, conv=new_conv)
            new_cache = cache
        else:
            y, st = S.linear_recurrence(q, k, v, g, ig, normalize=True)
            new_cache = None
            if ctx.mode == "prefill":
                tail = shd.act(xs, "dp", None, None)[
                    :, -(cfg.ssm_conv_width - 1):]
                new_cache = {"state": st, "conv": tail}
        y = y.reshape(B, Sx, di).astype(x.dtype)
        y = L.rms_head_norm(y.reshape(B, Sx, nh, hd),
                            pu["head_norm"]["scale"].reshape(nh, hd),
                            cfg.norm_eps).reshape(B, Sx, di)
        y = y * jax.nn.silu(z)
        x = x + shd.act(y @ pu["w_out"], "dp", "sp", None)
        return x, new_cache, aux

    if kind == "slstm":
        h0 = L.apply_norm(cfg, p["norm1"], x)
        state = cache.get("state") if cache else None
        if ctx.mode == "decode":
            y, st = S.slstm_apply(p["slstm"], h0, cfg.n_heads,
                                  init_state=state)
            new_cache = dict(cache, state=st)
        else:
            y, st = S.slstm_apply(p["slstm"], h0, cfg.n_heads)
            new_cache = {"state": st} if ctx.mode == "prefill" else None
        pw = shd.use_weight(p["w_out"])
        x = x + shd.act(y @ pw, "dp", "sp", None)
        h1 = L.apply_norm(cfg, p["norm2"], x)
        x = x + shd.act(L.apply_mlp(cfg, p["mlp"], h1), "dp", "sp", None)
        return x, new_cache, aux

    raise ValueError(f"unknown layer kind {kind!r}")


# ---------------------------------------------------------------------------
# Cache shape structs (for dry-run decode lowering).
# ---------------------------------------------------------------------------


def cache_struct(cfg, spec: LayerSpec, batch: int, cache_len: int, dtype):
    """Abstract cache shapes for one layer (decode entry point)."""
    hd = cfg.head_dim
    out = {}
    if spec.kind in ("attn_mlp", "attn_moe", "attn_dense", "dec", "hybrid"):
        out["attn"] = {
            "k": jax.ShapeDtypeStruct((batch, cache_len, cfg.n_kv_heads, hd),
                                      dtype),
            "v": jax.ShapeDtypeStruct((batch, cache_len, cfg.n_kv_heads, hd),
                                      dtype),
        }
    if spec.kind == "dec":
        out["cross"] = {
            "ck": jax.ShapeDtypeStruct(
                (batch, cfg.encoder_seq_len, cfg.n_kv_heads, hd), dtype),
            "cv": jax.ShapeDtypeStruct(
                (batch, cfg.encoder_seq_len, cfg.n_kv_heads, hd), dtype),
        }
    if spec.kind == "hybrid":
        di = cfg.ssm_expand * cfg.d_model
        h = cfg.n_heads
        out["mamba"] = {
            "state": S.ScanState(
                loga=jax.ShapeDtypeStruct((batch, h), jnp.float32),
                m=jax.ShapeDtypeStruct((batch, h), jnp.float32),
                C=jax.ShapeDtypeStruct((batch, h, cfg.ssm_state, di // h),
                                       jnp.float32),
                n=jax.ShapeDtypeStruct((batch, h, cfg.ssm_state), jnp.float32)),
            "conv": jax.ShapeDtypeStruct(
                (batch, cfg.ssm_conv_width - 1, di), dtype),
        }
    if spec.kind == "mlstm":
        di = cfg.ssm_expand * cfg.d_model
        h = cfg.n_heads
        hd_i = di // h
        out = {
            "state": S.ScanState(
                loga=jax.ShapeDtypeStruct((batch, h), jnp.float32),
                m=jax.ShapeDtypeStruct((batch, h), jnp.float32),
                C=jax.ShapeDtypeStruct((batch, h, hd_i, hd_i), jnp.float32),
                n=jax.ShapeDtypeStruct((batch, h, hd_i), jnp.float32)),
            "conv": jax.ShapeDtypeStruct(
                (batch, cfg.ssm_conv_width - 1, di), dtype),
        }
    if spec.kind == "slstm":
        h = cfg.n_heads
        hd_h = cfg.d_model // h
        z = jax.ShapeDtypeStruct((batch, h, hd_h), jnp.float32)
        out = {"state": (z, z, z, z)}
    return out
