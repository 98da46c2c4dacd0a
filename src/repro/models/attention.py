"""Attention: projections + two distributed cores.

* ``attention_sp``  — train/prefill.  q stays sequence-sharded over the
  "model" axis; k/v are all-gathered (context parallelism).  Inside each
  shard the core is q-chunked (memory O(S·chunk)) and sliding-window layers
  slice only the needed KV span (FLOPs O(S·window)).
* ``attn_decode``   — single-token decode with the KV cache sequence-sharded
  over "model" and a flash-decoding (max/sum-exp psum) combine.

Both wrap the same pure-jnp local core ``attn_core`` which is also the
oracle contract implemented by the Pallas flash-attention kernel
(`repro.kernels.flash_attention`).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.models import layers as L
from repro.perf.knobs import knobs

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params.
# ---------------------------------------------------------------------------


def attn_init(cfg, key, dtype):
    d, qd, kvd = cfg.d_model, cfg.qkv_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, qd, dtype),
        "wk": L.dense_init(ks[1], d, kvd, dtype),
        "wv": L.dense_init(ks[2], d, kvd, dtype),
        "wo": L.dense_init(ks[3], qd, d, dtype),
    }
    if cfg.attn_bias:
        p["bq"] = L.zeros((qd,), dtype)
        p["bk"] = L.zeros((kvd,), dtype)
        p["bv"] = L.zeros((kvd,), dtype)
    if cfg.attn_out_bias:
        p["bo"] = L.zeros((d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = L.ones((cfg.head_dim,), dtype)
        p["k_norm"] = L.ones((cfg.head_dim,), dtype)
    return p


def project_qkv(cfg, p, x, positions, *, rope: bool = True):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KV,hd), roped + qk-normed."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rms_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_head_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q, k = L.apply_rope(cfg, q, k, positions)
    return q, k, v


# ---------------------------------------------------------------------------
# Local core (oracle contract shared with the Pallas kernel).
# ---------------------------------------------------------------------------


def _scores_block(q, k, v, qpos, kpos, *, causal, window, softcap):
    """Dense attention on concrete blocks.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); qpos: (B, Sq); kpos: (Sk,).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    sdt = jnp.bfloat16 if knobs().attn_scores_bf16 else jnp.float32
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=sdt)
    s = s * jnp.asarray(1.0 / float(hd) ** 0.5, sdt)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.ones((B, 1, 1, Sq, kpos.shape[0]), dtype=bool)
    kb = kpos[None, None, None, None, :]
    qb = qpos[:, None, None, :, None]
    if causal:
        mask = mask & (kb <= qb)
    if window > 0:
        mask = mask & (kb > qb - window)
    s = jnp.where(mask, s, jnp.asarray(NEG_INF if sdt == jnp.float32
                                       else -3e38, sdt))
    a = jax.nn.softmax(s, axis=-1)  # max-subtracted; bf16-safe under knob
    o = jnp.einsum("bkgqs,bskh->bqkgh", a.astype(v.dtype), v)
    return o.reshape(B, Sq, H, hd)


def attn_core(q, k, v, qpos, kpos, *, causal=True, window=0, softcap=0.0,
              q_chunk=None, slice_window=None):
    """Chunked local attention.

    Iterates q in chunks of ``q_chunk`` (memory O(Sq_chunk · Sk)); for
    sliding-window layers only the [chunk_start - window, chunk_end) KV span
    is touched (assumes row-uniform positions, which all our pipelines use).
    Knobs (repro.perf.knobs) supply the defaults — §Perf hillclimb levers.
    """
    kn = knobs()
    q_chunk = kn.q_chunk if q_chunk is None else q_chunk
    slice_window = kn.window_slice if slice_window is None else slice_window
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    qc = q_chunk if (Sq % q_chunk == 0 and Sq > q_chunk) else Sq
    n = Sq // qc
    if n == 1:
        return _scores_block(q, k, v, qpos, kpos, causal=causal,
                             window=window, softcap=softcap)

    qs = q.reshape(B, n, qc, H, hd).swapaxes(0, 1)
    qps = qpos.reshape(B, n, qc).swapaxes(0, 1)
    win_span = window + qc if window > 0 else 0
    use_slice = slice_window and window > 0 and win_span < Sk and causal

    def one(args):
        qi, qpi = args
        if use_slice:
            start = jnp.clip(qpi[0, 0] - window + 1, 0, Sk - win_span)
            ki = jax.lax.dynamic_slice_in_dim(k, start, win_span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, win_span, axis=1)
            kpi = start + jnp.arange(win_span)
        else:
            ki, vi, kpi = k, v, kpos
        return _scores_block(qi, ki, vi, qpi, kpi, causal=causal,
                             window=window, softcap=softcap)

    if shd.unrolled():
        outs = [one((qs[i], qps[i])) for i in range(n)]
        out = jnp.stack(outs, axis=0)
    else:
        # remat per chunk: without it the backward keeps every chunk's
        # (qc, Sk) scores at once, i.e. the full (Sq, Sk) matrix
        out = jax.lax.map(jax.checkpoint(one, prevent_cse=False), (qs, qps))
    return out.swapaxes(0, 1).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Train / prefill: sequence-parallel wrapper.
# ---------------------------------------------------------------------------


def attention_sp(q, k, v, qpos, *, causal=True, window=0, softcap=0.0,
                 q_chunk=None, kpos=None):
    """q sequence-sharded over "model"; k/v gathered to full sequence.

    kpos defaults to arange over the full (gathered) key length — correct for
    self-attention where keys span the whole global sequence.
    """
    lay = shd.layout()
    Sk = k.shape[1]
    if lay.mesh is None or lay.mode != "train_sp" or lay.model_axis is None:
        kp = kpos if kpos is not None else jnp.arange(Sk)
        return attn_core(q, k, v, qpos, kp, causal=causal, window=window,
                         softcap=softcap, q_chunk=q_chunk)

    m = lay.model_axis
    dp = lay.dp if lay.dp else None
    tp = lay.n_shards
    S_loc = Sk // tp

    if (knobs().attn_halo and causal and window > 0
            and -(-window // S_loc) < tp - 1):
        # HALO EXCHANGE (beyond-paper §Perf): a sliding-window layer only
        # attends ceil(W / S_loc) chunks back — collect those via ppermute
        # instead of all-gathering the full sequence.  Backward traffic
        # (the dKV reduction) shrinks to the same neighborhood.
        n_hops = -(-window // S_loc)

        def halo_body(q_l, k_l, v_l, qpos_l):
            idx = jax.lax.axis_index(m)
            parts_k, parts_v = [], []
            for h in range(n_hops, 0, -1):
                perm = [(s, s + h) for s in range(tp - h)]
                parts_k.append(jax.lax.ppermute(k_l, m, perm))
                parts_v.append(jax.lax.ppermute(v_l, m, perm))
            k_ext = jnp.concatenate(parts_k + [k_l], axis=1)
            v_ext = jnp.concatenate(parts_v + [v_l], axis=1)
            base = (idx - n_hops) * S_loc
            kp = base + jnp.arange((n_hops + 1) * S_loc)
            # non-received halo chunks are zeros; their kp < 0 masks them out
            kp = jnp.where(kp < 0, -(10 ** 9), kp)
            return attn_core(q_l, k_ext, v_ext, qpos_l, kp, causal=causal,
                             window=window, softcap=softcap,
                             q_chunk=q_chunk, slice_window=False)

        return jax.shard_map(
            halo_body, mesh=lay.mesh,
            in_specs=(P(dp, m), P(dp, m), P(dp, m), P(dp, m)),
            out_specs=P(dp, m),
        )(q, k, v, qpos)

    def body(q_l, k_f, v_f, qpos_l):
        kp = jnp.arange(k_f.shape[1])
        return attn_core(q_l, k_f, v_f, qpos_l, kp, causal=causal,
                         window=window, softcap=softcap, q_chunk=q_chunk)

    return jax.shard_map(
        body, mesh=lay.mesh,
        in_specs=(P(dp, m), P(dp), P(dp), P(dp, m)),
        out_specs=P(dp, m),
    )(q, k, v, qpos)


# ---------------------------------------------------------------------------
# Decode: sequence-sharded KV cache + flash-decoding combine.
# ---------------------------------------------------------------------------


def _decode_block(q, k_l, v_l, kpos, pos, *, window, softcap):
    """Partial attention stats over a local KV span.

    q: (B, H, hd); k_l/v_l: (B, L_l, KV, hd); kpos: (L_l,) global positions.
    Returns (m, l, o) partials for the flash combine.
    """
    B, H, hd = q.shape
    KV = k_l.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_l).astype(jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    valid = kpos[None, None, None, :] <= pos
    if window > 0:
        valid = valid & (kpos[None, None, None, :] > pos - window)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # (B, KV, G)
    e = jnp.exp(s - m[..., None])
    l = jnp.sum(e, axis=-1)                      # (B, KV, G)
    o = jnp.einsum("bkgs,bskh->bkgh", e, v_l.astype(jnp.float32))
    return m, l, o


def attn_decode(q, k_new, v_new, cache_k, cache_v, pos, *, window=0,
                softcap=0.0):
    """One-token decode.

    q/k_new/v_new: (B, 1, {H|KV}, hd) replicated over "model";
    cache_{k,v}: (B, L, KV, hd), sequence-sharded over "model" in decode_tp.
    pos: scalar int32 — number of tokens already in the cache (the new token
    is written at index ``pos`` and attends over [0, pos]).
    Returns (y (B,1,H,hd), new_cache_k, new_cache_v).
    """
    lay = shd.layout()
    B, _, H, hd = q.shape

    if lay.mesh is None or lay.mode != "decode_tp" or lay.model_axis is None:
        ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, pos, axis=1)
        kpos = jnp.arange(ck.shape[1])
        m, l, o = _decode_block(q[:, 0], ck, cv, kpos, pos,
                                window=window, softcap=softcap)
        y = (o / l[..., None]).reshape(B, 1, H, hd).astype(q.dtype)
        return y, ck, cv

    m_ax = lay.model_axis
    dp = lay.dp_for(B)

    def body(q_f, kn, vn, ck_l, cv_l, pos_s):
        pos_s = pos_s[0] if pos_s.ndim else pos_s
        idx = jax.lax.axis_index(m_ax)
        L_l = ck_l.shape[1]
        lo = idx * L_l
        # write the new token into whichever shard owns position `pos`
        rel = jnp.clip(pos_s - lo, 0, L_l - 1)
        in_range = (pos_s >= lo) & (pos_s < lo + L_l)
        ck_u = jax.lax.dynamic_update_slice_in_dim(ck_l, kn, rel, axis=1)
        cv_u = jax.lax.dynamic_update_slice_in_dim(cv_l, vn, rel, axis=1)
        ck_l = jnp.where(in_range, ck_u, ck_l)
        cv_l = jnp.where(in_range, cv_u, cv_l)
        kpos = lo + jnp.arange(L_l)
        m, l, o = _decode_block(q_f[:, 0], ck_l, cv_l, kpos, pos_s,
                                window=window, softcap=softcap)
        m_g = jax.lax.pmax(m, m_ax)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, m_ax)
        o_g = jax.lax.psum(o * corr[..., None], m_ax)
        B_l = q_f.shape[0]
        y = (o_g / l_g[..., None]).reshape(B_l, 1, H, hd).astype(q_f.dtype)
        return y, ck_l, cv_l

    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    return jax.shard_map(
        body, mesh=lay.mesh,
        in_specs=(P(dp), P(dp), P(dp), P(dp, m_ax), P(dp, m_ax), P()),
        out_specs=(P(dp), P(dp, m_ax), P(dp, m_ax)),
    )(q, k_new, v_new, cache_k, cache_v, pos_arr)
