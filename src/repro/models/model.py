"""Model assembly: layer segments, init, train/prefill/decode entry points.

Layers are grouped into *segments*: (pattern of LayerSpecs, repeats).  A
segment with repeats > 1 runs under ``jax.lax.scan`` over parameters stacked
on a leading repeats dim (small HLO, fast compile, per-iteration remat) —
e.g. gemma3's "LLLLLG" pattern becomes one scan of 8 repeats whose body holds
6 layer applications.  Irregular layouts (hymba's global layers {0,15,31})
fall back to run-length segments.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.kernels.causal_attention import ATTN_RESIDUALS
from repro.models import attention as A
from repro.models import layers as L
from repro.models.blocks import Ctx, LayerSpec, apply_block, cache_struct, init_block


@dataclass(frozen=True)
class Segment:
    pattern: Tuple[LayerSpec, ...]
    repeats: int


# ---------------------------------------------------------------------------
# Layer specs & segments.
# ---------------------------------------------------------------------------


def layer_specs(cfg) -> List[LayerSpec]:
    specs = []
    for i in range(cfg.n_layers):
        if cfg.family == "moe":
            kind = "attn_dense" if i < cfg.first_dense_layers else "attn_moe"
        elif cfg.family == "ssm":
            kind = ("slstm" if cfg.slstm_every and
                    (i % cfg.slstm_every == cfg.slstm_every - 1) else "mlstm")
        elif cfg.family == "hybrid":
            kind = "hybrid"
        elif cfg.is_encoder_decoder:
            kind = "dec"
        else:
            kind = "attn_mlp"
        window = 0
        if kind in ("attn_mlp", "attn_moe", "attn_dense", "hybrid"):
            if cfg.attn_kind(i) == "L" and cfg.sliding_window:
                window = cfg.sliding_window
        specs.append(LayerSpec(kind=kind, window=window))
    return specs


def encoder_layer_specs(cfg) -> List[LayerSpec]:
    return [LayerSpec(kind="enc", window=0)
            for _ in range(cfg.n_encoder_layers)]


def build_segments(specs: Sequence[LayerSpec]) -> List[Segment]:
    n = len(specs)
    # try cyclic grouping with the smallest period
    for period in range(1, min(12, n) + 1):
        if n % period:
            continue
        if all(specs[i] == specs[i % period] for i in range(n)):
            return [Segment(tuple(specs[:period]), n // period)]
    # run-length fallback
    segs: List[Segment] = []
    i = 0
    while i < n:
        j = i
        while j < n and specs[j] == specs[i]:
            j += 1
        segs.append(Segment((specs[i],), j - i))
        i = j
    return segs


# ---------------------------------------------------------------------------
# Init.
# ---------------------------------------------------------------------------


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_segment(cfg, key, seg: Segment, dtype):
    def init_pattern(k):
        ks = jax.random.split(k, len(seg.pattern))
        return [init_block(cfg, ks[i], spec, dtype)
                for i, spec in enumerate(seg.pattern)]

    if seg.repeats == 1:
        return init_pattern(key)
    keys = jax.random.split(key, seg.repeats)
    return jax.vmap(init_pattern)(keys)


def init_model(cfg, key, dtype=None):
    dtype = dtype or _dtype(cfg)
    keys = jax.random.split(key, 8)
    segs = build_segments(layer_specs(cfg))
    params = {
        "embed": {"table": (jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype)},
        "segments": [init_segment(cfg, k, s, dtype)
                     for k, s in zip(jax.random.split(keys[1], len(segs)),
                                     segs)],
        "final_norm": L.norm_init(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L.dense_init(
            keys[2], cfg.d_model, cfg.vocab_size, dtype)}
    if cfg.is_encoder_decoder:
        esegs = build_segments(encoder_layer_specs(cfg))
        params["encoder"] = {
            "segments": [init_segment(cfg, k, s, dtype)
                         for k, s in zip(
                             jax.random.split(keys[3], len(esegs)), esegs)],
            "final_norm": L.norm_init(cfg, cfg.d_model, dtype),
            "pos_table": (jax.random.normal(
                keys[4], (cfg.encoder_seq_len, cfg.d_model), jnp.float32)
                * 0.02).astype(dtype),
        }
        params["dec_pos_table"] = (jax.random.normal(
            keys[5], (32_768, cfg.d_model), jnp.float32) * 0.02).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# Segment runner.
# ---------------------------------------------------------------------------


def _norm_cache(c):
    return () if c is None else c


_KEEP_ATTN = jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS)


def _layer_remat(fn):
    """``fn`` under the train step's layer checkpoint: the backward
    recomputes the layers' forward, all but the fused attention's output
    and logsumexp (``ATTN_RESIDUALS``), which it keeps, so the splash
    forward kernel runs once.  An ``attn_core`` site names nothing: its
    layer is recomputed whole."""
    ck = jax.checkpoint(fn, prevent_cse=False, policy=_KEEP_ATTN)

    def run(*args):
        with A.keeping_residuals():
            return ck(*args)
    return run


def run_segments(cfg, seg_params, segs, x, ctx: Ctx, caches=None,
                 remat: bool = True):
    """Returns (x, new_caches, aux_total)."""
    aux_total = jnp.float32(0.0)
    new_caches = []
    for si, seg in enumerate(segs):
        sp = seg_params[si]
        sc = caches[si] if caches is not None else None
        if seg.repeats == 1:
            ncs = []
            for pi, spec in enumerate(seg.pattern):
                cin = sc[pi] if sc is not None else None

                def call(p_, x_, spec=spec, cin=cin):
                    return apply_block(cfg, spec, p_, x_, ctx, cin)

                if remat and ctx.mode == "train":
                    call = _layer_remat(call)
                x, c, a = call(sp[pi], x)
                aux_total = aux_total + a
                ncs.append(_norm_cache(c))
            new_caches.append(ncs)
        else:
            def body(carry, xs):
                x_c, aux_c = carry
                p_sl, c_sl = xs
                outs = []
                for pi, spec in enumerate(seg.pattern):
                    cin = c_sl[pi] if c_sl is not None else None
                    cin = None if cin == () else cin
                    x_c, c, a = apply_block(cfg, spec, p_sl[pi], x_c, ctx, cin)
                    aux_c = aux_c + a
                    outs.append(_norm_cache(c))
                return (x_c, aux_c), outs

            fn = body
            if remat and ctx.mode == "train":
                fn = _layer_remat(body)
            xs = (sp, sc if sc is not None
                  else [() for _ in seg.pattern])
            (x, aux_total), ncs = jax.lax.scan(fn, (x, aux_total), xs)
            new_caches.append(ncs)
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# Embedding / head.
# ---------------------------------------------------------------------------


def embed_tokens(cfg, params, tokens, batch=None):
    lay = shd.layout()
    table = params["embed"]["table"]
    V = cfg.vocab_size
    if (lay.mesh is not None and lay.mode == "decode_tp"
            and lay.model_axis is not None and V % lay.n_shards == 0):
        # vocab-parallel lookup: mask + psum (keeps the table sharded)
        m_ax = lay.model_axis
        dp = lay.dp_for(tokens.shape[0])
        v_loc = V // lay.n_shards

        def body(tab_l, ids):
            lo = jax.lax.axis_index(m_ax) * v_loc
            rel = jnp.clip(ids - lo, 0, v_loc - 1)
            vals = jnp.take(tab_l, rel, axis=0)
            ok = ((ids >= lo) & (ids < lo + v_loc))[..., None]
            return jax.lax.psum(jnp.where(ok, vals, 0), m_ax)

        x = jax.shard_map(body, mesh=lay.mesh,
                          in_specs=(P(m_ax), P(dp)),
                          out_specs=P(dp))(table, tokens)
    else:
        table = shd.use_weight(table)
        x = jnp.take(table, tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if batch is not None and "patch_embeds" in batch:
        x = jnp.where(batch["image_mask"][..., None],
                      batch["patch_embeds"].astype(x.dtype), x)
    return x


def lm_logits(cfg, params, x):
    if cfg.tie_embeddings:
        w = shd.use_weight(params["embed"]["table"])  # (V, D)
        return x @ w.T.astype(x.dtype)
    w = shd.use_weight(params["lm_head"]["w"])        # (D, V)
    return x @ w.astype(x.dtype)


# ---------------------------------------------------------------------------
# Entry points.
# ---------------------------------------------------------------------------


def _run_encoder(cfg, params, frames):
    enc = params["encoder"]
    pos = jnp.arange(frames.shape[1])
    x = frames.astype(_dtype(cfg)) + enc["pos_table"][None, pos]
    x = shd.act(x, "dp", "sp", None)
    segs = build_segments(encoder_layer_specs(cfg))
    ctx = Ctx(mode="train", positions=jnp.broadcast_to(
        pos[None], frames.shape[:2]))
    x, _, _ = run_segments(cfg, enc["segments"], segs, x, ctx, remat=True)
    return L.apply_norm(cfg, enc["final_norm"], x)


def forward(cfg, params, batch, mode: str = "train", caches=None,
            pos=None, remat: bool = True, head: bool = True):
    """Unified forward.

    batch keys: tokens (B,S), positions ((B,S) or (3,B,S)); optional
    patch_embeds/image_mask (vlm), frames (audio).  decode: S == 1 and
    ``pos``/``caches`` are given.
    Returns (logits, new_caches, aux) — or the final-norm hidden instead of
    logits when ``head=False`` (the fused ring-CE path applies its own head).
    """
    tokens = batch["tokens"]
    positions = batch["positions"]
    x = embed_tokens(cfg, params, tokens, batch)
    if cfg.is_encoder_decoder:
        qpos = positions[0] if positions.ndim == 3 else positions
        x = x + jnp.take(params["dec_pos_table"], qpos, axis=0)
    if mode == "decode":
        x = shd.act(x, "dp", None, None)
    else:
        x = shd.act(x, "dp", "sp", None)

    encoder_out = None
    if cfg.is_encoder_decoder and mode != "decode":
        encoder_out = _run_encoder(cfg, params, batch["frames"])

    ctx = Ctx(mode=mode, positions=positions, pos=pos,
              encoder_out=encoder_out)
    segs = build_segments(layer_specs(cfg))
    x, new_caches, aux = run_segments(cfg, params["segments"], segs, x, ctx,
                                      caches=caches, remat=remat)
    x = L.apply_norm(cfg, params["final_norm"], x)
    if not head:
        return x, new_caches, aux
    if mode == "prefill":
        # serving only needs the last position's logits: slice BEFORE the
        # head so the (B, S, V) logits tensor never materializes
        x = shd.act(x[:, -1:], "dp", None, None)
    logits = lm_logits(cfg, params, x)
    return logits, new_caches, aux


def ring_ce_sum(cfg, params, x, labels, weights=None):
    """Vocab-ring fused cross-entropy (beyond-paper §Perf optimization).

    x: (B, S, D) final hidden, sequence-sharded over "model"; the head
    weight stays VOCAB-SHARDED and its blocks circulate the ring
    (collective-permute) while each shard streams its sequence chunk through
    running (max, sum-exp, label-logit) accumulators — neither the gathered
    (V, D) table nor any (B, S, V) logits tensor ever materializes.

    Returns sum of weighted token CE (replicated scalar).
    """
    lay = shd.layout()
    tied = cfg.tie_embeddings
    w = params["embed"]["table"] if tied else params["lm_head"]["w"]
    if lay.mesh is None or lay.mode != "train_sp" or lay.model_axis is None:
        logits = lm_logits(cfg, params, x)
        return _ce_sum_dense(logits, labels, weights)
    m_ax = lay.model_axis
    tp = lay.n_shards
    dp = lay.dp if lay.dp else None
    V = cfg.vocab_size
    v_loc = V // tp
    perm = [(s, (s - 1) % tp) for s in range(tp)]

    def body(x_l, w_l, lab_l, wt_l):
        idx = jax.lax.axis_index(m_ax)
        B_l, S_l, D = x_l.shape
        xf = x_l.reshape(-1, D)
        labf = lab_l.reshape(-1)
        T = xf.shape[0]
        m_run = jnp.full((T,), -1e30, jnp.float32)
        s_run = jnp.zeros((T,), jnp.float32)
        ll = jnp.zeros((T,), jnp.float32)
        blk = w_l
        for r in range(tp):
            off = ((idx + r) % tp) * v_loc
            wb = blk if tied else blk.T           # (v_loc, D)
            logits = jax.lax.dot_general(
                xf, wb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
            s_run = (s_run * jnp.exp(m_run - m_new)
                     + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1))
            m_run = m_new
            rel = labf - off
            inr = (rel >= 0) & (rel < v_loc)
            pick = jnp.take_along_axis(
                logits, jnp.clip(rel, 0, v_loc - 1)[:, None], axis=1)[:, 0]
            ll = jnp.where(inr, pick, ll)
            if r < tp - 1:
                blk = jax.lax.ppermute(blk, m_ax, perm)
        ce = (m_run + jnp.log(jnp.maximum(s_run, 1e-30))) - ll
        if wt_l is not None:
            wt = jnp.broadcast_to(wt_l.astype(jnp.float32)[:, None],
                                  (B_l, S_l)).reshape(-1)
            ce = ce * wt
        axes = tuple(lay.dp) + (m_ax,) if lay.dp else (m_ax,)
        return jax.lax.psum(jnp.sum(ce), axes)

    w_spec = P(m_ax) if tied else P(None, m_ax)
    if weights is None:
        fn = lambda a, b, c: body(a, b, c, None)
        return jax.shard_map(fn, mesh=lay.mesh,
                             in_specs=(P(dp, m_ax), w_spec, P(dp, m_ax)),
                             out_specs=P())(x, w, labels)
    return jax.shard_map(body, mesh=lay.mesh,
                         in_specs=(P(dp, m_ax), w_spec, P(dp, m_ax), P(dp)),
                         out_specs=P())(x, w, labels, weights)


def chunked_ce_sum(cfg, params, x, labels, weights, vchunk: int):
    """Vocab-chunked fused CE for the local / train_fsdp layouts.

    Streams the head in (D, vchunk) slices with running (max, sum-exp,
    label-logit) accumulators — the (T, V) logits tensor never materializes,
    forward or backward (peak extra memory = one (T, vchunk) fp32 tile +
    the gathered head).
    """
    tied = cfg.tie_embeddings
    w = params["embed"]["table"] if tied else params["lm_head"]["w"]
    w = shd.use_weight(w)
    B, S, D = x.shape
    V = cfg.vocab_size
    vchunk = min(vchunk, V)
    nch = -(-V // vchunk)
    xf = x.reshape(-1, D)
    labf = labels.reshape(-1)
    T = xf.shape[0]

    def body(carry, i):
        m_run, s_run, ll = carry
        start = i * vchunk
        # the last chunk is read from V - vchunk when vchunk does not
        # divide V; its columns below ``start`` were already counted
        off = jnp.minimum(start, V - vchunk)
        if tied:
            w_c = jax.lax.dynamic_slice(w, (off, 0), (vchunk, D))
            logits = jax.lax.dot_general(xf, w_c, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        else:
            w_c = jax.lax.dynamic_slice(w, (0, off), (D, vchunk))
            logits = jax.lax.dot_general(xf, w_c, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        col = off + jnp.arange(vchunk)
        logits = jnp.where(col[None, :] >= start, logits, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
        s_run = (s_run * jnp.exp(m_run - m_new)
                 + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1))
        rel = labf - off
        inr = (labf >= start) & (rel < vchunk)
        pick = jnp.take_along_axis(
            logits, jnp.clip(rel, 0, vchunk - 1)[:, None], axis=1)[:, 0]
        ll = jnp.where(inr, pick, ll)
        return (m_new, s_run, ll), None

    init = (jnp.full((T,), -1e30, jnp.float32), jnp.zeros((T,), jnp.float32),
            jnp.zeros((T,), jnp.float32))
    # remat per chunk: the backward recomputes each (T, vchunk) tile
    # instead of saving all of them, which would be the full logits
    (m_run, s_run, ll), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), init, jnp.arange(nch))
    ce = (m_run + jnp.log(jnp.maximum(s_run, 1e-30))) - ll
    if weights is not None:
        wt = jnp.broadcast_to(weights.astype(jnp.float32)[:, None],
                              (B, S)).reshape(-1)
        ce = ce * wt
    return jnp.sum(ce)


def _ce_sum_dense(logits, labels, weights=None):
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = lse - ll
    if weights is not None:
        ce = ce * jnp.broadcast_to(
            weights.astype(jnp.float32)[:, None], ce.shape)
    return jnp.sum(ce)


def cross_entropy(logits, labels, weights=None):
    """Mean CE with optional per-example/token weights (the cutoff mask).

    Implements the paper's Alg.1 line 29 normalization: sum(w * ce) / sum(w)
    — i.e. the update averages over *included* workers only.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = lse - ll
    if weights is None:
        return jnp.mean(ce)
    w = jnp.broadcast_to(weights.astype(jnp.float32).reshape(
        weights.shape + (1,) * (ce.ndim - weights.ndim)), ce.shape)
    return jnp.sum(w * ce) / jnp.maximum(jnp.sum(w), 1e-6)


def train_loss(cfg, params, batch, aux_coef: float = 0.01):
    logits, _, aux = forward(cfg, params, batch, mode="train")
    loss = cross_entropy(logits, batch["labels"], batch.get("weights"))
    return loss + aux_coef * aux, {"ce": loss, "aux": aux}


def prefill(cfg, params, batch):
    logits, caches, _ = forward(cfg, params, batch, mode="prefill",
                                remat=False)
    return logits[:, -1], caches


def decode_step(cfg, params, tokens, pos, caches, positions=None):
    """tokens: (B,1); pos: scalar int32 cache length so far."""
    B = tokens.shape[0]
    if positions is None:
        positions = jnp.full((B, 1), pos, jnp.int32)
    batch = {"tokens": tokens, "positions": positions}
    logits, caches, _ = forward(cfg, params, batch, mode="decode",
                                caches=caches, pos=pos, remat=False)
    return logits, caches


def pad_caches(caches, target_len: int):
    """Grow attention KV caches (leaves named k/v) to ``target_len`` slots."""

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in ("k", "v") and hasattr(v, "ndim"):
                    ax = v.ndim - 3
                    pad = [(0, 0)] * v.ndim
                    pad[ax] = (0, target_len - v.shape[ax])
                    out[k] = jnp.pad(v, pad)
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, (list, tuple)):
            t = [walk(v) for v in node]
            if hasattr(node, "_fields"):   # NamedTuple (e.g. ScanState)
                return type(node)(*t)
            return tuple(t) if isinstance(node, tuple) else t
        return node

    return walk(caches)


# ---------------------------------------------------------------------------
# Cache structs + shardings (for AOT decode lowering).
# ---------------------------------------------------------------------------


def cache_structs(cfg, batch: int, cache_len: int, dtype=None):
    dtype = dtype or _dtype(cfg)
    segs = build_segments(layer_specs(cfg))
    out = []
    for seg in segs:
        per_pos = [cache_struct(cfg, spec, batch, cache_len, dtype)
                   for spec in seg.pattern]
        if seg.repeats > 1:
            per_pos = [jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((seg.repeats,) + s.shape,
                                               s.dtype), c) for c in per_pos]
        out.append(per_pos)
    return out


def cache_pspec(path_leaf_name: str, shape, lay, stacked: bool):
    """PartitionSpec for a cache leaf in decode_tp layout."""
    if lay.mesh is None or lay.model_axis is None:
        return P()
    m = lay.model_axis
    off = 1 if stacked else 0
    tp = lay.mesh.shape[m]
    dims: list = [None] * len(shape)
    dp_dim = off  # batch dim
    if lay.dp and shape[dp_dim] % max(lay.dp_size, 1) == 0:
        dims[dp_dim] = lay.dp
    name = path_leaf_name

    def try_put(i):
        if shape[i] % tp == 0:
            dims[i] = m

    if name in ("k", "v", "ck", "cv"):
        try_put(off + 1)          # sequence dim
    elif name == "C":
        try_put(off + 2)          # dq dim
    elif name == "n":
        try_put(off + 2)
    elif name == "conv":
        try_put(off + 2)          # channel dim
    return P(*dims)
