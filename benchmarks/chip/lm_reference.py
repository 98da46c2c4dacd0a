"""Plain float32 reference of a dense decoder LM's training steps.

Written from the published descriptions (Qwen2, arXiv:2407.10671;
StarCoder2, arXiv:2402.19173), not from the program's ``models/``:

  x = embed[tokens]
  per layer:  x += Wo . attn(RoPE(norm1(x) Wq + bq), RoPE(... Wk + bk), ... Wv + bv) [+ bo]
              x += mlp(norm2(x))     SwiGLU: Wd(silu(x Wg) * x Wu)
                                     GELU:   Wd gelu_tanh(x Wu + bu) + bd
  logits = norm(x) . head            head = embed^T when tied
  attention: causal, grouped (head h reads kv head h // (H / KV)), scaled by
  1/sqrt(hd), keys older than ``sliding_window`` masked where one is set;
  RoPE rotates the two halves of each head (theta from the configuration).

Loss of a step: the token cross-entropy summed over the examples whose
weight is 1, over (number of such examples x sequence length): the masked
mean of the paper's Alg. 1.  AdamW as published (bias-corrected moments,
decoupled weight decay), moments in float32.  Parameters are stored in the
type the configuration states between steps (bfloat16: each step's new
value is rounded once); everything else is float32 at the highest matmul
precision.

Blocks keep it on one chip at the timed sizes: one example at a time, the
layers under a checkpointed scan, attention in query chunks and the loss in
token chunks, each recomputed in the backward pass.

``low="float8_e4m3fn"`` is the control: every matmul operand is scaled to
e4m3's range per tensor and cast to float8 (float32 accumulation).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import lm_weights

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 512            # query rows of one attention block, tokens of one
                       # loss block
E4M3_MAX = 448.0


def _q8(x, low):
    s = jnp.max(jnp.abs(x))
    s = jnp.where(s > 0, s / E4M3_MAX, 1.0)
    return (x / s).astype(low), s


def _ein(spec, a, b, low):
    if low is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    qa, sa = _q8(a, low)
    qb, sb = _q8(b, low)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32) * (sa * sb)


def _norm(c, p, x):
    if c["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + c["norm_eps"]) * p["scale"] \
            + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + c["norm_eps"]) * p["scale"]


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half RoPE."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(c, q, k, v, low):
    """q (S, H, hd), k/v (S, KV, hd) -> (S, H, hd), causal, in query
    chunks."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    window = c.get("sliding_window") or 0
    qc = min(CHUNK, S)
    kpos = jnp.arange(S)

    def block(args):
        qi, q0 = args
        s = _ein("qhd,khd->hqk", qi, k, low) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qc)
        keep = kpos[None, :] <= qpos[:, None]
        if window:
            keep &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(keep[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return _ein("hqk,khd->qhd", a, v, low)

    qs = q.reshape(S // qc, qc, H, hd)
    out = jax.lax.map(jax.checkpoint(block), (qs, jnp.arange(0, S, qc)))
    return out.reshape(S, H, hd)


def _layer(c, x, lp, pos, low):
    d = lm_weights.dims(c)
    S = x.shape[0]
    a = lp["attn"]
    h = _norm(c, lp["norm1"], x)
    q, k, v = (_ein("sd,de->se", h, a[w], low) for w in ("wq", "wk", "wv"))
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, d["H"], d["hd"]), pos, c["rope_theta"])
    k = _rope(k.reshape(S, d["KV"], d["hd"]), pos, c["rope_theta"])
    v = v.reshape(S, d["KV"], d["hd"])
    o = _attention(c, q, k, v, low).reshape(S, d["H"] * d["hd"])
    y = _ein("se,ed->sd", o, a["wo"], low)
    if "bo" in a:
        y = y + a["bo"]
    x = x + y
    m = lp["mlp"]
    h = _norm(c, lp["norm2"], x)
    up = _ein("sd,df->sf", h, m["w_up"], low)
    if lm_weights.gated(c):
        h = jax.nn.silu(_ein("sd,df->sf", h, m["w_gate"], low)) * up
    else:
        if "b_up" in m:
            up = up + m["b_up"]
        h = jax.nn.gelu(up, approximate=True)
    y = _ein("sf,fd->sd", h, m["w_down"], low)
    if "b_down" in m:
        y = y + m["b_down"]
    return x + y


def ce_sum(c, params, tokens, labels, low=None):
    """Summed token cross-entropy of one example (tokens, labels: (S,))."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"]["table"][tokens]

    def body(x, lp):
        return jax.checkpoint(partial(_layer, c, pos=pos, low=low))(x, lp), \
            None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _norm(c, params["final_norm"], x)
    head = (params["embed"]["table"].T if c["tie_word_embeddings"]
            else params["lm_head"]["w"])
    tc = min(CHUNK, S)

    def loss_block(args):
        xb, lb = args
        logits = _ein("td,dv->tv", xb, head, low)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lb[:, None],
                                                 axis=1)[:, 0])

    parts = jax.lax.map(jax.checkpoint(loss_block),
                        (x.reshape(S // tc, tc, -1), labels.reshape(-1, tc)))
    return jnp.sum(parts)


def leaf_norms(tree) -> dict:
    """{dotted path: float32 norm} of a nested dict of arrays."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in lm_weights.flat(tree).items()}


def train(c: dict, seed: int, batches, example_weights, opt: dict,
          low=None) -> dict:
    """Replay len(batches) training steps from the seed's weights.

    batches: [{"tokens", "labels"}] (B, S) numpy; example_weights: [(B,)].
    Returns the loss of every step, the norm of every leaf's first
    gradient, and the norm of every leaf's change after the last step."""
    pdt = jnp.dtype(c["param_dtype"])
    key = lm_weights.seed_key(seed)
    p = jax.jit(lambda k: jax.tree.map(
        lambda w: w.astype(jnp.float32), lm_weights.make(c, k, pdt)))(key)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    # parameters, the gradient sum, one example's gradient and Adam's two
    # moments are five float32 copies of the model: where they would take
    # most of the chip, the moments wait on the host while gradients are
    # summed
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    nbytes = sum(x.nbytes for x in jax.tree.leaves(p))
    offload = limit is not None and 5 * nbytes > 0.7 * limit
    low_dt = None if low is None else jnp.dtype(low)
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, lab: ce_sum(c, p, t, lab, low_dt)))
    acc = jax.jit(lambda tot, g, w: jax.tree.map(lambda a, b: a + w * b,
                                                 tot, g), donate_argnums=0)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]

    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, t):
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p_, m_, v_: (p_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                      + eps) + wd * p_)
                                ).astype(pdt).astype(jnp.float32), p, m, v)
        return p, m, v

    if offload:
        m, v = jax.device_get((m, v))
    losses, g1 = [], None
    for t, (batch, w) in enumerate(zip(batches, example_weights), 1):
        w = np.asarray(w, np.float32)
        S = batch["tokens"].shape[1]
        tot = jax.tree.map(jnp.zeros_like, p)
        ce = 0.0
        for b in np.flatnonzero(w):
            ce_b, g_b = vg(p, batch["tokens"][b], batch["labels"][b])
            tot = acc(tot, g_b, jnp.float32(w[b]))
            ce += float(w[b]) * float(ce_b)
        norm = float(w.sum()) * S
        g = jax.tree.map(lambda a: a / norm, tot)
        losses.append(ce / norm)
        if g1 is None:
            g1 = {k: float(x) for k, x in leaf_norms(g).items()}
        if offload:
            m, v = jax.device_put((m, v))
        p, m, v = adam(p, g, m, v, jnp.float32(t))
        if offload:
            m, v = jax.device_get((m, v))
    p0 = jax.jit(lambda k: lm_weights.make(c, k, pdt))(key)
    change = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), p, p0)))(p, p0)
    return {"losses": losses, "grad_norms": g1,
            "change_norms": {k: float(x) for k, x in change.items()}}
