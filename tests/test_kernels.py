"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU) —
fixed cases + hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.causal_attention import causal_attention
from repro.kernels.fused_adam import fused_adam
from repro.kernels.masked_grad_agg import masked_grad_agg
from repro.kernels.mlstm_chunk import mlstm_chunk
from repro.kernels import ops

SETTINGS = dict(max_examples=8, deadline=None)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_basic(heads):
    H, KV = heads
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 256, H, 64))
    k = jax.random.normal(ks[1], (2, 256, KV, 64))
    v = jax.random.normal(ks[2], (2, 256, KV, 64))
    out = causal_attention(q, k, v, interpret=True)
    want = ref.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@settings(**SETTINGS)
@given(
    b=st.sampled_from([1, 2]),
    s=st.sampled_from([128, 256, 384]),
    heads=st.sampled_from([(4, 4), (4, 2), (8, 1)]),
    hd=st.sampled_from([32, 64, 128]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_sweep(b, s, heads, hd, dtype):
    H, KV = heads
    key = jax.random.PRNGKey(hash((b, s, H, KV, hd)) % 2**31)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, KV, hd)).astype(dtype)
    out = causal_attention(q, k, v, interpret=True)
    want = ref.reference_attention(q, k, v, causal=True)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=atol, rtol=0.05)


@pytest.mark.parametrize("S", [1536, 2048])
def test_flash_matches_model_attention_core(S):
    """The kernel contract equals the model stack's attn_core path over
    several blocks, those above the diagonal skipped: three of 512 at
    1536, two of 1024 computed 512 keys at a time at 2048."""
    from repro.models.attention import attn_core
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, S, 4, 64))
    k = jax.random.normal(ks[1], (1, S, 2, 64))
    v = jax.random.normal(ks[2], (1, S, 2, 64))
    qpos = jnp.arange(S)[None]
    core = attn_core(q, k, v, qpos, jnp.arange(S), causal=True, window=0)
    kern = causal_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(core, kern, atol=3e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# mlstm chunk
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(
    s=st.sampled_from([128, 256]),
    chunk=st.sampled_from([32, 64, 128]),
    hd=st.sampled_from([16, 32, 64]),
    h=st.sampled_from([1, 2]),
)
def test_mlstm_chunk_sweep(s, chunk, hd, h):
    key = jax.random.PRNGKey(hash((s, chunk, hd, h)) % 2**31)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (2, s, h, hd)) * 0.5
    k = jax.random.normal(ks[1], (2, s, h, hd)) * 0.5
    v = jax.random.normal(ks[2], (2, s, h, hd))
    g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (2, s, h)) + 3.0)
    i = jax.random.normal(ks[4], (2, s, h)) * 0.5
    out = mlstm_chunk(q, k, v, g, i, chunk=chunk, interpret=True)
    want = ref.reference_mlstm(q, k, v, g, i)
    np.testing.assert_allclose(out, want, atol=5e-4, rtol=5e-4)


def test_mlstm_kernel_matches_model_recurrence():
    from repro.models import ssm as S
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (1, 128, 2, 32)) * 0.5
    k = jax.random.normal(ks[1], (1, 128, 2, 32)) * 0.5
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (1, 128, 2)) + 3.0)
    i = jax.random.normal(ks[4], (1, 128, 2)) * 0.5
    kern = mlstm_chunk(q, k, v, g, i, chunk=64, interpret=True)
    model, _ = S.linear_recurrence(q, k, v, g, i, chunk=64, normalize=True)
    np.testing.assert_allclose(kern, model, atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------------------
# fused adam
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(
    shape=st.sampled_from([(8, 128), (16, 256), (8, 1024)]),
    wd=st.sampled_from([0.0, 0.01]),
    step=st.sampled_from([1, 100]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_fused_adam_sweep(shape, wd, step, dtype):
    key = jax.random.PRNGKey(hash((shape, wd, step)) % 2**31)
    ks = jax.random.split(key, 4)
    p = jax.random.normal(ks[0], shape).astype(dtype)
    g = jax.random.normal(ks[1], shape).astype(dtype)
    m = jax.random.normal(ks[2], shape) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], shape)) * 0.01
    sc = jnp.array([1e-3, 1 - 0.9 ** step, 1 - 0.999 ** step], jnp.float32)
    po, mo, vo = fused_adam(p, g, m, v, sc, wd=wd, interpret=True)
    pw, mw, vw = ref.reference_adam(p, g, m, v, sc, wd=wd)
    np.testing.assert_allclose(mo, mw, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(vo, vw, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(po.astype(np.float32), pw.astype(np.float32),
                               atol=2e-3 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("wd", [0.0, 0.01])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_optim_adam_fused_matches_unfused(wd, backend, monkeypatch):
    """optim.adam(fused=True) — the kernel-backed optimizer — tracks the
    unfused reference over several steps, through both the pure-jnp
    fallback and the Pallas interpret path (pad plumbing included)."""
    from repro import optim
    monkeypatch.setattr(ops, "KERNEL_BACKEND", backend)
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    params = {"w": jax.random.normal(ks[0], (37, 5)),
              "b": jax.random.normal(ks[1], (13,)),
              "s": jax.random.normal(ks[2], (1,))}
    ref_opt = optim.adam(3e-3, weight_decay=wd)
    fus_opt = optim.adam(3e-3, weight_decay=wd, fused=True)
    p_ref, p_fus = params, params
    s_ref, s_fus = ref_opt.init(params), fus_opt.init(params)
    for i in range(3):
        grads = jax.tree.map(
            lambda p: 0.1 * jax.random.normal(jax.random.PRNGKey(i),
                                              p.shape), p_ref)
        u_ref, s_ref = ref_opt.update(grads, s_ref, p_ref)
        p_ref = optim.apply_updates(p_ref, u_ref)
        u_fus, s_fus = fus_opt.update(grads, s_fus, p_fus)
        p_fus = optim.apply_updates(p_fus, u_fus)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fus)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s_ref["m"]), jax.tree.leaves(s_fus["m"])):
        np.testing.assert_allclose(a, b.reshape(a.shape), atol=1e-6)
    for a, b in zip(jax.tree.leaves(s_ref["v"]), jax.tree.leaves(s_fus["v"])):
        np.testing.assert_allclose(a, b.reshape(a.shape), atol=1e-7)
    assert int(s_fus["step"]) == 3


def test_optim_adam_fused_jits_with_donation():
    """The fused optimizer composes with the donation-clean train-step jit
    pattern (state donated, params updated in place)."""
    from repro import optim
    import functools
    opt = optim.adam(1e-3, fused=True)
    params = {"w": jnp.ones((8, 16))}
    state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, grads):
        ups, state = opt.update(grads, state, params)
        return optim.apply_updates(params, ups), state

    grads = {"w": jnp.full((8, 16), 0.5)}
    p1, s1 = step(params, state, grads)
    assert int(s1["step"]) == 1   # read before s1 is donated away
    p2, _ = step(p1, s1, grads)
    assert np.all(np.isfinite(np.asarray(p2["w"])))


def test_adam_tree_wrapper_matches_optim():
    """ops.adam_update_tree (xla path) == repro.optim.adam update."""
    from repro import optim
    key = jax.random.PRNGKey(7)
    params = {"a": jax.random.normal(key, (37,)),
              "b": jax.random.normal(key, (5, 13))}
    grads = jax.tree.map(lambda x: x * 0.1, params)
    opt = optim.adam(1e-3)
    state = opt.init(params)
    ups, _ = opt.update(grads, state, params)
    want = optim.apply_updates(params, ups)
    m = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    v = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    got, _, _ = ops.adam_update_tree(params, grads, m, v,
                                     jnp.int32(0), 1e-3)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b.reshape(a.shape), atol=1e-6)


# ---------------------------------------------------------------------------
# masked aggregation
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(
    w=st.sampled_from([4, 8, 16]),
    n=st.sampled_from([128, 384, 1024]),
    frac=st.floats(0.1, 1.0),
)
def test_masked_agg_sweep(w, n, frac):
    key = jax.random.PRNGKey(hash((w, n, int(frac * 100))) % 2**31)
    g = jax.random.normal(key, (w, n))
    rng = np.random.default_rng(0)
    mask = (rng.uniform(size=w) < frac).astype(np.float32)
    if mask.sum() == 0:
        mask[0] = 1.0
    m = jnp.asarray(mask).reshape(w, 1)
    out = masked_grad_agg(g, m, interpret=True)
    want = ref.reference_masked_agg(g, m)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


def test_masked_agg_is_paper_update():
    """sum(bit*g)/c == the paper's Alg.1 line 29 for included workers."""
    g = jnp.arange(12.0).reshape(4, 3)
    mask = jnp.array([1.0, 0.0, 1.0, 0.0]).reshape(4, 1)
    out = ops.masked_aggregate(g, mask[:, 0])
    want = (g[0] + g[2]) / 2
    np.testing.assert_allclose(out, want)


@pytest.mark.parametrize("w", [2, 8, 158])
def test_masked_agg_kernel_worker_counts(w):
    """Interpret mode == jnp reference from 2 workers up to the paper's
    158-worker cluster."""
    key = jax.random.PRNGKey(w)
    g = jax.random.normal(key, (w, 256))
    mask = (jnp.arange(w) % 3 != 0).astype(jnp.float32).reshape(w, 1)
    out = masked_grad_agg(g, mask, interpret=True)
    want = ref.reference_masked_agg(g, mask)
    np.testing.assert_allclose(out, want, atol=1e-6, rtol=1e-6)


def test_masked_agg_kernel_all_zero_mask_clamps_c():
    """c = max(sum(bit), 1): an all-dropped step yields exact zeros, not
    NaNs."""
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    out = masked_grad_agg(g, jnp.zeros((8, 1)), interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_masked_agg_kernel_bf16():
    g = jax.random.normal(jax.random.PRNGKey(1), (8, 384)).astype(
        jnp.bfloat16)
    mask = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32).reshape(8, 1)
    out = masked_grad_agg(g, mask, interpret=True)
    want = ref.reference_masked_agg(g, mask)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("n", [1, 100, 333, 1000])
def test_masked_agg_ops_padding_path(n, monkeypatch):
    """Non-multiple-of-128 N goes through the ops.py pad plumbing — both
    the single-block (pad to 128) and tiled (pad to block) regimes."""
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    key = jax.random.PRNGKey(n)
    g = jax.random.normal(key, (4, n))
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    out = ops.masked_aggregate(g, mask, block=256)
    want = ref.reference_masked_agg(g, mask.reshape(4, 1))[0]
    np.testing.assert_allclose(out, want, atol=1e-6, rtol=1e-6)


def test_masked_aggregate_tree_kernel_matches_local(monkeypatch):
    """The fused flatten+concat tree combine (interpret kernel) == the
    pure-jnp LOCAL reference on a ragged pytree of leaf shapes."""
    from repro.core import aggregation
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    grads = {"w": jax.random.normal(ks[0], (4, 3, 5)),
             "b": jax.random.normal(ks[1], (4, 7)),
             "scale": jax.random.normal(ks[2], (4, 1)),
             "emb": jax.random.normal(ks[3], (4, 11, 13))}
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    want = aggregation.masked_mean_local(grads, mask)
    monkeypatch.setattr(ops, "KERNEL_BACKEND", "interpret")
    got = ops.masked_aggregate_tree(grads, mask)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
