"""Pytree optimizers built from scratch (no optax in this environment).

Minimal composable design: an ``Optimizer`` is (init, update); ``update``
maps (grads, state, params) -> (updates, state) where updates are *added* to
params (learning rate already folded in, sign flipped).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

Schedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (grads, state, params) -> (updates, new_state)


def _lr_at(lr: Schedule, step):
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def apply_updates(params, updates):
    return jax.tree.map(
        lambda p, u: (p + u.astype(p.dtype)), params, updates)


# ---------------------------------------------------------------------------


def sgd(lr: Schedule) -> Optimizer:
    def init(params):
        return {"step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params=None):
        step = state["step"]
        lr_t = _lr_at(lr, step)
        ups = jax.tree.map(lambda g: -lr_t * g.astype(jnp.float32), grads)
        return ups, {"step": step + 1}

    return Optimizer(init, update)


def momentum(lr: Schedule, beta: float = 0.9, nesterov: bool = False
             ) -> Optimizer:
    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "mu": jax.tree.map(lambda p: jnp.zeros_like(
                    p, dtype=jnp.float32), params)}

    def update(grads, state, params=None):
        step = state["step"]
        lr_t = _lr_at(lr, step)
        mu = jax.tree.map(lambda m, g: beta * m + g.astype(jnp.float32),
                          state["mu"], grads)
        if nesterov:
            ups = jax.tree.map(
                lambda m, g: -lr_t * (beta * m + g.astype(jnp.float32)),
                mu, grads)
        else:
            ups = jax.tree.map(lambda m: -lr_t * m, mu)
        return ups, {"step": step + 1, "mu": mu}

    return Optimizer(init, update)


def adam(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, weight_decay: float = 0.0, *,
         fused: bool = False) -> Optimizer:
    """Adam(W).  ``fused=True`` routes the whole moment-and-param update
    through ``kernels.ops.adam_update_tree`` — the Pallas one-HBM-pass
    kernel on TPU, with the pure-jnp reference under the ``"xla"`` kernel
    backend (the default off the TPU).  Matches the unfused path allclose
    (the fused kernel computes p' directly, so the returned "update" is
    p' - p up to one rounding)."""
    def init(params):
        z = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return {"step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(z, params),
                "v": jax.tree.map(z, params)}

    def fused_update(grads, state, params):
        from repro.kernels import ops
        if params is None:
            raise ValueError("adam(fused=True) needs params at update time")
        lr_t = _lr_at(lr, state["step"])
        p_new, m, v = ops.adam_update_tree(
            params, grads, state["m"], state["v"], state["step"], lr_t,
            b1=b1, b2=b2, eps=eps, wd=weight_decay)
        ups = jax.tree.map(
            lambda pn, p: pn.astype(jnp.float32) - p.astype(jnp.float32),
            p_new, params)
        return ups, {"step": state["step"] + 1, "m": m, "v": v}

    def update(grads, state, params=None):
        if fused:
            return fused_update(grads, state, params)
        step = state["step"] + 1
        lr_t = _lr_at(lr, state["step"])
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1)
                         * g.astype(jnp.float32), state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2)
                         * jnp.square(g.astype(jnp.float32)),
                         state["v"], grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(m_, v_, p):
            u = -(lr_t * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps))
            if weight_decay:
                u = u - lr_t * weight_decay * p.astype(jnp.float32)
            return u

        if weight_decay:
            ups = jax.tree.map(upd, m, v, params)
        else:
            ups = jax.tree.map(lambda m_, v_: upd(m_, v_, None), m, v)
        return ups, {"step": step, "m": m, "v": v}

    return Optimizer(init, update)


def adamw(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.01, *,
          fused: bool = False) -> Optimizer:
    return adam(lr, b1, b2, eps, weight_decay, fused=fused)


def clip_by_global_norm(opt: Optimizer, max_norm: float) -> Optimizer:
    def update(grads, state, params=None):
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
        return opt.update(grads, state, params)

    return Optimizer(opt.init, update)


def chain(*fns):
    """Compose gradient-mapping callables before an optimizer's update."""
    *pre, opt = fns

    def update(grads, state, params=None):
        for f in pre:
            grads = f(grads)
        return opt.update(grads, state, params)

    return Optimizer(opt.init, update)
