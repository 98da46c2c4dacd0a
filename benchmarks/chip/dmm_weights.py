"""Weights of the paper's runtime model (DMM + guide), made by the
benchmark from the seed, in the nested layout the program's
``RuntimeModel.params`` has (and the reference reads):

  dmm:   trans_lin [z->z], trans_h [z->h->z], trans_g [z->h->z],
         trans_std [z->z], emit_mu [z->h->n], emit_std [n->n], z0_mu, z0_logstd
  guide: rnn_left / rnn_right {wx (n,h), wh (h,h), b (h)},
         z_proj [z->h], mu [h->z], std [z->z]

A fitted model is a function of a recorded trace; here the weights are
random (dense N(0, 1/d_in)) except that the emission's output biases are
set from the seed window, so that the predictive mean of worker i is near
its recent mean and its spread near its recent spread: the decisions then
land inside the range a fitted model gives, and the work per decision is
the same as for a fitted model.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _dense(key, d_in, d_out, gain=1.0):
    return {"w": gain * jax.random.normal(key, (d_in, d_out)) / d_in ** 0.5,
            "b": jnp.zeros((d_out,))}


@partial(jax.jit, static_argnums=(1, 2, 3))
def make(key, n: int, z: int, h: int, window):
    """(params, norm_scale) for a width-``n`` model seeded on ``window``
    ((lag+1, n) raw step times), in one jitted call."""
    window = jnp.asarray(window, jnp.float32)
    scale = 2.0 * window.mean()
    mean = window.mean(0) / scale
    spread = jnp.maximum(window.std(0) / scale, 0.02)
    ks = iter(jax.random.split(key, 16))
    emit_mu = [_dense(next(ks), z, h), _dense(next(ks), h, n, gain=0.1)]
    emit_mu[1]["b"] = mean
    emit_std = [_dense(next(ks), n, n, gain=0.1)]
    # softplus^-1(spread)
    emit_std[0]["b"] = jnp.log(jnp.expm1(spread))
    dmm = {"trans_lin": [_dense(next(ks), z, z)],
           "trans_h": [_dense(next(ks), z, h), _dense(next(ks), h, z)],
           "trans_g": [_dense(next(ks), z, h), _dense(next(ks), h, z)],
           "trans_std": [_dense(next(ks), z, z, gain=0.1)],
           "emit_mu": emit_mu, "emit_std": emit_std,
           "z0_mu": jnp.zeros((z,)), "z0_logstd": jnp.zeros((z,))}

    def rnn(k):
        k1, k2 = jax.random.split(k)
        return {"wx": jax.random.normal(k1, (n, h)) / n ** 0.5,
                "wh": 0.5 * jax.random.normal(k2, (h, h)) / h ** 0.5,
                "b": jnp.zeros((h,))}

    guide = {"rnn_left": rnn(next(ks)), "rnn_right": rnn(next(ks)),
             "z_proj": [_dense(next(ks), z, h)],
             "mu": [_dense(next(ks), h, z)],
             "std": [_dense(next(ks), z, z, gain=0.1)]}
    return {"dmm": dmm, "guide": guide}, scale
