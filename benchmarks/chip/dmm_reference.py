"""Plain float64 reference of the paper's cutoff decision (arXiv:1803.04209
§3-§4) over a lag window of worker step times, and of the window's update.

Decision d of a job, from its window W ((lag+1, n) raw seconds, oldest
row first) and its scale s:

  x = W / s;  left and right ReLU RNN sweeps over x, shifted one step, give
  h_sum[t] = h_left[t] + h_right[t];  z_0 = 0 (K samples);
  z_t = mu_t + (softplus(mu_t Ws + bs) + 1e-3) eps_t,
        mu_t = ((tanh(z_{t-1} Wz + bz) + h_sum[t]) / 3) Wm + bm;
  transition of z_T (gated: (1-g) Lin(z) + g h(z), std softplus + 1e-3),
  one draw z';  emission mu(z') (two linear layers), std softplus + 1e-3;
  samples = (mu + std e) s.  Sort each of the K sample rows;
  omega(c) = mean_k c / x_(c);  c* = argmax over c >= ceil(min_frac n).
  The predicted iteration time is mean_k x_(c*); the predictive moments
  are mean_k mu s and sqrt(mean_k std^2 + var_k mu) s.

After the step, the observed times of the finished workers enter the
window; a censored worker's time is drawn from its predictive normal
truncated below the cutoff time (inverse CDF, with the clips 1e-6 on the
CDF and 1e-7 on the uniform), and the oldest row leaves.

Its random numbers follow the decision's published RNG layout: decision d
of a job with seed s_j draws from PRNGKey(s_j + d), split four ways (the
guide's per-step normals, the transition draw, the emission draws column
by column); the imputation after step d draws column-wise uniforms from
fold_in(PRNGKey(s_j + 1_000_003), d).  Those draws are made with
``jax.random``; everything else is numpy in float64.

``low="float8_e4m3fn", store="bfloat16"`` is the control, one step below
what the configuration states (float32 storage, float32 matmuls at the
default precision, which is one bfloat16 pass on the TPU): every matmul
operand is scaled to e4m3's range per matrix and rounded to float8 (the
products summed in float64), and every entry of the window is rounded to
bfloat16 as it is stored; every other operation stays in float64.
``low="bfloat16"`` alone rounds the operands to bfloat16: the TPU's
default precision for a float32 matmul.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from scipy.special import ndtr, ndtri

IMPUTE_OFFSET = 1_000_003
OMEGA_FLOOR = 1e-9
SIGMA_FLOOR = 1e-9
CDF_CLIP = 1e-6
U_CLIP_LO = 1e-7


E4M3_MAX = 448.0


def f64(x):
    return np.asarray(x, np.float64)


def _round(x, low):
    """x rounded to ``low``; a float8 type after scaling each matrix (the
    last two axes) to the format's range, and scaled back."""
    if low == "bfloat16":
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    s = np.max(np.abs(x), axis=(-2, -1), keepdims=True) / E4M3_MAX
    s = np.where(s > 0, s, 1.0)
    return (x / s).astype(getattr(ml_dtypes, low)).astype(np.float64) * s


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def noise(seeds, steps, T, K, zd, n):
    """The draws of decision ``steps[i]`` of the job with seed ``seeds[i]``
    and of the imputation after it: guide (M, T, K, zd), transition
    (M, K, zd), emission (M, K, n), imputation uniforms (M, n)."""
    cols = jnp.arange(n)

    def one(s, d):
        k1, k2, k3, _ = jax.random.split(jax.random.PRNGKey(s + d), 4)
        eg = jax.vmap(lambda k: jax.random.normal(k, (K, zd)))(
            jax.random.split(k1, T))
        ez = jax.random.normal(k2, (K, zd))
        ex = jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(k3, i),
                                                  (K,)), out_axes=1)(cols)
        ki = jax.random.fold_in(jax.random.PRNGKey(s + IMPUTE_OFFSET), d)
        u = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(ki, i), ()))(cols)
        return eg, ez, ex, u

    return jax.vmap(one)(seeds, steps)


def _softplus(x):
    return np.logaddexp(0.0, x)


def _relu(x):
    return np.maximum(x, 0.0)


class Replay:
    """The decisions of a set of jobs, step by step, batched over jobs.

    params: per job, the runtime model's nested weights (host arrays);
    windows: (J, lag+1, n) seed windows; scales: (J,); seeds: (J,) ints;
    low: None, or the type the matmul operands are rounded to; store:
    None, or the type each window entry is rounded to."""

    def __init__(self, params, windows, scales, seeds, *, k_samples: int,
                 min_frac: float, low=None, store=None):
        self.low, self.store = low, store
        stack = lambda f: np.stack([f64(f(p)) for p in params])
        d, g = (lambda p: p["dmm"]), (lambda p: p["guide"])
        self.w = {name: (stack(lambda p, f=f: f(p)["w"]),
                         stack(lambda p, f=f: f(p)["b"])[:, None, :])
                  for name, f in {
                      "lin": lambda p: d(p)["trans_lin"][0],
                      "h1": lambda p: d(p)["trans_h"][0],
                      "h2": lambda p: d(p)["trans_h"][1],
                      "g1": lambda p: d(p)["trans_g"][0],
                      "g2": lambda p: d(p)["trans_g"][1],
                      "ts": lambda p: d(p)["trans_std"][0],
                      "e1": lambda p: d(p)["emit_mu"][0],
                      "e2": lambda p: d(p)["emit_mu"][1],
                      "es": lambda p: d(p)["emit_std"][0],
                      "z": lambda p: g(p)["z_proj"][0],
                      "m": lambda p: g(p)["mu"][0],
                      "s": lambda p: g(p)["std"][0]}.items()}
        self.rnn = {side: tuple(stack(lambda p, k=k: g(p)[side][k])
                                for k in ("wx", "wh", "b"))
                    for side in ("rnn_left", "rnn_right")}
        self.window = self._stored(f64(windows))
        self.scale = f64(scales)[:, None, None]
        self.seeds = np.asarray(seeds, np.int64)
        self.K, self.min_frac = k_samples, min_frac
        J, T, n = self.window.shape
        self.lo = min(int(np.ceil(min_frac * n)), n - 1)
        self.pred = None

    def _stored(self, x):
        return x if self.store is None else _round(x, self.store)

    def _mm(self, a, b):
        if self.low is None:
            return np.matmul(a, b)
        return np.matmul(_round(a, self.low), _round(b, self.low))

    def _lin(self, x, name):
        w, b = self.w[name]
        return self._mm(x, w) + b

    def _sweep(self, x, side):
        wx, wh, b = self.rnn[side]
        h = np.zeros((x.shape[0], 1, wh.shape[-1]))
        out = []
        for t in range(x.shape[1]):
            h = _relu(self._mm(x[:, t:t + 1], wx) + self._mm(h, wh)
                      + b[:, None, :])
            out.append(h)
        return out

    def decide(self, eg, ez, ex):
        """One decision for every job.  eg/ez/ex: the draws.  Returns the
        sorted samples (J, K, n), the cutoffs (J,) and the omega curves."""
        x = self.window / self.scale                      # (J, T, n)
        T = x.shape[1]
        left = self._sweep(x, "rnn_left")
        right = self._sweep(x[:, ::-1], "rnn_right")[::-1]
        zero = np.zeros_like(left[0])
        h_sum = [(left[t - 1] if t else zero)
                 + (right[t + 1] if t + 1 < T else zero) for t in range(T)]
        z = np.zeros(eg.shape[:1] + eg.shape[2:])         # (J, K, zd)
        for t in range(T):
            h_out = (np.tanh(self._lin(z, "z")) + h_sum[t]) / 3.0
            mu = self._lin(h_out, "m")
            std = _softplus(self._lin(mu, "s")) + 1e-3
            z = mu + std * eg[:, t]
        lin = self._lin(z, "lin")
        h = self._lin(_relu(self._lin(z, "h1")), "h2")
        g = 1.0 / (1.0 + np.exp(-self._lin(_relu(self._lin(z, "g1")), "g2")))
        tmu = (1.0 - g) * lin + g * h
        tstd = _softplus(self._lin(_relu(tmu), "ts")) + 1e-3
        z = tmu + tstd * ez
        emu = self._lin(self._lin(z, "e1"), "e2")
        estd = _softplus(self._lin(_relu(emu), "es")) + 1e-3
        samples = (emu + estd * ex) * self.scale
        s = np.sort(samples, axis=2)
        n = s.shape[2]
        omega = np.mean(np.arange(1, n + 1) / np.maximum(s, OMEGA_FLOOR),
                        axis=1)                           # (J, n)
        cut = np.argmax(omega[:, self.lo:], axis=1) + self.lo + 1
        self.pred = (emu.mean(1) * self.scale[:, 0],
                     np.sqrt(np.mean(estd ** 2, 1) + emu.var(1))
                     * self.scale[:, 0])
        return s, cut, omega

    def observe(self, times, finished, u):
        """Append each job's row: observed times where finished, the
        truncated predictive draw (from uniforms ``u``) where not."""
        times = f64(times)
        cutoff = np.max(np.where(finished, times, -np.inf), axis=1,
                        keepdims=True)
        mu, std = self.pred
        sigma = np.maximum(std, SIGMA_FLOOR)
        a = np.clip(ndtr((cutoff - mu) / sigma), 0.0, 1.0 - CDF_CLIP)
        uu = np.clip(a + (1.0 - a) * u, U_CLIP_LO, 1.0 - CDF_CLIP)
        draw = np.maximum(mu + sigma * ndtri(uu), cutoff)
        row = self._stored(np.where(finished, times, draw))
        self.window = np.concatenate([self.window[:, 1:], row[:, None]],
                                     axis=1)


def replay(model: Replay, times, finished, first_step: int = 1,
           block: int = 64):
    """Run every decision and window update of the record.

    times, finished: (steps, J, n): what the jobs observed after each
    decision (the program's finished masks, so both replay the same
    observations).  Returns per step and job the cutoff, the omega curve
    value at every cutoff, and E[x_(c)] at every cutoff (mean of sorted
    samples), and the final windows."""
    steps, J, n = times.shape
    T = model.window.shape[1]
    zd = model.w["lin"][0].shape[-1]
    cuts = np.zeros((steps, J), int)
    omegas = np.zeros((steps, J, n))
    iters = np.zeros((steps, J, n))
    for b0 in range(0, steps, block):
        nb = min(block, steps - b0)
        d = np.arange(first_step + b0, first_step + b0 + nb)
        seeds = np.repeat(model.seeds[None], nb, 0).ravel()
        dd = np.repeat(d[:, None], J, 1).ravel()
        eg, ez, ex, u = (f64(a).reshape((nb, J) + a.shape[1:])
                         for a in jax.device_get(noise(
                             jnp.asarray(seeds, jnp.int32),
                             jnp.asarray(dd, jnp.int32), T, model.K, zd, n)))
        for i in range(nb):
            s, cut, omega = model.decide(eg[i], ez[i], ex[i])
            cuts[b0 + i], omegas[b0 + i] = cut, omega
            iters[b0 + i] = s.mean(axis=1)
            model.observe(times[b0 + i], finished[b0 + i], u[i])
    return cuts, omegas, iters, model.window
