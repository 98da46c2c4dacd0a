"""Minimal batched serving engine: prefill + greedy/temperature decode.

Used by examples/serve_decode.py and the decode-shape smoke tests.  The
production mesh path reuses the same decode_step the dry-run lowers
(feature-TP + sequence-sharded KV); on CPU it runs the local layout.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.obs.trace import span


@dataclass
class ServeEngine:
    cfg: object
    params: object
    max_len: int = 512

    def __post_init__(self):
        cfg = self.cfg

        def _prefill(params, batch):
            return M.prefill(cfg, params, batch)

        def _decode(params, tokens, pos, caches):
            return M.decode_step(cfg, params, tokens, pos, caches)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

    # reprolint: hot-path
    def generate(self, tokens: np.ndarray, n_new: int,
                 temperature: float = 0.0, seed: int = 0,
                 frames: Optional[np.ndarray] = None) -> np.ndarray:
        """tokens: (B, S) prompt -> (B, n_new) generated ids."""
        B, S = tokens.shape
        batch = {"tokens": jnp.asarray(tokens),
                 "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
        if self.cfg.mrope_sections:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S)[None, None], (3, B, S))
        if self.cfg.is_encoder_decoder:
            batch["frames"] = (jnp.asarray(frames) if frames is not None
                               else jnp.zeros(
                (B, self.cfg.encoder_seq_len, self.cfg.d_model)))
        # the spans time DISPATCH of the async calls; only serve.fetch
        # waits for the device
        with span("serve.prefill", batch=B, seq=S):
            last_logits, caches = self._prefill(self.params, batch)
        caches = M.pad_caches(caches, S + n_new)
        key = jax.random.PRNGKey(seed)
        out = []
        nxt = self._sample(last_logits, temperature, key)
        with span("serve.decode", batch=B, n_new=n_new):
            for t in range(n_new):
                # keep the loop transfer-free: collect DEVICE arrays so
                # each decode dispatch overlaps the previous step instead
                # of blocking on a per-token host copy
                out.append(nxt)
                logits, caches = self._decode(self.params, nxt[:, None],
                                              jnp.int32(S + t), caches)
                key, sub = jax.random.split(key)
                nxt = self._sample(logits[:, 0], temperature, sub)
        with span("serve.fetch", batch=B, n_new=n_new):
            # reprolint: disable=host-sync-in-hot-path -- the ONE designated fetch: all n_new tokens come back in a single transfer after the loop has been fully enqueued
            return np.asarray(jnp.stack(out, axis=1))

    @staticmethod
    def _sample(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)
