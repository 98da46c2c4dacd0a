"""Serving example: batched prefill + decode with a KV cache.

Decodes from three different architecture families (dense GQA, xLSTM
matrix-memory, Hymba hybrid) to show the cache machinery is uniform.

  PYTHONPATH=src python examples/serve_decode.py
"""
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import place_compile_cache
from repro.models import model as M
from repro.serving.engine import ServeEngine


def main():
    place_compile_cache()
    for name in ["qwen2-0.5b", "xlstm-350m", "hymba-1.5b"]:
        cfg = get_config(name).reduced()
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, size=(4, 12),
                               dtype=np.int32)
        t0 = time.time()
        out = eng.generate(prompts, n_new=16, temperature=0.8, seed=1)
        dt = time.time() - t0
        print(f"{name:14s} batch=4 prompt=12 new=16 "
              f"({dt:.2f}s incl. compile)")
        print(f"   sample continuation ids: {out[0][:10].tolist()}")


if __name__ == "__main__":
    main()
