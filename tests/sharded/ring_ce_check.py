"""Ring vocab-parallel CE == dense CE (loss + grads), tied & untied heads."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses

import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh
from repro.launch.train import make_loss_fn
from repro.models import model as M
from repro.perf.knobs import use_knobs

mesh = make_mesh((2, 4), ("data", "model"))

for name in ["qwen2-0.5b", "stablelm-3b"]:  # tied + untied
    cfg = get_config(name).reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_model(cfg, key)
    B, S = 4, 32
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S)),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "weights": jnp.asarray([1.0, 0.0, 1.0, 1.0]),
    }
    lay = shd.make_layout(mesh, "train_sp")
    loss_fn = make_loss_fn(cfg, aux_coef=0.0)
    norm = jnp.float32(3 * S)

    outs = {}
    for impl in ["dense", "ring"]:
        with use_knobs(ce_impl=impl):
            stacked = [f"segments/{i}" for i, s in enumerate(
                M.build_segments(M.layer_specs(cfg))) if s.repeats > 1]
            pshard = shd.named_sharding(params, lay,
                                        stacked_paths=tuple(stacked))
            params_s = jax.device_put(params, pshard)
            bshard = {k: NamedSharding(mesh, P("data", "model"))
                      if v.ndim == 2 else NamedSharding(mesh, P("data"))
                      for k, v in batch.items()}
            batch_s = {k: jax.device_put(v, bshard[k])
                       for k, v in batch.items()}

            def run(p, b):
                with shd.use_layout(lay), use_knobs(ce_impl=impl):
                    (l, _), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(p, b, norm)
                return l, g

            with jax.set_mesh(mesh):
                outs[impl] = jax.jit(run)(params_s, batch_s)

    l_d, g_d = outs["dense"]
    l_r, g_r = outs["ring"]
    dl = abs(float(l_d) - float(l_r))
    gerr = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_r)))
    print(f"{name:16s} tied={cfg.tie_embeddings} dloss={dl:.2e} "
          f"gerr={gerr:.2e} {'OK' if dl < 1e-4 and gerr < 1e-3 else 'FAIL'}")
