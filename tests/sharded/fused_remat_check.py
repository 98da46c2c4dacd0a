"""The layer checkpoint keeps the fused attention's residuals under a
4-device host mesh, where the kernel runs inside ``shard_map`` over the
batch (``models.attention._fused``, the four-chip cell's train_fsdp
layout): the checkpoint name survives ``shard_map``, so the train step's
gradient holds one splash forward kernel, where a checkpoint without the
policy holds two, with the same loss and gradients.  Interpret mode.
Prints FAIL on any violated property; driven by
tests/test_fused_attention.py.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import optim
from repro.configs.base import get_config
from repro.dist import sharding as shd
from repro.kernels import ops
from repro.launch.train import make_train_step
from repro.models import model as M
from repro.obs import trace

failures = []


def check(name, ok):
    print(f"{name:52s} {'OK' if ok else 'FAIL'}")
    if not ok:
        failures.append(name)


def forwards(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == "splash_mqa_fwd_residuals"
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += forwards(sub)
    return n


ops.KERNEL_BACKEND = "interpret"
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), n_layers=2,
                          head_dim=64)
B, S = 4, 256
params = M.init_model(cfg, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                            cfg.vocab_size)
batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
         "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
opt = optim.sgd(1.0)
state = {"params": params, "opt": opt.init(params)}
lay = shd.Layout(mesh=Mesh(np.asarray(jax.devices()[:4]), ("data",)),
                 mode="train_fsdp", dp=("data",))
keep = M._KEEP_ATTN
out = {}
for name, policy in (("kept", keep), ("full recompute", None)):
    M._KEEP_ATTN = policy
    step = make_train_step(cfg, opt)
    with shd.use_layout(lay):
        trace.reset_counted()
        n = forwards(jax.make_jaxpr(step)(state, batch).jaxpr)
        counts = trace.counted()
        new, metrics = jax.jit(step)(state, batch)
    check(f"{name}: {n} splash forward kernels", n == (1 if policy else 2))
    if policy:
        check("kept: attn.fwd_saved == attn.fused == 1",
              counts.get("attn.fwd_saved") == counts.get("attn.fused") == 1)
    out[name] = (float(metrics["loss"]),
                 jax.tree.map(lambda p, q: p - q, params, new["params"]))
M._KEEP_ATTN = keep

(loss_k, g_k), (loss_r, g_r) = out["kept"], out["full recompute"]
check("same loss", abs(loss_k - loss_r) <= 1e-6 * abs(loss_r))
check("same gradients", all(
    float(jnp.max(jnp.abs(a - b))) <= 1e-6 * float(jnp.max(jnp.abs(b))) + 1e-9
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_r))))
sys.exit(1 if failures else 0)
