"""Trainer cells: the program's ``Trainer.run`` with the DMM cutoff
controller in the loop, on one chip or a data mesh.

Set-up builds one Trainer (its jitted step, its state, its controller) and
drives it through its first ``reference_steps`` steps, which compile and
warm every shape; it reads the program's numbers there (each step's loss,
the first gradient from Adam's first moment after one step, the change of
the parameters after the last of them).  The same Trainer then runs the
window.  Once the window has closed and the program's state is freed, the
reference replays those first steps on the same tokens, weights and
cutoff masks, and the numbers are compared.

The program receives only generated inputs: the batches (``data``), the
workers' step times (``timer``), and weights made from the seed.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import decision_check
import dmm_weights
import harness
import lm_flops
import lm_reference
import lm_weights
import trace_reduce
from traffic.cluster_times import ClusterTimes
from traffic.markov_tokens import MarkovTokens

from repro import optim
from repro.configs.base import get_config
from repro.core.controller import CutoffController
from repro.core.runtime_model.api import RuntimeModel
from repro.dist import sharding as shd
from repro.launch.train import Trainer, make_train_step
from repro.models import model as M
from repro.perf.knobs import use_knobs

# configuration key -> the program's ArchConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "norm": "norm", "norm_eps": "norm_eps", "rope_theta": "rope_theta",
          "tie_word_embeddings": "tie_embeddings", "qkv_bias": "attn_bias",
          "attention_out_bias": "attn_out_bias", "mlp_bias": "mlp_bias",
          "param_dtype": "dtype"}
MLP = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def program_config(c: dict, seq_len: int):
    """The program's ArchConfig for configuration file ``c``; raises where
    the program would run anything else than the file states."""
    prog = c["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("replace", {}))
    want = {f: c[k] for k, f in FIELDS.items()}
    want["mlp"] = MLP[c["hidden_act"]]
    bad = {f: (getattr(cfg, f), v) for f, v in want.items()
           if getattr(cfg, f) != v}
    if cfg.sliding_window or (c.get("sliding_window") or seq_len) < seq_len:
        bad["sliding_window"] = (cfg.sliding_window, c.get("sliding_window"))
    if bad:
        raise ValueError(f"program config differs from {c['name']}: {bad}")
    return cfg


def program_params(c: dict, key):
    """The benchmark's weights in the program's parameter tree (one
    segment of identical layers, stacked on the layer axis)."""
    w = lm_weights.make(c, key, jnp.dtype(c["param_dtype"]))
    layers = w.pop("layers")
    if c["num_hidden_layers"] == 1:
        layers = jax.tree.map(lambda x: x[0], layers)
    w["segments"] = [[layers]]
    return w


def reference_names(tree) -> dict:
    """{reference dotted path: leaf} of a program parameter tree."""
    tree = dict(tree)
    seg = tree.pop("segments")
    out = lm_weights.flat(tree)
    out.update(lm_weights.flat(seg[0][0], "layers."))
    return out


def optimizer(c: dict):
    o = c["optimizer"]
    return optim.adamw(o["lr"], o["b1"], o["b2"], o["eps"],
                       o["weight_decay"])


class Tokens:
    """The Trainer's ``data``: Markov batches, placed on the data axis."""

    def __init__(self, gen: MarkovTokens, sharding):
        self.gen, self.sharding = gen, sharding

    def batch(self, step: int) -> dict:
        with TraceAnnotation("bench.data"):
            b = self.gen.batch(step)
            if self.sharding is not None:
                b = {k: jax.device_put(v, self.sharding)
                     for k, v in b.items()}
            return b


class Times:
    """The Trainer's ``timer``: the simulated cluster's step times."""

    def __init__(self, cluster: ClusterTimes):
        self.cluster, self.rows = cluster, []

    def step(self) -> np.ndarray:
        with TraceAnnotation("bench.timer"):
            t = self.cluster.step()
            self.rows.append(t)
            return t


class Decisions:
    """The controller, with host spans around its two calls.

    The fused decision is dispatched after the train step and queued
    behind it on the device, so fetching it waits for that step.  The wait
    gets a span of its own (bench.decision_wait, the step's time as the
    host sees it), and bench.decide holds only the host work that follows.
    """

    def __init__(self, inner, seeded: dict):
        self.inner, self.seeded = inner, seeded
        self.cutoffs, self.iters = [], []

    def predict_cutoff(self) -> int:
        pending = getattr(self.inner, "_pending_decision", None)
        if pending is not None:
            with TraceAnnotation("bench.decision_wait"):
                pending[1].block_until_ready()
        with TraceAnnotation("bench.decide"):
            c = self.inner.predict_cutoff()
        self.cutoffs.append(int(c))
        self.iters.append(self.inner.predicted_iter_time())
        return c

    def observe(self, times, finished_mask=None):
        with TraceAnnotation("bench.observe"):
            self.inner.observe(times, finished_mask)


def controller(tr: dict, seed: int):
    """The cell's DMM cutoff controller (runtime-model weights made from
    the seed, its window seeded from the cluster's first rows), wrapped in
    Decisions, and the cluster that times its workers."""
    ct, W = tr["controller"], tr["workers"]
    cluster = ClusterTimes.preset(tr["cluster"], W, seed + 1)
    window = cluster.run(ct["lag"] + 1)
    params, scale = dmm_weights.make(
        jax.random.fold_in(lm_weights.seed_key(seed), 1), W, ct["z_dim"],
        ct["hidden"], window)
    rm = RuntimeModel(n_workers=W, lag=ct["lag"], z_dim=ct["z_dim"],
                      hidden=ct["hidden"], params=params,
                      norm_scale=float(scale))
    ctl = CutoffController(rm, k_samples=ct["k_samples"],
                           min_frac=ct["min_frac"], seed=seed % 2 ** 30,
                           backend="device")
    ctl.seed_window(window)
    seeded = {"params": [jax.device_get(params)], "windows": window[None],
              "scales": [float(scale)], "seeds": [ctl.seed]}
    return Decisions(ctl, seeded), cluster


def decision_record(ctl: Decisions, rows: list) -> dict:
    """The controller's record (decision_check.py) over its decisions so
    far; ``rows`` are the workers' times it observed."""
    return dict(ctl.seeded, times=np.asarray(rows)[:, None],
                cuts=np.asarray(ctl.cutoffs)[:, None])


def build(c: dict, tr: dict, seed: int, devices):
    """The Trainer of a cell, its state made from the seed."""
    S, B, W = tr["seq_len"], tr["global_batch"], tr["workers"]
    cfg = program_config(c, S)
    opt = optimizer(c)
    key = lm_weights.seed_key(seed)
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices), ("data",))
        lay = shd.Layout(mesh=mesh, mode=tr["layout"], dp=("data",))
        rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    else:
        lay, rep, dp = shd.LOCAL, None, None
    shapes = jax.eval_shape(lambda: M.init_model(cfg, key))
    mine = jax.eval_shape(lambda: program_params(c, key))
    if jax.tree.map(lambda a: (a.shape, a.dtype), shapes) != jax.tree.map(
            lambda a: (a.shape, a.dtype), mine):
        raise ValueError("the benchmark's weights do not fit the program's "
                         "parameter tree")

    # the key is an argument, not a constant of the program, so that every
    # seed finds the same compiled program in the cache
    def init(key):
        params = program_params(c, key)
        return {"params": params, "opt": opt.init(params)}

    state = jax.jit(init, out_shardings=rep)(key)
    step = make_train_step(cfg, opt, mask_agg=tr["mask_agg"])

    def run(state, batch):
        with shd.use_layout(lay), use_knobs(**c["program"]["knobs"]):
            return step(state, batch)

    jitted = jax.jit(run, donate_argnums=(0,))

    def step_fn(state, batch):
        with TraceAnnotation("bench.dispatch"):
            return jitted(state, batch)

    ctl, cluster = controller(tr, seed)
    gen = MarkovTokens(c["vocab_size"], S, B, seed)
    trainer = Trainer(cfg=cfg, step_fn=step_fn, data=Tokens(gen, dp),
                      controller=ctl, timer=Times(cluster),
                      n_workers=W, mask_agg=tr["mask_agg"], metrics_every=0)
    trainer.state = state
    return trainer, gen


def example_weights(times: np.ndarray, c: int, batch: int) -> np.ndarray:
    """The fastest ``c`` workers' examples weigh 1 (worker w owns the w-th
    contiguous slice of the batch), the rest 0."""
    mask = np.zeros(times.shape[0], np.float32)
    mask[np.argsort(times)[:c]] = 1.0
    return np.repeat(mask, batch // times.shape[0])


def gap(prog: float, ref: float, floor: float) -> float:
    return abs(prog - ref) / max(abs(ref), floor)


def loss_gaps(prog: dict, ref: dict) -> list:
    """|loss - ref| / |ref| of every step."""
    return [gap(p, r, 0.0) for p, r in zip(prog["losses"], ref["losses"])]


def compare(c: dict, prog: dict, ref: dict) -> dict:
    """The numbers compared, each the worst over steps or leaves.

    loss_gap: |loss - ref| / |ref| of the first step.  The later steps'
    losses are compared in loss_gaps(), which is printed and not a limit:
    Adam's first update moves every weight by about lr whatever its
    gradient's size, so where the bfloat16 gradient and the float32 one
    differ in sign or, near eps, in size, the later losses part by more
    than the arithmetic of one step.  grad_norm_gap and update_norm_gap:
    per leaf, the gap between the program's norm and the reference's, over
    the larger of the reference's norm of that leaf and of the median
    leaf.  Leaves whose reference gradient is under 1/1000 of the median
    leaf's (nought to rounding, such as a key bias under the softmax) are
    left out of the change: Adam moves them by round-off."""
    g_ref, d_ref = ref["grad_norms"], ref["change_norms"]
    g_med = float(np.median(list(g_ref.values())))
    d_med = float(np.median(list(d_ref.values())))
    moved = [k for k in d_ref if g_ref[k] >= 1e-3 * g_med]
    return {
        "loss_gap": loss_gaps(prog, ref)[0],
        "grad_norm_gap": max(gap(prog["grad_norms"][k], g_ref[k], g_med)
                             for k in g_ref),
        "update_norm_gap": max(gap(prog["change_norms"][k], d_ref[k], d_med)
                               for k in moved),
    }


def program_readings(trainer, c: dict, steps: int, seed: int) -> dict:
    """Drive the Trainer through its first ``steps`` steps and read the
    program's numbers off its state."""
    b1 = c["optimizer"]["b1"]
    key = lm_weights.seed_key(seed)
    norms = jax.jit(lambda t: lm_reference.leaf_norms(reference_names(t)))
    trainer.run(1)
    g1 = {k: float(v) / (1.0 - b1)
          for k, v in norms(trainer.state["opt"]["m"]).items()}
    trainer.run(steps - 1)
    change = jax.jit(lambda p, k: lm_reference.leaf_norms(reference_names(
        jax.tree.map(lambda a, b: a.astype(jnp.float32)
                     - b.astype(jnp.float32), p, program_params(c, k)))))
    d = {k: float(v)
         for k, v in change(trainer.state["params"], key).items()}
    return {"losses": [float(h["loss"]) for h in trainer.history],
            "grad_norms": g1, "change_norms": d}


def run(*, workload: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, t_start: float, devices):
    c, tr = config, traffic
    B, S = tr["global_batch"], tr["seq_len"]
    nref = tr["reference_steps"]
    split = {}
    with harness.CompileClock() as clock:
        t0 = time.perf_counter()
        trainer, gen = build(c, tr, seed, devices)
        jax.block_until_ready(trainer.state)
        split["init_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        prog = program_readings(trainer, c, nref, seed)
        split["first_steps_s"] = time.perf_counter() - t0
        split["compile_s"] = clock.seconds
        split["compiles"], split["cache_hits"] = clock.count, clock.cache_hits
        setup_s = time.perf_counter() - t_start
        print(f"setup: {setup_s!r} s {split}", flush=True)
        n_setup = clock.count
        log_dir = os.path.join(harness.ROOT, ".bench", "trace",
                               f"{workload['name']}-{seed}")
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        steps = 0
        with TraceAnnotation(trace_reduce.WINDOW):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                trainer.run(1)
                steps += 1
            jax.block_until_ready(trainer.state)
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        compiles_in_window = clock.count - n_setup
    print(f"window: {window_s!r} s, {steps} steps, "
          f"{compiles_in_window} compiles", flush=True)
    peak = harness.memory_peak(devices)
    summary = None
    if trace:
        summary = trace_reduce.summarize(trace_reduce.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    ctl = trainer.controller
    rows, cuts, iters = trainer.timer.rows, ctl.cutoffs, ctl.iters
    final = ctl.inner.window_array()[None]
    rec = decision_record(ctl, rows)
    trainer.state = None
    trainer.controller = None
    # the reference replays the first steps on the same tokens and masks
    t0 = time.perf_counter()
    batches = [gen.batch(t) for t in range(nref)]
    weights = [example_weights(rows[t], cuts[t], B) for t in range(nref)]
    ref = lm_reference.train(c, seed, batches, weights, c["optimizer"])
    print(f"reference: {time.perf_counter() - t0!r} s", flush=True)
    values = compare(c, prog, ref)
    print(f"program {prog['losses']} reference {ref['losses']} loss gaps "
          f"{loss_gaps(prog, ref)}", flush=True)
    ct = tr["controller"]
    t0 = time.perf_counter()
    dref = decision_check.replay(**rec, k_samples=ct["k_samples"],
                                 min_frac=ct["min_frac"])
    values.update(decision_check.gaps(rec["cuts"],
                                      np.asarray(iters)[:, None], final,
                                      dref))
    print(f"decisions: {len(cuts)} replayed in "
          f"{time.perf_counter() - t0!r} s; program {cuts}, reference "
          f"{dref['cuts'][:, 0].tolist()}", flush=True)
    for k in ("grad_norms", "change_norms"):
        med = float(np.median(list(ref[k].values())))
        worst = sorted(ref[k], key=lambda n: -gap(prog[k][n], ref[k][n],
                                                   med))[:3]
        print(f"{k} worst leaves: " + ", ".join(
            f"{n} {prog[k][n]!r} vs {ref[k][n]!r}" for n in worst),
              flush=True)
    lim = harness.limits(workload["name"])
    checks = [harness.Check(k, values[k], lim[k]) for k in lim]
    r = harness.Run(config=c, traffic=tr, chips=len(devices),
                    device_kind=devices[0].device_kind, setup_s=setup_s,
                    window_s=window_s,
                    counts={"steps": steps, "tokens": steps * B * S,
                            "model_flops": steps * B * S
                            * lm_flops.per_token(c, S)},
                    samples={}, trace=summary)
    return r, checks, steps, peak


def control(*, config: dict, traffic: dict, seed: int, devices, **_):
    """Readings of the control and of the faults a Trainer cell can have,
    at the cell's own size: the reference with every matmul in float8
    (the control), and the reference with half of every batch left out,
    the mean taken over the rest.  A step that returns its state unchanged
    reads 1 on update_norm_gap and needs no run.  Masks: the fastest
    three quarters of the workers of each step.  The decisions: the
    program's controller alone, driven through ``decisions`` steps of the
    cell's cluster, replayed in float64 and by the control and faults of
    decision_check.faults."""
    c, tr = config, traffic
    ct = tr["controller"]
    ctl, cluster = controller(tr, seed)
    rows = []
    for _ in range(tr["decisions"]):
        cut = ctl.predict_cutoff()
        t = cluster.step()
        finished = np.zeros(t.shape, bool)
        finished[np.argsort(t)[:cut]] = True
        ctl.observe(t, finished)
        rows.append(t)
    out = decision_check.faults(
        **decision_record(ctl, rows), iters=np.asarray(ctl.iters)[:, None],
        finals=ctl.inner.window_array()[None], k_samples=ct["k_samples"],
        min_frac=ct["min_frac"], n=tr["workers"])
    B, S, W = tr["global_batch"], tr["seq_len"], tr["workers"]
    nref = tr["reference_steps"]
    gen = MarkovTokens(c["vocab_size"], S, B, seed)
    cluster = ClusterTimes.preset(tr["cluster"], W, seed + 1)
    batches = [gen.batch(t) for t in range(nref)]
    keep = -(-3 * W // 4)
    weights = [example_weights(cluster.step(), keep, B) for _ in range(nref)]
    ref = lm_reference.train(c, seed, batches, weights, c["optimizer"])
    low = lm_reference.train(c, seed, batches, weights, c["optimizer"],
                             low="float8_e4m3fn")
    half = [np.where(np.arange(B) < B // 2, w, 0.0) for w in weights]
    halved = lm_reference.train(c, seed, batches, half, c["optimizer"])
    out["control"].update(compare(c, low, ref))
    out["half_batch"] = compare(c, halved, ref)
    out["loss_gaps"] = {"control": loss_gaps(low, ref),
                        "half_batch": loss_gaps(halved, ref)}
    out["state_unchanged"]["update_norm_gap"] = 1.0
    return out
