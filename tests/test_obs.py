"""Obs spine contracts: bit-exactness, ring drain, spans, streams, CLI.

The load-bearing promise of ``repro.obs`` is that attaching it changes
NOTHING: a seeded Trainer run and a J=3 PSServer run must produce
bit-identical losses and cutoff sequences with obs on vs off.  Around
that sit the mechanism contracts — ring overflow drops oldest and is
counted, spans nest lexically and land in the profiler's trace on its
clock (and in the profiled summary), the JSONL streams keep the
``controlplane.events`` monotone-seq / torn-tail conventions, and the
CLI renders a run from artifacts alone.
"""
import gc
import glob
import os
import time

import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.cluster.simulator import ClusterSim, paper_cluster_158
from repro.core.controller import CutoffController
from repro.core.cutoff import order_stats
from repro.core.runtime_model.api import RuntimeModel
from repro.obs import ObsRun
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import OBS_KINDS, ObsLog, Tracer
from repro.ps import PSServer


# ---------------------------------------------------------------------------
# Metric rings: drain contract.
# ---------------------------------------------------------------------------


def test_ring_drain_returns_pushed_rows_oldest_first():
    reg = MetricsRegistry()
    ring = reg.ring("r", ("x", "y"), cap=8)
    for i in range(5):
        ring.push((float(i), float(10 * i)))
    p = ring.drain()
    assert p["dropped"] == 0 and p["pushed"] == 5
    np.testing.assert_array_equal(
        np.asarray(p["rows"])[:, 0], [0.0, 1.0, 2.0, 3.0, 4.0])
    # nothing new since: drain is None, not a repeat
    assert ring.drain() is None
    ring.push((99.0, 0.0))
    assert np.asarray(ring.drain()["rows"])[:, 0] == [99.0]


def test_ring_overflow_drops_oldest_and_counts():
    ring = MetricsRegistry().ring("r", ("v",), cap=4)
    for i in range(11):
        ring.push((float(i),))
    p = ring.drain()
    # the ring keeps the most recent cap rows; the 7 oldest are dropped
    # and the drop is COUNTED — truncation is never silent
    assert p["dropped"] == 7
    np.testing.assert_array_equal(np.asarray(p["rows"])[:, 0],
                                  [7.0, 8.0, 9.0, 10.0])
    assert ring.drain() is None


def test_ring_rejects_arity_and_column_drift():
    reg = MetricsRegistry()
    ring = reg.ring("r", ("a", "b"))
    with pytest.raises(ValueError, match="wants 2 values"):
        ring.push((1.0,))
    with pytest.raises(ValueError, match="re-registered"):
        reg.ring("r", ("a", "c"))


# ---------------------------------------------------------------------------
# Bit-exactness: obs attached changes nothing.
# ---------------------------------------------------------------------------


def _parent(rec, spans):
    """The record of the span that directly encloses ``rec``."""
    end = rec["ts_us"] + rec["dur_us"]
    return next(s for s in spans if s["depth"] == rec["depth"] - 1
                and s["ts_us"] <= rec["ts_us"]
                and end <= s["ts_us"] + s["dur_us"])


def _scale_model(n, trace, seed=0):
    rm = RuntimeModel(n_workers=n, lag=10).init(seed)
    rm.norm_scale = float(2.0 * trace[:21].mean())
    return rm


_CACHE = {}


def _run_trainer(obs, steps=50, n=8):
    import jax

    from repro import optim
    from repro.configs.base import bench_tiny_config
    from repro.launch.train import Trainer, jit_train_step
    from repro.models import model as M

    cfg = bench_tiny_config()
    opt = optim.adamw(3e-3)
    if "step_fn" not in _CACHE:                # share one compile cache
        _CACHE["step_fn"] = jit_train_step(cfg, opt)
    step_fn = _CACHE["step_fn"]
    trace = paper_cluster_158(seed=0, n_workers=n).run(60)
    ctl = CutoffController(_scale_model(n, trace), k_samples=16, seed=0)
    ctl.seed_window(trace)
    from repro.data.pipeline import SyntheticTokens
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=8,
                           global_batch=n * 3, seed=0)
    tr = Trainer(cfg=cfg, step_fn=step_fn, data=data,
                 controller=obs.wrap(ctl, policy="dmm") if obs else ctl,
                 timer=ClusterSim(n_workers=n, n_nodes=2, seed=5),
                 n_workers=n, metrics_every=7, obs=obs, name="dmm")

    def init_fn():
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": opt.init(params)}

    tr.restore_or_init(init_fn)
    tr.run(steps)
    return tr


def test_trainer_bit_exact_with_obs_attached():
    """Seeded 50-step run: identical losses AND cutoff sequences with the
    full spine on (spans + ring pushes + quality wrapper) vs bare."""
    bare = _run_trainer(None)
    with ObsRun() as obs:
        inst = _run_trainer(obs)
    assert [h["c"] for h in inst.history] == [h["c"] for h in bare.history]
    assert ([h["loss"] for h in inst.history]
            == [h["loss"] for h in bare.history])
    # and the spine actually recorded: the step stream mirrors history,
    # every decision was scored, the trainer ring drained its pushes
    assert len(obs.steps) == len(bare.history) == 50
    assert len(obs.decisions.records) == 50
    names = {s["name"] for s in obs.trace.spans}
    assert {"trainer.step", "trainer.timer", "trainer.batch",
            "train.dispatch", "controller.predict_cutoff",
            "controller.fetch", "controller.observe", "obs.drain"} <= names
    spans = obs.trace.spans
    for child, parent in (("trainer.batch", "trainer.step"),
                          ("trainer.timer", "trainer.step"),
                          ("train.dispatch", "trainer.step"),
                          ("controller.predict_cutoff", "trainer.step"),
                          ("controller.observe", "trainer.step"),
                          ("controller.fetch", "controller.predict_cutoff")):
        assert {_parent(s, spans)["name"] for s in spans
                if s["name"] == child} == {parent}, child
    assert obs.metrics.ring("trainer[dmm]",
                            ("loss", "gnorm", "c", "iter_time")).pushed == 50


def _drive_ps(obs, J=3, steps=25, n=8):
    trace = paper_cluster_158(seed=0, n_workers=n).run(60)
    rm = _scale_model(n, trace)
    srv = PSServer(obs=obs)
    ctls = []
    for j in range(J):
        h = srv.admit(f"job{j}", rm,
                      window=paper_cluster_158(seed=30 + j,
                                               n_workers=n).run(40),
                      k_samples=16, seed=7 * j)
        ctls.append(obs.wrap(h, policy=f"job{j}") if obs else h)
    sims = [paper_cluster_158(seed=50 + j, n_workers=n) for j in range(J)]
    seqs = [[] for _ in range(J)]
    for _ in range(steps):
        for j in range(J):
            c = ctls[j].predict_cutoff()
            times = sims[j].step()
            it = order_stats.iter_time(times, c)
            ctls[j].observe(times, times <= it + 1e-12)
            seqs[j].append(int(c))
        srv.flush()
    if obs is not None:
        obs.drain()
    return seqs, [srv.window_array(f"job{j}") for j in range(J)]


def test_psserver_bit_exact_with_obs_attached():
    """J=3 batched server: identical cutoff sequences and windows with
    spans + refit counters + per-job quality wrappers on vs off."""
    bare, bare_windows = _drive_ps(None)
    with ObsRun() as obs:
        inst, inst_windows = _drive_ps(obs)
    assert inst == bare
    for a, b in zip(inst_windows, bare_windows):
        np.testing.assert_array_equal(a, b)
    # three distinct jobs: each job's window is its own
    for i in range(3):
        for k in range(i + 1, 3):
            assert not np.array_equal(bare_windows[i], bare_windows[k])
    # the PS span tree: the decision fetch inside predict_cutoff, the
    # host packing and the dispatch inside flush
    spans = obs.trace.spans
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert len(by_name["ps.flush"]) == 25
    assert len(by_name["ps.predict_cutoff"]) == 3 * 25
    assert len(by_name["ps.observe"]) == 3 * 25
    assert by_name["ps.fetch"]
    for child, parent in (("ps.fetch", "ps.predict_cutoff"),
                          ("ps.pack", "ps.flush"),
                          ("ps.dispatch", "ps.flush")):
        assert {_parent(s, spans)["name"] for s in by_name[child]} == {
            parent}, child
    # one packing and one dispatch per flush: one bucket
    assert len(by_name["ps.pack"]) == len(by_name["ps.dispatch"]) == 25
    # every decision scored with the shared schema, lazy samples included
    recs = obs.decisions.records
    assert len(recs) == 3 * 25
    assert {r["policy"] for r in recs} == {"job0", "job1", "job2"}
    assert all(r["cov50"] is not None for r in recs)


# ---------------------------------------------------------------------------
# Spans: the profiler's trace, the profiled summary, the no-op path.
# ---------------------------------------------------------------------------


def _profile(fn, log_dir):
    """Run ``fn`` under the CPU profiler; returns the host events
    of the written ``.xplane.pb`` as (plane, name, start_ns, end_ns)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return [(plane.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _nested_spans():
    for i in range(3):
        with obs_trace.span("t.outer", tick=i):
            time.sleep(0.004)
            for _ in range(2):
                with obs_trace.span("t.inner"):
                    time.sleep(0.002)
    with TraceAnnotation("t.raw"):
        time.sleep(0.001)


@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    """The nested spans run once under the profiler: (the profiled
    summary, the xplane's host events)."""
    obs_trace.reset_profiled()
    try:
        events = _profile(_nested_spans, tmp_path_factory.mktemp("prof"))
        return obs_trace.profiled(), events
    finally:
        obs_trace.reset_profiled()


def test_cpu_profiler_records_host_plane(profiled_run):
    _, events = profiled_run
    raw = [e for e in events if e[1] == "t.raw"]
    assert len(raw) == 1 and raw[0][0] == "/host:CPU"


def test_program_spans_land_in_xplane_nested(profiled_run):
    """Bare names (the ``tick=`` attr is not in the event name), each
    inner span inside an outer one, on the profiler's clock."""
    _, events = profiled_run
    outer = [e for e in events if e[1] == "t.outer"]
    inner = [e for e in events if e[1] == "t.inner"]
    assert len(outer) == 3 and len(inner) == 6
    assert not [e for e in events if e[1].startswith("t.outer")
                and e[1] != "t.outer"]
    for _, _, s, e in inner:
        assert any(os_ <= s and e <= oe for _, _, os_, oe in outer)


def test_profiled_summary_matches_xplane(profiled_run):
    summary, events = profiled_run
    spans = summary["spans"]
    assert set(spans) == {"t.outer", "t.inner"}    # t.raw is not ours
    for name in spans:
        mine = [e for e in events if e[1] == name]
        assert spans[name]["count"] == len(mine)
        xplane_s = sum(e - s for _, _, s, e in mine) / 1e9
        assert spans[name]["total_s"] == pytest.approx(xplane_s, rel=0.05)


def test_profiled_self_time_is_total_minus_children(profiled_run):
    summary, _ = profiled_run
    outer, inner = summary["spans"]["t.outer"], summary["spans"]["t.inner"]
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], rel=1e-9)
    assert inner["self_s"] == pytest.approx(inner["total_s"], rel=1e-9)
    # only the outer spans are top-level
    assert summary["top_level_s"] == pytest.approx(outer["total_s"],
                                                   rel=1e-9)
    assert outer["self_s"] >= 3 * 0.004


def test_profiled_summary_counts_every_thread(tmp_path):
    """Spans completing on several threads at once, under a profiler
    trace: no update of the summary is lost, and each thread nests
    its own spans."""
    import sys
    import threading

    n_threads, n_spans = 8, 300

    def work():
        for _ in range(n_spans):
            with obs_trace.span("t.thread"):
                with obs_trace.span("t.child"):
                    pass

    def many():
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

    obs_trace.reset_profiled()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        _profile(many, tmp_path)
        spans = obs_trace.profiled()["spans"]
    finally:
        sys.setswitchinterval(interval)
        obs_trace.reset_profiled()
    assert spans["t.thread"]["count"] == spans["t.child"]["count"] == (
        n_threads * n_spans)
    assert spans["t.thread"]["self_s"] == pytest.approx(
        spans["t.thread"]["total_s"] - spans["t.child"]["total_s"])


def test_span_is_a_shared_noop_without_listeners():
    gc.collect()
    obs_trace.reset_profiled()
    assert not TraceAnnotation.is_enabled()
    s = obs_trace.span("t.idle", step=1)
    assert s is obs_trace.NO_SPAN and obs_trace.span("t.other") is s
    with s:
        pass
    assert obs_trace.profiled() == {}
    # an open tracer listens; once closed, nobody does again
    tracer = Tracer()
    with obs_trace.span("t.idle"):
        pass
    tracer.close()
    assert [r["name"] for r in tracer.spans] == ["t.idle"]
    assert obs_trace.span("t.idle") is obs_trace.NO_SPAN
    assert obs_trace.profiled() == {}        # the profiler never traced


def test_span_nesting_and_chrome_export(tmp_path):
    """One span call, both listeners: an open Tracer's records (depth,
    attrs nested under ``attrs``) and the profiler trace, which replaces
    the Chrome-trace JSON export (a Perfetto view of the same spans)."""
    tracer = Tracer()

    def nested():
        with obs_trace.span("outer", tick=3):
            with obs_trace.span("inner", step=9):
                pass

    try:
        events = _profile(nested, tmp_path)
    finally:
        tracer.close()
        obs_trace.reset_profiled()
    inner, outer = tracer.spans            # completion order: inner first
    assert (outer["name"], outer["depth"]) == ("outer", 1)
    assert (inner["name"], inner["depth"]) == ("inner", 2)
    # attribution rides in a nested dict: component clocks named
    # tick/step can never collide with the EventLog wire fields
    assert outer["attrs"] == {"tick": 3} and inner["attrs"] == {"step": 9}
    assert outer["ts_us"] <= inner["ts_us"]
    assert outer["dur_us"] >= inner["dur_us"]
    assert "track" not in outer

    ours = sorted((e for e in events if e[1] in ("outer", "inner")),
                  key=lambda e: e[2])
    assert [e[1] for e in ours] == ["outer", "inner"]    # start order
    (_, _, o0, o1), (_, _, i0, i1) = ours
    assert o0 <= i0 and i1 <= o1


# ---------------------------------------------------------------------------
# Streams: monotone seq, torn tails, CLI render.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    d = tmp_path_factory.mktemp("obs") / "run"
    obs = ObsRun(str(d))
    _run_trainer(obs, steps=12)
    obs.close()
    return str(d)


def test_obslog_streams_monotone_seq_and_kinds(recorded_run):
    from repro.controlplane.events import read_events

    for stream in ("spans", "steps", "decisions", "metrics"):
        events = read_events(f"{recorded_run}/{stream}.jsonl")
        assert events, stream
        seqs = [e.seq for e in events]
        assert seqs == sorted(set(seqs)), stream     # strictly monotone
        assert all(e.kind in OBS_KINDS for e in events), stream
    mets = read_events(f"{recorded_run}/metrics.jsonl")
    assert mets[0].kind == "run" and mets[0].data["phase"] == "start"
    assert mets[-1].kind == "run" and mets[-1].data["phase"] == "end"
    assert "counters" in mets[-1].data["summary"]


def test_torn_tail_still_renders(recorded_run, tmp_path):
    """A crashed writer's half-line tail must not poison the readers."""
    import shutil

    from repro.obs import report as R

    d = tmp_path / "torn"
    shutil.copytree(recorded_run, d)
    with open(d / "spans.jsonl", "a") as f:
        f.write('{"seq": 999999, "tick": 999, "kind": "sp')   # torn write
    run = R.load_run(str(d))
    whole = R.load_run(recorded_run)
    assert len(run["spans"]) == len(whole["spans"])   # tail dropped, rest kept
    assert R.render(run)


def test_cli_renders_timeline_and_calibration(recorded_run, tmp_path,
                                              capsys):
    from repro.obs.__main__ import main

    assert main([recorded_run]) == 0
    out = capsys.readouterr().out
    assert "12 step records" in out
    assert "timeline" in out and "decision quality" in out
    assert "trainer.step" in out and "dmm" in out
    # the JSON export is gone: the profiler trace is the timeline view
    with pytest.raises(SystemExit):
        main([recorded_run, "--chrome", str(tmp_path / "trace.json")])


def test_cli_empty_dir_is_an_error(tmp_path):
    from repro.obs.__main__ import main

    assert main([str(tmp_path)]) == 1


def test_obslog_rejects_unknown_kind():
    log = ObsLog(None)
    with pytest.raises(ValueError):
        # reprolint: disable=event-kind-drift -- deliberately unregistered: this pins the runtime rejection the lint rule mirrors
        log.emit(log.autotick(), "not-a-kind")
