"""The metrics that read the program's own spans: each reader's arithmetic
on a hand-built profiled summary, its silence on the other cell's summary
and on an empty one, and its entry in BENCHMARK.json."""
import json
import os

import pytest

from bench_chip_util import ROOT

import harness  # noqa: E402  (bench_chip_util puts benchmarks/chip first)

PS_CELL = "dmm-paper158.ps.j16"
TRAIN_CELL = "qwen2-0.5b.train4k.w8"


def _spans(**named):
    """{name: (count, total_s, self_s)} -> the profiled() layout."""
    return {name.replace("__", "."): {"count": c, "total_s": t, "self_s": s}
            for name, (c, t, s) in named.items()}


# a 2.5 s window of 500 ticks, one bucket, 16 jobs per tick
PS_SUMMARY = {"spans": _spans(
    ps__schedule=(500, 0.010, 0.010),
    ps__predict_cutoff=(8000, 0.90, 0.40),
    ps__fetch=(500, 0.45, 0.45),
    ps__observe=(8000, 0.30, 0.30),
    ps__flush=(500, 0.80, 0.05),
    ps__pack=(500, 0.25, 0.25),
    ps__dispatch=(500, 0.50, 0.50),
    ps__decide=(10, 0.02, 0.02)),
    "top_level_s": 0.010 + 0.90 + 0.30 + 0.80}
# a 10 s window of 2 steps
TRAIN_SUMMARY = {"spans": _spans(
    trainer__step=(2, 9.0, 0.2),
    trainer__batch=(2, 0.08, 0.06),
    trainer__timer=(2, 0.002, 0.001),
    train__dispatch=(2, 0.02, 0.015),
    controller__predict_cutoff=(2, 8.8, 0.004),
    controller__fetch=(2, 8.796, 8.796),
    controller__observe=(2, 0.006, 0.006)),
    "top_level_s": 9.0}
SUMMARIES = {PS_CELL: PS_SUMMARY, TRAIN_CELL: TRAIN_SUMMARY}
WINDOW_S = {PS_CELL: 2.5, TRAIN_CELL: 10.0}

EXPECTED = {
    "ps_predict_ms": (PS_CELL, 1e3 * 0.40 / 500),
    "ps_observe_ms": (PS_CELL, 1e3 * 0.30 / 500),
    "ps_fetch_wait_ms": (PS_CELL, 1e3 * 0.45 / 500),
    "ps_pack_ms": (PS_CELL, 1e3 * 0.25 / 500),
    "ps_dispatch_ms": (PS_CELL, 1e3 * 0.50 / 500),
    "ps_unspanned_ms": (PS_CELL, 1e3 * (2.5 - 2.01) / 500),
    "ps_dispatches_per_tick": (PS_CELL, 510 / 500),
    "trainer_host_ms": (TRAIN_CELL, 1e3 * (0.06 + 0.001 + 0.015) / 2),
    "controller_host_ms": (TRAIN_CELL, 1e3 * (0.004 + 0.006) / 2),
}


def _run(cell):
    return harness.Run(config={}, traffic={}, chips=1,
                       device_kind="TPU v5 lite", setup_s=1.0,
                       window_s=WINDOW_S[cell], counts={}, samples={})


def _read(monkeypatch, metric, summary, cell):
    from repro.obs import trace
    monkeypatch.setattr(trace, "profiled", lambda: summary)
    return harness.reader(metric)(_run(cell))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_arithmetic(monkeypatch, metric):
    cell, want = EXPECTED[metric]
    got = _read(monkeypatch, metric, SUMMARIES[cell], cell)
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_is_silent_on_the_other_cell(monkeypatch, metric):
    cell, _ = EXPECTED[metric]
    other = TRAIN_CELL if cell == PS_CELL else PS_CELL
    assert _read(monkeypatch, metric, SUMMARIES[other], other) is None
    # and where the profiler never traced, or a program has no summary
    assert _read(monkeypatch, metric, {}, cell) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_benchmark_lists_the_metric(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = harness.entry(bench["per_layer"], metric)
    cell, _ = EXPECTED[metric]
    assert entry["workloads"] == [cell]
    assert entry["source"] == "program_span"
    e2e = harness.entry(bench["end_to_end"], entry["moves"])
    assert cell in e2e["workloads"]
