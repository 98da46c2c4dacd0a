"""The trace reduction: window, busy and idle time, collectives, top
operations and idle gaps labelled by host span, on a profile whose every
number is known (built as an XSpace, the profiler's own format), and on a
small trace recorded on a TPU v5e (record_trace.py)."""
import os

import pytest
from jax.profiler import ProfileData

import bench_chip_util  # noqa: F401  (puts benchmarks/chip on the path)
import trace_reduce  # noqa: E402

MS = 10 ** 9            # picoseconds


def _plane(pid, name, line, events, names):
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {o} "
                  f"duration_ps: {d} }}\n" for m, o, d in events)
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 0 name: "{line}" timestamp_ns: 1000\n{evs}}}\n'
            f'{meta}}}\n')


@pytest.fixture(scope="module")
def summary():
    # host: a 100 ms window; 20 ms of host work at 10 ms; a dispatch at 40
    # device: ops at [45, 75], [60, 80] (overlapping) and an all-reduce
    # at [85, 90] ms
    text = (_plane(1, "/host:CPU", "python",
                   [(1, 0, 100 * MS), (2, 10 * MS, 20 * MS),
                    (3, 40 * MS, 5 * MS)],
                   ["bench.window", "bench.host", "bench.dispatch"])
            + _plane(2, "/device:TPU:0", "XLA Ops",
                     [(1, 45 * MS, 30 * MS), (2, 60 * MS, 20 * MS),
                      (3, 85 * MS, 5 * MS)],
                     ["fusion.1", "convolution.2", "all-reduce.3"]))
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.from_profile(pd)


def test_window_busy_and_idle(summary):
    assert summary.window_s == pytest.approx(0.1)
    assert summary.busy_s == [pytest.approx(0.040)]      # union, not sum
    assert summary.idle_share == pytest.approx(0.6)
    assert summary.collective_s == [pytest.approx(0.005)]


def test_host_spans_in_the_window(summary):
    assert summary.host["bench.host"] == (pytest.approx(0.02), 1)
    assert summary.host["bench.dispatch"] == (pytest.approx(0.005), 1)
    assert "bench.window" not in summary.host


def test_breakdown(summary):
    assert [n for n, _ in summary.top_ops] == [
        "fusion.1", "convolution.2", "all-reduce.3"]
    assert summary.top_ops[0][1] == pytest.approx(0.03)
    # the longest gap, [0, 45] ms, has the host work in its middle
    assert summary.gaps[0] == ["bench.host", pytest.approx(0.045)]
    assert summary.gaps[1] == ["no host span", pytest.approx(0.010)]
    assert len(summary.gaps) == 3


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]


def test_a_trace_without_a_chip_is_refused():
    text = _plane(1, "/host:CPU", "python", [(1, 0, MS)], ["bench.window"])
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError, match="no TPU plane"):
        trace_reduce.from_profile(pd)



CHIP_TRACE = os.path.join(os.path.dirname(__file__), "chip_trace.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    """A trace recorded on a TPU v5e by record_trace.py: inside the window,
    five rounds of a dispatched bfloat16 matmul chain, a wait for it, and
    20 ms of host work (bench.host) with the chip idle."""
    return trace_reduce.summarize(CHIP_TRACE)


def test_chip_trace_window_and_host_spans(chip):
    assert chip.host["bench.dispatch"][1] == 5
    seconds, count = chip.host["bench.host"]
    assert count == 5 and seconds >= 5 * 0.02
    assert chip.window_s > seconds


def test_chip_trace_busy_idle_and_breakdown(chip):
    assert len(chip.busy_s) == 1 and 0 < chip.busy_s[0] < 0.01
    assert chip.idle_share > 0.9
    assert chip.collective_s == [0.0]
    # the five longest idle gaps are the host's work, one per round
    assert [g[0] for g in chip.gaps[:5]] == ["bench.host"] * 5
    assert all(g[1] >= 0.02 for g in chip.gaps[:5])
    # the chain's two fusions (matmul + tanh, then matmul) take the time,
    # named as the program names them
    assert all(" " not in name for name, _ in chip.top_ops)
    assert sum(s for _, s in chip.top_ops[:2]) > 0.9 * chip.busy_s[0]
