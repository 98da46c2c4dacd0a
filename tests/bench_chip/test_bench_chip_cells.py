"""Every one-chip cell end to end on the CPU at reduced widths: set-up,
window, reference check and result line, through the same harness call
the command makes once it has found its chip."""
import jax
import pytest

from bench_chip_util import result_line, small, workloads  # noqa: F401


@pytest.mark.parametrize("workload", workloads(chips=1))
def test_cell_runs_and_proves_correct(small, capsys, workload):
    bench = small.benchmark()
    cell = small.entry(bench["workloads"], workload)
    assert small.run_cell(bench, cell, jax.devices()[:1], seed=2 ** 31 + 7,
                          seconds=1.0, trace=False, t_start=0.0) == 0
    res = result_line(capsys.readouterr().out)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in small.cell_metrics(bench, workload, False)}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
