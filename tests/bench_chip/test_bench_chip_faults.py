"""The comparison that decides ``correct`` fails the faults each cell can
have, planted underneath a whole run on the CPU at reduced widths, and its
control reads past the limits.

Trainer cells: a step that returns its state unchanged; half of every
batch left out, the mean taken over the rest; an answer altered where it
is produced (every cutoff returned as full sync).  Parameter-server cells:
a flush that leaves every job's window unchanged; an answer altered where
it is produced."""
import json
import os

import jax
import pytest

from bench_chip_util import CHIP, result_line, small, workloads  # noqa: F401

PS_TICKS = 60


def _run(small, capsys, workload):
    bench = small.benchmark()
    cell = small.entry(bench["workloads"], workload)
    small.run_cell(bench, cell, jax.devices()[:1], seed=9001, seconds=0.5,
                   trace=False, t_start=0.0)
    return result_line(capsys.readouterr().out)


def _driver(workload):
    import harness
    cell = harness.entry(harness.benchmark()["workloads"], workload)
    return harness.traffic(cell["traffic"])["driver"]


def cell_traffic(name):
    """A traffic mix as the cell runs it (the fixture's is cut down)."""
    with open(os.path.join(CHIP, "traffic", f"{name}.json")) as f:
        return json.load(f)


TRAINER = [w for w in workloads(chips=1) if _driver(w) == "trainer"]
PS = [w for w in workloads(chips=1) if _driver(w) == "ps"]


def frozen_step(make):
    def build(cfg, opt, **kw):
        step = make(cfg, opt, **kw)

        def run(state, batch):
            return state, step(state, batch)[1]
        return run
    return build


def half_batch_step(make):
    def build(cfg, opt, **kw):
        step = make(cfg, opt, **kw)

        def run(state, batch):
            w = batch["weights"]
            half = w.shape[0] // 2
            return step(state, dict(batch, weights=w.at[half:].set(0.0)))
        return run
    return build


def full_sync_cutoffs(monkeypatch):
    from repro.core import controller
    predict = controller.CutoffController.predict_cutoff

    def altered(self):
        predict(self)
        return self.n
    monkeypatch.setattr(controller.CutoffController, "predict_cutoff",
                        altered)


def planted(step_fault):
    def plant(monkeypatch):
        import drive_trainer
        monkeypatch.setattr(drive_trainer, "make_train_step",
                            step_fault(drive_trainer.make_train_step))
    plant.__name__ = step_fault.__name__
    return plant


@pytest.mark.parametrize("workload", TRAINER)
@pytest.mark.parametrize("fault", [planted(frozen_step),
                                   planted(half_batch_step),
                                   full_sync_cutoffs])
def test_trainer_fault_is_not_correct(small, capsys, monkeypatch, workload,
                                      fault):
    fault(monkeypatch)
    assert _run(small, capsys, workload)["correct"] is False


def unchanged_windows(monkeypatch):
    from repro.ps import server
    full = server._full_observe_decide

    def keep(params, rings, heads, *args, **kw):
        out = full(params, rings, heads, *args, **kw)
        return (rings, heads) + tuple(out[2:])
    monkeypatch.setattr(server, "_full_observe_decide", keep)


def full_sync_answers(monkeypatch):
    from repro.ps import server
    predict = server.JobHandle.predict_cutoff

    def altered(self):
        predict(self)
        return self.n
    monkeypatch.setattr(server.JobHandle, "predict_cutoff", altered)


@pytest.mark.parametrize("workload", PS)
@pytest.mark.parametrize("fault", [unchanged_windows, full_sync_answers])
def test_ps_fault_is_not_correct(small, capsys, monkeypatch, workload,
                                 fault):
    fault(monkeypatch)
    assert _run(small, capsys, workload)["correct"] is False


@pytest.mark.parametrize("workload", TRAINER + PS)
def test_control_reads_past_a_limit(small, workload):
    """The control (float8 matmul operands in the LM and in the decision)
    fails at least one of the cell's numbers.  A PS cell keeps its own
    number of jobs and runs a fixed number of ticks: its numbers are the
    worst over the ticks of the jobs the check samples."""
    cell = small.entry(small.benchmark()["workloads"], workload)
    tr = small.traffic(cell["traffic"])
    if tr["driver"] == "ps":
        tr = dict(tr, jobs=cell_traffic(cell["traffic"])["jobs"])
    out = small.driver(tr["driver"]).control(
        config=small.config(cell["config"]), traffic=tr, seed=77,
        seconds=3.0, ticks=PS_TICKS, devices=jax.devices()[:1])
    limits = small.limits(workload)
    assert any(out["control"][k] > v for k, v in limits.items()), out
