"""Host time per Trainer step making the batch, taking the workers'
times and dispatching the step: the self time of program spans
trainer.batch, trainer.timer and train.dispatch, per trainer.step, ms."""
from repro.obs import trace

SPANS = ("trainer.batch", "trainer.timer", "train.dispatch")


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "trainer.step" not in spans:
        return None
    total = sum(spans[k]["self_s"] for k in SPANS if k in spans)
    return 1e3 * total / spans["trainer.step"]["count"]
