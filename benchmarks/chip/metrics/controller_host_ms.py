"""Host time per Trainer step in the DMM controller's predict_cutoff
(less its cutoff fetch, program span controller.fetch) and observe: the
self time of program spans controller.predict_cutoff and
controller.observe, per trainer.step, ms."""
from repro.obs import trace

SPANS = ("controller.predict_cutoff", "controller.observe")


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "trainer.step" not in spans or SPANS[0] not in spans:
        return None
    total = sum(spans[k]["self_s"] for k in SPANS if k in spans)
    return 1e3 * total / spans["trainer.step"]["count"]
