"""Host time per Trainer step in the controller's predict_cutoff and
observe (spans bench.decide, bench.observe), ms.  The wait for the fused
decision, which is queued behind the train step on the device, has its own
span (bench.decision_wait) and is not counted here."""


def read(run):
    host = run.trace.host if run.trace is not None else {}
    if "bench.dispatch" not in host or "bench.decide" not in host:
        return None
    steps = host["bench.dispatch"][1]
    total = host["bench.decide"][0] + host.get("bench.observe", (0.0, 0))[0]
    return 1e3 * total / steps
