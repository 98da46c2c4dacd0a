"""Host time per Trainer step in the batch, the workers' times and the
step's dispatch (spans bench.data, bench.timer, bench.dispatch), ms."""


def read(run):
    host = run.trace.host if run.trace is not None else {}
    if "bench.dispatch" not in host:
        return None
    steps = host["bench.dispatch"][1]
    total = sum(host.get(k, (0.0, 0))[0]
                for k in ("bench.data", "bench.timer", "bench.dispatch"))
    return 1e3 * total / steps
