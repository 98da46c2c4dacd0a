"""Host time per tick blocked in the tick's first predict_cutoff, which
fetches the batched decision to the host (span bench.fetch), ms."""


def read(run):
    host = run.trace.host if run.trace is not None else {}
    if "bench.fetch" not in host:
        return None
    seconds, count = host["bench.fetch"]
    return 1e3 * seconds / count
