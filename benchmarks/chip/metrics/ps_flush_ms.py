"""Host time of PSServer.flush per tick (span bench.flush), the mean over
the window, ms."""


def read(run):
    host = run.trace.host if run.trace is not None else {}
    if "bench.flush" not in host:
        return None
    seconds, count = host["bench.flush"]
    return 1e3 * seconds / count
