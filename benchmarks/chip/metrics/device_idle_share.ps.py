"""1 - (union of the device's operation intervals / traced window), in %:
parameter-server cells."""


def read(run):
    if run.trace is None or "decisions" not in run.counts:
        return None
    return 100.0 * run.trace.idle_share
