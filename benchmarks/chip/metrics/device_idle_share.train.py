"""1 - (union of the device's operation intervals / traced window), the
mean over the chips, in %: Trainer cells."""


def read(run):
    if run.trace is None or "tokens" not in run.counts:
        return None
    return 100.0 * run.trace.idle_share
