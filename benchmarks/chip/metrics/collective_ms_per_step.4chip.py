"""Device time of the collective operations (all-reduce, all-gather,
reduce-scatter, ...) per Trainer step, the mean over the chips, ms."""


def read(run):
    if run.trace is None or run.chips < 2 or "steps" not in run.counts:
        return None
    per_chip = sum(run.trace.collective_s) / len(run.trace.collective_s)
    return 1e3 * per_chip / run.counts["steps"]
