"""Cutoffs delivered to jobs (jobs x ticks completed) over the window's
wall time."""


def read(run):
    if "decisions" not in run.counts:
        return None
    return run.counts["decisions"] / run.window_s
