"""Seconds from process start to the first timed step or tick: imports,
weights and state, compile or cache reads, the warm-up steps."""


def read(run):
    return run.setup_s
