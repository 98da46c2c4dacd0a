"""Tokens of every global batch whose step finished in the window, over
the window's wall time (it ends once the last step's state is ready)."""


def read(run):
    if "tokens" not in run.counts:
        return None
    return run.counts["tokens"] / run.window_s
