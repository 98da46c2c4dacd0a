"""95th percentile of the wall time of every tick of the window (ms)."""
import numpy as np


def read(run):
    ticks = run.samples.get("tick_s")
    if not ticks:
        return None
    return 1e3 * float(np.percentile(ticks, 95))
