"""Host time per PS tick outside every program span: the window's wall
time less the time in top-level program spans, per ps.flush, ms.  It is
the caller's own loop, outside the PS."""
from repro.obs import trace


def read(run):
    summary = getattr(trace, "profiled", dict)()
    spans = summary.get("spans", {})
    if "ps.flush" not in spans:
        return None
    ticks = spans["ps.flush"]["count"]
    return 1e3 * (run.window_s - summary["top_level_s"]) / ticks
