"""Host time per PS tick in the flush's uploads and jit call: the total
of program span ps.dispatch, per ps.flush, ms."""
from repro.obs import trace


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "ps.flush" not in spans or "ps.dispatch" not in spans:
        return None
    ticks = spans["ps.flush"]["count"]
    return 1e3 * spans["ps.dispatch"]["total_s"] / ticks
