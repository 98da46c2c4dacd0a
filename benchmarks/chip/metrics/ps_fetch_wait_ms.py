"""Host time per PS tick in the decision's one host fetch (the
device_get of the batched cutoffs, which waits for the flushed
dispatch): the total of program span ps.fetch, per ps.flush, ms."""
from repro.obs import trace


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "ps.flush" not in spans or "ps.fetch" not in spans:
        return None
    ticks = spans["ps.flush"]["count"]
    return 1e3 * spans["ps.fetch"]["total_s"] / ticks
