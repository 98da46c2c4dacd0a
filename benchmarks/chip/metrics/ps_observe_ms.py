"""Host time per PS tick in PSServer.observe, all jobs: the self time of
program span ps.observe, per ps.flush, ms."""
from repro.obs import trace


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "ps.flush" not in spans or "ps.observe" not in spans:
        return None
    ticks = spans["ps.flush"]["count"]
    return 1e3 * spans["ps.observe"]["self_s"] / ticks
