"""Host time per PS tick in PSServer.predict_cutoff, all jobs, less the
decision fetch inside it: the self time of program span
ps.predict_cutoff (its child ps.fetch excluded), per ps.flush, ms."""
from repro.obs import trace


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "ps.flush" not in spans or "ps.predict_cutoff" not in spans:
        return None
    ticks = spans["ps.flush"]["count"]
    return 1e3 * spans["ps.predict_cutoff"]["self_s"] / ticks
