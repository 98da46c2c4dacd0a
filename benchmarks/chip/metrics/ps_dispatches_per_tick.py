"""Decision dispatches per PS tick: the count of program spans
ps.dispatch (the flush's fused observe+decide) and ps.decide (a
decide-only dispatch), per ps.flush."""
from repro.obs import trace


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "ps.flush" not in spans:
        return None
    dispatches = sum(spans[k]["count"] for k in ("ps.dispatch", "ps.decide")
                     if k in spans)
    return dispatches / spans["ps.flush"]["count"]
