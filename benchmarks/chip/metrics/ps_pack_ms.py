"""Host time per PS tick packing the flush's upload (the (4, m, n_pad)
block and the keys, on the host): the total of program span ps.pack,
per ps.flush, ms."""
from repro.obs import trace


def read(run):
    spans = getattr(trace, "profiled", dict)().get("spans", {})
    if "ps.flush" not in spans or "ps.pack" not in spans:
        return None
    ticks = spans["ps.flush"]["count"]
    return 1e3 * spans["ps.pack"]["total_s"] / ticks
