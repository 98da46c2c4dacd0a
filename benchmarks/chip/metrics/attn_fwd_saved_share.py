"""Fused attention sites whose forward output and logsumexp the train
step's layer checkpoint keeps, so the backward runs no forward kernel
again, of the fused sites the program traced (program counters
attn.fwd_saved and attn.fused, counted when the step is traced, in
set-up), in %.  Silent where the program keeps no such counter."""
from repro.obs import trace


def read(run):
    counts = getattr(trace, "counted", dict)()
    fused = counts.get("attn.fused", 0)
    if "attn.fwd_saved" not in counts or fused == 0:
        return None
    return 100.0 * counts["attn.fwd_saved"] / fused
