"""Attention sites lowered to the fused flash attention, of all attention
sites the program traced (program counters attn.fused and attn.unfused,
counted when the step is traced, in set-up), in %."""
from repro.obs import trace


def read(run):
    counts = getattr(trace, "counted", dict)()
    fused = counts.get("attn.fused", 0)
    total = fused + counts.get("attn.unfused", 0)
    if total == 0:
        return None
    return 100.0 * fused / total
