"""Model FLOPs of the window's steps (lm_flops.per_token: 6 x matmul
parameters + causal attention, no recompute) over window seconds x chips x
the chip's bf16 peak (peaks.py), in %."""
import peaks


def read(run):
    flops = run.counts.get("model_flops")
    if flops is None:
        return None
    peak = peaks.peak(run.device_kind)["bf16_flops"]
    return 100.0 * flops / (run.window_s * run.chips * peak)
