"""The comparison of a DMM controller's recorded decisions with the
float64 replay of ``dmm_reference.py``, shared by the cells that run one.

A record is what the program decided and observed, step by step, for a
set of jobs: ``times`` (steps, J, n) the workers' step times, ``cuts``
(steps, J) its cutoffs, ``iters`` (steps, J) its predicted iteration time
E[x_(c)], and the final windows (J, lag+1, n).  The replay runs the same
decisions on the same observations (the finished masks the program's
cutoffs made), so that one decision's error does not steer the next.
"""
from __future__ import annotations

import numpy as np

import dmm_reference


def replay(params, windows, scales, seeds, times, cuts, *, k_samples: int,
           min_frac: float, low=None, store=None,
           cls=dmm_reference.Replay) -> dict:
    """The reference's cutoffs, omega curves, E[x_(c)] at every cutoff and
    final windows over the record; ``low`` and ``store`` make it the
    control (dmm_reference.py)."""
    rank = np.argsort(np.argsort(times, axis=2), axis=2)
    finished = rank < np.asarray(cuts)[:, :, None]
    model = cls(params, np.asarray(windows), scales, seeds,
                k_samples=k_samples, min_frac=min_frac, low=low, store=store)
    ref_cuts, omegas, iters, window = dmm_reference.replay(model, times,
                                                           finished)
    return {"cuts": ref_cuts, "omegas": omegas, "iters": iters,
            "window": window, "finished": finished}


def pick(a, cuts):
    """a[..., c - 1] for each cutoff c."""
    return np.take_along_axis(a, np.asarray(cuts)[..., None] - 1, -1)[..., 0]


def gaps(cuts, iters, window, ref) -> dict:
    """The numbers compared, each the worst over steps and jobs.

    cutoff_gap: how far below the reference's best throughput (omega) the
    cutoff lies, as a share of that best.  iter_time_gap: the predicted
    iteration time against the reference's at the same cutoff, relative:
    the decision's arithmetic (guide, transition, emission, the sort).
    window_gap: the final windows at the entries the workers reported (the
    last rows are the last steps' observations; imputed entries are left
    out): the observations reach the window."""
    best = pick(ref["omegas"], ref["cuts"])
    at = pick(ref["iters"], cuts)
    rows = min(ref["finished"].shape[0], window.shape[1])
    obs = ref["finished"][-rows:].transpose(1, 0, 2)
    want = ref["window"][:, -rows:]
    return {
        "cutoff_gap": float(np.max((best - pick(ref["omegas"], cuts))
                                   / best)),
        "iter_time_gap": float(np.max(np.abs(iters - at) / at)),
        "window_gap": float(np.max(np.abs(window[:, -rows:] - want)[obs]
                                   / np.abs(want)[obs])),
    }


class Unchanged(dmm_reference.Replay):
    """A fault: a window that never takes a new row."""

    def observe(self, times, finished, u):
        pass


def faults(params, windows, scales, seeds, times, cuts, iters, finals, *,
           k_samples: int, min_frac: float, n: int) -> dict:
    """Readings of the program, the control (float8 matmul operands, the
    window stored in bfloat16) and the faults a controller can have, on
    one record: a window that never changes, and every answer altered
    (each cutoff moved to full sync, or one worker below it where it was
    full sync).  ``bf16_operands`` is
    the replay with bfloat16 matmul operands, the TPU's default precision
    for float32: where the record was made off the chip, it stands for
    the program's reading there."""
    kw = dict(k_samples=k_samples, min_frac=min_frac)
    args = (params, windows, scales, seeds, times, cuts)
    ref = replay(*args, **kw)
    out = {"program": gaps(cuts, iters, finals, ref)}
    for name, extra in (("control", {"low": "float8_e4m3fn",
                                     "store": "bfloat16"}),
                        ("bf16_operands", {"low": "bfloat16"}),
                        ("state_unchanged", {"cls": Unchanged})):
        r = replay(*args, **kw, **extra)
        out[name] = gaps(r["cuts"], pick(r["iters"], r["cuts"]), r["window"],
                         ref)
    cuts = np.asarray(cuts)
    altered = np.where(cuts < n, n, n - 1)
    out["answer_altered"] = gaps(altered, iters, finals, ref)
    return out
