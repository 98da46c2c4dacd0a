"""From a profiler trace (``.xplane.pb``) to what the metric readers need.

The run marks its measured window with a host span ``bench.window`` and
the work on the host with spans ``bench.<what>`` (``TraceAnnotation``).
Device planes are those named ``/device:TPU:<i>``; their ``XLA Ops``
line holds one event per operation run on the chip.

  busy_s        union of the operations' intervals inside the window,
                per chip
  collective_s  summed time of the collective operations (all-reduce,
                all-gather, reduce-scatter, all-to-all, collective-permute),
                per chip
  top_ops       operations by total time on all chips
  gaps          the device's idle intervals inside the window, each with
                the innermost host span that covers its middle
  host          total seconds and count of each host span in the window
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute", re.I)
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: list                 # per chip
    collective_s: list           # per chip
    top_ops: list                # [(name, seconds)] over all chips
    gaps: list                   # [(host span, seconds)], longest first
    host: dict                   # span name -> (seconds, count)

    @property
    def idle_share(self) -> float:
        """1 - busy / window, the mean over the chips."""
        return 1.0 - sum(self.busy_s) / (len(self.busy_s) * self.window_s)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(event: str) -> str:
    """An operation's name: the TPU's trace names each event by its whole
    HLO instruction ("%fusion.3 = bf16[...] fusion(...), ..."); the part
    before " = " is the name, unique within its program."""
    return event.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def summarize(path: str, top: int = 10) -> Summary:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), top)


def from_profile(pd, top: int = 10) -> Summary:
    """The summary of a ``jax.profiler.ProfileData``."""
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            line = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not line:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                                 f"{[ln.name for ln in plane.lines]}")
            devices.append((plane.name, [
                (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line[0].events]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"want one {WINDOW} span, found {len(windows)}")
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    _, w0, w1 = windows[0]
    inner = [s for s in spans if s[0] != WINDOW and s[1] < w1 and s[2] > w0]
    host = defaultdict(lambda: [0.0, 0])
    for name, s, e in inner:
        host[name][0] += (min(e, w1) - max(s, w0)) / 1e9
        host[name][1] += 1
    busy, coll, per_op, gaps = [], [], defaultdict(float), []
    for _, events in sorted(devices):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in events
                   if e > w0 and s < w1]
        merged = _union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        coll.append(sum(e - s for n, s, e in clipped
                        if COLLECTIVE.search(n)) / 1e9)
        for n, s, e in clipped:
            per_op[n] += (e - s) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [sp for sp in inner if sp[1] <= mid <= sp[2]]
        label = (min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover
                 else "no host span")
        labelled.append([label, (e - s) / 1e9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy, collective_s=coll,
                   top_ops=[[n, s] for n, s in ops], gaps=labelled,
                   host={k: tuple(v) for k, v in host.items()})
