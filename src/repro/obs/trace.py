"""Program spans + obs event streams (the telemetry wire format).

:func:`span` is the program's one span call.  Code calls it
unconditionally at every layer boundary (``with span("ps.flush"):``)
and it writes to whoever listens:

  * **the profiler, while it traces** (``jax.profiler.start_trace`` /
    ``trace``): the span enters ``jax.profiler.TraceAnnotation(name)``,
    so it lands in the ``.xplane.pb`` on the same clock as the device's
    ``XLA Ops``, and it is added to the in-memory *profiled summary*
    (:func:`profiled`): per name the count, total seconds and self
    seconds (total less its direct child spans), plus the total over
    top-level spans.  The summary fills only while the profiler traces,
    so it covers exactly the traced window;
  * **every open** :class:`Tracer` (one per open ``ObsRun``): the
    completed span as a record on its ``spans.jsonl`` stream.

With neither listening, :func:`span` returns the shared :data:`NO_SPAN`
— one list check and one ``TraceAnnotation.is_enabled()`` call, no
clock read, no allocation.

:func:`count` adds to a named counter kept from process start (or
:func:`reset_counted`), listened to or not; :func:`counted` reads them.
Code that counts while a step is traced (``attn.fused`` /
``attn.unfused``: the attention sites ``models.attention`` lowers to
the fused kernel or leaves unfused) counts once per trace, in a
benchmark's set-up, so ``reset_profiled`` leaves the counters alone.

Spans are host-edge timestamps only: ``time.perf_counter()`` at enter
and exit, nothing else — a span around a jit dispatch measures dispatch
(the async-dispatch cost model the repo optimizes for), never inserts a
``block_until_ready``.  Nesting is lexical, per thread.

``ObsLog`` subclasses ``controlplane.events.EventLog`` — same
append-only JSONL lines, same strictly-monotone ``seq``, same
torn-tail-tolerant reader (``controlplane.events.read_events``) — with
its own kind vocabulary (``OBS_KINDS``, walked by the
``event-kind-drift`` lint rule alongside ``EVENT_KINDS``).  The one
semantic difference: obs streams are written by several components whose
logical clocks interleave (three trainers behind one PS, a supervisor
beside a trainer), so the event ``tick`` is a per-stream monotone record
index (``ObsLog.autotick``) and the COMPONENT clock (SGD step, PS tick,
job id) travels in the payload.
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import List, Optional

from jax.profiler import TraceAnnotation

OBS_KINDS = (
    "run",        # run-level marker: start / end + registry summary
    "span",       # one completed tracer span (host perf_counter edges)
    "step",       # one trainer step record (the obs step stream)
    "decision",   # one scored cutoff decision (quality layer)
    "metrics",    # one drained device collector payload
)

_profiling = TraceAnnotation.is_enabled
_tracers: list = []           # weakrefs to the open Tracers
_local = threading.local()    # .stack: this thread's open spans
_summary: dict = {}           # name -> [count, total_s, self_s]
_top = [0.0]                  # seconds in top-level profiled spans
_summary_lock = threading.Lock()
_counts: dict = {}            # name -> count (count / counted)


class _NoSpan:
    """What :func:`span` returns when nobody listens."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, typ, value, tb):
        return False


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "attrs", "_ann", "_t0", "_child", "_stack")

    def __init__(self, name: str, attrs: dict, profiling: bool):
        self.name = name
        self.attrs = attrs
        self._ann = TraceAnnotation(name) if profiling else None

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        stack.append(self)
        self._stack = stack
        self._child = 0.0
        self._t0 = time.perf_counter()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        dur = time.perf_counter() - self._t0
        stack = self._stack
        stack.pop()
        if stack:
            stack[-1]._child += dur
        if self._ann is not None:
            with _summary_lock:
                rec = _summary.get(self.name)
                if rec is None:
                    rec = _summary[self.name] = [0, 0.0, 0.0]
                rec[0] += 1
                rec[1] += dur
                rec[2] += dur - self._child
                if not stack:
                    _top[0] += dur
        for ref in _tracers:
            listener = ref()
            if listener is not None:
                listener.record(self.name, self._t0, dur, len(stack) + 1,
                                self.attrs)
        return False


def span(name: str, **attrs):
    """A context manager timing the enclosed host work as ``name``.

    ``attrs`` (component clocks such as ``tick``/``step``/``job``) ride
    on the ``Tracer`` records only; the profiler sees the bare name."""
    profiling = _profiling()
    if profiling or _tracers:
        return _Span(name, attrs, profiling)
    return NO_SPAN


def profiled() -> dict:
    """The profiled summary: ``{}`` until a span completes under a
    profiler trace, else ``{"spans": {name: {"count", "total_s",
    "self_s"}}, "top_level_s": seconds}``."""
    with _summary_lock:
        if not _summary:
            return {}
        return {"spans": {name: {"count": c, "total_s": t, "self_s": s}
                          for name, (c, t, s) in _summary.items()},
                "top_level_s": _top[0]}


def reset_profiled():
    with _summary_lock:
        _summary.clear()
        _top[0] = 0.0


def count(name: str):
    with _summary_lock:
        _counts[name] = _counts.get(name, 0) + 1


def counted() -> dict:
    """``{name: count}`` of every counter since process start."""
    with _summary_lock:
        return dict(_counts)


def reset_counted():
    with _summary_lock:
        _counts.clear()


# below the span machinery: importing the control plane imports its
# supervisor, which imports ``span`` from this module
from repro.controlplane.events import EventLog  # noqa: E402


class ObsLog(EventLog):
    """An ``EventLog`` speaking the obs vocabulary.

    ``autotick`` hands out the per-stream monotone tick; callers pass it
    straight to ``emit`` so the inherited monotonicity check holds by
    construction while component clocks ride in the payload."""

    KINDS = OBS_KINDS

    def __init__(self, path: Optional[str] = None, *, clock=time.time):
        super().__init__(path, clock=clock)
        self._auto = 0

    def autotick(self) -> int:
        t = self._auto
        self._auto += 1
        return t


def _forget(ref):
    if ref in _tracers:
        _tracers.remove(ref)


class Tracer:
    """Records every span the process completes while it is open.

    Open from construction until :meth:`close` (or until it is garbage
    collected).  Each completed span (name, offset ``ts_us`` from tracer
    start, ``dur_us``, nesting ``depth``, plus the span's attribution
    kwargs under a nested ``attrs`` dict — nested so component clocks
    named ``tick``/``step`` can never collide with the EventLog wire
    fields) lands in ``self.spans`` and — when a log is attached — on
    the ``spans.jsonl`` stream.
    """

    def __init__(self, log: Optional[ObsLog] = None):
        self._t0 = time.perf_counter()
        self._log = log
        self.spans: List[dict] = []
        self._ref = weakref.ref(self, _forget)
        _tracers.append(self._ref)

    def record(self, name: str, t0: float, dur: float, depth: int,
               attrs: dict):
        rec = {"name": name, "ts_us": (t0 - self._t0) * 1e6,
               "dur_us": dur * 1e6, "depth": depth, "attrs": attrs}
        self.spans.append(rec)
        if self._log is not None:
            self._log.emit(self._log.autotick(), "span", **rec)

    def close(self):
        _forget(self._ref)
