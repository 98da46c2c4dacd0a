"""repro.obs — the zero-sync telemetry spine.

Device-side metric rings, host-edge spans on the profiler's clock, and
decision-quality scoring for every cutoff policy; see
``src/repro/obs/README.md`` for the contracts (ring drain rules, span
schema, calibration definitions).

The names below load on first use, so that the controller, the PS and
the control plane can import ``repro.obs.trace.span`` without pulling
in the quality layer, which itself wraps the controllers.
"""
import importlib

_EXPORTS = {
    "Counter": "metrics", "Gauge": "metrics", "LabelSet": "metrics",
    "MetricHistogram": "metrics", "MetricRing": "metrics",
    "MetricsRegistry": "metrics", "Series": "metrics",
    "DecisionRecorder": "quality", "QualityController": "quality",
    "score_decision": "quality",
    "ObsRun": "run", "StepStream": "run",
    "OBS_KINDS": "trace", "ObsLog": "trace", "Tracer": "trace",
    "span": "trace",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name not in _EXPORTS:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    return getattr(importlib.import_module(f"repro.obs.{_EXPORTS[name]}"),
                   name)
