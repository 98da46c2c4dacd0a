"""What every cell shares: finding a cell's files by name, the device gate,
the compile cache, the compile clock, and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its files:

  configs/<config>.json    sizes as run, source, reduced, assumed
  traffic/<traffic>.json   the mix; its "driver" names drive_<driver>.py
  limits/<workload>.json   the limit of every number compared
  metrics/<metric>.py      read(run) -> number or None, one per metric

so that a cell, a configuration or a metric is added with files and
``BENCHMARK.json`` entries alone.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def entry(items: list, name: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no entry named {name!r}; known: "
                   f"{sorted(i['name'] for i in items)}")


def config(name: str) -> dict:
    return _json(HERE, "configs", f"{name}.json")


def traffic(name: str) -> dict:
    return _json(HERE, "traffic", f"{name}.json")


def limits(workload: str) -> dict:
    return _json(HERE, "limits", f"{workload}.json")


def driver(name: str):
    return importlib.import_module(f"drive_{name}")


def reader(metric: str) -> Callable:
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metrics a run of ``workload`` reports: the end-to-end ones with
    ``trace`` off, the per-layer ones with it on."""
    items = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in items
            if workload in m.get("workloads", [workload])]


@dataclasses.dataclass
class Run:
    """What a driver measured in one run, for the metric readers."""
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    setup_s: float
    window_s: float
    counts: dict                     # work done in the window
    samples: dict                    # per-event host times in the window
    trace: Any = None                # trace_reduce.Summary (--trace 1)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # NaN compares False: a check that read nothing fails
        return self.value <= self.limit


def device_gate(chips: int):
    """The chips of this run.  Exits non-zero, printing no result, unless
    JAX's first device is a TPU and there are ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX's first device is {devices[0].platform!r}, not "
              f"a TPU; refusing to run", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def place_compile_cache() -> str:
    """JAX's persistent cache where the program puts it (a fixed path
    inside the checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every
    program cached, so that a second run of a cell compiles nothing."""
    import jax
    from repro.launch.compile_cache import place_compile_cache as place
    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Sums XLA compile time while active (a persistent-cache read counts
    as a compile of its read time) and counts compiles and cache hits."""

    def __init__(self):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(entries: list, run: Run, *, required: bool) -> dict:
    out = {}
    for m in entries:
        value = reader(m["name"])(run)
        if value is None:
            if required:
                raise RuntimeError(f"metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(*, checks: list, attempted: int, failed: int, metrics: dict,
         device: dict, breakdown: Optional[dict] = None):
    """Print each compared number beside its limit as the last lines of
    stderr, and the result as the last line of stdout."""
    correct = bool(checks) and all(c.ok for c in checks)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    res = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return correct


def run_cell(bench: dict, cell: dict, devices, *, seed: int, seconds: float,
             trace: bool, t_start: float) -> int:
    """One run of ``cell``: set-up, the window, the check; prints the
    result line.  Returns the exit code."""
    place_compile_cache()
    config_ = config(cell["config"])
    traffic_ = traffic(cell["traffic"])
    run, checks, attempted, peak = driver(traffic_["driver"]).run(
        workload=cell, config=config_, traffic=traffic_, seed=seed,
        seconds=seconds, trace=trace, t_start=t_start, devices=devices)
    metrics = read_metrics(cell_metrics(bench, cell["name"], trace), run,
                           required=not trace)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        device["busy_s"] = sum(run.trace.busy_s) / len(run.trace.busy_s)
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops,
                     "idle_gaps": run.trace.gaps}
    emit(checks=checks, attempted=attempted, failed=0, metrics=metrics,
         device=device, breakdown=breakdown)
    return 0
