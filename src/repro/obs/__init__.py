"""repro.obs — jax-optional telemetry for the partitioning runtime.

Round-level tracing (nested spans + counters → per-host JSONL logs, and
``jax.profiler`` annotations once jax is loaded, ``trace``), one shared
peak-RSS implementation (``rss``), Chrome ``trace_event`` / Perfetto
export (``export``), run-directory aggregation into per-phase /
per-round summaries (``report``), and the live side: the store-backed
per-host metrics bus (``live``) plus the stall/straggler monitor and
Prometheus exposition behind ``scripts/monitor_run.py`` (``monitor``).
See docs/DESIGN-observability.md for the event schema, span taxonomy
and live-bus snapshot schema.

JSONL tracing is off by default and near-zero cost when off: the
module-level ``trace.span`` / ``trace.counter`` front door checks one
global (plus, once jax is loaded, a profiler annotation of about a
microsecond per span).  Turn it on with ``REPRO_TRACE=1`` (or
``REPRO_TRACE=<dir>``) or by calling ``trace.configure`` explicitly — the multihost launcher's ``--trace-dir``
does the latter per worker.

Re-exports resolve lazily (PEP 562) and every submodule imports without
jax — the benchmark RSS children and the report CLI must never pay (or
depend on) a jax import.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "Tracer": "repro.obs.trace",
    "add": "repro.obs.trace",
    "configure": "repro.obs.trace",
    "counter": "repro.obs.trace",
    "disable": "repro.obs.trace",
    "enabled": "repro.obs.trace",
    "from_env": "repro.obs.trace",
    "get_tracer": "repro.obs.trace",
    "log_name": "repro.obs.trace",
    "span": "repro.obs.trace",
    "traced": "repro.obs.trace",
    "watch_compiles": "repro.obs.trace",
    "peak_rss_kb": "repro.obs.rss",
    "vm_hwm_kb": "repro.obs.rss",
    "vm_rss_kb": "repro.obs.rss",
    "chrome_trace": "repro.obs.export",
    "host_logs": "repro.obs.export",
    "load_events": "repro.obs.export",
    "merge_events": "repro.obs.export",
    "write_chrome_trace": "repro.obs.export",
    "legacy_timing": "repro.obs.report",
    "render": "repro.obs.report",
    "summarize_run": "repro.obs.report",
    "LiveBus": "repro.obs.live",
    "host_metrics": "repro.obs.live",
    "live_enabled": "repro.obs.live",
    "load_snapshots": "repro.obs.live",
    "metrics_name": "repro.obs.live",
    "publish": "repro.obs.live",
    "tail_snapshots": "repro.obs.live",
    "BusMonitor": "repro.obs.monitor",
    "MonitorConfig": "repro.obs.monitor",
    "render_dashboard": "repro.obs.monitor",
    "render_prometheus": "repro.obs.monitor",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        value = getattr(importlib.import_module(_EXPORTS[name]), name)
        globals()[name] = value          # cache for subsequent lookups
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
