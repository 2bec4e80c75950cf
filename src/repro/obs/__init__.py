"""repro.obs — tracing + metrics for the SpGEMM stack.

Disabled by default and free when disabled; ``repro.obs.enable()`` turns on
span recording (trace.py, mirrored into the JAX profiler's trace) and
counters/planner-evidence (metrics.py), including the backend-compile
counter.

    import repro.obs as obs
    obs.enable()
    c = spgemm_coo(a, b)                  # instrumented library call
    obs.export_chrome("trace.json")       # Perfetto / chrome://tracing
    obs.snapshot()["metrics"]["planner"]  # est-vs-measured per plan
"""
from __future__ import annotations

from typing import Any, Dict

from . import metrics, trace
from .trace import (NULL_SPAN, Span, Tracer, call, export_chrome,
                    get_tracer, instant, is_enabled, span, sync)


def enable(reset: bool = False) -> None:
    """Turn on tracing + metrics. ``reset=True`` clears prior recordings."""
    if reset:
        trace.reset()
        metrics.reset()
    trace.enable()
    metrics.watch_compiles()


def disable() -> None:
    trace.disable()


def reset() -> None:
    trace.reset()
    metrics.reset()


def snapshot() -> Dict[str, Any]:
    """Combined plain-dict snapshot: ``{"trace": ..., "metrics": ...}``."""
    return {"trace": trace.get_tracer().snapshot(),
            "metrics": metrics.snapshot()}


__all__ = [
    "trace", "metrics", "enable", "disable", "reset", "snapshot",
    "span", "call", "sync", "instant", "is_enabled", "export_chrome",
    "get_tracer", "Span", "Tracer", "NULL_SPAN",
]
