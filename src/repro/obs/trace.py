"""Runtime tracing for the SpGEMM stack (jax is imported only once enabled).

One global :class:`Tracer`, **disabled by default**: every instrumentation
point in the library goes through :func:`span` / :func:`instant` /
:func:`sync`, which are true no-ops while disabled — ``span`` returns a
shared singleton context manager (no per-call allocation of trace state),
``sync`` returns its argument untouched, and nothing is ever recorded. The
overhead gate in tests/test_obs.py holds the instrumented hot path to this
contract.

Enabled, the tracer records **host-side wall-clock spans** with proper
nesting (a ``contextvars`` stack, so threads and nested calls interleave
correctly) and explicit **device-sync points**: call sites wrap each phase's
result in :func:`sync`, which blocks until the device work is done before
the span closes — so a span measures compute, not jit dispatch. Under
``jax.jit`` the instrumentation runs once at trace time (spans are tagged
``traced=True`` and never block on tracers); real per-phase numbers come
from calling the instrumented entry points outside jit, or from jitting the
phases separately.

Every recorded event carries an ``id``, the ``parent_id`` of the span it
ran inside, and a ``call_id``: the id of the enclosing :func:`call` span
(``spgemm.call`` at the ``repro.spgemm`` front door), shared by every span
of one library call. An enabled span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so while a JAX profiler
trace runs each span is a host event in the same ``.xplane.pb`` as the
device ops, on the profiler's clock.

Span args are sanitized: numbers/strings/bools pass through, arrays are
reduced to ``dtype+shape`` strings — **matrix values never enter a trace**
(indices/shape metadata only; see README §Observability).

Export: :meth:`Tracer.export_chrome` emits Chrome-trace/Perfetto JSON
(``traceEvents`` with ``ph='X'`` complete events, µs timestamps);
:meth:`Tracer.snapshot` returns the raw span dicts for programmatic joins
(obs/metrics.py consumes it).
"""
from __future__ import annotations

import contextvars
import itertools
import json
import threading
import time
from typing import Any, Dict, List, Optional

MAX_EVENTS = 200_000     # hard buffer bound; beyond it events are counted, not kept

_stack: "contextvars.ContextVar[tuple]" = contextvars.ContextVar(
    "repro_obs_span_stack", default=())
_ids = itertools.count(1)   # event ids; next() is atomic under the GIL


def _clean_args(args: Dict[str, Any]) -> Dict[str, Any]:
    """Sanitize span args: scalars pass, arrays become dtype+shape strings.
    Array *contents* are never recorded (privacy contract)."""
    out: Dict[str, Any] = {}
    for k, v in args.items():
        if v is None or isinstance(v, (bool, int, float, str)):
            out[k] = v
        elif hasattr(v, "item") and getattr(v, "shape", None) == ():
            try:
                out[k] = v.item()
            except Exception:
                out[k] = f"<{type(v).__name__}>"
        else:
            shape = getattr(v, "shape", None)
            dtype = getattr(v, "dtype", "")
            out[k] = (f"<{dtype}{tuple(shape)}>" if shape is not None
                      else f"<{type(v).__name__}>")
    return out


class _NullSpan:
    """Shared do-nothing span returned while tracing is disabled — one
    module-level instance, so a disabled ``span(...)`` allocates no trace
    state whatsoever."""

    __slots__ = ()
    dur_us: Optional[float] = None
    name = ""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):            # parity with Span.set
        return self


NULL_SPAN = _NullSpan()


class Span:
    """One live span. Use as a context manager; ``dur_us`` is readable after
    exit (callers feed it to the metrics registry)."""

    __slots__ = ("tracer", "name", "args", "t0", "dur_us", "_token", "_ann",
                 "id", "parent_id", "call_id", "new_call", "depth", "traced")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any],
                 traced: bool, new_call: bool = False):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.traced = traced
        self.new_call = new_call
        self.t0 = 0
        self.dur_us: Optional[float] = None
        self.id = next(_ids)
        self.parent_id: Optional[int] = None
        self.call_id: Optional[int] = None
        self.depth = 0

    def set(self, **kw) -> "Span":
        """Attach/override args mid-span (e.g. a result's nnz)."""
        self.args.update(kw)
        return self

    def __enter__(self) -> "Span":
        from jax.profiler import TraceAnnotation
        stack = _stack.get()
        parent = stack[-1] if stack else None
        self.parent_id = parent.id if parent else None
        self.call_id = (self.id if self.new_call
                        else parent.call_id if parent else None)
        self.depth = len(stack)
        self._token = _stack.set(stack + (self,))
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack.reset(self._token)
        self.dur_us = (t1 - self.t0) / 1e3
        self.tracer._record(self, t1)
        return False


class Tracer:
    """Thread-safe span/instant recorder (see module docstring)."""

    def __init__(self):
        self._enabled = False
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        self._epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------- control

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, reset: bool = False) -> None:
        if reset:
            self.reset()
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        with self._lock:
            self._events = []
            self._dropped = 0
            self._epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------ recording

    def span(self, name: str, new_call: bool = False, **args) -> Span:
        traced = bool(args.pop("traced", False)) or _under_jit()
        return Span(self, name, _clean_args(args), traced, new_call)

    def instant(self, name: str, **args) -> None:
        """Record a point event (chrome ``ph='i'``) in the current span."""
        if not self._enabled:
            return
        now = time.perf_counter_ns()
        stack = _stack.get()
        parent = stack[-1] if stack else None
        self._append({"name": name, "ph": "i",
                      "ts_us": (now - self._epoch_ns) / 1e3, "dur_us": 0.0,
                      "tid": threading.get_ident() & 0xFFFF,
                      "depth": len(stack), "id": next(_ids),
                      "parent_id": parent.id if parent else None,
                      "call_id": parent.call_id if parent else None,
                      "args": _clean_args(args)})

    def _record(self, sp: Span, t1_ns: int) -> None:
        if not self._enabled:
            return
        args = sp.args
        if sp.traced:
            args = dict(args, traced=True)
        self._append({"name": sp.name, "ph": "X",
                      "ts_us": (sp.t0 - self._epoch_ns) / 1e3,
                      "dur_us": (t1_ns - sp.t0) / 1e3,
                      "tid": threading.get_ident() & 0xFFFF,
                      "depth": sp.depth, "id": sp.id,
                      "parent_id": sp.parent_id, "call_id": sp.call_id,
                      "args": args})

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) < MAX_EVENTS:
                self._events.append(ev)
            else:
                self._dropped += 1

    # -------------------------------------------------------------- export

    def snapshot(self) -> Dict[str, Any]:
        """Plain-dict copy of every recorded event (programmatic joins)."""
        with self._lock:
            events = [dict(e) for e in self._events]
            dropped = self._dropped
        return {"events": events, "dropped": dropped}

    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """Recorded complete spans, optionally filtered by exact name."""
        snap = self.snapshot()["events"]
        return [e for e in snap
                if e["ph"] == "X" and (name is None or e["name"] == name)]

    def export_chrome(self, path: Optional[str] = None,
                      extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Chrome-trace/Perfetto JSON: ``{"traceEvents": [...]}`` with µs
        timestamps. ``extra`` keys (e.g. a metrics snapshot) are merged at
        the top level — trace viewers ignore unknown keys."""
        snap = self.snapshot()
        trace_events = []
        for e in snap["events"]:
            trace_events.append({
                "name": e["name"], "cat": "repro", "ph": e["ph"],
                "ts": e["ts_us"], "dur": e["dur_us"], "pid": 0,
                "tid": e["tid"], "args": e["args"]})
        out: Dict[str, Any] = {"traceEvents": trace_events,
                               "displayTimeUnit": "ms"}
        if snap["dropped"]:
            out["droppedEvents"] = snap["dropped"]
        if extra:
            out.update(extra)
        if path is not None:
            with open(path, "w") as f:
                json.dump(out, f, indent=1)
        return out


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def is_enabled() -> bool:
    return _tracer._enabled


def enable(reset: bool = False) -> None:
    _tracer.enable(reset=reset)


def disable() -> None:
    _tracer.disable()


def reset() -> None:
    _tracer.reset()


def _under_jit() -> bool:
    """True while jax is tracing (jit, vmap, grad: spans then measure trace
    time, flagged). Reads JAX's trace state and dispatches nothing."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


def span(name: str, **args):
    """The library-wide instrumentation point. Disabled: returns the shared
    null span — no state allocated, nothing recorded."""
    if not _tracer._enabled:
        return NULL_SPAN
    return _tracer.span(name, **args)


def call(name: str, **args):
    """A span that starts one library call: it and every event inside it
    carry its id as ``call_id``. Disabled: the shared null span."""
    if not _tracer._enabled:
        return NULL_SPAN
    return _tracer.span(name, new_call=True, **args)


def instant(name: str, **args) -> None:
    if _tracer._enabled:
        _tracer.instant(name, **args)


def sync(x):
    """Device-sync point: block until ``x``'s arrays are ready — only while
    tracing (so spans measure compute, not dispatch) and only on concrete
    arrays (tracers pass through untouched). Returns ``x``."""
    if not _tracer._enabled:
        return x
    import jax
    for leaf in jax.tree_util.tree_leaves(x):
        if isinstance(leaf, jax.core.Tracer):
            continue
        blk = getattr(leaf, "block_until_ready", None)
        if blk is not None:
            blk()
    return x


def export_chrome(path: Optional[str] = None, extra=None) -> Dict[str, Any]:
    return _tracer.export_chrome(path, extra=extra)
