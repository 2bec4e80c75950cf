"""Reduction of a JAX profiler trace of the measured window to the
numbers the per-layer metrics and the breakdown read.

Device planes are the ``/device:...`` planes that carry an ``XLA Ops``
line; busy time is the union of the intervals of their op events inside
the window, averaged over the devices that ran anything. The window is
the ``bench.window`` annotation the harness puts around the measured
loop. Each of the longest idle gaps is labelled by the innermost host
event that spans its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LABELLED_GAPS = 256     # the longest gaps get a host label each

Interval = Tuple[float, float]


@dataclasses.dataclass
class Events:
    """The trace reduced to plain intervals, in nanoseconds."""

    window: Optional[Interval]
    device_ops: Dict[str, List[Tuple[float, float, str]]]   # per device
    host: List[Tuple[float, float, str]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over devices that ran ops
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_events(path: str) -> Events:
    """Device op and host events of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, device, host = None, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.start_ns, e.end_ns, e.name)
                               for e in line.events]
            if ops:
                device[plane.name] = _with_module(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.start_ns, e.end_ns, e.name))
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
    return Events(window=window, device_ops=device, host=host)


def _with_module(ops, modules):
    """Name each op ``<module>/<op>`` after the program that ran it."""
    if not modules:
        return ops
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and modules[i][1] >= s:
            name = f"{modules[i][2]}/{name}"
        out.append((s, e, name))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _labels(times, host) -> List[str]:
    """The innermost host event (latest start) spanning each time."""
    import numpy as np
    if not host:
        return ["(no host event)"] * len(times)
    starts = np.array([h[0] for h in host], dtype=np.float64)
    ends = np.array([h[1] for h in host], dtype=np.float64)
    out = []
    for t in times:
        hit = np.flatnonzero((starts <= t) & (ends >= t))
        if hit.size == 0:
            out.append("(no host event)")
            continue
        i = hit[np.lexsort((ends[hit], -starts[hit]))[0]]
        out.append(host[i][2].lstrip("$"))
    return out


def summarize(ev: Events, top: int = 10) -> Optional[Summary]:
    """Busy time, top ops and labelled idle gaps over the window; None
    when the trace holds no window or no device op."""
    if ev.window is None or not ev.device_ops:
        return None
    lo, hi = ev.window
    busy, ops = [], defaultdict(float)
    merged_all = []
    for dev_ops in ev.device_ops.values():
        clipped = _clip([(s, e) for s, e, _ in dev_ops], lo, hi)
        merged = union(clipped)
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged))
        merged_all.extend(merged)
        for s, e, name in dev_ops:
            if e > lo and s < hi:
                ops[name] += (min(e, hi) - max(s, lo)) / 1e9
    if not busy:
        return None
    merged = union(merged_all)
    gaps = []
    edge = lo
    for s, e in merged + [(hi, hi)]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    host = [h for h in ev.host if h[1] > lo and h[0] < hi]
    gaps.sort(key=lambda g: g[0] - g[1])
    by_label = defaultdict(float)
    named = gaps[:LABELLED_GAPS]
    for (s, e), name in zip(named, _labels([(s + e) / 2 for s, e in named],
                                          host)):
        by_label[name] += (e - s) / 1e9
    rest = sum(e - s for s, e in gaps[LABELLED_GAPS:]) / 1e9
    if rest:
        by_label[f"(the {len(gaps) - LABELLED_GAPS} shorter gaps)"] += rest
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9,
                   top_ops=[[k, v] for k, v in rank(ops)],
                   idle_gaps=[[k, v] for k, v in rank(by_label)])
