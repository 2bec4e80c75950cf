"""Device seconds per ``jax.named_scope``, from a JAX profiler trace.

On a TPU the metadata of each ``XLA Ops`` event carries a ``tf_op`` stat:
the op's HLO ``op_name``, the jit names and named scopes it ran under,
e.g. ``jit(_numeric_scatter)/jit(_search_slots)/numeric.search/sort``. A
fusion carries the op_name of its root instruction, so a fusion that spans
two scopes counts under the scope of its root op. Copies and other ops
without an op_name count under no scope.

``jax.profiler.ProfileData`` does not hand out event-metadata stats, so
:func:`device_ops` reads the ``XSpace`` protobuf's wire format itself and
decodes only the device planes.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

import tracing

SCOPE_STAT = "tf_op"
TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "bench_out")

Op = Tuple[float, float, str]       # start ns, end ns, op_name


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0,
            hi: Optional[int] = None) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for varint and
    fixed-width fields, a ``(start, end)`` slice for length-delimited."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif kind == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, val


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[tuple]]:
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_ops(buf: bytes, plane) -> Tuple[str, List[Op]]:
    """The name of one plane and, where it is a device plane, its ``XLA
    Ops`` events with the scope stat of each."""
    plane_name, lines, event_md, stat_names = "", [], {}, {}
    for f, v in _fields(buf, *plane):
        if f == 2:
            plane_name = _text(buf, v)
            if not plane_name.startswith("/device:"):
                return plane_name, []
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, md = _map_entry(buf, v)
            event_md[k] = md
        elif f == 5:
            k, md = _map_entry(buf, v)
            stat_names[k] = next((_text(buf, s) for n, s in
                                  _fields(buf, *md) if n == 2), "")
    scope_id = next((k for k, n in stat_names.items() if n == SCOPE_STAT),
                    None)

    def scope_of(md) -> str:
        for f, st in _fields(buf, *md):
            if f != 5:
                continue
            stat = dict(_fields(buf, *st))
            if stat.get(1) != scope_id:
                continue
            if 5 in stat:
                return _text(buf, stat[5])
            if 7 in stat:
                return stat_names.get(stat[7], "")
        return ""

    scopes: Dict[int, str] = {}
    ops: List[Op] = []
    for line in lines:
        fields = list(_fields(buf, *line))
        name = next((_text(buf, v) for f, v in fields if f == 2), "")
        if name != tracing.OPS_LINE:
            continue
        t0_ns = next((v for f, v in fields if f == 3), 0)
        for f, ev in fields:
            if f != 4:
                continue
            e = dict(_fields(buf, *ev))
            md = e.get(1, 0)
            if md not in scopes:
                scopes[md] = (scope_of(event_md[md])
                              if md in event_md and scope_id else "")
            start = t0_ns + e.get(2, 0) / 1e3
            ops.append((start, start + e.get(3, 0) / 1e3, scopes[md]))
    return plane_name, ops


def device_ops(path: str) -> Dict[str, List[Op]]:
    """``{plane name: [(start ns, end ns, op_name), ...]}`` of the ``XLA
    Ops`` events of every device plane of an ``.xplane.pb`` file."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for field, plane in _fields(buf):
        if field == 1:
            name, ops = _plane_ops(buf, plane)
            if ops:
                out[name] = ops
    return out


def in_scope(op_name: str, scope: str) -> bool:
    """Whether an op's ``op_name`` lies under the named scope ``scope``."""
    return f"/{scope}/" in f"/{op_name}"


def scope_seconds(ops: Dict[str, List[Op]], window: Tuple[float, float],
                  scope: str) -> Optional[float]:
    """Seconds of the window in which ops under ``scope`` ran, averaged
    over the devices that ran one; None where no op lies under it."""
    lo, hi = window
    per_device = []
    for dev_ops in ops.values():
        hit = [(max(s, lo), min(e, hi)) for s, e, name in dev_ops
               if e > lo and s < hi and in_scope(name, scope)]
        if hit:
            per_device.append(sum(e - s for s, e in tracing.union(hit)))
    if not per_device:
        return None
    return sum(per_device) / len(per_device) / 1e9


def find_trace(root: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``root`` (by default ``TRACES``,
    where ``run.py`` points the profiler), or None."""
    paths = glob.glob(os.path.join(root or TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def per_product(ctx, scope: str,
                root: Optional[str] = None) -> Optional[float]:
    """Device seconds per window product of the ops under ``scope``, read
    from the run's trace: the newest one under ``root``, taken only where
    its window is the one ``ctx.trace`` summarized."""
    if ctx.trace is None or not ctx.products:
        return None
    path = find_trace(root)
    if path is None:
        return None
    window = tracing.read_events(path).window
    if window is None or abs((window[1] - window[0]) / 1e9
                             - ctx.trace.window_s) > 1e-6:
        return None
    got = scope_seconds(device_ops(path), window, scope)
    return None if got is None else got / ctx.products
