"""The 'search' accumulate's share of its roofline: the least time the
chip could take to accumulate the window's products over the device time
of the accumulate's three programs, emit, align and land (device trace).

The least time of one product's accumulate is its compulsory bytes over
the HBM bandwidth: every valid product read once and every output entry
written once, 12 B each (a float32 value and two int32 coordinates). Both
counts come from the configuration's pattern, not from what implements
the accumulate. The device time is the union of the intervals of every op
the trace's ``XLA Modules`` line places in one of the three programs,
inside the window. None without the chip's peaks, without a trace of the
window, or where no op of the three programs ran.
"""

import scopes
import tracing

ENTRY_BYTES = 12
# XLA module names of the jitted programs of ``ops.search_merge``
PROGRAMS = ("jit__search_emit", "jit_align_ranked", "jit__search_land")


def least_time(work: dict, peaks: dict) -> float:
    """Seconds of one product's accumulate at the HBM bandwidth."""
    return ((work["valid_products"] + work["nnz_c"]) * ENTRY_BYTES
            / peaks["hbm_bytes_per_s"])


def in_programs(op_name: str) -> bool:
    """Whether an op named ``<module>(<fingerprint>)/<op>`` by
    ``tracing.read_events`` ran in one of the three programs."""
    return op_name.split("(", 1)[0] in PROGRAMS


def program_seconds(ev: tracing.Events):
    """Device seconds of the three programs inside the window, averaged
    over the devices that ran one; None where none ran."""
    if ev.window is None:
        return None
    lo, hi = ev.window
    per_device = []
    for ops in ev.device_ops.values():
        hit = [(max(s, lo), min(e, hi)) for s, e, name in ops
               if e > lo and s < hi and in_programs(name)]
        if hit:
            per_device.append(sum(e - s for s, e in tracing.union(hit)))
    if not per_device:
        return None
    return sum(per_device) / len(per_device) / 1e9


def read(ctx):
    if ctx.peaks is None or ctx.trace is None or not ctx.work:
        return None
    path = scopes.find_trace()
    if path is None:
        return None
    ev = tracing.read_events(path)
    if ev.window is None or abs((ev.window[1] - ev.window[0]) / 1e9
                                - ctx.trace.window_s) > 1e-6:
        return None
    spent = program_seconds(ev)
    if not spent:
        return None
    least = sum(least_time(w, ctx.peaks) for w in ctx.work)
    return {"value": 100.0 * least / spent, "bound": "bytes"}
