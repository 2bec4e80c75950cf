"""The whole product's share of its roofline: the least time the chip
could take for the window's products over the device's busy time.

The least time of one product C = A·Aᵀ is the larger of its compulsory
bytes over the HBM bandwidth, (nnz(A) + nnz(B))·8 B read and nnz(C)·12 B
written, and its operations over the peak rate, 2 per valid product.
Both come from the configuration's pattern, not from the backend that
runs, so the number reads the same work whatever implements it.
"""

VAL_IDX_BYTES = 8        # float32 value + int32 index per operand entry
COO_BYTES = 12           # float32 value + two int32 coordinates per output


def least_time(work: dict, peaks: dict):
    """(seconds, bound) of one product."""
    nbytes = ((work["nnz_a"] + work["nnz_b"]) * VAL_IDX_BYTES
              + work["nnz_c"] * COO_BYTES)
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = 2 * work["valid_products"] / peaks["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "flops")


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not ctx.work:
        return None
    times = [least_time(w, ctx.peaks) for w in ctx.work]
    bounds = sorted({b for _, b in times})
    return {"value": 100.0 * sum(t for t, _ in times) / ctx.trace.busy_s,
            "bound": "+".join(bounds)}
