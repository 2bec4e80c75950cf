"""Unstructured accumulation via the in-situ-search equivalent (paper §III-B).

SPLIM's hardware repeatedly bit-serial-searches the (RI, CI) planes for the
minimum coordinate, emitting groups with equal coordinates in sorted order and
summing each group on a small accumulator (Alg. 1 + Fig. 11). The *output
contract* is: a sorted, duplicate-free COO stream, produced without a
scheduler and without a dense intermediate.

TPU has no leakage-current search primitive, so we realize the same contract
with the TPU-native dual (DESIGN.md §2): a **stable multi-key sort** of the
coordinate planes followed by a **segmented sum**. ``jax.lax.sort`` with
``num_keys=2`` is a lexicographic (row, col) sort — invalid lanes are parked
at row = n_rows so they fall to the tail, exactly like the paper flipping the
sign bit to invalidate consumed coordinates.

The Pallas kernel (kernels/bitonic_merge.py) is the explicitly tiled
in-VMEM version for coordinate spaces that fit 16-bit tiles.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.obs import trace as _obs

from .formats import Coo, INVALID


def sort_by_coords(row: jax.Array, col: jax.Array, val: jax.Array,
                   n_rows: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Lexicographic (row, col) sort; invalid entries sink to the tail."""
    row = row.reshape(-1)
    col = col.reshape(-1)
    val = val.reshape(-1)
    park = row < 0
    row_s = jnp.where(park, n_rows, row)          # sentinel sorts last
    col_s = jnp.where(park, 0, col)
    row_s, col_s, val_s = jax.lax.sort(
        (row_s, col_s, val), dimension=0, num_keys=2, is_stable=False)
    row_o = jnp.where(row_s >= n_rows, INVALID, row_s)
    col_o = jnp.where(row_s >= n_rows, INVALID, col_s)
    val_o = jnp.where(row_s >= n_rows, 0, val_s)
    return row_o, col_o, val_o


class AccumulatorOverflow(ValueError):
    """The true unique-coordinate count exceeded the static ``out_cap``."""


def merge_sorted(row: jax.Array, col: jax.Array, val: jax.Array,
                 out_cap: int, n_rows: int, n_cols: int) -> Coo:
    """Coalesce a coordinate-sorted stream: sum runs of equal (row, col).

    Static output size ``out_cap``; if the true number of unique coordinates
    exceeds it the stored stream is truncated (callers size out_cap from
    hwmodel / upper bounds) — but the returned ``Coo`` carries ``ngroups``,
    the TRUE group count, so truncation is detectable (``coo.overflowed()``
    in-graph, ``check_no_overflow`` on the host). This is the "on-chip
    accumulator" epilogue of Fig. 11(c).
    """
    valid = row >= 0
    new_grp = jnp.logical_or(row != jnp.roll(row, 1), col != jnp.roll(col, 1))
    new_grp = new_grp.at[0].set(True)
    new_grp = jnp.logical_and(new_grp, valid)
    seg = jnp.cumsum(new_grp.astype(jnp.int32)) - 1          # group id, -1 before first
    seg = jnp.where(valid, seg, out_cap)                      # park invalid
    seg = jnp.clip(seg, 0, out_cap)                           # truncate overflow
    sums = jax.ops.segment_sum(val, seg, num_segments=out_cap + 1)[:out_cap]
    # representative coordinates per group = first element of each run
    first = jnp.where(new_grp, jnp.arange(row.shape[0]), row.shape[0] - 1)
    first_idx = jax.ops.segment_min(first, seg, num_segments=out_cap + 1)[:out_cap]
    ngroups = jnp.sum(new_grp)
    slot_ok = jnp.arange(out_cap) < ngroups
    out_row = jnp.where(slot_ok, row[first_idx], INVALID).astype(jnp.int32)
    out_col = jnp.where(slot_ok, col[first_idx], INVALID).astype(jnp.int32)
    out_val = jnp.where(slot_ok, sums, 0)
    return Coo(row=out_row, col=out_col, val=out_val, shape=(n_rows, n_cols),
               ngroups=ngroups.astype(jnp.int32))


def accumulate(row: jax.Array, col: jax.Array, val: jax.Array,
               out_cap: int, n_rows: int, n_cols: int) -> Coo:
    """sort + merge: the full in-situ-search-equivalent accumulation.

    Instrumented (repro.obs): ``spgemm.accumulate.sort`` and
    ``spgemm.accumulate.merge`` spans, each closing on a device sync while
    tracing is on (outside jit both halves run op by op, so the host span
    is what bounds their device time)."""
    with _obs.span("spgemm.accumulate.sort", lanes=int(row.size)):
        r, c, v = _obs.sync(sort_by_coords(row, col, val, n_rows))
    with _obs.span("spgemm.accumulate.merge", out_cap=int(out_cap)):
        return _obs.sync(merge_sorted(r, c, v, out_cap, n_rows, n_cols))


def check_no_overflow(coo: Coo) -> Coo:
    """Host-side guard: raise ``AccumulatorOverflow`` if the producer dropped
    groups beyond ``cap``. Call outside jit (forces a sync on ``ngroups``);
    inside traced code use ``coo.overflowed()`` and route the flag out.
    Accepts batched ``Coo`` (leading axis on ``ngroups``, e.g. from
    ``spgemm_coo_batched``): raises if ANY batch entry overflowed.
    """
    if coo.ngroups is None:
        return coo
    import numpy as np
    ngroups = np.asarray(jax.device_get(coo.ngroups))
    cap = coo.row.shape[-1]
    worst = int(ngroups.max())
    if worst > cap:
        n_bad = int((ngroups > cap).sum()) if ngroups.ndim else 1
        where = "" if ngroups.ndim == 0 else f" in {n_bad} batch entr{'y' if n_bad == 1 else 'ies'}"
        # exactly one event per offending call (not per batch entry)
        from repro.obs import metrics as _obs_metrics
        _obs_metrics.inc("spgemm.overflow_events")
        _obs.instant("spgemm.overflow", worst=worst, cap=cap, n_bad=n_bad)
        raise AccumulatorOverflow(
            f"accumulation produced up to {worst} unique coordinates but "
            f"out_cap={cap}{where}; {worst - cap} group(s) were dropped — "
            f"resize out_cap (e.g. from hwmodel upper bounds)")
    return coo


def accumulate_checked(row: jax.Array, col: jax.Array, val: jax.Array,
                       out_cap: int, n_rows: int, n_cols: int) -> Coo:
    """``accumulate`` + host-side overflow check (raises on truncation)."""
    return check_no_overflow(accumulate(row, col, val, out_cap,
                                        n_rows, n_cols))


def scatter_dense(row: jax.Array, col: jax.Array, val: jax.Array,
                  n_rows: int, n_cols: int) -> jax.Array:
    """Decompression-style accumulation into a dense C — this is what
    COO-SPLIM / GraphR do (paper Fig. 5 / Fig. 9b). Kept as the oracle and as
    the explicit baseline the paper argues against."""
    r = jnp.where(row.reshape(-1) >= 0, row.reshape(-1), n_rows)
    c = jnp.where(col.reshape(-1) >= 0, col.reshape(-1), 0)
    dense = jnp.zeros((n_rows + 1, n_cols), val.dtype)
    dense = dense.at[r, c].add(jnp.where(row.reshape(-1) >= 0, val.reshape(-1), 0))
    return dense[:n_rows]
