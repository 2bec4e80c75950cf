"""Public jit'd wrappers around the Pallas kernels.

Handle padding/alignment (lane tiles multiple of 128, power-of-2 merge
tiles), pick the realization from ``platform.on_tpu`` and the tile width a
kernel block can hold, and raise where a kernel's structural preconditions
can't be met (e.g. coordinate space too large for 32-bit packed keys).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import trace as _obs

from . import (fused_sccp_stream, hash_accum, insitu_search, platform,
               radix_bucket)
from .bitonic_merge import (KEY_INVALID, MAX_KERNEL_TILE, next_pot,
                            sort_merge_tree_pallas)
from .ell_spmm import BM, BN, ell_spmm_pallas
from .sccp_multiply import LANE_BLOCK, sccp_multiply_pallas

INVALID = -1


def pad_to(x: jax.Array, axis: int, mult: int, fill):
    """Pad ``x`` along ``axis`` (negative ok) up to a multiple of ``mult``
    with ``fill`` — the shared alignment helper (kernel lane tiles, merge
    tiles, distributed slab padding)."""
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def sccp_multiply(a_val, a_idx, b_val, b_idx, *, block_n: int | None = None):
    """Tiled SCCP multiply; pads the lane axis to the VMEM block size."""
    n = a_val.shape[1]
    bn = block_n or min(LANE_BLOCK, max(128, 1 << (n - 1).bit_length()))
    a_val_p = pad_to(a_val, 1, bn, 0)
    a_idx_p = pad_to(a_idx, 1, bn, INVALID)
    b_val_p = pad_to(b_val, 0, bn, 0)
    b_idx_p = pad_to(b_idx, 0, bn, INVALID)
    val, row, col = sccp_multiply_pallas(
        a_val_p, a_idx_p, b_val_p, b_idx_p, block_n=bn,
        interpret=not platform.on_tpu())
    return val[:, :n, :], row[:, :n, :], col[:, :n, :]


def fused_slab_sort(a_val, a_idx, b_val, b_idx, *, n_cols: int):
    """One streaming step: slab products → sorted packed keys + run totals.

    On TPU, tiles of at most ``MAX_KERNEL_TILE`` lanes are sorted in VMEM by
    the Pallas tile kernel (kernels/fused_sccp_stream); wider tiles, and
    every tile elsewhere, go through XLA's fused sort — never interpret-mode
    Pallas, which would put an interpreter inside the streaming engine's
    innermost scan loop. Coordinate spaces ≥ 2³¹ can't pack (callers route
    those to the unpacked two-key 'sort' path, as spgemm_coo does
    automatically).
    """
    if platform.on_tpu() and next_pot(b_val.size) <= MAX_KERNEL_TILE:
        return fused_sccp_stream.fused_slab_sort_pallas(
            a_val, a_idx, b_val, b_idx, n_cols=n_cols, interpret=False)
    return fused_sccp_stream.fused_slab_sort_xla(
        a_val, a_idx, b_val, b_idx, n_cols=n_cols)


def sort_merge(row, col, val, n_rows: int, n_cols: int, *, tile: int = 4096):
    """Coalesce duplicate coordinates: sorted keys + run-tail totals.

    Packs (row, col) into one int32 key; coordinate spaces with
    n_rows·n_cols ≥ 2³¹ cannot be represented in packed keys at all (the
    unpack in downstream compaction would wrap too) and raise — route those
    through the unpacked two-key path (core.accumulate), as spgemm_coo does
    automatically (documented structural precondition).

    Streams up to one ``tile`` run the single bitonic network; larger
    streams go through the multi-tile merge tree (sort VMEM-sized tiles
    independently, pairwise-merge sorted runs up the tree) so the k_a·n·k_b
    product stream never has to fit one monolithic power-of-two network.
    On TPU the tile is capped at ``MAX_KERNEL_TILE`` (one VMEM block).
    """
    packed = _packed_stream(row, col, val, n_rows, n_cols)
    if packed is None:
        _unpackable(n_rows, n_cols)
    key, val = packed
    tpu = platform.on_tpu()
    return sort_merge_tree_pallas(
        key, val, tile=min(tile, MAX_KERNEL_TILE) if tpu else tile,
        interpret=not tpu)


def _packed_stream(row, col, val, n_rows: int, n_cols: int):
    """Flatten + pack coordinates to int32 keys, padded to a power of two.

    Returns ``None`` when the coordinate space doesn't fit packed 32-bit
    keys (callers raise via ``_unpackable`` — the structural precondition
    ``sort_merge`` documents; the unpacked two-key sort in core.accumulate
    is the path for such spaces).
    """
    if n_rows * n_cols >= jnp.iinfo(jnp.int32).max:
        return None
    row = row.reshape(-1)
    col = col.reshape(-1)
    val = val.reshape(-1)
    pot = 1 << (row.shape[0] - 1).bit_length()
    key = jnp.where(row >= 0, row * n_cols + col, KEY_INVALID).astype(jnp.int32)
    key = pad_to(key, 0, pot, KEY_INVALID)[:pot]
    val = pad_to(val, 0, pot, 0.0)[:pot]
    return key, val


def _unpackable(n_rows: int, n_cols: int):
    raise ValueError(
        f"coordinate space {n_rows}x{n_cols} exceeds packed int32 keys; "
        "use the unpacked two-key path (core.accumulate / "
        "spgemm_coo(accumulator='sort')) — spgemm_coo routes there "
        "automatically")


@functools.partial(jax.jit, static_argnames=("n_rows", "n_cols", "out_cap"))
def _search_emit(row, col, val, *, n_rows: int, n_cols: int, out_cap: int):
    """Emission program: pack the stream's keys, sort them with their lanes
    and emit the sorted unique keys (``insitu_search.emit_ranked``)."""
    key, _ = _packed_stream(row, col, val, n_rows, n_cols)
    return (key,) + insitu_search.emit_ranked(key, out_cap=out_cap)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _search_land(val, key, slot, hit, *, out_cap: int):
    """Land program: sum every valid product whose key was emitted into its
    slot; the rest go to the dump slot ``out_cap``."""
    v = pad_to(val.reshape(-1), 0, key.shape[0], 0.0)
    ok = jnp.logical_and(key != KEY_INVALID, hit)
    return jax.ops.segment_sum(jnp.where(ok, v, 0),
                               jnp.where(ok, slot, out_cap),
                               num_segments=out_cap + 1)[:out_cap]


def search_merge(row, col, val, n_rows: int, n_cols: int, *,
                 out_cap: int, interpret: bool | None = None,
                 faithful: bool = False):
    """The paper's in-situ-search accumulation (Alg. 1 / Fig. 11): emit the
    sorted unique coordinate list, align every product against it, land
    the values in their slots.

    Three programs, each compiled once per shape: emit
    (``insitu_search.emit_ranked``: one sort of the packed keys with their
    lanes, the run heads giving the sorted unique keys and each lane's
    rank), align (``insitu_search.align_ranked``: each product's slot
    through the sort's permutation) and land (one segment-sum of the values,
    which are never sorted). ``faithful=True`` emits by the literal iterated
    Alg. 1 scan instead and aligns with ``insitu_search.align_keys``. While
    tracing is on, each phase is a span (``spgemm.accumulate.emit``,
    ``.align``, ``.land``) that closes on a device sync.

    Returns ``(uk, sums, nnz)``: the (out_cap,) sorted unique keys with
    KEY_INVALID padding, the per-slot value totals, and the TRUE unique
    count (``nnz > out_cap`` flags truncation; the kept slots are the first
    ``out_cap`` unique keys, matching the 'sort' backend's truncation
    order). Coordinate spaces ≥ 2³¹ can't pack and raise, like the other
    packed-key backends (spgemm_coo reroutes those to 'sort').
    """
    if n_rows * n_cols >= jnp.iinfo(jnp.int32).max:
        _unpackable(n_rows, n_cols)
    with _obs.span("spgemm.accumulate.emit", lanes=int(row.size),
                   out_cap=int(out_cap)):
        if faithful:
            key, _ = _packed_stream(row, col, val, n_rows, n_cols)
            uk, nnz = _obs.sync(insitu_search.emit_sorted_unique(
                key, out_cap, interpret=interpret, faithful=True))
        else:
            key, uk, nnz, perm, rank = _obs.sync(_search_emit(
                row, col, val, n_rows=n_rows, n_cols=n_cols, out_cap=out_cap))
    with _obs.span("spgemm.accumulate.align"):
        if faithful:
            slot, hit = insitu_search.align_keys(key, uk, interpret=interpret)
        else:
            slot, hit = insitu_search.align_ranked(perm, rank,
                                                   out_cap=out_cap)
        _obs.sync(slot)
    with _obs.span("spgemm.accumulate.land"):
        sums = _obs.sync(_search_land(val, key, slot, hit, out_cap=out_cap))
    return uk, sums, nnz


def bucket_merge(row, col, val, n_rows: int, n_cols: int, *,
                 n_buckets: int | None = None,
                 bucket_cap: int | None = None):
    """Propagation-blocking coalesce: bin by row range, sort each bucket.

    Returns ``(key_sorted, totals, dropped)`` — same stream contract as
    ``sort_merge`` plus the count of products lost to full buckets
    (``dropped == 0`` when ``bucket_cap`` was planner-sized). Without an
    explicit ``bucket_cap`` every bucket must be able to hold the whole
    stream (worst-case skew), so the no-argument default is ONE
    stream-sized bucket; multi-bucket blocking with tight caps comes from
    plan.make_plan — asking for ``n_buckets`` alone costs n_buckets× the
    stream in memory and sort width.
    """
    if n_buckets is None and bucket_cap is None:
        n_buckets = 1
    n_buckets = n_buckets or 8
    packed = _packed_stream(row, col, val, n_rows, n_cols)
    if packed is None:
        _unpackable(n_rows, n_cols)
    key, val = packed
    cap = bucket_cap or key.shape[0]
    if cap & (cap - 1):
        raise ValueError(f"bucket_cap must be a power of two, got {cap}")
    kpb = radix_bucket.bucket_bounds(n_rows, n_cols, n_buckets)
    # interpret auto: compiled Pallas on TPU, XLA realization elsewhere
    return radix_bucket.bucket_merge(key, val, n_buckets=n_buckets,
                                     bucket_cap=cap, keys_per_bucket=kpb)


def hash_merge(row, col, val, n_rows: int, n_cols: int, *,
               n_blocks: int | None = None, block_cap: int | None = None,
               max_probes: int | None = None):
    """Hash-accumulate into per-row-block open-addressing tables.

    Returns ``(key_sorted, totals, dropped)`` — the sorted *tables*, not the
    stream, so the bitonic pass is table-sized. ``dropped`` counts probe/
    table exhaustion (0 with planner-sized ``block_cap``). As with
    ``bucket_merge``, the no-argument default is ONE stream-sized table;
    tight multi-block caps come from plan.make_plan.
    """
    if n_blocks is None and block_cap is None:
        n_blocks = 1
    n_blocks = n_blocks or 8
    packed = _packed_stream(row, col, val, n_rows, n_cols)
    if packed is None:
        _unpackable(n_rows, n_cols)
    key, val = packed
    cap = block_cap or key.shape[0]
    if cap & (cap - 1):
        raise ValueError(f"block_cap must be a power of two, got {cap}")
    kpb = radix_bucket.bucket_bounds(n_rows, n_cols, n_blocks)
    # interpret auto: compiled Pallas on TPU, XLA realization elsewhere
    return hash_accum.hash_merge(key, val, n_blocks=n_blocks, block_cap=cap,
                                 keys_per_block=kpb, max_probes=max_probes)


def ell_spmm(a_val, a_idx, x, n_rows: int, *, d_chunk: int = 512):
    """A(ELL rows) @ X with padding to MXU tiles and D chunking."""
    k, n = a_val.shape
    a_val_p = pad_to(a_val, 1, BN, 0)
    a_idx_p = pad_to(a_idx, 1, BN, INVALID)
    x_p = pad_to(x, 0, BN, 0)
    m_pad = n_rows + ((-n_rows) % BM)
    d = x.shape[-1]
    outs = []
    for lo in range(0, d, d_chunk):
        xc = x_p[:, lo:lo + d_chunk]
        outs.append(ell_spmm_pallas(a_val_p, a_idx_p, xc, n_rows=m_pad,
                                    interpret=not platform.on_tpu()))
    out = jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
    return out[:n_rows]
