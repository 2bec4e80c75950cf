"""Streaming fused SpGEMM accumulation — slab-scan multiply→compact→merge.

The paper's BSS memory argument (§III-A, Fig. 8) is that slab products are
*streamed* into accumulation: the hardware never holds the full product
stream, only the tile of the current iteration. The ``'sort'``/``'tiled'``/
``'bucket'``/``'hash'`` backends all break that — they accumulate a fully
materialized ``(k_a, n, k_b)`` product tensor (12 B/lane, mostly INVALID
ELLPACK-padding lanes) and sort *all* of it. This module is the faithful
streaming realization: the working set is bounded by one slab-group tile
plus the running output buffer, O(group·n·k_b + out_cap), independent of
``k_a``.

Per ``lax.scan`` step over A slab groups:

  1. **multiply + sort** — the group's (group, n, k_b) product tile is
     formed, packed into int32 coordinate keys and sorted. On TPU with
     ``group=1`` and a tile that fits one VMEM block this is the Pallas
     tile kernel (kernels/fused_sccp_stream); otherwise the identical
     contract goes through XLA's fused ``lax.sort`` (kernels/ops.
     fused_slab_sort picks), and the planner sizes ``group``
     so the tile amortizes the per-step dispatch floor while staying ≪ the
     full stream.
  2. **compact** — run tails (the tile's unique coordinates with their
     totals) are packed to the front of a ``stream_cap``-lane buffer. The
     INVALID padding lanes — the dead weight that dominates the
     materialized backends — die here, inside the step. Compaction is
     cumsum + ``searchsorted`` + a single cap-sized take: no scatters (slow
     element loops on CPU XLA) and no gathers inside unrolled networks (the
     pinned-jax compile hazard — one take per scan body traces once).
  3. **merge** — the compacted tile is merged into the running sorted,
     coalesced buffer and the result compacted back to the buffer width.
     On TPU the merge is the bitonic two-list network
     (kernels.bitonic_merge.merge_coalesce_pair — rotation partner
     exchange, no gathers); off-TPU one fused ``lax.sort`` over the
     concatenated pair realizes the same contract without putting ~100
     dispatch-bound vector ops in the innermost loop. Both lists are
     duplicate-free, so merged runs have length ≤ 2 and the run total is a
     single shifted add.

``StreamState.dropped`` counts every unique coordinate lost to an
undersized ``stream_cap`` or buffer; any drop poisons ``Coo.ngroups`` past
the cap (the repo-wide overflow contract), so ``check_no_overflow`` raises
instead of returning silently-wrong output. Planner-sized runs
(plan.make_plan: ``stream_cap``/``stream_group`` from the exact per-slab
product histogram, ``out_cap`` from the symbolic phase) never drop.

Packed int32 keys require ``n_rows·n_cols < 2³¹``; ``spgemm_coo`` reroutes
larger coordinate spaces to the unpacked two-key ``'sort'`` path before
reaching this module.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import platform
from repro.kernels.bitonic_merge import (KEY_INVALID, _segmented_total_rows,
                                         merge_coalesce_pair,
                                         next_pot as _pot)
from repro.obs import trace as _obs

from .formats import Coo, EllCols, EllRows, INVALID


class StreamState(NamedTuple):
    """Running sorted+coalesced output buffer of the streaming engine.

    ``key``/``tot``: (buf_cap,) ascending unique packed coordinates with
    their running totals, KEY_INVALID/0 padding after the first ``count``
    lanes. ``dropped`` counts unique coordinates lost to undersized caps —
    any non-zero poisons the final ``ngroups``.
    """

    key: jax.Array      # (buf_cap,) int32
    tot: jax.Array      # (buf_cap,) values
    count: jax.Array    # () int32 — valid unique lanes in the buffer
    dropped: jax.Array  # () int32 — uniques lost to stream_cap/buffer limits


def stream_init(buf_cap: int, dtype=jnp.float32, lead=()) -> StreamState:
    """Empty state. ``buf_cap`` must be a power of two (merge network width);
    ``lead`` adds leading batch axes (distributed/batched callers)."""
    assert buf_cap & (buf_cap - 1) == 0, f"buf_cap {buf_cap} must be pow2"
    return StreamState(
        key=jnp.full(lead + (buf_cap,), KEY_INVALID, jnp.int32),
        tot=jnp.zeros(lead + (buf_cap,), dtype),
        count=jnp.zeros(lead, jnp.int32),
        dropped=jnp.zeros(lead, jnp.int32))


def _coalesce_compact(key: jax.Array, tot: jax.Array, cap: int):
    """Pack a sorted run-tail-total stream's unique coordinates into ``cap``
    lanes (ascending, KEY_INVALID padding). Tails are already in ascending
    key order, so ``searchsorted`` over the tail prefix-sum maps output
    slot → source lane directly (two takes, no scatter). Tails beyond
    ``cap`` are counted, never silently lost.
    Returns ``(key, tot, count, dropped)``."""
    nxt = jnp.concatenate(
        [key[1:], jnp.full((1,), KEY_INVALID - 1, key.dtype)])
    tail = jnp.logical_and(key != nxt, key != KEY_INVALID)
    csum = jnp.cumsum(tail.astype(jnp.int32))
    n_tail = csum[-1]
    src = platform.searchsorted(csum, jnp.arange(1, cap + 1, dtype=jnp.int32))
    ok = jnp.arange(cap) < jnp.minimum(n_tail, cap)
    src = jnp.minimum(src, key.shape[0] - 1)
    out_key = jnp.where(ok, key[src], KEY_INVALID)
    out_tot = jnp.where(ok, tot[src], 0)
    return (out_key, out_tot, jnp.minimum(n_tail, cap),
            jnp.maximum(n_tail - cap, 0))


def _merge_coalesced(key_a, tot_a, key_b, tot_b):
    """Merge two same-length ascending *duplicate-free* lists into one
    sorted run-tail-total stream. TPU: the bitonic two-list network
    (no gathers); elsewhere one fused ``lax.sort`` — each key appears at
    most twice, so the run total is one shifted add."""
    if platform.on_tpu():
        return merge_coalesce_pair(key_a, tot_a, key_b, tot_b)
    key = jnp.concatenate([key_a, key_b])
    tot = jnp.concatenate([tot_a, tot_b])
    key, tot = jax.lax.sort((key, tot), dimension=0, num_keys=1,
                            is_stable=False)
    prev_k = jnp.concatenate(
        [jnp.full((1,), -2, key.dtype), key[:-1]])    # -2: never a key
    prev_t = jnp.concatenate([jnp.zeros((1,), tot.dtype), tot[:-1]])
    tot = tot + jnp.where(prev_k == key, prev_t, 0)   # run length ≤ 2
    return key, tot


def absorb_sorted(state: StreamState, key: jax.Array, tot: jax.Array, *,
                  stream_cap: int) -> StreamState:
    """Compact one sorted run-tail-total tile and merge it into the buffer.

    The compaction width is ``min(stream_cap, buf_cap)`` — a tile can never
    contribute more surviving uniques than the buffer holds, so a
    planner-sized ``stream_cap`` larger than the buffer costs nothing.
    """
    buf_cap = state.key.shape[-1]
    cap = min(int(stream_cap), buf_cap)
    with _obs.span("stream.compact", cap=cap):
        k_t, v_t, _, drop_t = _obs.sync(_coalesce_compact(key, tot, cap))
    if cap < buf_cap:                      # pad keeps the list ascending
        k_t = jnp.concatenate(
            [k_t, jnp.full((buf_cap - cap,), KEY_INVALID, k_t.dtype)])
        v_t = jnp.concatenate([v_t, jnp.zeros((buf_cap - cap,), v_t.dtype)])
    with _obs.span("stream.merge", buf_cap=buf_cap):
        mk, mt = _merge_coalesced(state.key, state.tot, k_t, v_t)
        k_b, v_b, count, drop_m = _obs.sync(
            _coalesce_compact(mk, mt, buf_cap))
    return StreamState(key=k_b, tot=v_b, count=count,
                       dropped=state.dropped + drop_t + drop_m)


def _sort_tile(row: jax.Array, col: jax.Array, val: jax.Array,
               n_cols: int):
    """Pack one raw product tile and sort it (XLA fused sort + log-step
    segmented totals — the same contract ops.fused_slab_sort emits)."""
    row, col, val = row.reshape(-1), col.reshape(-1), val.reshape(-1)
    pot = _pot(row.shape[0])
    key = jnp.where(row >= 0, row * n_cols + col,
                    KEY_INVALID).astype(jnp.int32)
    pad = pot - key.shape[0]
    if pad:
        key = jnp.concatenate(
            [key, jnp.full((pad,), KEY_INVALID, key.dtype)])
        val = jnp.concatenate([val, jnp.zeros((pad,), val.dtype)])
    key, val = jax.lax.sort((key, val), dimension=0, num_keys=1,
                            is_stable=False)
    tot = _segmented_total_rows(key[None, :], val[None, :])[0]
    return key, tot


def absorb_products(state: StreamState, row: jax.Array, col: jax.Array,
                    val: jax.Array, *, n_cols: int,
                    stream_cap: int) -> StreamState:
    """Stream a block of raw product tiles through sort→compact→merge.

    ``row``/``col``/``val``: (tiles, m) — one step per leading-axis tile
    via ``lax.scan`` (the 2-D reshape is the caller's slab grouping; a 1-D
    stream is treated as a single tile). Working set per step: one tile +
    the buffer, never the whole block.
    """
    if row.ndim == 1:
        row, col, val = row[None], col[None], val[None]

    def step(st, rcv):
        r, c, v = rcv
        key, tot = _sort_tile(r, c, v, n_cols)
        return absorb_sorted(st, key, tot, stream_cap=stream_cap), ()

    state, _ = jax.lax.scan(step, state, (row, col, val))
    return state


def finalize(state: StreamState, out_cap: int, n_rows: int,
             n_cols: int) -> Coo:
    """Unpack the buffer into ``Coo(out_cap)``. ``ngroups`` is the true
    unique count while nothing was dropped; any drop (or uniques beyond
    ``out_cap`` surviving in an oversized buffer) pushes it past the cap so
    the overflow machinery flags the loss."""
    buf_cap = state.key.shape[-1]
    key, tot = state.key, state.tot
    if buf_cap < out_cap:
        key = jnp.concatenate(
            [key, jnp.full((out_cap - buf_cap,), KEY_INVALID, key.dtype)])
        tot = jnp.concatenate(
            [tot, jnp.zeros((out_cap - buf_cap,), tot.dtype)])
    key, tot = key[:out_cap], tot[:out_cap]
    valid = key != KEY_INVALID
    row = jnp.where(valid, key // n_cols, INVALID).astype(jnp.int32)
    col = jnp.where(valid, key % n_cols, INVALID).astype(jnp.int32)
    val = jnp.where(valid, tot, 0)
    ngroups = state.count + jnp.where(state.dropped > 0,
                                      jnp.int32(out_cap + 1), jnp.int32(0))
    return Coo(row=row, col=col, val=val, shape=(n_rows, n_cols),
               ngroups=ngroups.astype(jnp.int32))


def _check_packable(n_rows: int, n_cols: int):
    if n_rows * n_cols >= jnp.iinfo(jnp.int32).max:
        raise ValueError(
            f"coordinate space {n_rows}x{n_cols} exceeds packed int32 keys; "
            "the streaming engine cannot span it — use the unpacked two-key "
            "path (spgemm_coo(accumulator='sort') routes automatically)")


def buffer_cap(out_cap: int, *, lane: int = 128) -> int:
    """Merge-buffer width for a given output capacity: power of two, at
    least one VPU lane tile."""
    return _pot(max(int(out_cap), lane))


def spgemm_coo_stream(a: EllRows, b: EllCols, out_cap="auto", *,
                      stream_cap: Optional[int] = None,
                      group: Optional[int] = None) -> Coo:
    """C = A·B as sorted COO without ever materializing the product stream.

    Prefer ``repro.spgemm(a, b, accumulator='stream')`` — the unified front
    door (core/api.py) routes here with the same semantics.

    ``lax.scan`` over groups of ``group`` A slabs: per step one
    (group, n, k_b) tile is multiplied, sorted (fused in VMEM on TPU when
    ``group=1`` — ops.fused_slab_sort), compacted to its unique coordinates
    and merged into the running buffer. Peak intermediate is
    O(group·n·k_b + stream_cap) vs the materialized backends'
    O(k_a·n·k_b). ``stream_cap`` defaults to the full group tile (never
    drops); the planner passes the exact per-slab product bound and sizes
    ``group`` to amortize the off-TPU per-step dispatch floor.
    jit/vmap-compatible with static caps; ``out_cap='auto'`` (and
    ``group=None``) run ``plan.make_plan(backend='stream')`` on concrete
    operands, matching every other entry point's auto-sizing.
    """
    if a.n_cols != b.n_rows:
        raise ValueError(f"contraction mismatch: A has {a.n_cols} cols, "
                         f"B has {b.n_rows} rows")
    _check_packable(a.n_rows, b.n_cols)
    if out_cap == "auto":
        if isinstance(a.val, jax.core.Tracer):
            raise ValueError(
                "out_cap='auto' plans from operand VALUES, which jit/vmap "
                "abstract away — call plan.make_plan(backend='stream') "
                "outside the trace and pass its out_cap, or a concrete int")
        from repro.plan import make_plan
        plan = make_plan(a, b, backend="stream")
        out_cap = plan.out_cap
        stream_cap = plan.stream_cap if stream_cap is None else stream_cap
        group = plan.stream_group if group is None else group
    group = max(1, min(int(group or 1), a.k))
    from repro.kernels.ops import pad_to
    a_val = pad_to(a.val, 0, group, 0)
    a_idx = pad_to(a.idx, 0, group, INVALID)
    n_groups = a_val.shape[0] // group
    tile_lanes = group * a.n_cols * b.k
    scap = int(stream_cap) if stream_cap else _pot(tile_lanes)
    state0 = stream_init(buffer_cap(out_cap), a.val.dtype)
    fused = platform.on_tpu() and group == 1

    def tile_sorted(g):
        av = jax.lax.dynamic_slice_in_dim(a_val, g * group, group, 0)
        ai = jax.lax.dynamic_slice_in_dim(a_idx, g * group, group, 0)
        if fused:
            from repro.kernels import ops
            return ops.fused_slab_sort(av[0], ai[0], b.val, b.idx,
                                       n_cols=b.n_cols)
        v = av[:, :, None] * b.val[None, :, :]            # (group, n, k_b)
        r = jnp.broadcast_to(ai[:, :, None], v.shape)
        ok = jnp.logical_and(r >= 0, b.idx[None, :, :] >= 0)
        return _sort_tile(
            jnp.where(ok, r, INVALID),
            jnp.where(ok, b.idx[None, :, :], INVALID),
            jnp.where(ok, v, 0), b.n_cols)

    def step(st, g):
        key, tot = tile_sorted(g)
        return absorb_sorted(st, key, tot, stream_cap=scap), ()

    if _obs.is_enabled() and not isinstance(a.val, jax.core.Tracer):
        # Traced mode: unroll the scan in Python — the identical tiles in
        # the identical order (float-identical result), but each slab step
        # gets its own multiply+sort / compact+merge spans with device
        # syncs. Only reachable outside jit with concrete operands.
        state = state0
        for g in range(n_groups):
            with _obs.span("stream.step", step=g, group=group, fused=fused):
                with _obs.span("stream.sort", lanes=tile_lanes):
                    key, tot = _obs.sync(tile_sorted(jnp.int32(g)))
                state = absorb_sorted(state, key, tot, stream_cap=scap)
    else:
        state, _ = jax.lax.scan(step, state0, jnp.arange(n_groups))
    return finalize(state, out_cap, a.n_rows, b.n_cols)


def spgemm_coo_stream_numeric(a: EllRows, b: EllCols, structure, *,
                              check: bool = False,
                              validate: bool = True) -> Coo:
    """Numeric phase of the streaming path: slab-scan scatter into a
    precomputed structure (plan.make_structure) — ``repro.spgemm(a, b,
    structure=st)`` reaches this realization automatically for
    stream-planned structures; call this wrapper only to force it. Same
    O(group·n·k_b + out_cap) working set as ``spgemm_coo_stream`` but with
    the per-step sort/compact/merge machinery replaced by one
    ``searchsorted`` + segment-sum per step — the structure already knows
    every output coordinate. Thin streaming-layer alias of the dispatch
    ``core.spgemm.spgemm_coo_numeric`` performs for stream-backed plans;
    use this to force the slab-scan realization regardless of the
    structure's planned backend."""
    if validate:
        structure.validate(a, b)
    from .spgemm import _numeric_stream
    plan = structure.plan
    grp = 1 if plan is None else max(1, min(plan.stream_group, a.k))
    coo = _numeric_stream(a.val, a.idx, b.val, b.idx, structure.key,
                          structure.nnz, out_cap=structure.out_cap,
                          n_rows=structure.n_rows, n_cols=structure.n_cols,
                          group=grp)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


def accumulate_products_stream(row: jax.Array, col: jax.Array,
                               val: jax.Array, out_cap: int, n_rows: int,
                               n_cols: int, *, chunk: int = 4096,
                               stream_cap: Optional[int] = None,
                               group: int = 1) -> Coo:
    """Streaming accumulation of an already-materialized product stream.

    The ``accumulate_stream(backend='stream')`` realization: the caller
    holds the products, but the *sort* working set stays one tile. A 3-D
    ``(k_a, n, k_b)`` stream is chunked by groups of ``group`` slabs —
    bit-identical (float-exact) to ``spgemm_coo_stream`` on the same
    operands and plan, which scans the identical tiles in the identical
    order. Flat streams are chunked by ``chunk`` lanes; ``stream_cap`` is a
    *slab-group* unique bound, meaningless for an arbitrary lane chunk, so
    the flat path compacts at the full chunk width (never drops).
    """
    _check_packable(n_rows, n_cols)
    from repro.kernels.ops import pad_to
    if row.ndim == 3:
        group = max(1, min(int(group), row.shape[0]))
        row = pad_to(row, 0, group, INVALID)
        col = pad_to(col, 0, group, INVALID)
        val = pad_to(val, 0, group, 0)
        tiles = row.shape[0] // group
        row, col, val = (x.reshape(tiles, -1) for x in (row, col, val))
    else:
        row, col, val = row.reshape(-1), col.reshape(-1), val.reshape(-1)
        chunk = min(chunk, _pot(row.shape[0]))
        row = pad_to(row, 0, chunk, INVALID)
        col = pad_to(col, 0, chunk, INVALID)
        val = pad_to(val, 0, chunk, 0)
        row, col, val = (x.reshape(-1, chunk) for x in (row, col, val))
        stream_cap = None
    scap = int(stream_cap) if stream_cap else _pot(row.shape[-1])
    state = stream_init(buffer_cap(out_cap), val.dtype)
    state = absorb_products(state, row, col, val, n_cols=n_cols,
                            stream_cap=scap)
    return finalize(state, out_cap, n_rows, n_cols)
