"""Pallas TPU kernel: bitonic sort + segmented sum — the in-situ-search dual.

SPLIM's accumulation repeatedly bit-serial-searches the coordinate planes for
the minimal (RI, CI), emitting equal-coordinate groups in sorted order
(paper Alg. 1 / Fig. 11). The TPU-native realization of the same contract
(DESIGN.md §2) is a bitonic compare-exchange network over packed coordinate
keys, entirely in VMEM, followed by a log-step *segmented* inclusive scan so
each run of equal keys ends with its total. Output per tile:

    key_sorted : ascending, invalid lanes parked at INT32_MAX
    val_out    : run-tail lanes carry the run total, all other lanes 0

which is exactly the paper's "sorted list of the output matrix" (Fig. 11c) —
non-tail lanes correspond to coordinates the hardware invalidated by flipping
their sign bit.

Every compare-exchange partner sits at a power-of-2 distance, so the network
needs no general gathers: the partner of lane ``l`` is ``l ^ d``, read as
one of two rotations of the tile (``l + d`` for lanes whose bit ``d`` is
clear, ``l − d`` otherwise). Inside a kernel the tile is a ``(rows, 128)``
VMEM block and the rotations are ``pltpu.roll`` along lanes (``d < 128``) or
sublanes (``d ≥ 128``) — ops Mosaic lowers, unlike reversals or dynamic
slices. As plain XLA the same network runs on ``(…, tile)`` rows with
``jnp.roll``.

For product streams larger than one tile, ``sort_merge_tree_pallas`` is the
blocked realization (cf. propagation blocking in bandwidth-optimized
SpGEMM): sort all power-of-2 tiles independently (one kernel grid step per
VMEM block of tiles), then pairwise-merge sorted runs up a binary tree.
Merge levels span more than one VMEM block, so they run as XLA ops: a single
bitonic *merge network* per level (O(L log L), not a full re-sort) followed
by the segmented total — coalesced run-tail totals compose across levels
because non-tail lanes are already 0, so re-summing a merged run reproduces
the grand total at the new tail.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KEY_INVALID = jnp.iinfo(jnp.int32).max
_KEY_FILL = -2  # never a packed coordinate (>= 0) nor KEY_INVALID

LANES = 128
# Widest tile one kernel block sorts. The network is unrolled, so its
# compile time grows as log² of the tile; at 8K lanes (32 KiB per operand)
# the block and its temporaries sit far inside the 16 MiB scoped VMEM of a
# v5e core. Wider tiles take the XLA realization.
MAX_KERNEL_TILE = 1 << 13


def next_pot(x: int) -> int:
    """Smallest power of two ≥ ``x`` (≥ 1) — the network/tile width helper
    shared by the sort kernels, the streaming engine and the planner."""
    return 1 << max(0, int(x) - 1).bit_length()


def _roll_vmem(x, shift: int, axis: int):
    # pltpu.roll has jnp.roll's semantics but takes non-negative shifts
    axis %= x.ndim
    return pltpu.roll(x, shift % x.shape[axis], axis)


def _lanes(x: jax.Array, tile: int) -> jax.Array:
    """Each element's lane index within its tile. Tiles are ``tile``
    contiguous lanes in row-major order over the last two axes: one tile per
    row when ``tile == x.shape[-1]`` (XLA use), ``tile / 128`` rows per tile
    in a kernel's ``(rows, 128)`` block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    if tile > x.shape[-1]:
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)
        lane = row * x.shape[-1] + lane
    return jnp.bitwise_and(lane, tile - 1)


def _partner(x: jax.Array, d: int, lane: jax.Array, roll) -> jax.Array:
    """x[lane ^ d] within each tile: two rotations and a select."""
    w = x.shape[-1]
    if d < w:
        up, dn = roll(x, -d, -1), roll(x, d, -1)
    else:
        up, dn = roll(x, -(d // w), -2), roll(x, d // w, -2)
    return jnp.where(jnp.bitwise_and(lane, d) == 0, up, dn)


def _shift(x: jax.Array, d: int, fill, lane: jax.Array, tile: int,
           roll) -> jax.Array:
    """x[lane − d] within each tile (``d`` may be negative); lanes whose
    source falls outside the tile get ``fill``."""
    w = x.shape[-1]
    a = abs(d)
    if a < w:
        y = roll(x, d, -1)
        if tile > w:                  # the source may sit on the next row
            col = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
            wrap = col < a if d > 0 else col >= w - a
            y = jnp.where(wrap, roll(y, 1 if d > 0 else -1, -2), y)
    else:
        y = roll(x, d // w, -2)
    inside = lane >= d if d > 0 else lane < tile + d
    return jnp.where(inside, y, fill)


def _compare_exchange(key, val, d: int, keep_min, lane, roll):
    """One network stage: exchange with the lane at distance ``d``.

    Equal keys are the common case here (duplicate coordinates!) — tie-break
    toward the lower lane so both values survive the exchange.
    """
    pk = _partner(key, d, lane, roll)
    new_key = jnp.where(keep_min, jnp.minimum(key, pk), jnp.maximum(key, pk))
    pv = _partner(val, d, lane, roll)
    is_lo = jnp.bitwise_and(lane, d) == 0
    take_self_min = jnp.logical_or(
        key < pk, jnp.logical_and(key == pk, is_lo))
    vmin = jnp.where(take_self_min, val, pv)
    vmax = jnp.where(take_self_min, pv, val)
    return new_key, jnp.where(keep_min, vmin, vmax)


def _bitonic_sort_rows(key, val, *, tile: int | None = None,
                       roll=jnp.roll):
    """Full ascending bitonic sort of every power-of-2 tile (one tile per
    row by default); ``val`` rides along."""
    tile = tile or key.shape[-1]
    lane = _lanes(key, tile)
    for stage in range(int(math.log2(tile))):   # bitonic runs of 2^(s+1)
        up = jnp.bitwise_and(lane, 1 << (stage + 1)) == 0  # direction bit
        for sub in range(stage, -1, -1):        # merge step distance 2^sub
            d = 1 << sub
            is_lo = jnp.bitwise_and(lane, d) == 0
            keep_min = is_lo == up
            key, val = _compare_exchange(key, val, d, keep_min, lane, roll)
    return key, val


def _bitonic_merge_rows(key, val, *, tile: int | None = None,
                        roll=jnp.roll):
    """Ascending merge of *bitonic* tiles: the final log₂ tile stages."""
    tile = tile or key.shape[-1]
    lane = _lanes(key, tile)
    for sub in range(int(math.log2(tile)) - 1, -1, -1):
        d = 1 << sub
        keep_min = jnp.bitwise_and(lane, d) == 0
        key, val = _compare_exchange(key, val, d, keep_min, lane, roll)
    return key, val


def _segmented_total_rows(key, val, *, tile: int | None = None,
                          roll=jnp.roll):
    """Inclusive log-step segmented scan; then keep totals at run tails."""
    tile = tile or key.shape[-1]
    lane = _lanes(key, tile)
    for p in range(int(math.log2(tile))):
        d = 1 << p
        gv = _shift(val, d, 0, lane, tile, roll)
        gk = _shift(key, d, _KEY_FILL, lane, tile, roll)
        val = val + jnp.where(gk == key, gv, 0)
    nxt_key = _shift(key, -1, KEY_INVALID - 1, lane, tile, roll)
    is_tail = key != nxt_key
    valid = key != KEY_INVALID
    return jnp.where(jnp.logical_and(is_tail, valid), val, 0)


def _merge_pairs(key, val, run: int):
    """One tree level as XLA ops: merge adjacent sorted runs of length
    ``run`` into sorted runs of ``2·run`` with run-tail totals."""
    k2 = key.reshape(-1, 2, run)
    key = jnp.concatenate([k2[:, 0], jnp.flip(k2[:, 1], axis=-1)], axis=-1)
    v2 = val.reshape(-1, 2, run)
    val = jnp.concatenate([v2[:, 0], jnp.flip(v2[:, 1], axis=-1)], axis=-1)
    key, val = _bitonic_merge_rows(key, val)
    return key.reshape(-1), _segmented_total_rows(key, val).reshape(-1)


def merge_coalesce_pair(key_a, val_a, key_b, val_b):
    """Two-list bitonic merge: two equal-length ascending streams → one.

    Inputs follow the module's stream contract per list (ascending keys,
    KEY_INVALID padding at the tail, each valid lane carrying a total —
    coalesced lists qualify, run-tail-total streams likewise since their
    non-tail lanes are 0). Output is the merged contract over 2·L lanes:
    globally ascending keys with run-tail totals, so keys appearing in both
    inputs end with the grand total at their tail.

    Plain XLA on the bitonic machinery (the streaming engine's TPU merge
    step). O(L log L) compare-exchanges, no gathers.
    """
    return _merge_pairs(jnp.concatenate([key_a, key_b]),
                        jnp.concatenate([val_a, val_b]), key_a.shape[-1])


def _make_sort_kernel(tile: int):
    def kernel(key_ref, val_ref, key_out_ref, val_out_ref):
        key, val = _bitonic_sort_rows(key_ref[...], val_ref[...], tile=tile,
                                      roll=_roll_vmem)
        key_out_ref[...] = key
        val_out_ref[...] = _segmented_total_rows(key, val, tile=tile,
                                                 roll=_roll_vmem)
    return kernel


def tile_call(kernel, tile: int, arrays, *, interpret: bool):
    """Run a per-tile kernel over 1-D streams whose length is a multiple of
    the power-of-2 ``tile``. The streams are viewed as ``(rows, w)`` with
    ``w = min(tile, 128)`` lanes; each grid step holds whole tiles and at
    least 8 sublanes where the stream is that long (Mosaic's block tiling).
    """
    (n,) = arrays[0].shape
    assert tile & (tile - 1) == 0 and n % tile == 0, (n, tile)
    w = min(tile, LANES)
    rows = n // w
    block_rows = min(rows, max(8, tile // w))
    spec = pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[spec] * len(arrays),
        out_specs=[spec] * len(arrays),
        out_shape=[jax.ShapeDtypeStruct((rows, w), a.dtype) for a in arrays],
        interpret=interpret,
    )(*[a.reshape(rows, w) for a in arrays])
    return [o.reshape(n) for o in out]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_merge_pallas(key: jax.Array, val: jax.Array, *, interpret: bool):
    """Sort a power-of-2-length tile of (key, val) and coalesce equal keys.

    key int32 (invalid = INT32_MAX), val float32, both 1-D of length 2^p
    (at most ``MAX_KERNEL_TILE`` on TPU). Returns (key_sorted,
    val_coalesced) — run tails carry totals, rest 0. For longer streams use
    ``sort_merge_tree_pallas`` (what ops.sort_merge does).
    """
    (n,) = key.shape
    assert n & (n - 1) == 0, f"length {n} must be a power of two"
    return tuple(tile_call(_make_sort_kernel(n), n, [key, val],
                           interpret=interpret))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sort_tiles_pallas(key: jax.Array, val: jax.Array, *, tile: int,
                      interpret: bool):
    """Independently sort+coalesce every length-``tile`` block of the stream
    (``tile`` ≤ ``MAX_KERNEL_TILE`` on TPU) — one grid step per VMEM block
    of whole tiles, every step the same network."""
    return tuple(tile_call(_make_sort_kernel(tile), tile, [key, val],
                           interpret=interpret))


@functools.partial(jax.jit, static_argnames=("tile",))
def sort_tiles_xla(key: jax.Array, val: jax.Array, *, tile: int):
    """XLA realization of ``sort_tiles_pallas``'s exact output contract.

    One batched ``lax.sort`` over the (n/tile, tile) view plus the same
    segmented-total pass (pure jnp, shared with the kernels). The off-TPU
    half of the bucket/hash auto-select, and the TPU path for tiles wider
    than one VMEM block.
    """
    (n,) = key.shape
    assert tile & (tile - 1) == 0 and n % tile == 0, (n, tile)
    k2, v2 = jax.lax.sort((key.reshape(-1, tile), val.reshape(-1, tile)),
                          dimension=1, num_keys=1, is_stable=False)
    tot = _segmented_total_rows(k2, v2)
    return k2.reshape(n), tot.reshape(n)


def sort_tiles(key: jax.Array, val: jax.Array, *, tile: int, mode: str):
    """Per-tile sort+coalesce in the realization ``mode`` names
    (``resolve_mode``); tiles wider than ``MAX_KERNEL_TILE`` never go to the
    compiled kernel, whose block would not fit VMEM."""
    if mode == "xla" or (mode == "pallas" and tile > MAX_KERNEL_TILE):
        return sort_tiles_xla(key, val, tile=tile)
    return sort_tiles_pallas(key, val, tile=tile,
                             interpret=mode == "interpret")


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sort_merge_tree_pallas(key: jax.Array, val: jax.Array, *,
                           tile: int = 4096, interpret: bool):
    """Blocked sort+coalesce of an arbitrary power-of-2-length stream.

    key length must be 2^p (callers pad with KEY_INVALID / 0). Streams that
    fit one tile take the single-network path; larger streams are tile-sorted
    by the kernel, then pairwise-merged up the tree as XLA ops: log₂(n/tile)
    levels of O(n log run) compare-exchanges — O(n log² tile + n
    log(n/tile)·log n) total instead of the monolithic O(n log² n)
    single-tile network. Output contract matches ``bitonic_merge_pallas``:
    globally sorted keys, run-tail totals.
    """
    (n,) = key.shape
    assert n & (n - 1) == 0, f"length {n} must be a power of two"
    assert tile & (tile - 1) == 0, f"tile {tile} must be a power of two"
    tile = min(tile, n)
    key, val = sort_tiles_pallas(key, val, tile=tile, interpret=interpret)
    run = tile
    while run < n:
        key, val = _merge_pairs(key, val, run)
        run *= 2
    return key, val
