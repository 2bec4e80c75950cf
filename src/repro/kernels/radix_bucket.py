"""Propagation-blocking accumulation: bin the product stream by row range,
then sort/reduce every bucket independently (cf. Gu et al., "Bandwidth-
Optimized Parallel Algorithms for SpGEMM using Propagation Blocking").

The monolithic sort paths (core/accumulate, the bitonic merge tree) touch the
whole k_a·n·k_b product stream at every network level. Propagation blocking
replaces the global pass with two bandwidth-friendly ones:

  1. **Stable binning** — one linear sweep assigns every product to the bucket
     that owns its output-row range and writes it at ``(bucket, rank)`` where
     ``rank`` is the running per-bucket count. Ranks come from a chunked scan
     carrying one (n_buckets,) counter vector (``bin_ranks_pallas``): each
     chunk does a one-hot cumsum in VMEM (one triangular matmul on the MXU),
     gather-free — the rank readback is a masked row-sum, not a dynamic
     gather.
  2. **Per-bucket sort+coalesce** — every bucket is a power-of-2 tile, so ALL
     buckets ride the batch axis of ONE bitonic network
     (``bitonic_merge.sort_tiles_pallas``), working-set bounded by
     n_buckets-way blocking exactly like ``spgemm_streaming`` bounds the
     multiply — but the output stays sparse COO, not dense.

Because buckets partition the *key range* (contiguous output-row spans),
concatenating sorted buckets in bucket order is globally sorted: a run of
equal keys can never straddle a bucket boundary, and the KEY_INVALID padding
parked at each bucket tail is exactly what the downstream compaction
(`spgemm._coo_from_merged`) already skips.

Bucket capacity is static (JAX shapes). Products that land beyond a full
bucket are *dropped and counted* — callers surface ``dropped`` by poisoning
``Coo.ngroups`` so the existing overflow machinery (``check_no_overflow`` /
``overflowed()``) reports it; the planner sizes ``bucket_cap`` from an exact
per-bucket histogram so the planned path never drops.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitonic_merge import KEY_INVALID, sort_tiles
from .platform import resolve_mode

_RANK_CHUNK = 256


def _make_rank_kernel(n_buckets: int, chunk: int):
    """Per-element rank within its bucket, one chunk per grid step.

    A VMEM scratch row carries the per-bucket element count seen so far;
    within a chunk the inclusive one-hot cumsum is one lower-triangular
    matmul on the MXU (float32 counts ≤ 2²⁴ are exact) and the rank
    readback is a masked row-sum (no gather). Invalid lanes (bid < 0) match
    no one-hot column and rank -1, which the binning scatter parks in the
    dump slot.
    """
    def kernel(bid_ref, rank_out_ref, count_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            count_ref[...] = jnp.zeros_like(count_ref)

        bid = bid_ref[...]                                    # (chunk, 1)
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, n_buckets), 1)
        oh = (bid == ids).astype(jnp.float32)                 # (chunk, nb)
        r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        tril = (c <= r).astype(jnp.float32)
        incl = jnp.dot(tril, oh, preferred_element_type=jnp.float32)
        incl = incl + count_ref[...]
        rank = jnp.sum(oh * incl, axis=1, keepdims=True) - 1.0
        rank_out_ref[...] = rank.astype(jnp.int32)
        count_ref[...] += jnp.sum(oh, axis=0, keepdims=True)
    return kernel


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def bin_ranks_pallas(bid: jax.Array, *, n_buckets: int,
                     interpret: bool) -> jax.Array:
    """Stable-binning ranks: rank[i] = #{j <= i : bid[j] == bid[i]} - 1.

    ``bid`` int32 (-1 = invalid, yields rank -1); length must be a multiple
    of the scan chunk (callers pad — product streams are already padded to a
    power of two for the sort stage). The XLA realization is
    ``bin_ranks_xla``; ``bucket_merge`` picks per ``resolve_mode``.
    """
    (n,) = bid.shape
    chunk = min(_RANK_CHUNK, n)
    assert n % chunk == 0, (n, chunk)
    col = pl.BlockSpec((chunk, 1), lambda i: (i, 0))
    rank = pl.pallas_call(
        _make_rank_kernel(n_buckets, chunk),
        grid=(n // chunk,),
        in_specs=[col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, n_buckets), jnp.float32)],
        interpret=interpret,
    )(bid.reshape(n, 1))
    return rank.reshape(n)


@functools.partial(jax.jit, static_argnames=("n_buckets",))
def bin_ranks_xla(bid: jax.Array, *, n_buckets: int) -> jax.Array:
    """XLA realization of ``bin_ranks_pallas``'s exact contract.

    A stable argsort groups equal bucket ids; rank-in-bucket is position
    minus the group's first position (one ``searchsorted`` against the
    sorted ids), scattered back to input order. ``n_buckets`` is accepted
    for signature parity — the rank of an element never depends on it.
    """
    (n,) = bid.shape
    order = jnp.argsort(bid, stable=True)
    sb = bid[order]
    first = jnp.searchsorted(sb, sb, side="left").astype(jnp.int32)
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - first
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    return jnp.where(bid < 0, -1, rank)


def bucket_bounds(n_rows: int, n_cols: int, n_buckets: int) -> int:
    """Keys-per-bucket span: buckets own ``rows_per_bucket`` contiguous
    output rows, i.e. ``rows_per_bucket * n_cols`` contiguous packed keys."""
    rows_per_bucket = -(-n_rows // n_buckets)   # ceil
    return rows_per_bucket * n_cols


def bucket_merge(key: jax.Array, val: jax.Array, *, n_buckets: int,
                 bucket_cap: int, keys_per_bucket: int,
                 interpret: bool | None = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Propagation-blocking sort+coalesce of a packed-key product stream.

    key   : (n,) int32 packed row*n_cols+col, KEY_INVALID for dead lanes.
    val   : (n,) float.
    Returns ``(key_sorted, totals, dropped)``: bucket-concatenated globally
    sorted keys with run-tail totals (the ``sort_merge`` output contract,
    with KEY_INVALID runs at each bucket tail), plus the count of products
    dropped by full buckets (0 when ``bucket_cap`` was sized from the true
    histogram — see plan.planner).

    ``interpret=None`` (default) auto-selects the realization of the two
    kernel stages: compiled Pallas on TPU, the XLA equivalents
    (``bin_ranks_xla`` / ``sort_tiles_xla``) elsewhere — never the
    interpreter, which ``interpret=True`` still forces for kernel tests.
    """
    return _bucket_merge_jit(key, val, n_buckets=n_buckets,
                             bucket_cap=bucket_cap,
                             keys_per_bucket=keys_per_bucket,
                             mode=resolve_mode(interpret))


@functools.partial(jax.jit, static_argnames=("n_buckets", "bucket_cap",
                                             "keys_per_bucket", "mode"))
def _bucket_merge_jit(key: jax.Array, val: jax.Array, *, n_buckets: int,
                      bucket_cap: int, keys_per_bucket: int,
                      mode: str) -> Tuple[jax.Array, jax.Array, jax.Array]:
    (n,) = key.shape
    assert bucket_cap & (bucket_cap - 1) == 0, bucket_cap
    valid = key != KEY_INVALID
    bid = jnp.where(valid, key // keys_per_bucket, -1).astype(jnp.int32)
    bid = jnp.minimum(bid, n_buckets - 1)       # ceil-split slack rows
    if mode == "xla":
        rank = bin_ranks_xla(bid, n_buckets=n_buckets)
    else:
        rank = bin_ranks_pallas(bid, n_buckets=n_buckets,
                                interpret=mode == "interpret")

    in_cap = jnp.logical_and(rank >= 0, rank < bucket_cap)
    dump = n_buckets * bucket_cap
    dst = jnp.where(in_cap, bid * bucket_cap + rank, dump)
    binned_key = (jnp.full((dump + 1,), KEY_INVALID, jnp.int32)
                  .at[dst].set(jnp.where(in_cap, key, KEY_INVALID))[:dump])
    binned_val = (jnp.zeros((dump + 1,), val.dtype)
                  .at[dst].set(jnp.where(in_cap, val, 0))[:dump])
    dropped = jnp.sum(jnp.logical_and(valid, jnp.logical_not(in_cap)))

    key_s, tot = sort_tiles(binned_key, binned_val, tile=bucket_cap,
                            mode=mode)
    return key_s, tot, dropped.astype(jnp.int32)
