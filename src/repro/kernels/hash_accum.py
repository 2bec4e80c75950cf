"""Fixed-capacity open-addressing hash accumulation (cf. Nagasaka et al.,
"High-performance sparse matrix-matrix products on Intel KNL").

Hash accumulators skip sorting the product stream entirely: every product
scatter-adds into a hash table keyed by its packed output coordinate, and
only the *table* (size ~ nnz(C), not ~ flops) is sorted to meet the
sorted-COO output contract. When the compression ratio flops/nnz(C) is low —
lots of distinct output coordinates, few duplicates per coordinate — the
stream-sized sort the other backends pay for buys almost no coalescing, and
probing + a table-sized bitonic pass wins.

Layout: output rows are split into ``n_blocks`` contiguous ranges; each block
owns a private power-of-two table of ``block_cap`` slots (linear probing,
multiplicative hashing). Blocks exist for the same reason propagation-blocking
buckets do — they bound the probe working set AND make the final sort
block-local: per-block tables sorted independently (all blocks ride the batch
axis of ONE bitonic network, ``bitonic_merge.sort_tiles_pallas``) concatenate
into a globally sorted stream because block key ranges are disjoint.

Slot assignment is a ``lax.while_loop`` over probe rounds (traced once, so
its gathers compile once rather than per unrolled round): each round
gathers the current occupant of every pending product's probe slot, claims
empty slots with a scatter-min (ties between distinct keys racing for one
slot resolve to the min; losers probe on), and retires
products whose slot now holds their key. Values never enter the loop — once
every product knows its slot, ONE segment_sum accumulates the whole stream.

A product that exhausts ``max_probes`` (or a full block table) is dropped and
counted; callers poison ``Coo.ngroups`` with the drop count so the existing
overflow machinery reports it. By default ``max_probes = block_cap`` — linear
probing visits every slot in a full cycle, so insertion only fails when a
block's table is genuinely full. The planner sizes ``block_cap`` at ≥ 2× the
per-block nnz(C) upper bound, keeping load factor ≤ 0.5 and expected probes
O(1).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bitonic_merge import KEY_INVALID, sort_tiles
from .platform import resolve_mode

_EMPTY = KEY_INVALID              # sorts-last sentinel doubles as empty slot
_HASH_MULT = np.uint32(2654435761)    # Knuth multiplicative (2^32 / phi)


def _hash(key: jax.Array, cap: int) -> jax.Array:
    """Multiplicative hash of a packed coordinate into [0, cap)."""
    h = key.astype(jnp.uint32) * _HASH_MULT
    h = h ^ (h >> np.uint32(16))
    return (h & np.uint32(cap - 1)).astype(jnp.int32)


def hash_merge(key: jax.Array, val: jax.Array, *, n_blocks: int,
               block_cap: int, keys_per_block: int,
               max_probes: Optional[int] = None,
               interpret: bool | None = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Hash-accumulate a packed-key product stream; emit sorted table.

    key : (n,) int32 packed row*n_cols+col, KEY_INVALID for dead lanes.
    val : (n,) float.
    Returns ``(key_sorted, totals, dropped)`` in the ``sort_merge`` output
    contract: globally sorted unique keys (block-concatenated, _EMPTY slots
    parked at each block tail) whose lanes carry full group totals, plus the
    count of products dropped by probe/table exhaustion.

    The probe loop is plain XLA everywhere; only the final table sort is a
    kernel. ``interpret=None`` (default) auto-selects its realization:
    compiled Pallas on TPU, ``sort_tiles_xla`` elsewhere — never the
    interpreter, which ``interpret=True`` still forces for kernel tests.
    """
    return _hash_merge_jit(key, val, n_blocks=n_blocks, block_cap=block_cap,
                           keys_per_block=keys_per_block,
                           max_probes=max_probes,
                           mode=resolve_mode(interpret))


@functools.partial(jax.jit, static_argnames=("n_blocks", "block_cap",
                                             "keys_per_block", "max_probes",
                                             "mode"))
def _hash_merge_jit(key: jax.Array, val: jax.Array, *, n_blocks: int,
                    block_cap: int, keys_per_block: int,
                    max_probes: Optional[int],
                    mode: str) -> Tuple[jax.Array, jax.Array, jax.Array]:
    (n,) = key.shape
    assert block_cap & (block_cap - 1) == 0, block_cap
    probes = block_cap if max_probes is None else min(max_probes, block_cap)
    tsize = n_blocks * block_cap

    valid = key != KEY_INVALID
    block = jnp.minimum(key // keys_per_block, n_blocks - 1)
    base = jnp.where(valid, block * block_cap, 0)
    h0 = _hash(key, block_cap)

    def cond(state):
        p, _, _, pending = state
        return jnp.logical_and(p < probes, jnp.any(pending))

    def body(state):
        p, table, slot_of, pending = state
        slot = base + ((h0 + p) & (block_cap - 1))
        occupant = table[slot]
        attempt = jnp.where(jnp.logical_and(pending, occupant == _EMPTY),
                            key, _EMPTY)
        table = table.at[slot].min(attempt)
        matched = jnp.logical_and(pending, table[slot] == key)
        slot_of = jnp.where(matched, slot, slot_of)
        return p + 1, table, slot_of, jnp.logical_and(
            pending, jnp.logical_not(matched))

    state = (jnp.zeros((), jnp.int32),
             jnp.full((tsize,), _EMPTY, jnp.int32),
             jnp.full((n,), -1, jnp.int32),
             valid)
    _, table_key, slot_of, pending = jax.lax.while_loop(cond, body, state)
    dropped = jnp.sum(pending)

    seg = jnp.where(slot_of >= 0, slot_of, tsize)
    table_val = jax.ops.segment_sum(jnp.where(slot_of >= 0, val, 0), seg,
                                    num_segments=tsize + 1)[:tsize]
    key_s, tot = sort_tiles(table_key, table_val, tile=block_cap, mode=mode)
    return key_s, tot, dropped.astype(jnp.int32)
