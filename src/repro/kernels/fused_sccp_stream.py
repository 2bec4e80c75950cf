"""One streaming step: SCCP slab multiply + tile sort (Pallas on TPU).

One streaming step of the paper's Fig. 8 iteration: the products of one A
slab against all B slabs are formed and packed into coordinate keys (one
XLA fusion, 8 B/lane of key+val), then the whole ``pot(n·k_b)`` tile is
bitonic-sorted and its run totals formed in one VMEM residency by the
``bitonic_merge`` tile kernel. Output is the ``bitonic_merge`` stream
contract (ascending keys, invalid lanes parked at INT32_MAX, run-tail
totals), which the streaming accumulation engine (core/streaming.py)
compacts and merges into its running buffer.

The kernel path holds the tile in one block, so it serves tiles of at most
``bitonic_merge.MAX_KERNEL_TILE`` lanes (kernels/ops.fused_slab_sort
routes wider tiles, and every tile off-TPU, to ``fused_slab_sort_xla`` —
packed keys through XLA's fused ``lax.sort`` plus the log-step segmented
total).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bitonic_merge import (KEY_INVALID, _make_sort_kernel,
                            _segmented_total_rows, next_pot as _pot,
                            tile_call)

INVALID = -1


def _pack_tile(a_val, a_idx, b_val, b_idx, n_cols: int, pot_len: int):
    """Slab products → packed int32 keys + values, padded to ``pot_len``.

    a_val/a_idx: (n,) one A slab; b_val/b_idx: (n, k_b) all B slabs.
    Shared by the kernel path and the XLA realization.
    """
    val = a_val[:, None] * b_val                       # (n, k_b)
    row = jnp.broadcast_to(a_idx[:, None], val.shape)
    ok = jnp.logical_and(row >= 0, b_idx >= 0)
    key = jnp.where(ok, row * n_cols + b_idx, KEY_INVALID).astype(jnp.int32)
    val = jnp.where(ok, val, 0)
    key = key.reshape(1, -1)
    val = val.reshape(1, -1)
    pad = pot_len - key.shape[-1]
    if pad:
        key = jnp.concatenate(
            [key, jnp.full((1, pad), KEY_INVALID, key.dtype)], axis=-1)
        val = jnp.concatenate(
            [val, jnp.zeros((1, pad), val.dtype)], axis=-1)
    return key, val


@functools.partial(jax.jit, static_argnames=("n_cols", "interpret"))
def fused_slab_sort_pallas(a_val: jax.Array, a_idx: jax.Array,
                           b_val: jax.Array, b_idx: jax.Array, *,
                           n_cols: int, interpret: bool):
    """Multiply + in-VMEM sort of one slab tile.

    ``a_val``/``a_idx``: (n,) — one A slab; ``b_val``/``b_idx``: (n, k_b),
    with ``pot(n·k_b) ≤ MAX_KERNEL_TILE`` on TPU (one block). Returns
    ``(key, tot)`` of length ``pot(n·k_b)``: ascending packed coordinate
    keys (invalid = INT32_MAX) with run-tail totals. Requires
    ``n_rows·n_cols < 2³¹`` (packed int32 keys).
    """
    n, k_b = b_val.shape
    pot_len = _pot(n * k_b)
    key, val = _pack_tile(a_val, a_idx, b_val, b_idx, n_cols, pot_len)
    key, tot = tile_call(_make_sort_kernel(pot_len), pot_len,
                         [key.reshape(-1), val.reshape(-1)],
                         interpret=interpret)
    return key, tot


@functools.partial(jax.jit, static_argnames=("n_cols",))
def fused_slab_sort_xla(a_val: jax.Array, a_idx: jax.Array,
                        b_val: jax.Array, b_idx: jax.Array, *,
                        n_cols: int):
    """Same contract through XLA's fused sort (the off-TPU realization)."""
    n, k_b = b_val.shape
    pot_len = _pot(n * k_b)
    key, val = _pack_tile(a_val, a_idx, b_val, b_idx, n_cols, pot_len)
    key, val = key.reshape(-1), val.reshape(-1)
    key, val = jax.lax.sort((key, val), dimension=0, num_keys=1,
                            is_stable=False)
    tot = _segmented_total_rows(key[None, :], val[None, :])[0]
    return key, tot
