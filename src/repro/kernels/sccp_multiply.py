"""Pallas TPU kernel: SCCP slab-pair structured multiply (paper Fig. 8).

The hot inner loop of SPLIM's multiply phase: every (A row-slab, B col-slab)
pair combined element-wise along the shared axis. On the memristor array this
is one in-situ ⊙ over all lanes; on TPU v5e we tile the lane axis ``n`` into
VMEM blocks (lane-dim multiple of 128 for VREG alignment) and let the VPU
stream the broadcasted product. Slab counts (k_a, k_b) are small (ELLPACK
widths), so they ride whole in each block.

Memory layout per grid step (lane tile of size BN):
    a_val/a_idx : (k_a, BN)   VMEM
    b_val/b_idx : (BN, k_b)   VMEM
    out         : (k_a, BN, k_b) val/row/col  VMEM
VMEM working set = BN·(2·k_a + 2·k_b + 3·k_a·k_b)·4B — BN=512, k=32 →
~6.5 MB, inside the 16 MB v5e VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INVALID = -1
LANE_BLOCK = 512  # multiple of 128 (VREG lane width)


def _sccp_kernel(a_val_ref, a_idx_ref, b_val_ref, b_idx_ref,
                 val_ref, row_ref, col_ref):
    a_val = a_val_ref[...]            # (k_a, BN)
    a_idx = a_idx_ref[...]
    b_val = b_val_ref[...]            # (BN, k_b)
    b_idx = b_idx_ref[...]
    val = a_val[:, :, None] * b_val[None, :, :]
    row = jnp.broadcast_to(a_idx[:, :, None], val.shape)
    col = jnp.broadcast_to(b_idx[None, :, :], val.shape)
    ok = jnp.logical_and(row >= 0, col >= 0)
    val_ref[...] = jnp.where(ok, val, 0)
    row_ref[...] = jnp.where(ok, row, INVALID)
    col_ref[...] = jnp.where(ok, col, INVALID)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sccp_multiply_pallas(a_val: jax.Array, a_idx: jax.Array,
                         b_val: jax.Array, b_idx: jax.Array,
                         *, block_n: int = LANE_BLOCK, interpret: bool):
    """Tiled SCCP multiply. Shapes: a (k_a, n), b (n, k_b); n % block_n == 0.

    Returns (val, row, col) each (k_a, n, k_b).
    """
    k_a, n = a_val.shape
    n2, k_b = b_val.shape
    assert n == n2, (n, n2)
    assert n % block_n == 0, f"n={n} must be a multiple of block_n={block_n}"
    grid = (n // block_n,)
    out_shape = [
        jax.ShapeDtypeStruct((k_a, n, k_b), a_val.dtype),
        jax.ShapeDtypeStruct((k_a, n, k_b), jnp.int32),
        jax.ShapeDtypeStruct((k_a, n, k_b), jnp.int32),
    ]
    return pl.pallas_call(
        _sccp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k_a, block_n), lambda i: (0, i)),
            pl.BlockSpec((k_a, block_n), lambda i: (0, i)),
            pl.BlockSpec((block_n, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_n, k_b), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k_a, block_n, k_b), lambda i: (0, i, 0)),
            pl.BlockSpec((k_a, block_n, k_b), lambda i: (0, i, 0)),
            pl.BlockSpec((k_a, block_n, k_b), lambda i: (0, i, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(a_val, a_idx, b_val, b_idx)
