"""Pallas TPU kernel: ELLPACK × dense SpMM (structured multiply, MXU path).

C[m, :] = Σ_{s,c : A.idx[s,c] == m} A.val[s,c] · X[c, :]

This is the SCCP multiply with a *structured* output (the scatter target is
the row coordinate), the workhorse behind MoE dispatch/combine and
SparseLinear (DESIGN.md §3). TPU has no scatter unit; the idiomatic mapping
is **expansion to a one-hot tile × MXU matmul** — the systolic array performs
the scatter-accumulate as a dense (BM × BN) @ (BN × D) product per tile,
which is how the hardware wants it (HW-adaptation note: a CUDA kernel would
use atomics; on TPU the one-hot matmul is the roofline-correct choice
whenever k·n/m is within ~MXU occupancy, which holds for ELLPACK widths).

Grid: (m_tiles, n_tiles); the ELLPACK slab loop (k, small & static) is
unrolled inside the kernel. Output tile (BM, D) is revisited across n_tiles
and accumulated in place (init at j == 0).

VMEM per step: a tiles 2·k·BN·4B + x tile BN·D·4B + out BM·D·4B.
BM = BN = 128 (MXU native), D ≤ 512 per call (ops.py chunks larger D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BM = 128
BN = 128


def _ell_spmm_kernel(a_val_ref, a_idx_ref, x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    i = pl.program_id(0)
    row_base = i * BM
    rows = row_base + jax.lax.broadcasted_iota(jnp.int32, (BM, BN), 0)
    a_val = a_val_ref[...]            # (k, BN)
    a_idx = a_idx_ref[...]            # (k, BN)
    x = x_ref[...]                    # (BN, D)
    k = a_val.shape[0]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for s in range(k):                # static unroll over ELLPACK slabs
        onehot = jnp.where(a_idx[s][None, :] == rows, a_val[s][None, :], 0.0)
        acc = acc + jnp.dot(onehot, x, preferred_element_type=jnp.float32)
    o_ref[...] += acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def ell_spmm_pallas(a_val: jax.Array, a_idx: jax.Array, x: jax.Array,
                    *, n_rows: int, interpret: bool) -> jax.Array:
    """A(ELLPACK row-wise, (k, n)) @ X(n, d) -> (n_rows, d).

    n % BN == 0, n_rows % BM == 0, handled by ops.ell_spmm padding.
    """
    k, n = a_val.shape
    n2, d = x.shape
    assert n == n2 and n % BN == 0 and n_rows % BM == 0
    grid = (n_rows // BM, n // BN)
    return pl.pallas_call(
        _ell_spmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, BN), lambda i, j: (0, j)),
            pl.BlockSpec((k, BN), lambda i, j: (0, j)),
            pl.BlockSpec((BN, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BM, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, d), x.dtype),
        interpret=interpret,
    )(a_val, a_idx, x)
