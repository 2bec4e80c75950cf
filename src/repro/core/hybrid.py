"""Hybrid ELLPACK + COO format (paper §III-C, Fig. 12).

Rows/columns whose non-zero count exceeds ``NNZ-a + σ`` (mean + one stddev)
would inflate the ELLPACK width ``k`` for everyone; their overflow beyond the
threshold is diverted to a COO side structure. ELL-PEs process the condensed
part with SCCP; COO-PEs process the remainder "following the procedure of
Fig. 5" — i.e. decompression against the other operand (paper §IV-B). We keep
that split faithfully: the COO partial products are computed against the
*densified* other operand, exactly the paper's COO-PE dataflow.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import (Coo, EllCols, EllRows, coo_from_dense,
                      ell_cols_from_dense, ell_rows_from_dense)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HybridRows:
    """Row-wise hybrid for the left matrix: ELLPACK trunk + COO overflow."""

    ell: EllRows
    coo: Coo

    def tree_flatten(self):
        return (self.ell, self.coo), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def to_dense(self) -> jax.Array:
        return self.ell.to_dense() + self.coo.to_dense()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HybridCols:
    ell: EllCols
    coo: Coo

    def tree_flatten(self):
        return (self.ell, self.coo), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def to_dense(self) -> jax.Array:
        return self.ell.to_dense() + self.coo.to_dense()


def ell_width_rule(nnz_per_lane: np.ndarray) -> int:
    """Paper's boundary: k = ceil(mean + std) of per-lane non-zero counts,
    and never wider than the widest lane (slots past it would stay empty,
    and a width past the lane's length cannot be condensed into)."""
    nnz_av = float(np.mean(nnz_per_lane))
    sigma = float(np.std(nnz_per_lane))
    widest = int(np.max(nnz_per_lane))
    return max(1, min(int(np.ceil(nnz_av + sigma)), widest))


def split_rows_hybrid(a: jax.Array, k: int, coo_cap: int) -> HybridRows:
    """Left matrix: first k non-zeros of each *column* into ELLPACK, rest COO."""
    ell = ell_rows_from_dense(a, k)
    trunk = ell.to_dense()
    overflow = a - trunk
    return HybridRows(ell=ell, coo=coo_from_dense(overflow, coo_cap))


def split_cols_hybrid(b: jax.Array, k: int, coo_cap: int) -> HybridCols:
    """Right matrix: first k non-zeros of each *row* into ELLPACK, rest COO."""
    ell = ell_cols_from_dense(b, k)
    trunk = ell.to_dense()
    overflow = b - trunk
    return HybridCols(ell=ell, coo=coo_from_dense(overflow, coo_cap))


def _coo_matmul_dense(coo: Coo, other_dense: jax.Array, left: bool) -> jax.Array:
    """COO-PE path: partial products of a COO operand against the densified
    other operand (paper Fig. 5 procedure). left=True → coo is the A part."""
    m, n = coo.shape
    ok = coo.valid_mask()
    if left:
        # C[r, :] += v * B[c, :]
        rows = jnp.where(ok, coo.row, m)
        gathered = other_dense[jnp.where(ok, coo.col, 0)]          # (cap, n_out)
        contrib = jnp.where(ok[:, None], coo.val[:, None] * gathered, 0)
        out = jnp.zeros((m + 1, other_dense.shape[1]), contrib.dtype)
        return out.at[rows].add(contrib)[:m]
    else:
        # C[:, c] += A[:, r] * v
        cols = jnp.where(ok, coo.col, n)
        gathered = other_dense[:, jnp.where(ok, coo.row, 0)]        # (n_out, cap)
        contrib = jnp.where(ok[None, :], gathered * coo.val[None, :], 0)
        out = jnp.zeros((other_dense.shape[0], n + 1), contrib.dtype)
        return out.at[:, cols].add(contrib)[:, :n]


def hybrid_spgemm_dense(a: HybridRows, b: HybridCols) -> jax.Array:
    """Full hybrid SpGEMM (dense output): ELL×ELL via SCCP + three COO-PE terms."""
    from .spgemm import spgemm_dense  # local import to avoid cycle

    c = spgemm_dense(a.ell, b.ell)                              # ELL-PEs (SCCP)
    b_dense = b.to_dense()
    a_ell_dense = a.ell.to_dense()
    c = c + _coo_matmul_dense(a.coo, b_dense, left=True)        # COO_A × (all of B)
    c = c + _coo_matmul_dense(b.coo, a_ell_dense, left=False)   # ELL_A × COO_B
    return c
