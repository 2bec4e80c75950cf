"""The plain reference for C = A·Aᵀ and the comparison that decides
``correct``.

The reference is scipy's sparse product in float64 on the host. It imports
nothing of the program and takes nothing the program made: it is given
the pattern and the values the benchmark generated. It yields, for every
structural output coordinate in row-major order, the exact-to-rounding
value and the entry's scale Σ|a_ik·b_kj|, the size against which the
float32 rounding of a sum of products is measured.

The control is the same reference computed one precision below the one
the configuration states: the values rounded to bfloat16 and the product
rounded to bfloat16. It stands in the program's place and must come out
as not correct.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp


@dataclasses.dataclass
class Product:
    """Reference C: sorted coordinates, values and per-entry scales."""

    n: int
    row: np.ndarray      # (nnz,) int32, row-major order
    col: np.ndarray      # (nnz,) int32
    val: np.ndarray      # (nnz,) float64
    scale: np.ndarray    # (nnz,) float64, Σ|a_ik·b_kj| of the entry

    @property
    def nnz(self) -> int:
        return int(self.row.size)


def product(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
            n: int) -> Product:
    """C = A·Aᵀ for A given as coordinates and values (float64 sums).

    The coordinates are the structural pattern the program must return:
    every (i, j) that some product a_ik·a_jk reaches, whatever its value.
    scipy's product drops an entry whose sum is exactly 0. A sum of
    |a_ik·a_jk| vanishes only where each of its products has a zero
    factor, so where A holds no zero the pattern of |A|·|A|ᵀ is the
    structural one; where A holds a zero (a float32 normal draw is exactly
    0 about once in 2²³) the pattern comes from A's structure, with a 1 at
    every entry, instead.
    """
    v = vals.astype(np.float64)
    a = sp.csr_matrix((v, (rows, cols)), shape=(n, n))
    at = a.T.tocsr()
    mag = abs(a) @ abs(at)
    if np.all(vals != 0):
        pat = mag
    else:
        s = sp.csr_matrix((np.ones_like(v), (rows, cols)), shape=(n, n))
        pat = s @ s.T.tocsr()
    # scipy also drops a signed sum that cancels to exactly 0, as sums of
    # products of bfloat16-rounded values (the control) can; the
    # coordinate stays in the pattern, so read the signed values at it
    signed = _at(a @ at, pat)
    scale = _at(mag, pat)
    # one in-place row sort carries both arrays along: value as the real
    # part, scale as the imaginary part
    both = sp.csr_matrix((signed + 1j * scale, pat.indices, pat.indptr),
                         shape=(n, n))
    both.sort_indices()
    row = np.repeat(np.arange(n, dtype=np.int32), np.diff(both.indptr))
    return Product(n=n, row=row, col=both.indices.astype(np.int32),
                   val=both.data.real.copy(), scale=both.data.imag.copy())


def _at(x: sp.csr_matrix, pat: sp.csr_matrix) -> np.ndarray:
    """Values of ``x`` at every stored coordinate of ``pat``, in ``pat``'s
    order, 0 where ``x`` has none; ``x`` holds a subset of ``pat``'s
    coordinates. A row that ``x`` holds whole, in ``pat``'s order (scipy
    drops an entry without moving the others), is read through as it is;
    the coordinates of the other rows are looked up."""
    xn, pn = np.diff(x.indptr), np.diff(pat.indptr)
    whole = xn == pn
    if whole.all() and np.array_equal(x.indices, pat.indices):
        return x.data
    xw, pw = np.repeat(whole, xn), np.repeat(whole, pn)
    if not np.array_equal(x.indices[xw], pat.indices[pw]):
        whole[:] = False
        xw[:], pw[:] = False, False
    out = np.zeros(pat.nnz, x.data.dtype)
    out[pw] = x.data[xw]
    n = pat.shape[1]
    rows = np.arange(pat.shape[0], dtype=np.int64)
    xkey = np.repeat(rows, xn)[~xw] * n + x.indices[~xw]
    pkey = np.repeat(rows, pn)[~pw] * n + pat.indices[~pw]
    if xkey.size == 0:
        return out
    order = np.argsort(xkey)
    pos = np.minimum(np.searchsorted(xkey[order], pkey), xkey.size - 1)
    hit = xkey[order][pos] == pkey
    out[~pw] = np.where(hit, x.data[~xw][order][pos], 0.0)
    return out


def control(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
            n: int) -> dict:
    """The reference one precision down, in the program's output layout:
    values rounded to bfloat16, the product rounded to bfloat16."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    low = vals.astype(bf16).astype(np.float64)
    ref = product(rows, cols, low, n)
    return {"row": ref.row, "col": ref.col,
            "val": ref.val.astype(bf16).astype(np.float32),
            "ngroups": ref.nnz}


def compare(out: dict, ref: Product) -> dict:
    """Readings of one program output against the reference.

    ``out`` holds the program's padded COO on the host: ``row``, ``col``,
    ``val`` (capacity-long) and ``ngroups``. Returns

    * ``nnz_gap``: |ngroups − nnz(C)|;
    * ``coord_mismatch``: slots whose coordinate differs from the
      reference's at the same position, plus reference coordinates past
      the capacity and filled slots past nnz(C);
    * ``value_gap``: the largest |c − c_ref| / Σ|a·b| over the slots whose
      coordinate matches (infinite where none does; 0 at an entry whose
      scale and gap are both 0).
    """
    m = ref.nnz
    cap = int(out["row"].shape[0])
    k = min(m, cap)
    row, col = out["row"][:k], out["col"][:k]
    same = (row == ref.row[:k]) & (col == ref.col[:k])
    mismatch = (k - int(np.count_nonzero(same)) + (m - k)
                + int(np.count_nonzero(out["row"][k:] >= 0)))
    if np.count_nonzero(same):
        diff = np.abs(out["val"][:k].astype(np.float64) - ref.val[:k])
        # an entry whose every product has a zero factor has scale 0: it
        # reads 0 where the program gives exactly 0, and infinite elsewhere
        with np.errstate(divide="ignore", invalid="ignore"):
            gap = np.where(diff == 0, 0.0, diff / ref.scale[:k])
        value_gap = float(gap[same].max())
    else:
        value_gap = float("inf")
    return {"nnz_gap": abs(int(out["ngroups"]) - m),
            "coord_mismatch": int(mismatch),
            "value_gap": value_gap}
