"""Random pattern matched to a row of the SPLIM paper's Table I.

The SuiteSparse file is not in the repository, so the pattern is drawn
from the row's published statistics: ``n`` rows and columns, ``nnz``
entries in all, per-row counts from a normal law with the row's sigma,
columns placed uniformly at random. This is the random, no-locality
regime: almost every product lands on an output coordinate of its own.

The ELL width is fixed by the configuration (``ell_k``), so every seed
gives operands of one shape and the same work: the rare column that draws
more than ``ell_k`` entries hands its excess to random columns with room.
"""
from __future__ import annotations

import numpy as np


def _row_counts(rng, n: int, nnz: int, sigma: float) -> np.ndarray:
    """Per-row counts from N(nnz/n, sigma), clipped to [0, n], then moved
    one at a time to sum to exactly ``nnz``."""
    counts = np.clip(np.round(rng.normal(nnz / n, sigma, size=n)), 0, n)
    counts = counts.astype(np.int64)
    while (diff := nnz - int(counts.sum())) != 0:
        room = np.flatnonzero(counts < n) if diff > 0 else \
            np.flatnonzero(counts > 0)
        pick = rng.choice(room, size=min(abs(diff), room.size),
                          replace=False)
        counts[pick] += 1 if diff > 0 else -1
    return counts


def _distinct_keys(rng, rows: np.ndarray, n: int) -> np.ndarray:
    """Sorted keys ``row·n + col`` with a uniformly random column for each
    entry of ``rows``, redrawn until no row holds a column twice."""
    key = rows * n + rng.integers(0, n, size=rows.size)
    while True:
        key.sort()
        dup = np.flatnonzero(key[1:] == key[:-1]) + 1
        if dup.size == 0:
            return key
        key[dup] = (key[dup] // n) * n + rng.integers(0, n, size=dup.size)


def _cap_columns(rng, rows: np.ndarray, cols: np.ndarray, n: int, k: int):
    """Move entries out of columns holding more than ``k`` to random
    columns with room, never into a column the entry's row already has."""
    while True:
        cnt = np.bincount(cols, minlength=n)
        over = np.flatnonzero(cnt > k)
        if over.size == 0:
            return cols
        for c in over:
            members = np.flatnonzero(cols == c)
            move = rng.choice(members, size=int(cnt[c]) - k, replace=False)
            for e in move:
                taken = set(cols[rows == rows[e]].tolist())
                while True:
                    d = int(rng.integers(0, n))
                    if cnt[d] < k and d not in taken:
                        break
                cols[e] = d
                cnt[c] -= 1
                cnt[d] += 1


def pattern(cfg: dict, index: int, seed: int):
    """(rows, cols, n) of pattern ``index`` of the configuration, from
    ``seed``: int64 coordinates sorted by row then column, and the order
    n of the square matrix."""
    n, nnz, k = int(cfg["n"]), int(cfg["nnz"]), int(cfg["ell_k"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1, index]))
    counts = _row_counts(rng, n, nnz, float(cfg["sigma"]))
    rows = np.repeat(np.arange(n, dtype=np.int64), counts)
    key = _distinct_keys(rng, rows, n)
    rows, cols = key // n, key % n
    if np.bincount(cols, minlength=n).max() > k:
        cols = _cap_columns(rng, rows, cols, n, k)
        key = np.sort(rows * n + cols)
        rows, cols = key // n, key % n
    return rows, cols, n
