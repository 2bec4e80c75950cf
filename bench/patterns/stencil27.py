"""The HPCG reference problem's matrix pattern: a 27-point stencil.

HPCG's ``GenerateProblem`` couples each point of an ``nx × ny × nz`` grid
to every grid point within one step along each axis (itself included),
and numbers the points with x fastest. Pattern ``index`` takes the grid
as the configuration gives it (index 0) or with its axes reversed
(index 1): the same matrix sizes, a different ordering of the unknowns,
so a different pattern.
"""
from __future__ import annotations

import numpy as np


def pattern(cfg: dict, index: int, seed: int):
    """(rows, cols, n) of the stencil matrix: int64 coordinates sorted by
    row then column, and the order n. The pattern does not depend on
    ``seed``."""
    dims = (int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"]))
    if index % 2:
        dims = dims[::-1]
    nx, ny, nz = dims
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                          indexing="ij")
    x, y, z = x.ravel(), y.ravel(), z.ravel()
    row = (z * ny + y) * nx + x
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                xx, yy, zz = x + dx, y + dy, z + dz
                ok = ((xx >= 0) & (xx < nx) & (yy >= 0) & (yy < ny)
                      & (zz >= 0) & (zz < nz))
                rows.append(row[ok])
                cols.append(((zz * ny + yy) * nx + xx)[ok])
    rows = np.concatenate(rows).astype(np.int64)
    cols = np.concatenate(cols).astype(np.int64)
    order = np.lexsort((cols, rows))
    return rows[order], cols[order], nx * ny * nz

