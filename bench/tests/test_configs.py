"""The configurations' patterns have the sizes their sources state."""
import json

import numpy as np
import pytest
import scipy.sparse as sp

import harness
from patterns import stencil27, table1

CONFIGS = harness.BENCH / "configs"


def _load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("index", [0, 1])
def test_hpcg27_counts(index):
    cfg = _load("hpcg27")
    rows, cols, n = stencil27.pattern(cfg, index, seed=0)
    a = sp.csr_matrix((np.ones(rows.size), (rows, cols)), shape=(n, n))
    col_n = np.bincount(cols, minlength=n).astype(np.int64)
    assert (n, rows.size) == (40_960, 1_042_648)
    assert (a @ a.T).nnz == 4_600_904
    assert int((col_n ** 2).sum()) == 27_049_400
    assert col_n.max() == cfg["ell_k"] == 27
    assert (abs(a - a.T)).nnz == 0          # the stencil is symmetric


def test_hpcg27_orientations_differ():
    cfg = _load("hpcg27")
    p0 = stencil27.pattern(cfg, 0, seed=0)
    p1 = stencil27.pattern(cfg, 1, seed=0)
    assert p0[2] == p1[2] and p0[0].size == p1[0].size
    assert not np.array_equal(p0[1], p1[1])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_bcsstk32_draw(seed):
    cfg = _load("bcsstk32")
    rows, cols, n = table1.pattern(cfg, 0, seed)
    row_n = np.bincount(rows, minlength=n)
    col_n = np.bincount(cols, minlength=n)
    assert n == 44_609 and rows.size == 2_014_701
    assert abs(row_n.std() - cfg["sigma"]) < 0.02 * cfg["sigma"]
    assert col_n.max() <= cfg["ell_k"]
    key = rows * n + cols
    assert np.all(np.diff(key) > 0)         # sorted, no repeated entry


def test_bcsstk32_seeded():
    cfg = _load("bcsstk32")
    a = table1.pattern(cfg, 0, 11)
    b = table1.pattern(cfg, 0, 11)
    c = table1.pattern(cfg, 1, 11)
    assert np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])


def test_column_cap_moves_excess():
    cfg = {"n": 400, "nnz": 16_000, "sigma": 3.0, "ell_k": 44}
    rows, cols, n = table1.pattern(cfg, 0, 5)
    assert rows.size == 16_000
    assert np.bincount(cols, minlength=n).max() <= 44
    assert np.all(np.diff(rows * n + cols) > 0)
