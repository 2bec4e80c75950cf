"""The ``'search'`` accumulate on the HPCG 27-point stencil's product
C = A·Aᵀ (``bench/configs/hpcg27.json`` at small grids) against the
benchmark's plain reference; its emission and alignment against
``jnp.unique`` / ``searchsorted``; the planner's choice on a v5e at the
published sizes of the benchmark's configurations; and its spans and
counters.
"""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402
from patterns import stencil27, table1  # noqa: E402

import repro  # noqa: E402
from repro.kernels import insitu_search as isr  # noqa: E402
from repro.kernels import ops, platform  # noqa: E402
from repro.kernels.bitonic_merge import KEY_INVALID  # noqa: E402
from repro.plan import make_plan, planner, symbolic  # noqa: E402

GRIDS = [(8, 8, 16), (9, 11, 13)]
V5E_BUDGET = 16 * 10 ** 9 // planner.DEVICE_MEM_SHARE


def _config(name, **sizes):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(sizes)
    return cfg


def _operands(rows, cols, vals, n, k):
    val, idx = harness.ell_planes(rows, cols, vals, n, k)
    a = repro.EllRows(val=jnp.asarray(val), idx=jnp.asarray(idx), n_rows=n)
    b = repro.EllCols(val=jnp.asarray(np.ascontiguousarray(val.T)),
                      idx=jnp.asarray(np.ascontiguousarray(idx.T)), n_cols=n)
    return a, b


def _stencil(grid, index, seed=7):
    cfg = _config("hpcg27", nx=grid[0], ny=grid[1], nz=grid[2])
    rows, cols, n = stencil27.pattern(cfg, index, seed)
    vals = np.random.default_rng(seed).standard_normal(rows.size,
                                                       dtype=np.float32)
    return rows, cols, vals, n, cfg["ell_k"]


def _tpu_plan(a, b, monkeypatch, **kw):
    """The plan ``accumulator='auto'`` makes on a v5e: the planner's
    platform switched to TPU while it plans, and the chip's budget."""
    with monkeypatch.context() as mp:
        mp.setattr(platform, "on_tpu", lambda: True)
        return make_plan(a, b, mem_budget=V5E_BUDGET, **kw)


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("how", ["pinned", "auto"])
def test_search_matches_reference(grid, how, monkeypatch):
    limit = _config("hpcg27")["limits"]["value_gap"]
    for index in (0, 1):
        rows, cols, vals, n, k = _stencil(grid, index)
        a, b = _operands(rows, cols, vals, n, k)
        if how == "auto":
            plan = _tpu_plan(a, b, monkeypatch)
            assert plan.backend == "search"
            out = repro.spgemm(a, b, plan=plan, check=True)
        else:
            out = repro.spgemm(a, b, out_cap="auto", accumulator="search",
                               check=True)
        got = reference.compare(harness.to_host(out),
                                reference.product(rows, cols, vals, n))
        assert got["nnz_gap"] == 0 and got["coord_mismatch"] == 0, got
        assert got["value_gap"] <= limit, got


def _stream(case, seed=3, n=4096):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, 700, n).astype(np.int32)
    if case == "padded":
        key[rng.random(n) < 0.3] = KEY_INVALID
        key[-500:] = KEY_INVALID
    elif case == "all_invalid":
        key[:] = KEY_INVALID
    return key


@pytest.mark.parametrize("case,out_cap", [("padded", 1024), ("dense", 1024),
                                          ("truncated", 256),
                                          ("all_invalid", 128)])
def test_emission_and_alignment_match_unique(case, out_cap):
    key = _stream("dense" if case == "truncated" else case)
    uniq = np.unique(key[key != KEY_INVALID])
    want_uk = np.full(out_cap, KEY_INVALID, np.int32)
    want_uk[:min(out_cap, uniq.size)] = uniq[:out_cap]
    k = jnp.asarray(key)

    uk, nnz = isr.emit_sorted_unique(k, out_cap)
    np.testing.assert_array_equal(np.asarray(uk), want_uk)
    assert int(nnz) == uniq.size
    ju = jnp.unique(k, size=out_cap, fill_value=KEY_INVALID)
    if uniq.size <= out_cap:
        np.testing.assert_array_equal(np.asarray(uk), np.asarray(ju))

    uk_r, nnz_r, perm, rank = isr.emit_ranked(k, out_cap=out_cap)
    np.testing.assert_array_equal(np.asarray(uk_r), want_uk)
    assert int(nnz_r) == uniq.size
    assert sorted(np.asarray(perm).tolist()) == list(range(key.size))
    slot, hit = isr.align_ranked(perm, rank, out_cap=out_cap)
    # the alignment contract: slot = #{uk < pk}, hit = pk ∈ uk
    want_slot = np.searchsorted(want_uk, key, side="left")
    np.testing.assert_array_equal(np.asarray(slot), want_slot)
    np.testing.assert_array_equal(np.asarray(hit), np.isin(key, want_uk))
    ss = jnp.searchsorted(jnp.asarray(want_uk), k, side="left")
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(ss))


@pytest.mark.parametrize("case,out_cap", [("padded", 1024), ("truncated", 256),
                                          ("all_invalid", 128)])
def test_search_merge_sums(case, out_cap):
    key = _stream("dense" if case == "truncated" else case, seed=9)
    n_cols = 64
    valid = key != KEY_INVALID
    row = np.where(valid, key // n_cols, -1).astype(np.int32)
    col = np.where(valid, key % n_cols, -1).astype(np.int32)
    val = np.random.default_rng(9).standard_normal(key.size).astype(
        np.float32) * valid
    uk, sums, nnz = ops.search_merge(jnp.asarray(row), jnp.asarray(col),
                                     jnp.asarray(val), 64, n_cols,
                                     out_cap=out_cap)
    uniq = np.unique(key[valid])
    kept = uniq[:out_cap]
    want = np.zeros(out_cap, np.float64)
    want[:kept.size] = [val[key == u].astype(np.float64).sum() for u in kept]
    assert int(nnz) == uniq.size
    np.testing.assert_array_equal(np.asarray(uk)[:kept.size], kept)
    np.testing.assert_allclose(np.asarray(sums), want, atol=1e-5)


def _published_plan(name, monkeypatch):
    """``make_plan`` for the configuration ``name`` at its full size, on a
    v5e. The symbolic pass is replaced by its known result: each row's
    product count, and its output count scaled to the configuration's
    nnz(C) (the CPU need not sort 10⁸ lanes to learn the published
    statistic)."""
    if name == "hpcg27":
        cfg = _config(name)
        rows, cols, n = stencil27.pattern(cfg, 0, 0)
        nnz_c = 4_600_904
    else:
        cfg = _config(name)
        rows, cols, n = table1.pattern(cfg, 0, 0)
        nnz_c = 88_600_000
    col_n = np.bincount(cols, minlength=n)
    per_row = np.bincount(rows, weights=col_n[cols], minlength=n)
    uniq = np.floor(per_row * (nnz_c / per_row.sum())).astype(np.int64)
    a, b = _operands(rows, cols, np.ones(rows.size, np.float32), n,
                     cfg["ell_k"])
    monkeypatch.setattr(symbolic, "per_row_counts",
                        lambda *_, **__: (per_row.astype(np.int64), uniq))
    return _tpu_plan(a, b, monkeypatch)


@pytest.mark.parametrize("name,backend", [("hpcg27", "search"),
                                          ("bcsstk32", "sort")])
def test_planner_choice_at_published_sizes(name, backend, monkeypatch):
    plan = _published_plan(name, monkeypatch)
    assert plan.backend == backend, plan.est


def test_spans_and_counters_only_under_tracing():
    import repro.obs
    rows, cols, vals, n, k = _stencil(GRIDS[1], 0)
    a, b = _operands(rows, cols, vals, n, k)
    phases = {"spgemm.accumulate.emit", "spgemm.accumulate.align",
              "spgemm.accumulate.land"}
    repro.obs.disable()
    repro.obs.reset()
    try:
        repro.spgemm(a, b, out_cap="auto", accumulator="search", check=True)
        assert not repro.obs.get_tracer().spans()
        assert not repro.obs.snapshot()["metrics"]["counters"]
        repro.obs.enable(reset=True)
        repro.spgemm(a, b, out_cap="auto", accumulator="search", check=True)
        repro.spgemm(a, b, out_cap="auto", accumulator="sort", check=True)
        names = [e["name"] for e in repro.obs.get_tracer().spans()]
        assert all(names.count(p) == 1 for p in phases), names
        counters = repro.obs.snapshot()["metrics"]["counters"]
        assert counters["spgemm.accumulate.calls"] == 2
        assert counters["spgemm.accumulate.search_calls"] == 1
    finally:
        repro.obs.disable()
        repro.obs.reset()
