"""The ``'search'`` accumulation backend: the paper's own in-situ-search
accumulation (Alg. 1 / Fig. 11) as a first-class ``spgemm_coo`` backend.

The backend must reproduce the ``'sort'`` backend's sorted-COO output
bit-for-bit on integer-valued matrices (float32 sums of small integers are
exact) across the matrix zoo — including batched, truncated and warm
numeric-phase calls — while its three realizations (XLA, compiled Pallas,
faithful iterated Alg. 1) stay mutually bit-identical. Also the home of the
extreme-key boundary regressions: the packed-key sentinels
(``KEY_INVALID``/``KEY_INVALID-1``) must never collide with a legal
coordinate key, whose maximum is 2³¹−3.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AccumulatorOverflow, ell_cols_from_dense,
                        ell_rows_from_dense, spgemm_coo, spgemm_coo_batched)
from repro.core.formats import EllCols, EllRows
from repro.core.spgemm import spgemm_coo_numeric
from repro.plan import make_plan, make_structure

from conftest import random_sparse


def _int_sparse(rng, m, n, density, lo=-4, hi=5):
    return (((rng.random((m, n)) < density)
             * rng.integers(lo, hi, (m, n))).astype(np.float32))


def _ell_pair(a, b, ka=None, kb=None):
    ka = ka or max(1, int((a != 0).sum(0).max()))
    kb = kb or max(1, int((b != 0).sum(1).max()))
    return (ell_rows_from_dense(jnp.array(a), ka),
            ell_cols_from_dense(jnp.array(b), kb))


def _assert_bit_identical(got, ref):
    assert got.cap == ref.cap
    np.testing.assert_array_equal(np.asarray(got.row), np.asarray(ref.row))
    np.testing.assert_array_equal(np.asarray(got.col), np.asarray(ref.col))
    np.testing.assert_array_equal(np.asarray(got.val), np.asarray(ref.val))
    assert int(got.ngroups) == int(ref.ngroups)


def test_search_bit_identical_to_sort():
    """The matrix zoo: square, rectangular, skewed, duplicate-heavy,
    padding-heavy (oversized k) and empty — all bit-identical to 'sort'."""
    rng = np.random.default_rng(0)
    cases = []
    cases.append(_ell_pair(_int_sparse(rng, 32, 32, 0.25),
                           _int_sparse(rng, 32, 32, 0.25)))
    cases.append(_ell_pair(_int_sparse(rng, 24, 40, 0.3),
                           _int_sparse(rng, 40, 56, 0.2)))     # rectangular
    skew_a = _int_sparse(rng, 48, 48, 0.05)
    hot = rng.choice(48, 6, replace=False)
    skew_a[hot] = _int_sparse(rng, 6, 48, 0.7)                 # hot rows
    cases.append(_ell_pair(skew_a, _int_sparse(rng, 48, 48, 0.1)))
    cases.append(_ell_pair(_int_sparse(rng, 16, 16, 0.8),
                           _int_sparse(rng, 16, 16, 0.8)))     # dup-heavy
    cases.append(_ell_pair(_int_sparse(rng, 32, 32, 0.05),
                           _int_sparse(rng, 32, 32, 0.05),
                           ka=12, kb=12))                      # padding-heavy
    z = np.zeros((16, 16), np.float32)
    cases.append(_ell_pair(z, z, ka=2, kb=2))                  # empty
    for ea, eb in cases:
        plan = make_plan(ea, eb, backend="search")
        ref = spgemm_coo(ea, eb, out_cap=plan.out_cap)
        got = spgemm_coo(ea, eb, out_cap=plan.out_cap, accumulator="search",
                         plan=plan, check=True)
        _assert_bit_identical(got, ref)
        np.testing.assert_allclose(
            np.asarray(got.to_dense()),
            np.asarray(ea.to_dense()) @ np.asarray(eb.to_dense()), atol=1e-4)


def test_search_truncation_matches_sort():
    """An undersized out_cap keeps the first out_cap (lowest) unique keys
    and reports the TRUE group count — exactly the 'sort' backend's
    truncation contract, bit-for-bit — and check=True raises for both."""
    rng = np.random.default_rng(1)
    ea, eb = _ell_pair(_int_sparse(rng, 32, 32, 0.4),
                       _int_sparse(rng, 32, 32, 0.4))
    full = spgemm_coo(ea, eb, out_cap="auto")
    cap = int(full.ngroups) // 2
    assert cap > 0
    ref = spgemm_coo(ea, eb, out_cap=cap)
    got = spgemm_coo(ea, eb, out_cap=cap, accumulator="search")
    _assert_bit_identical(got, ref)
    assert bool(got.overflowed())
    with pytest.raises(AccumulatorOverflow):
        spgemm_coo(ea, eb, out_cap=cap, accumulator="search", check=True)


def test_search_batched_matches_per_slice():
    rng = np.random.default_rng(2)
    n, bsz = 24, 3
    As = np.stack([_int_sparse(rng, n, n, 0.2) for _ in range(bsz)])
    Bs = np.stack([_int_sparse(rng, n, n, 0.2) for _ in range(bsz)])
    als = [ell_rows_from_dense(jnp.array(As[i]), 10) for i in range(bsz)]
    bls = [ell_cols_from_dense(jnp.array(Bs[i]), 10) for i in range(bsz)]
    ab = EllRows(val=jnp.stack([x.val for x in als]),
                 idx=jnp.stack([x.idx for x in als]), n_rows=n)
    bb = EllCols(val=jnp.stack([x.val for x in bls]),
                 idx=jnp.stack([x.idx for x in bls]), n_cols=n)
    plan = make_plan(als[0], bls[0], backend="search", slack=2.0)
    coo = spgemm_coo_batched(ab, bb, plan.out_cap, accumulator="search",
                             plan=plan, check=True)
    assert coo.ngroups.shape == (bsz,)
    shared = dataclasses.replace(plan, fp=None)
    for i in range(bsz):
        ref = spgemm_coo(als[i], bls[i], out_cap=plan.out_cap,
                         accumulator="search", plan=shared)
        np.testing.assert_array_equal(np.asarray(coo.row[i]),
                                      np.asarray(ref.row))
        np.testing.assert_array_equal(np.asarray(coo.val[i]),
                                      np.asarray(ref.val))
        assert int(coo.ngroups[i]) == int(ref.ngroups)


def test_search_jit_compatible():
    from functools import partial
    rng = np.random.default_rng(3)
    a = _int_sparse(rng, 24, 24, 0.3)
    b = _int_sparse(rng, 24, 24, 0.3)
    ea, eb = _ell_pair(a, b)
    plan = make_plan(ea, eb, backend="search")
    f = jax.jit(partial(spgemm_coo, out_cap=plan.out_cap,
                        accumulator="search", plan=plan))
    np.testing.assert_allclose(np.asarray(f(ea, eb).to_dense()), a @ b,
                               atol=1e-4)


def test_search_warm_numeric_matches_cold():
    """A search-planned SpgemmStructure feeds the numeric phase: the
    structure's sorted keys ARE the emission result, so warm calls skip
    emission entirely and stay bit-identical to the cold path."""
    rng = np.random.default_rng(4)
    ea, eb = _ell_pair(_int_sparse(rng, 32, 32, 0.3),
                       _int_sparse(rng, 32, 32, 0.3))
    st = make_structure(ea, eb, backend="search")
    assert st.plan.backend == "search"
    ref = spgemm_coo(ea, eb, out_cap=st.out_cap)
    warm = spgemm_coo_numeric(ea, eb, st, check=True)
    _assert_bit_identical(warm, ref)


def test_search_faithful_matches_batched_emission():
    """The literal iterated Alg. 1 scan and the batched key-only network
    emit the identical sorted-unique list; their nnz agrees exactly when
    untruncated and both flag past cap when truncated (the faithful scan's
    count is a floor — it stops scanning at out_cap)."""
    from repro.kernels.insitu_search import KEY_INVALID, emit_sorted_unique
    rng = np.random.default_rng(5)
    key = rng.integers(0, 96, 256).astype(np.int32)
    key[200:] = int(KEY_INVALID)                     # stream padding lanes
    k = jnp.asarray(key)
    n_uniq = len(np.unique(key[:200]))
    uk_b, nnz_b = emit_sorted_unique(k, 128)
    uk_f, nnz_f = emit_sorted_unique(k, 128, faithful=True)
    np.testing.assert_array_equal(np.asarray(uk_b), np.asarray(uk_f))
    assert int(nnz_b) == int(nnz_f) == n_uniq
    cap = n_uniq // 2
    uk_bt, nnz_bt = emit_sorted_unique(k, cap)
    uk_ft, nnz_ft = emit_sorted_unique(k, cap, faithful=True)
    np.testing.assert_array_equal(np.asarray(uk_bt), np.asarray(uk_ft))
    assert int(nnz_bt) == n_uniq                     # batched: true count
    assert int(nnz_ft) > cap                         # faithful: floor past cap


def test_search_interpret_auto_select(monkeypatch):
    """insitu_search mirrors the repo-wide auto-select: the XLA realization
    (minima_mask_xla / jnp.sort / searchsorted, zero pallas_call) off-TPU,
    the compiled Pallas kernels (interpret=False) when the backend is TPU;
    explicit interpret=True reserves the interpreter for kernel tests."""
    import repro.kernels.insitu_search as isrch
    from repro.kernels import platform
    seen = []
    real = isrch.pl.pallas_call

    def spy(*args, **kw):
        seen.append(kw.get("interpret"))
        kw["interpret"] = True        # keep it executable on this host
        return real(*args, **kw)

    monkeypatch.setattr(isrch.pl, "pallas_call", spy)

    assert platform.resolve_mode(None) == "xla"  # this host has no TPU
    rng = np.random.default_rng(6)
    k = jnp.asarray(rng.integers(0, 4096, 512), jnp.int32)
    uk_x, nnz_x = isrch.emit_sorted_unique(k, 64)
    slot_x, hit_x = isrch.align_keys(k, uk_x)
    mask_x = isrch.minima_mask_pallas(k)
    isrch.search_emit_sorted(k, max_unique=8)
    assert seen == []                 # auto → pure-XLA path, no Pallas at all

    uk_i, nnz_i = isrch.emit_sorted_unique(k, 64, interpret=True)
    slot_i, hit_i = isrch.align_keys(k, uk_i, interpret=True)
    mask_i = isrch.minima_mask_pallas(k, interpret=True)
    assert seen and all(i is True for i in seen)
    np.testing.assert_array_equal(np.asarray(uk_x), np.asarray(uk_i))
    assert int(nnz_x) == int(nnz_i)
    np.testing.assert_array_equal(np.asarray(slot_x), np.asarray(slot_i))
    np.testing.assert_array_equal(np.asarray(hit_x), np.asarray(hit_i))
    np.testing.assert_array_equal(np.asarray(mask_x), np.asarray(mask_i))

    seen.clear()
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert platform.resolve_mode(None) == "pallas"
    k2 = jnp.asarray(rng.integers(0, 4096, 1024), jnp.int32)  # fresh traces
    uk2, _ = isrch.emit_sorted_unique(k2, 128)
    isrch.align_keys(k2, uk2)
    isrch.minima_mask_pallas(k2)
    assert seen and all(i is False for i in seen)   # compiled on TPU


def test_extreme_key_boundary_all_backends():
    """Largest packable coordinate space: n_rows·n_cols = 2³¹−2 (one below
    the packed-key cutoff), so the maximal legal key is 2³¹−3 =
    KEY_INVALID−2. Neither the KEY_INVALID padding nor the KEY_INVALID−1
    run-tail sentinel (_coo_from_merged's nxt fill) can collide with a real
    key — every packed backend must stay exact with keys at both ends of
    int32, including duplicates on the maximal key."""
    n_rows, n_cols = 2, (1 << 30) - 1
    assert n_rows * n_cols == jnp.iinfo(jnp.int32).max - 1
    k, n = 2, 2
    r = np.asarray([[0, 1], [1, 0]], np.int32)
    c = np.asarray([[0, n_cols - 1], [n_cols - 1, 0]], np.int32)
    ea = EllRows(val=jnp.ones((k, n), jnp.float32), idx=jnp.asarray(r),
                 n_rows=n_rows)
    eb = EllCols(val=jnp.ones((n, k), jnp.float32), idx=jnp.asarray(c.T),
                 n_cols=n_cols)
    expect = {}
    for i in range(k):
        for j in range(n):
            for l in range(k):
                rc = (int(r[i, j]), int(c[l, j]))
                expect[rc] = expect.get(rc, 0) + 1.0
    # keys span the full legal range: 0 … 2³¹−3 == KEY_INVALID−2
    keys = sorted(rr * n_cols + cc for rr, cc in expect)
    assert keys[0] == 0
    assert keys[-1] == int(jnp.iinfo(jnp.int32).max) - 2
    for acc in ("sort", "tiled", "bucket", "hash", "stream", "search"):
        coo = spgemm_coo(ea, eb, out_cap=16, accumulator=acc, check=True)
        rr, cc, vv = map(np.asarray, (coo.row, coo.col, coo.val))
        got = {(int(a_), int(b_)): float(v_)
               for a_, b_, v_ in zip(rr, cc, vv) if a_ >= 0}
        assert got == expect, acc
    # the warm numeric path packs/searches the same extreme keys
    st = make_structure(ea, eb)
    warm = spgemm_coo_numeric(ea, eb, st, check=True)
    ref = spgemm_coo(ea, eb, out_cap=st.out_cap, check=True)
    _assert_bit_identical(warm, ref)


def test_stale_structure_miss_poisons_every_backend_plan():
    """Satellite: a structure reused (validate=False) on operands whose
    pattern grew must route the unknown products to the discarded overflow
    slot AND poison ngroups — for structures planned under every backend,
    including the scan-based stream numeric path — so check=True raises
    instead of returning silently-wrong values."""
    rng = np.random.default_rng(7)
    a1, b1 = _ell_pair(_int_sparse(rng, 32, 32, 0.05),
                       _int_sparse(rng, 32, 32, 0.05))
    a2, b2 = _ell_pair(_int_sparse(rng, 32, 32, 0.4),
                       _int_sparse(rng, 32, 32, 0.4))
    for backend in ("sort", "tiled", "bucket", "hash", "stream", "search"):
        st = make_structure(a1, b1, backend=backend)
        clean = spgemm_coo_numeric(a1, b1, st, check=True)
        assert not bool(clean.overflowed()), backend
        stale = spgemm_coo_numeric(a2, b2, st, validate=False)
        assert int(stale.ngroups) > st.out_cap, backend   # poisoned past cap
        with pytest.raises(AccumulatorOverflow):
            spgemm_coo_numeric(a2, b2, st, validate=False, check=True)


def test_planner_search_cost_and_sizing():
    """Duplicate-heavy streams are where alignment beats a full re-sort:
    the model must rank 'search' below 'sort' there, expose its cost and
    intermediate estimates, and the plan's out_cap never drops a group."""
    rng = np.random.default_rng(8)
    ea, eb = _ell_pair(_int_sparse(rng, 48, 48, 0.5),
                       _int_sparse(rng, 48, 48, 0.5))
    plan = make_plan(ea, eb)
    assert {"cost_search", "interm_search"} <= set(plan.est)
    assert plan.est["cost_search"] < plan.est["cost_sort"]
    full = spgemm_coo(ea, eb, out_cap="auto")
    assert plan.out_cap >= int(full.ngroups)          # never-drop sizing
    forced = make_plan(ea, eb, backend="search")
    assert forced.backend == "search"
    coo = spgemm_coo(ea, eb, accumulator="auto", plan=plan, check=True)
    np.testing.assert_allclose(
        np.asarray(coo.to_dense()),
        np.asarray(ea.to_dense()) @ np.asarray(eb.to_dense()), atol=1e-4)


def test_search_property_vs_dense_oracle(rng):
    for seed in range(4):
        r = np.random.default_rng(seed)
        n = int(r.integers(8, 40))
        dens = float(r.uniform(0.05, 0.5))
        a = random_sparse(r, n, n, dens)
        b = random_sparse(r, n, n, dens)
        ea, eb = _ell_pair(a, b)
        coo = spgemm_coo(ea, eb, out_cap="auto", accumulator="search",
                         check=True)
        np.testing.assert_allclose(np.asarray(coo.to_dense()), a @ b,
                                   atol=1e-3)
