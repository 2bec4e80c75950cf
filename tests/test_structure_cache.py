"""Two-phase SpGEMM: structure correctness, plan/structure staleness
validation, and the fingerprint-keyed StructureCache (LRU / disk / autotune /
thread-safety).

Values are integer-valued floats throughout: every accumulation order sums
them exactly, so numeric-vs-cold comparisons can demand bit-identity across
backends whose float summation orders differ.
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.core.formats import ell_cols_from_dense, ell_rows_from_dense
from repro.core.spgemm import (spgemm_coo, spgemm_coo_batched,
                               spgemm_coo_numeric,
                               spgemm_coo_numeric_batched, spgemm_dense)
from repro.core.streaming import spgemm_coo_stream_numeric
from repro.plan import (BACKENDS, StructureCache, fingerprint, make_plan,
                        make_structure, make_structure_batched)

N, M, P = 96, 80, 72


def _int_sparse(rng, n, m, density=0.08):
    """Sparse matrix of small integer-valued float32 (exact summation)."""
    return np.where(rng.random((n, m)) < density,
                    rng.integers(-4, 5, (n, m)).astype(np.float32), 0.0)


def _pair(rng, n=N, m=M, p=P, density=0.08):
    # EllRows condenses A's columns upward (k = max nnz per column);
    # EllCols condenses B's rows leftward (k = max nnz per row)
    ad, bd = _int_sparse(rng, n, m, density), _int_sparse(rng, m, p, density)
    a = ell_rows_from_dense(jnp.asarray(ad), max(1, int((ad != 0).sum(0).max())))
    b = ell_cols_from_dense(jnp.asarray(bd), max(1, int((bd != 0).sum(1).max())))
    return a, b, ad, bd


def _perturb_pattern(ad):
    """Move one nonzero to a previously-zero slot (same shape, new pattern)."""
    out = ad.copy()
    nz = np.argwhere(out != 0)
    z = np.argwhere(out == 0)
    out[tuple(nz[0])] = 0.0
    out[tuple(z[0])] = 3.0
    return out


def _coo_eq(x, y):
    return (np.array_equal(np.asarray(x.row), np.asarray(y.row))
            and np.array_equal(np.asarray(x.col), np.asarray(y.col))
            and np.array_equal(np.asarray(x.val), np.asarray(y.val))
            and np.array_equal(np.asarray(x.ngroups), np.asarray(y.ngroups)))


# ---------------------------------------------------------------------------
# Numeric phase vs cold path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_numeric_bitident_per_backend(rng, backend):
    a, b, ad, bd = _pair(rng)
    plan = make_plan(a, b, backend=backend)
    st = make_structure(a, b, plan=plan)
    cold = spgemm_coo(a, b, plan=plan, check=True)
    warm = spgemm_coo_numeric(a, b, st, check=True)
    assert _coo_eq(cold, warm)
    # and both match the dense oracle
    dense = np.zeros((N, P), np.float32)
    r, c, v = (np.asarray(warm.row), np.asarray(warm.col),
               np.asarray(warm.val))
    ok = r >= 0
    np.add.at(dense, (r[ok], c[ok]), v[ok])
    np.testing.assert_array_equal(dense, ad @ bd)


def test_numeric_structure_row_nnz_seg(rng):
    a, b, ad, bd = _pair(rng)
    st = make_structure(a, b)
    ref_rows = ((ad != 0).astype(np.int64) @ (bd != 0).astype(np.int64) > 0)
    np.testing.assert_array_equal(np.asarray(st.row_nnz), ref_rows.sum(1))
    np.testing.assert_array_equal(
        np.asarray(st.seg), np.concatenate([[0], ref_rows.sum(1).cumsum()]))
    assert int(st.nnz) == int(ref_rows.sum())


def test_numeric_value_only_update_reuses_structure(rng):
    a, b, ad, _ = _pair(rng)
    st = make_structure(a, b)
    a2 = ell_rows_from_dense(jnp.asarray(ad * 5), a.val.shape[0])
    warm = spgemm_coo_numeric(a2, b, st)       # validates: same fingerprint
    cold = spgemm_coo(a2, b, out_cap=st.out_cap)
    assert _coo_eq(cold, warm)


def test_numeric_stream_entry_point(rng):
    a, b, _, _ = _pair(rng)
    st = make_structure(a, b, backend="stream")
    cold = spgemm_coo(a, b, plan=st.plan)
    assert _coo_eq(cold, spgemm_coo_stream_numeric(a, b, st))


def test_numeric_batched_bitident(rng):
    bsz = 3
    ads = np.stack([_int_sparse(rng, N, M) for _ in range(bsz)])
    bds = np.stack([_int_sparse(rng, M, P) for _ in range(bsz)])
    ka = max(1, int((ads != 0).sum(1).max()))   # per-column, over the batch
    kb = max(1, int((bds != 0).sum(2).max()))   # per-row, over the batch
    a = jax.vmap(lambda d: ell_rows_from_dense(d, ka))(jnp.asarray(ads))
    b = jax.vmap(lambda d: ell_cols_from_dense(d, kb))(jnp.asarray(bds))
    st = make_structure_batched(a, b)
    warm = spgemm_coo_numeric_batched(a, b, st, check=True)
    plan = make_plan(
        ell_rows_from_dense(jnp.asarray(ads[0]), ka),
        ell_cols_from_dense(jnp.asarray(bds[0]), kb),
        out_cap=st.out_cap, backend="sort")
    cold = spgemm_coo_batched(a, b, plan=dataclasses.replace(plan, fp=None),
                              check=True)
    assert _coo_eq(cold, warm)


def test_numeric_distributed_bitident():
    out = run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.formats import ell_rows_from_dense, ell_cols_from_dense
from repro.core.distributed import spgemm_coo_sharded, spgemm_coo_sharded_numeric
from repro.plan import make_structure

rng = np.random.default_rng(7)
def mk(n, m):
    return np.where(rng.random((n, m)) < 0.08,
                    rng.integers(-4, 5, (n, m)).astype(np.float32), 0.0)
ad, bd = mk(64, 96), mk(96, 80)
a = ell_rows_from_dense(jnp.asarray(ad), max(1, int((ad != 0).sum(0).max())))
b = ell_cols_from_dense(jnp.asarray(bd), max(1, int((bd != 0).sum(1).max())))
mesh = Mesh(np.array(jax.devices()), ("x",))
st = make_structure(a, b, n_dev=4, schedules=("ring", "cstat"))
cold = spgemm_coo_sharded(a, b, mesh, "x", check=True)
warm = spgemm_coo_sharded_numeric(a, b, mesh, "x", st, check=True)
assert np.array_equal(np.asarray(cold.row), np.asarray(warm.row))
assert np.array_equal(np.asarray(cold.val), np.asarray(warm.val))
assert int(cold.ngroups) == int(warm.ngroups)
for sched in ("ring", "cstat"):
    again = spgemm_coo_sharded(a, b, mesh, "x", schedule=sched,
                               structure=st, check=True)
    assert np.array_equal(np.asarray(again.val), np.asarray(cold.val))
print("DIST-NUMERIC-OK")
""", n_devices=4)
    assert "DIST-NUMERIC-OK" in out


# ---------------------------------------------------------------------------
# Staleness validation
# ---------------------------------------------------------------------------

def test_stale_plan_raises_and_optout(rng):
    a, b, ad, _ = _pair(rng)
    plan = make_plan(a, b)
    a2 = ell_rows_from_dense(jnp.asarray(_perturb_pattern(ad)),
                             a.val.shape[0])
    with pytest.raises(ValueError, match="stale plan"):
        spgemm_coo(a2, b, plan=plan)
    # the documented opt-out for deliberate cross-pattern reuse
    spgemm_coo(a2, b, plan=dataclasses.replace(plan, fp=None))


def test_stale_structure_raises(rng):
    a, b, ad, _ = _pair(rng)
    st = make_structure(a, b)
    a2 = ell_rows_from_dense(jnp.asarray(_perturb_pattern(ad)),
                             a.val.shape[0])
    with pytest.raises(ValueError, match="stale structure"):
        spgemm_coo_numeric(a2, b, st)
    # validate=False never crashes — unknown keys park in the dump slot
    spgemm_coo_numeric(a2, b, st, validate=False)


def test_fingerprint_semantics(rng):
    a, b, ad, _ = _pair(rng)
    a_scaled = ell_rows_from_dense(jnp.asarray(ad * 2), a.val.shape[0])
    assert fingerprint(a, b) == fingerprint(a_scaled, b)
    a_moved = ell_rows_from_dense(jnp.asarray(_perturb_pattern(ad)),
                                  a.val.shape[0])
    assert fingerprint(a, b) != fingerprint(a_moved, b)


# ---------------------------------------------------------------------------
# StructureCache
# ---------------------------------------------------------------------------

def test_cache_hit_on_value_only_change(rng):
    a, b, ad, _ = _pair(rng)
    cache = StructureCache(capacity=4)
    st1 = cache.get(a, b)
    a2 = ell_rows_from_dense(jnp.asarray(ad * 7), a.val.shape[0])
    st2 = cache.get(a2, b)
    assert st2 is st1
    s = cache.stats()
    assert (s["hits"], s["misses"]) == (1, 1)


def test_cache_miss_on_pattern_change(rng):
    a, b, ad, _ = _pair(rng)
    cache = StructureCache(capacity=4)
    cache.get(a, b)
    a2 = ell_rows_from_dense(jnp.asarray(_perturb_pattern(ad)),
                             a.val.shape[0])
    st2 = cache.get(a2, b)
    assert cache.stats()["misses"] == 2
    # and the fresh structure is valid for the new pattern
    assert _coo_eq(spgemm_coo(a2, b, out_cap=st2.out_cap),
                   spgemm_coo_numeric(a2, b, st2))


def test_cache_lru_eviction_order(rng):
    _, b, _, _ = _pair(rng)
    mats = []
    for s in range(3):
        ad = _int_sparse(np.random.default_rng(50 + s), N, M)
        mats.append(ell_rows_from_dense(
            jnp.asarray(ad), max(1, int((ad != 0).sum(0).max()))))
    cache = StructureCache(capacity=2)
    cache.get(mats[0], b)
    cache.get(mats[1], b)
    cache.get(mats[0], b)           # touch 0 → 1 is now least-recent
    cache.get(mats[2], b)           # evicts 1, not 0
    assert cache.stats()["evictions"] == 1
    base = cache.stats()["hits"]
    cache.get(mats[0], b)           # survived → hit
    assert cache.stats()["hits"] == base + 1
    cache.get(mats[1], b)           # evicted → miss (rebuild)
    assert cache.stats()["misses"] == 4


def test_cache_disk_round_trip(rng, tmp_path):
    a, b, _, _ = _pair(rng)
    c1 = StructureCache(capacity=4, cache_dir=str(tmp_path))
    st1 = c1.get(a, b, n_dev=2, schedules=("ring",))
    c2 = StructureCache(capacity=4, cache_dir=str(tmp_path))
    st2 = c2.get(a, b)
    assert c2.stats() == dict(hits=0, misses=0, evictions=0, disk_hits=1,
                              autotuned=0, size=1)
    assert np.array_equal(np.asarray(st1.key), np.asarray(st2.key))
    assert st2.plan == st1.plan
    assert st2.dist_plan("ring") == st1.dist_plan("ring")
    assert _coo_eq(spgemm_coo_numeric(a, b, st1),
                   spgemm_coo_numeric(a, b, st2))
    # a corrupt file is a plain miss, never an error
    for f in tmp_path.iterdir():
        f.write_bytes(b"not an npz")
    c3 = StructureCache(capacity=4, cache_dir=str(tmp_path))
    c3.get(a, b)
    assert c3.stats()["disk_hits"] == 0 and c3.stats()["misses"] == 1


def test_cache_autotune_records_probes(rng):
    a, b, _, _ = _pair(rng)
    cache = StructureCache(capacity=4, autotune=True, probe_iters=1,
                           autotune_backends=("sort", "hash"))
    st = cache.get(a, b)
    assert cache.stats()["autotuned"] == 1
    assert st.plan.backend in ("sort", "hash")
    assert set(st.plan.est["autotune_us"]) == {"sort", "hash"}
    assert _coo_eq(spgemm_coo(a, b, plan=st.plan),
                   spgemm_coo_numeric(a, b, st))
    cache.get(a, b)                 # warm: no re-probe
    assert cache.stats()["autotuned"] == 1


def test_cache_thread_safety(rng):
    a, b, ad, _ = _pair(rng)
    a2 = ell_rows_from_dense(jnp.asarray(_perturb_pattern(ad)),
                             a.val.shape[0])
    cache = StructureCache(capacity=8)
    errors = []

    def worker(op):
        try:
            for _ in range(6):
                st = cache.get(op, b)
                st.validate(op, b)
        except Exception as exc:  # noqa: BLE001 — surface any thread failure
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(a if i % 2 else a2,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    s = cache.stats()
    assert s["hits"] + s["misses"] == 48 and s["size"] == 2


# ---------------------------------------------------------------------------
# Model / serve rewiring
# ---------------------------------------------------------------------------

def test_sparse_linear_two_phase(rng):
    from repro.models.sparse import SparseLinear
    w = rng.standard_normal((M, P)).astype(np.float32)
    layer = SparseLinear(jnp.asarray(w), sparsity=0.8)
    xd = _int_sparse(rng, 24, M, density=0.2)
    xa = ell_rows_from_dense(jnp.asarray(xd),
                             max(1, int((xd != 0).sum(0).max())))
    coo1 = layer.matmul_sparse(xa)
    coo2 = layer.matmul_sparse(xa)
    assert layer.cache.stats()["hits"] == 1
    assert _coo_eq(coo1, coo2)
    dense = np.zeros((24, P), np.float32)
    r, c, v = (np.asarray(coo1.row), np.asarray(coo1.col),
               np.asarray(coo1.val))
    ok = r >= 0
    np.add.at(dense, (r[ok], c[ok]), v[ok])
    np.testing.assert_allclose(dense, np.asarray(spgemm_dense(xa, layer.w_ell)),
                               rtol=1e-5, atol=1e-5)


def test_sparse_mlp_shares_cache(rng):
    from repro.models.ffn import SparseMLP
    w_in = rng.standard_normal((32, 48)).astype(np.float32)
    w_out = rng.standard_normal((48, 32)).astype(np.float32)
    mlp = SparseMLP(jnp.asarray(w_in), jnp.asarray(w_out), sparsity=0.7)
    assert mlp.fc_in.cache is mlp.fc_out.cache is mlp.cache
    y = mlp(jnp.asarray(rng.standard_normal((4, 32)).astype(np.float32)))
    assert y.shape == (4, 32)
    assert mlp.cache_stats()["size"] == 0   # dense applies need no structure


def test_engine_level_structure_cache(rng, tmp_path):
    from repro.serve.engine import ServeConfig, ServingEngine

    class _Stub:                    # engine jits lazily; never called here
        def decode_step(self, p, c, t):
            raise NotImplementedError

        def prefill(self, p, batch, s_max):
            raise NotImplementedError

    eng = ServingEngine(_Stub(), {}, ServeConfig(
        structure_cache_size=4, structure_cache_dir=str(tmp_path)))
    a, b, _, _ = _pair(rng)
    coo1 = eng.spgemm(a, b)
    coo2 = eng.spgemm(a, b)
    assert _coo_eq(coo1, coo2)
    assert eng.cache_stats()["hits"] == 1
    # a restarted engine warm-starts from the shared cache dir
    eng2 = ServingEngine(_Stub(), {}, ServeConfig(
        structure_cache_size=4, structure_cache_dir=str(tmp_path)))
    eng2.spgemm(a, b)
    assert eng2.cache_stats()["disk_hits"] == 1


# ---------------------------------------------------------------------------
# Cached per-lane slots
# ---------------------------------------------------------------------------

SLOT_BACKENDS = tuple(b for b in BACKENDS if b != "stream")


def _no_slots(st):
    return dataclasses.replace(st, slot=None, a_idx=None, b_idx=None)


def _slot_counters():
    from repro.obs import metrics
    c = metrics.snapshot()["counters"]
    return (c.get("spgemm.numeric.slot_hits", 0),
            c.get("spgemm.numeric.slot_searches", 0))


@pytest.fixture
def obs_on():
    import repro.obs
    repro.obs.enable(reset=True)
    yield repro.obs
    repro.obs.disable()
    repro.obs.reset()


def _stale_same_shape(a, ad, bd):
    """A's pattern with one entry moved down its column to a row whose
    product with B reaches an output coordinate C does not have: the ELL
    shapes stay, the index plane changes, and a valid product misses."""
    sa, sb = ad != 0, bd != 0
    c = sa.astype(np.int64) @ sb.astype(np.int64) > 0
    for k, j in np.argwhere(sb):
        rows = np.flatnonzero(~sa[:, k] & ~c[:, j])
        if sa[:, k].any() and rows.size:
            out = ad.copy()
            i0 = np.flatnonzero(sa[:, k])[0]
            out[rows[0], k], out[i0, k] = out[i0, k], 0.0
            a2 = ell_rows_from_dense(jnp.asarray(out), a.val.shape[0])
            assert a2.idx.shape == a.idx.shape
            assert not np.array_equal(np.asarray(a2.idx), np.asarray(a.idx))
            return a2
    raise AssertionError("no entry to move")


@pytest.mark.parametrize("backend", SLOT_BACKENDS)
def test_cached_slots_bitident_to_search(rng, backend, obs_on):
    a, b, ad, _ = _pair(rng)
    st = make_structure(a, b, backend=backend)
    assert st.slot.shape == (a.idx.size * b.idx.shape[1],)
    assert st.slot.dtype == jnp.int32
    a2 = ell_rows_from_dense(jnp.asarray(ad * 3), a.val.shape[0])
    for ops in ((a, b), (a2, b)):
        cached = spgemm_coo_numeric(*ops, st, check=True)
        searched = spgemm_coo_numeric(*ops, _no_slots(st), check=True)
        assert _coo_eq(cached, searched)
        assert _coo_eq(cached, spgemm_coo(*ops, plan=st.plan, check=True))
    assert _slot_counters() == (2, 2)


def test_stale_same_shape_structure_searches_and_poisons(rng, obs_on):
    from repro.core.accumulate import AccumulatorOverflow
    a, b, ad, bd = _pair(rng)
    st = make_structure(a, b)
    a2 = _stale_same_shape(a, ad, bd)
    stale = spgemm_coo_numeric(a2, b, st, validate=False)
    assert _slot_counters() == (0, 1)            # the search branch
    assert int(stale.ngroups) > st.out_cap        # poisoned past cap
    with pytest.raises(AccumulatorOverflow):
        spgemm_coo_numeric(a2, b, st, validate=False, check=True)
    assert _coo_eq(stale, spgemm_coo_numeric(a2, b, _no_slots(st),
                                             validate=False))


def _wave_results(a, b):
    from repro.serve import SparseGemmBatcher
    cache = StructureCache(capacity=4)
    bt = SparseGemmBatcher(cache, max_slots=2)
    a2 = ell_rows_from_dense(a.to_dense() * 2, a.val.shape[0])
    rids = [bt.submit(a, b), bt.submit(a2, b)]
    res = bt.flush()
    return res[rids[0]]


@pytest.mark.parametrize("kind", ["stream", "batched", "wave", "disk"])
def test_structures_without_cached_slots_match_cold(rng, tmp_path, kind):
    a, b, ad, bd = _pair(rng)
    if kind == "stream":
        st = make_structure(a, b, backend="stream")
        assert st.slot is None and st.a_idx is None and st.b_idx is None
        got = spgemm_coo_numeric(a, b, st, check=True)
        ref = spgemm_coo(a, b, plan=st.plan, check=True)
    elif kind == "batched":
        ab = jax.tree_util.tree_map(lambda x: x[None], a)
        bb = jax.tree_util.tree_map(lambda x: x[None], b)
        st = make_structure_batched(ab, bb)
        assert st.slot is None
        got = spgemm_coo_numeric_batched(ab, bb, st, check=True)
        ref = spgemm_coo_batched(ab, bb, plan=dataclasses.replace(
            st.plan, fp=None), check=True)
    elif kind == "wave":
        got = _wave_results(a, b)
        ref = spgemm_coo(a, b, out_cap=make_structure(a, b).out_cap)
    else:
        fresh = StructureCache(capacity=4, cache_dir=str(tmp_path)).get(a, b)
        c2 = StructureCache(capacity=4, cache_dir=str(tmp_path))
        st = c2.get(a, b)
        assert c2.stats()["disk_hits"] == 1
        np.testing.assert_array_equal(np.asarray(st.slot),
                                      np.asarray(fresh.slot))
        got = spgemm_coo_numeric(a, b, st, check=True)
        ref = spgemm_coo(a, b, plan=st.plan, check=True)
    assert _coo_eq(got, ref)


def test_slot_counters_once_per_call_only_when_tracing(rng):
    import repro.obs
    a, b, _, _ = _pair(rng)
    st = make_structure(a, b, backend="sort")
    st_stream = make_structure(a, b, backend="stream")
    repro.obs.disable()
    repro.obs.reset()
    try:
        spgemm_coo_numeric(a, b, st)
        assert _slot_counters() == (0, 0)
        repro.obs.enable(reset=True)
        for expected in ((1, 0), (2, 0)):
            spgemm_coo_numeric(a, b, st)
            assert _slot_counters() == expected
        spgemm_coo_numeric(a, b, _no_slots(st))
        spgemm_coo_numeric(a, b, st_stream)
        assert _slot_counters() == (2, 2)
        repro.obs.disable()
        spgemm_coo_numeric(a, b, st)
        assert _slot_counters() == (2, 2)
    finally:
        repro.obs.disable()
        repro.obs.reset()


def test_structure_with_slots_is_a_jit_argument(rng):
    a, b, ad, _ = _pair(rng)
    st = make_structure(a, b)
    leaves, tree = jax.tree_util.tree_flatten(st)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert back.slot is st.slot and back.a_idx is st.a_idx
    assert back.plan == st.plan and back.fp == st.fp
    fn = jax.jit(lambda a, b, st: spgemm_coo_numeric(a, b, st,
                                                     validate=False))
    a2 = ell_rows_from_dense(jnp.asarray(ad * 2), a.val.shape[0])
    for ops in ((a, b), (a2, b)):
        assert _coo_eq(fn(*ops, st), spgemm_coo_numeric(*ops, st))
