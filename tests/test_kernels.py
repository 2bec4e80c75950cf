"""Per-kernel allclose vs ref.py oracles with shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline: fixed-seed shim
    from _propcheck import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.bitonic_merge import KEY_INVALID, bitonic_merge_pallas
from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.sccp_multiply import sccp_multiply_pallas


def _ell_inputs(rng, ka, n, kb, occupancy=0.7, dtype=np.float32):
    a_val = (rng.standard_normal((ka, n)) * (rng.random((ka, n)) < occupancy))
    a_idx = np.where(a_val != 0, rng.integers(0, 64, (ka, n)), -1)
    b_val = (rng.standard_normal((n, kb)) * (rng.random((n, kb)) < occupancy))
    b_idx = np.where(b_val != 0, rng.integers(0, 64, (n, kb)), -1)
    return (a_val.astype(dtype), a_idx.astype(np.int32),
            b_val.astype(dtype), b_idx.astype(np.int32))


@pytest.mark.parametrize("ka,n,kb", [(1, 128, 1), (4, 256, 4), (7, 384, 3),
                                     (8, 512, 8)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_sccp_kernel_sweep(rng, ka, n, kb, dtype):
    ins = _ell_inputs(rng, ka, n, kb, dtype=dtype)
    jins = list(map(jnp.asarray, ins))
    got = sccp_multiply_pallas(*jins, block_n=128, interpret=True)
    exp = ref.sccp_multiply_ref(*jins)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=1e-6)


def test_sccp_interpret_auto_select(rng, monkeypatch):
    """ops.sccp_multiply runs the COMPILED kernel when the platform
    predicate says TPU and the interpreter elsewhere — the kernel itself
    takes ``interpret`` as a required argument, so no caller can reach the
    interpreter on a TPU by omission."""
    import repro.kernels.sccp_multiply as sm
    from repro.kernels import platform
    seen = {}
    real = sm.pl.pallas_call

    def spy(*args, **kw):
        seen["interpret"] = kw.get("interpret")
        kw["interpret"] = True          # keep it executable on this host
        return real(*args, **kw)

    monkeypatch.setattr(sm.pl, "pallas_call", spy)
    ins = list(map(jnp.asarray, _ell_inputs(rng, 2, 128, 2)))

    assert platform.on_tpu() is False        # this host has no TPU
    ops.sccp_multiply(*ins, block_n=128)
    assert seen["interpret"] is True         # auto → interpreter off-TPU

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    ins2 = list(map(jnp.asarray, _ell_inputs(rng, 3, 128, 2)))  # fresh trace
    got = ops.sccp_multiply(*ins2, block_n=128)
    assert seen["interpret"] is False        # auto → compiled on TPU
    exp = ref.sccp_multiply_ref(*ins2)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=1e-6)


def test_fused_slab_sort_kernel_matches_xla(rng):
    """fused_sccp_stream: the in-VMEM multiply+sort kernel (interpret) and
    the XLA realization emit the identical stream contract (integer values
    → exact totals regardless of within-run association)."""
    from repro.kernels.fused_sccp_stream import (fused_slab_sort_pallas,
                                                 fused_slab_sort_xla)
    n, k_b, n_cols = 96, 5, 64
    a_val = jnp.asarray(rng.integers(-3, 4, n).astype(np.float32))
    a_idx = jnp.asarray(np.where(rng.random(n) < 0.7,
                                 rng.integers(0, 64, n), -1).astype(np.int32))
    b_val = jnp.asarray(rng.integers(-3, 4, (n, k_b)).astype(np.float32))
    b_idx = jnp.asarray(np.where(rng.random((n, k_b)) < 0.7,
                                 rng.integers(0, n_cols, (n, k_b)),
                                 -1).astype(np.int32))
    k1, t1 = fused_slab_sort_pallas(a_val, a_idx, b_val, b_idx,
                                    n_cols=n_cols, interpret=True)
    k2, t2 = fused_slab_sort_xla(a_val, a_idx, b_val, b_idx, n_cols=n_cols)
    assert k1.shape[0] == 512               # pot(96·5)
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    kk = np.asarray(k1)
    assert (np.diff(kk) >= 0).all()
    tails = np.concatenate([kk[1:] != kk[:-1], [True]]) & (kk != KEY_INVALID)
    assert (np.asarray(t1)[~tails] == 0).all()


def test_sccp_ops_padding(rng):
    """ops wrapper pads non-128-multiple lane counts correctly."""
    ins = _ell_inputs(rng, 3, 217, 5)
    jins = list(map(jnp.asarray, ins))
    got = ops.sccp_multiply(*jins)
    exp = ref.sccp_multiply_ref(*jins)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=1e-6)


@pytest.mark.parametrize("length", [64, 128, 1024])
def test_bitonic_merge_sweep(rng, length):
    key = rng.integers(0, 50, length).astype(np.int32)
    key[rng.random(length) < 0.2] = KEY_INVALID
    val = rng.standard_normal(length).astype(np.float32)
    k_got, v_got = bitonic_merge_pallas(jnp.asarray(key), jnp.asarray(val),
                                        interpret=True)
    k_exp, v_exp = ref.bitonic_merge_ref(jnp.asarray(key), jnp.asarray(val))
    np.testing.assert_array_equal(np.asarray(k_got), np.asarray(k_exp))
    # value placement within equal-key runs may differ; compare per-key sums
    def sums(k, v):
        out = {}
        for kk, vv in zip(np.asarray(k), np.asarray(v)):
            out[int(kk)] = out.get(int(kk), 0.0) + float(vv)
        return out
    got_s, exp_s = sums(k_got, v_got), sums(k_exp, v_exp)
    for kk in exp_s:
        np.testing.assert_allclose(got_s.get(kk, 0.0), exp_s[kk], atol=1e-3)


def test_bitonic_merge_totals_at_tails(rng):
    key = np.repeat(np.arange(8, dtype=np.int32), 16)
    val = np.ones(128, np.float32)
    k, v = bitonic_merge_pallas(jnp.asarray(key), jnp.asarray(val),
                                interpret=True)
    v = np.asarray(v)
    assert (np.sort(v[v != 0]) == 16).all()
    assert (v != 0).sum() == 8


@pytest.mark.parametrize("k,n,m,d", [(1, 128, 128, 8), (4, 256, 128, 64),
                                     (8, 128, 256, 128)])
def test_ell_spmm_kernel_sweep(rng, k, n, m, d):
    a_val = (rng.standard_normal((k, n)) * (rng.random((k, n)) < 0.6)).astype(np.float32)
    a_idx = np.where(a_val != 0, rng.integers(0, m, (k, n)), -1).astype(np.int32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    got = ell_spmm_pallas(jnp.asarray(a_val), jnp.asarray(a_idx),
                          jnp.asarray(x), n_rows=m, interpret=True)
    exp = ref.ell_spmm_ref(jnp.asarray(a_val), jnp.asarray(a_idx),
                           jnp.asarray(x), m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=1e-3, rtol=1e-3)


def test_ell_spmm_ops_ragged(rng):
    a_val = (rng.standard_normal((3, 300))).astype(np.float32)
    a_idx = rng.integers(0, 150, (3, 300)).astype(np.int32)
    x = rng.standard_normal((300, 70)).astype(np.float32)
    got = ops.ell_spmm(jnp.asarray(a_val), jnp.asarray(a_idx),
                       jnp.asarray(x), 150)
    exp = ref.ell_spmm_ref(jnp.asarray(a_val), jnp.asarray(a_idx),
                           jnp.asarray(x), 150)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=1e-3, rtol=1e-3)


@settings(max_examples=15, deadline=None)
@given(logn=st.integers(5, 10), nkeys=st.integers(1, 60),
       seed=st.integers(0, 2 ** 16))
def test_bitonic_property(logn, nkeys, seed):
    rng = np.random.default_rng(seed)
    length = 1 << logn
    key = rng.integers(0, nkeys, length).astype(np.int32)
    val = rng.standard_normal(length).astype(np.float32)
    k, v = bitonic_merge_pallas(jnp.asarray(key), jnp.asarray(val),
                                interpret=True)
    k = np.asarray(k)
    assert (np.diff(k) >= 0).all()
    # conservation: total mass preserved
    np.testing.assert_allclose(float(np.asarray(v).sum()), float(val.sum()),
                               atol=1e-2)


@pytest.mark.parametrize("n,tile", [(512, 128), (4096, 512)])
def test_sort_merge_tree_matches_single_tile(rng, n, tile):
    """Multi-tile merge tree ≡ the monolithic single-tile network."""
    from repro.kernels.bitonic_merge import sort_merge_tree_pallas
    key = rng.integers(0, n // 4, n).astype(np.int32)
    key[rng.random(n) < 0.15] = KEY_INVALID
    val = rng.standard_normal(n).astype(np.float32)
    k_got, v_got = sort_merge_tree_pallas(jnp.asarray(key), jnp.asarray(val),
                                          tile=tile, interpret=True)
    k_exp, v_exp = ref.bitonic_merge_ref(jnp.asarray(key), jnp.asarray(val))
    np.testing.assert_array_equal(np.asarray(k_got), np.asarray(k_exp))
    kk, vv = np.asarray(k_got), np.asarray(v_got)
    tails = np.concatenate([kk[1:] != kk[:-1], [True]]) & (kk != KEY_INVALID)
    assert (vv[~tails] == 0).all(), "non-tail lanes must be zeroed"
    np.testing.assert_allclose(vv[tails], np.asarray(v_exp)[np.asarray(
        np.concatenate([np.asarray(k_exp)[1:] != np.asarray(k_exp)[:-1],
                        [True]]) & (np.asarray(k_exp) != KEY_INVALID))],
        atol=1e-3)


@settings(max_examples=6, deadline=None)
@given(logn=st.sampled_from([10, 14, 18]), logc=st.integers(4, 6),
       seed=st.integers(0, 2 ** 16))
def test_sort_merge_property_vs_accumulate(logn, logc, seed):
    """ops.sort_merge (merge tree) ≡ core accumulate up to 2^18 products."""
    from repro.core.accumulate import accumulate
    rng = np.random.default_rng(seed)
    n = 1 << logn
    n_rows = n_cols = 1 << logc
    row = rng.integers(0, n_rows, n).astype(np.int32)
    col = rng.integers(0, n_cols, n).astype(np.int32)
    bad = rng.random(n) < 0.1
    row[bad] = -1
    col[bad] = -1
    val = np.where(bad, 0, rng.standard_normal(n)).astype(np.float32)
    key, tot = ops.sort_merge(jnp.asarray(row), jnp.asarray(col),
                              jnp.asarray(val), n_rows, n_cols, tile=1024)
    kk, vv = np.asarray(key), np.asarray(tot)
    tails = np.concatenate([kk[1:] != kk[:-1], [True]]) & (kk != KEY_INVALID)
    out_cap = n_rows * n_cols
    coo = accumulate(jnp.asarray(row), jnp.asarray(col), jnp.asarray(val),
                     out_cap, n_rows, n_cols)
    m = np.asarray(coo.row) >= 0
    exp_keys = np.asarray(coo.row)[m] * n_cols + np.asarray(coo.col)[m]
    np.testing.assert_array_equal(kk[tails], exp_keys)
    np.testing.assert_allclose(vv[tails], np.asarray(coo.val)[m],
                               atol=5e-3)
    assert tails.sum() == int(coo.ngroups)


def test_bin_ranks_stable(rng):
    """bin_ranks = stable per-bucket running count; invalid lanes rank -1."""
    from repro.kernels.radix_bucket import bin_ranks_pallas
    n, n_buckets = 2048, 8
    bid = rng.integers(0, n_buckets, n).astype(np.int32)
    bid[rng.random(n) < 0.15] = -1
    got = np.asarray(bin_ranks_pallas(jnp.asarray(bid), n_buckets=n_buckets,
                                      interpret=True))
    seen = {}
    for i, b in enumerate(bid):
        if b < 0:
            assert got[i] == -1, i
        else:
            assert got[i] == seen.get(int(b), 0), i
            seen[int(b)] = seen.get(int(b), 0) + 1


@pytest.mark.parametrize("merge_kind", ["bucket", "hash"])
def test_blocked_merge_matches_ref(rng, merge_kind):
    """bucket_merge / hash_merge reproduce the sort_merge stream contract:
    per-key totals match the reference coalesce, tails sorted globally."""
    n, n_rows, n_cols = 4096, 64, 64
    row = rng.integers(0, n_rows, n).astype(np.int32)
    col = rng.integers(0, n_cols, n).astype(np.int32)
    bad = rng.random(n) < 0.1
    row[bad] = -1
    col[bad] = -1
    val = np.where(bad, 0, rng.standard_normal(n)).astype(np.float32)
    fn = ops.bucket_merge if merge_kind == "bucket" else ops.hash_merge
    kw = ({"n_buckets": 8} if merge_kind == "bucket" else {"n_blocks": 8})
    key, tot, dropped = fn(jnp.asarray(row), jnp.asarray(col),
                           jnp.asarray(val), n_rows, n_cols, **kw)
    assert int(dropped) == 0
    kk, vv = np.asarray(key), np.asarray(tot)
    tails = (np.concatenate([kk[1:] != kk[:-1], [True]])
             & (kk != KEY_INVALID))
    assert (vv[~tails] == 0).all()
    assert (np.diff(kk[tails]) > 0).all(), "tails must be globally sorted"
    ref_key = np.where(row >= 0, row * n_cols + col, int(KEY_INVALID))
    k_exp, v_exp = ref.bitonic_merge_ref(jnp.asarray(ref_key.astype(np.int32)),
                                         jnp.asarray(val))
    k_exp, v_exp = np.asarray(k_exp), np.asarray(v_exp)
    exp_tails = (np.concatenate([k_exp[1:] != k_exp[:-1], [True]])
                 & (k_exp != KEY_INVALID))
    np.testing.assert_array_equal(kk[tails], k_exp[exp_tails])
    np.testing.assert_allclose(vv[tails], v_exp[exp_tails], atol=1e-3)


def test_bucket_merge_reports_drops(rng):
    """A bucket smaller than its load must count (not silently lose) drops."""
    n, n_rows, n_cols = 1024, 8, 8
    row = np.zeros(n, np.int32)              # everything lands in bucket 0
    col = rng.integers(0, n_cols, n).astype(np.int32)
    val = np.ones(n, np.float32)
    key, tot, dropped = ops.bucket_merge(jnp.asarray(row), jnp.asarray(col),
                                         jnp.asarray(val), n_rows, n_cols,
                                         n_buckets=4, bucket_cap=128)
    assert int(dropped) == n - 128
    # hash: 2 blocks of 8-slot tables cannot hold 8 distinct cols per block
    key, tot, dropped = ops.hash_merge(jnp.asarray(row), jnp.asarray(col),
                                       jnp.asarray(val), n_rows, n_cols,
                                       n_blocks=2, block_cap=4)
    assert int(dropped) > 0
    # non-power-of-two caps are rejected at the wrapper boundary
    for bad_kw in ({"bucket_cap": 100}, ):
        with pytest.raises(ValueError):
            ops.bucket_merge(jnp.asarray(row), jnp.asarray(col),
                             jnp.asarray(val), n_rows, n_cols, **bad_kw)
    with pytest.raises(ValueError):
        ops.hash_merge(jnp.asarray(row), jnp.asarray(col),
                       jnp.asarray(val), n_rows, n_cols, block_cap=100)


@settings(max_examples=8, deadline=None)
@given(logn=st.sampled_from([12, 14]), n_buckets=st.sampled_from([2, 4, 16]),
       logc=st.integers(4, 7), seed=st.integers(0, 2 ** 16))
def test_bucket_merge_property_vs_accumulate(logn, n_buckets, logc, seed):
    """Propagation blocking ≡ core accumulate across bucket counts/shapes."""
    from repro.core.accumulate import accumulate
    rng = np.random.default_rng(seed)
    n = 1 << logn
    n_rows = n_cols = 1 << logc
    row = rng.integers(0, n_rows, n).astype(np.int32)
    col = rng.integers(0, n_cols, n).astype(np.int32)
    val = rng.standard_normal(n).astype(np.float32)
    key, tot, dropped = ops.bucket_merge(jnp.asarray(row), jnp.asarray(col),
                                         jnp.asarray(val), n_rows, n_cols,
                                         n_buckets=n_buckets)
    assert int(dropped) == 0
    kk, vv = np.asarray(key), np.asarray(tot)
    tails = (np.concatenate([kk[1:] != kk[:-1], [True]])
             & (kk != KEY_INVALID))
    coo = accumulate(jnp.asarray(row), jnp.asarray(col), jnp.asarray(val),
                     n_rows * n_cols, n_rows, n_cols)
    m = np.asarray(coo.row) >= 0
    exp_keys = np.asarray(coo.row)[m] * n_cols + np.asarray(coo.col)[m]
    np.testing.assert_array_equal(kk[tails], exp_keys)
    np.testing.assert_allclose(vv[tails], np.asarray(coo.val)[m], atol=5e-3)


@settings(max_examples=10, deadline=None)
@given(ka=st.integers(1, 6), kb=st.integers(1, 6),
       n=st.sampled_from([128, 256]), seed=st.integers(0, 2 ** 16))
def test_sccp_property(ka, kb, n, seed):
    rng = np.random.default_rng(seed)
    ins = _ell_inputs(rng, ka, n, kb)
    jins = list(map(jnp.asarray, ins))
    got = sccp_multiply_pallas(*jins, block_n=128, interpret=True)
    exp = ref.sccp_multiply_ref(*jins)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=1e-6)


def _packed_stream(rng, n, keyspace=64 * 64):
    key = rng.integers(0, keyspace, n).astype(np.int32)
    val = rng.standard_normal(n).astype(np.float32)
    return jnp.asarray(key), jnp.asarray(val)


def test_bucket_interpret_auto_select(rng, monkeypatch):
    """bucket_merge mirrors sccp's auto-select: the XLA realization
    (bin_ranks_xla + sort_tiles_xla, zero pallas_call) off-TPU, the compiled
    Pallas kernels (interpret=False) when the backend is TPU."""
    import repro.kernels.radix_bucket as rb
    from repro.kernels import platform
    seen = []
    real = rb.pl.pallas_call          # pl is the shared pallas module

    def spy(*args, **kw):
        seen.append(kw.get("interpret"))
        kw["interpret"] = True        # keep it executable on this host
        return real(*args, **kw)

    monkeypatch.setattr(rb.pl, "pallas_call", spy)

    assert platform.resolve_mode(None) == "xla"  # this host has no TPU
    k, v = _packed_stream(rng, 512)
    key_x, tot_x, drop_x = rb.bucket_merge(
        k, v, n_buckets=4, bucket_cap=512, keys_per_bucket=1024)
    assert seen == []                 # auto → pure-XLA path, no Pallas at all

    ki, ti, di = rb.bucket_merge(k, v, n_buckets=4, bucket_cap=512,
                                 keys_per_bucket=1024, interpret=True)
    assert seen and all(i is True for i in seen)
    np.testing.assert_array_equal(np.asarray(key_x), np.asarray(ki))
    np.testing.assert_allclose(np.asarray(tot_x), np.asarray(ti), atol=1e-5)
    assert int(drop_x) == int(di)

    seen.clear()
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert platform.resolve_mode(None) == "pallas"
    k2, v2 = _packed_stream(rng, 1024)          # fresh shape → fresh trace
    rb.bucket_merge(k2, v2, n_buckets=4, bucket_cap=1024, keys_per_bucket=1024)
    assert seen and all(i is False for i in seen)   # compiled on TPU


def test_hash_interpret_auto_select(rng, monkeypatch):
    """hash_merge auto-select: probe loop is plain XLA everywhere; only the
    final table sort switches between sort_tiles_xla and compiled Pallas."""
    import repro.kernels.bitonic_merge as bm
    import repro.kernels.hash_accum as ha
    from repro.kernels import platform
    seen = []
    real = bm.pl.pallas_call          # hash_accum's only Pallas use is the
                                      # bitonic_merge sort stage

    def spy(*args, **kw):
        seen.append(kw.get("interpret"))
        kw["interpret"] = True
        return real(*args, **kw)

    monkeypatch.setattr(bm.pl, "pallas_call", spy)

    # shapes deliberately distinct from the bucket test's: the shared
    # sort_tiles_pallas jit cache would otherwise satisfy identical
    # signatures without re-tracing, blinding the spy
    assert platform.resolve_mode(None) == "xla"
    k, v = _packed_stream(rng, 512)
    key_x, tot_x, drop_x = ha.hash_merge(
        k, v, n_blocks=4, block_cap=256, keys_per_block=1024)
    assert seen == []

    ki, ti, di = ha.hash_merge(k, v, n_blocks=4, block_cap=256,
                               keys_per_block=1024, interpret=True)
    assert seen and all(i is True for i in seen)
    np.testing.assert_array_equal(np.asarray(key_x), np.asarray(ki))
    np.testing.assert_allclose(np.asarray(tot_x), np.asarray(ti), atol=1e-5)
    assert int(drop_x) == int(di)

    seen.clear()
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    k2, v2 = _packed_stream(rng, 1024)
    ha.hash_merge(k2, v2, n_blocks=8, block_cap=256, keys_per_block=512)
    assert seen and all(i is False for i in seen)
