"""Sparse-native distributed SpGEMM on 8 fake host devices.

Multi-host-shaped property tests: ``spgemm_coo_sharded`` must be
*bit-identical* to single-device ``spgemm_coo`` — same sorted coordinate
stream, same padding, same ``ngroups`` — for all three schedules (1D
``ring``/``cstat`` and the 2D ``summa`` grid). Test matrices
carry small-integer values so every partial sum is exact in float32 and the
bit-exact comparison is order-independent (the distributed path sums each
output group in two stages).

The ``summa`` tests honor ``REPRO_SUMMA_GRID`` (e.g. ``"2x4"``, ``"1x8"``;
CI's fake-8-device job matrixes over both) to pin the logical grid — a
``1x8`` run exercises the degenerate-grid path end to end.

All snippets run subprocess-isolated (jax pins the device count at first
init) via ``conftest.run_with_devices``.
"""
from conftest import run_with_devices

_PRELUDE = """
import warnings; warnings.filterwarnings("ignore")
import dataclasses, os
import numpy as np, jax, jax.numpy as jnp
from repro.core import (ell_rows_from_dense, ell_cols_from_dense, spgemm_coo,
                        spgemm_coo_sharded, AccumulatorOverflow)
from repro.plan import make_dist_plan

from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("ring",))
rng = np.random.default_rng(0)

def env_grid():
    pr, pc = os.environ.get("REPRO_SUMMA_GRID", "2x4").split("x")
    return int(pr), int(pc)

def with_grid(dp, sched):
    # pin the summa grid from the CI matrix (identity for 1D schedules)
    if sched != "summa":
        return dataclasses.replace(dp, schedule=sched)
    pr, pc = env_grid()
    return dataclasses.replace(dp, schedule=sched, pr=pr, pc=pc)

def int_sparse(m, n, density, lo=-4, hi=5):
    # small-integer values: float32 sums are exact, so bit-equality holds
    # regardless of the distributed summation order
    return (((rng.random((m, n)) < density)
             * rng.integers(lo, hi, (m, n))).astype(np.float32))

def assert_bit_identical(got, ref):
    assert got.cap == ref.cap, (got.cap, ref.cap)
    np.testing.assert_array_equal(np.asarray(got.row), np.asarray(ref.row))
    np.testing.assert_array_equal(np.asarray(got.col), np.asarray(ref.col))
    np.testing.assert_array_equal(np.asarray(got.val), np.asarray(ref.val))
    assert int(got.ngroups) == int(ref.ngroups)
"""


def test_sharded_matches_single_device_square():
    run_with_devices(_PRELUDE + """
A, B = int_sparse(32, 32, 0.25), int_sparse(32, 32, 0.25)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
ref = spgemm_coo(a, b, out_cap="auto")
for sched in ("ring", "cstat", "summa"):
    got = spgemm_coo_sharded(a, b, mesh, "ring", schedule=sched, check=True)
    assert_bit_identical(got, ref)
    np.testing.assert_allclose(np.asarray(got.to_dense()), A @ B, atol=1e-4)
    # a prebuilt DistPlan keeps the whole engine jit-compatible
    dp = make_dist_plan(a, b, n_dev=8, schedule=sched)
    got_j = jax.jit(lambda x, y: spgemm_coo_sharded(
        x, y, mesh, "ring", dist_plan=dp))(a, b)
    assert_bit_identical(got_j, ref)
print("OK")
""", timeout=600)


def test_sharded_rectangular_nondivisible_slabs():
    """k_a=5, k_b=3 don't divide the 8-ring: exercises INVALID slab padding
    (the old ring_spgemm failed here with an opaque reshape error)."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(24, 32, 0.2), int_sparse(32, 40, 0.2)
a = ell_rows_from_dense(jnp.array(A), 5)
b = ell_cols_from_dense(jnp.array(B), 3)
ref = spgemm_coo(a, b, out_cap="auto")
for sched in ("ring", "cstat", "summa"):
    got = spgemm_coo_sharded(a, b, mesh, "ring", schedule=sched, check=True)
    assert_bit_identical(got, ref)
print("OK")
""", timeout=600)


def test_sharded_skewed_rows():
    """Skewed row distribution: a few hot output rows stress the per-owner
    block/bin capacities (exact histograms must still never drop)."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(64, 64, 0.05), int_sparse(64, 64, 0.08)
hot = rng.choice(64, 8, replace=False)
A[hot] = ((rng.random((8, 64)) < 0.6) * rng.integers(-4, 5, (8, 64))).astype(np.float32)
ka = max(1, int((A != 0).sum(0).max()))
kb = max(1, int((B != 0).sum(1).max()))
a = ell_rows_from_dense(jnp.array(A), ka)
b = ell_cols_from_dense(jnp.array(B), kb)
ref = spgemm_coo(a, b, out_cap="auto")
for sched in ("ring", "cstat", "summa"):
    got = spgemm_coo_sharded(a, b, mesh, "ring", schedule=sched, check=True)
    assert_bit_identical(got, ref)
print("OK")
""", timeout=600)


def test_sharded_empty_and_tiny():
    """All-zero operands and fewer rows than devices both stay exact."""
    run_with_devices(_PRELUDE + """
Z = np.zeros((16, 16), np.float32)
az = ell_rows_from_dense(jnp.array(Z), 2)
bz = ell_cols_from_dense(jnp.array(Z), 2)
refz = spgemm_coo(az, bz, out_cap="auto")
for sched in ("ring", "cstat", "summa"):
    got = spgemm_coo_sharded(az, bz, mesh, "ring", schedule=sched, check=True)
    assert_bit_identical(got, refz)
    assert int(got.nnz()) == 0
A, B = int_sparse(5, 6, 0.5), int_sparse(6, 7, 0.5)   # n_rows < n_dev
a = ell_rows_from_dense(jnp.array(A), 5)
b = ell_cols_from_dense(jnp.array(B), 6)
ref = spgemm_coo(a, b, out_cap="auto")
for sched in ("ring", "cstat", "summa"):
    got = spgemm_coo_sharded(a, b, mesh, "ring", schedule=sched, check=True)
    assert_bit_identical(got, ref)
print("OK")
""")


def test_sharded_planned_backends():
    """Every accumulation backend runs device-local inside the ring and
    still reproduces the single-device stream bit-exactly. 'stream' is the
    special one: accumulation happens *inside* the ring scan, so the
    stacked n_dev-step product stream is never materialized per device."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(32, 32, 0.25), int_sparse(32, 32, 0.25)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
ref = spgemm_coo(a, b, out_cap="auto")
for backend in ("sort", "tiled", "bucket", "hash", "stream", "search"):
    for sched in ("ring", "cstat", "summa"):
        got = spgemm_coo_sharded(a, b, mesh, "ring", accumulator=backend,
                                 schedule=sched, check=True)
        assert_bit_identical(got, ref)
print("OK")
""", timeout=600)


def test_sharded_stream_backend_planned():
    """The streaming accumulator under a prebuilt DistPlan (jit-compatible)
    stays bit-identical, and skewed rows don't break its device-local
    buffers (exact per-shard histograms size local/block caps)."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(64, 64, 0.08), int_sparse(64, 64, 0.08)
hot = rng.choice(64, 6, replace=False)
A[hot] = ((rng.random((6, 64)) < 0.5) * rng.integers(-4, 5, (6, 64))).astype(np.float32)
ka = max(1, int((A != 0).sum(0).max()))
kb = max(1, int((B != 0).sum(1).max()))
a = ell_rows_from_dense(jnp.array(A), ka)
b = ell_cols_from_dense(jnp.array(B), kb)
ref = spgemm_coo(a, b, out_cap="auto")
for sched in ("ring", "cstat", "summa"):
    dp = make_dist_plan(a, b, n_dev=8, schedule=sched, backend="stream")
    assert dp.base.backend == "stream"
    got = jax.jit(lambda x, y: spgemm_coo_sharded(
        x, y, mesh, "ring", dist_plan=dp))(a, b)
    assert_bit_identical(got, ref)
print("OK")
""", timeout=600)


def test_sharded_batched():
    run_with_devices(_PRELUDE + """
from repro.core import spgemm_coo_sharded_batched
from repro.core.formats import EllRows, EllCols
n, bsz = 32, 3
As = np.stack([int_sparse(n, n, 0.2) for _ in range(bsz)])
Bs = np.stack([int_sparse(n, n, 0.2) for _ in range(bsz)])
als = [ell_rows_from_dense(jnp.array(As[i]), 12) for i in range(bsz)]
bls = [ell_cols_from_dense(jnp.array(Bs[i]), 12) for i in range(bsz)]
ab = EllRows(val=jnp.stack([x.val for x in als]),
             idx=jnp.stack([x.idx for x in als]), n_rows=n)
bb = EllCols(val=jnp.stack([x.val for x in bls]),
             idx=jnp.stack([x.idx for x in bls]), n_cols=n)
dp = make_dist_plan(als[0], bls[0], n_dev=8, slack=2.0)
for sched in ("ring", "cstat", "summa"):
    dps = with_grid(dp, sched)
    got = spgemm_coo_sharded_batched(ab, bb, mesh, "ring", dist_plan=dps,
                                     check=True)
    assert got.row.shape[0] == bsz and got.ngroups.shape == (bsz,)
    for i in range(bsz):
        ref = spgemm_coo(als[i], bls[i], out_cap=dp.out_cap)
        np.testing.assert_array_equal(np.asarray(got.row[i]), np.asarray(ref.row))
        np.testing.assert_array_equal(np.asarray(got.val[i]), np.asarray(ref.val))
print("OK")
""", timeout=600)


def test_overflow_poisoning_crosses_collective():
    """An undersized per-owner block truncates on *some* device; the psum'd
    poison must surface in the replicated result and make check raise."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(32, 32, 0.25), int_sparse(32, 32, 0.25)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
for sched in ("ring", "cstat", "summa"):
    tiny = dataclasses.replace(with_grid(make_dist_plan(a, b, n_dev=8), sched),
                               block_cap=2, bin_cap=2)
    got = spgemm_coo_sharded(a, b, mesh, "ring", dist_plan=tiny)
    assert bool(got.overflowed()), int(got.ngroups)
    try:
        spgemm_coo_sharded(a, b, mesh, "ring", dist_plan=tiny, check=True)
        raise SystemExit("check=True should have raised")
    except AccumulatorOverflow:
        pass
print("OK")
""")


def test_ring_spgemm_pads_nondivisible_slabs():
    """Satellite fix: the dense-baseline ring pads instead of failing."""
    run_with_devices(_PRELUDE + """
from repro.core.distributed import ring_spgemm
A, B = int_sparse(24, 32, 0.2), int_sparse(32, 40, 0.2)
a = ell_rows_from_dense(jnp.array(A), 5)     # 5 % 8 != 0 (truncating k is
b = ell_cols_from_dense(jnp.array(B), 3)     # fine: compare vs to_dense)
C = ring_spgemm(a, b, mesh, "ring")
ref = np.asarray(a.to_dense()) @ np.asarray(b.to_dense())
np.testing.assert_allclose(np.asarray(C), ref, atol=1e-4)
print("OK")
""")


def test_put_spgemm_operands_presharded():
    """Pre-sharded operands (parallel.sharding.put_spgemm_operands) feed the
    engine without changing results."""
    run_with_devices(_PRELUDE + """
from repro.parallel.sharding import put_spgemm_operands
A, B = int_sparse(32, 32, 0.25), int_sparse(32, 32, 0.25)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
ref = spgemm_coo(a, b, out_cap="auto")
dp = make_dist_plan(a, b, n_dev=8, schedule="ring")
ash, bsh = put_spgemm_operands(a, b, mesh, "ring", schedule="ring")
got = spgemm_coo_sharded(ash, bsh, mesh, "ring", dist_plan=dp, check=True)
assert_bit_identical(got, ref)
print("OK")
""")


def test_facade_parity_sharded_paths():
    """repro.spgemm(mesh=, axis=) must be bit-identical to the legacy
    spgemm_coo_sharded / _sharded_numeric wrappers it routes to."""
    run_with_devices(_PRELUDE + """
import repro
from repro.core.distributed import spgemm_coo_sharded_numeric
from repro.plan import make_structure

A, B = int_sparse(32, 32, 0.25), int_sparse(32, 32, 0.25)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
for sched in ("ring", "cstat", "summa"):
    ref = spgemm_coo_sharded(a, b, mesh, "ring", schedule=sched, check=True)
    got = repro.spgemm(a, b, mesh=mesh, axis="ring", schedule=sched,
                       check=True)
    assert_bit_identical(got, ref)

st = make_structure(a, b, n_dev=8)
ref_n = spgemm_coo_sharded_numeric(a, b, mesh, "ring", st)
got_n = repro.spgemm(a, b, mesh=mesh, axis="ring", structure=st)
assert_bit_identical(got_n, ref_n)
print("OK")
""", timeout=600)

def test_summa_nonsquare_grids():
    """Both 8-device factorizations (2×4, 4×2) plus the CI-matrixed grid
    stay bit-identical with overlap on and off — the logical grid is index
    arithmetic over the same flat slab sharding, so the factorization can
    only change communication, never the result."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(40, 32, 0.2), int_sparse(32, 48, 0.2)
a = ell_rows_from_dense(jnp.array(A), 7)
b = ell_cols_from_dense(jnp.array(B), 5)
ref = spgemm_coo(a, b, out_cap="auto")
dp = make_dist_plan(a, b, n_dev=8)
for pr, pc in ((2, 4), (4, 2), env_grid()):
    dps = dataclasses.replace(dp, schedule="summa", pr=pr, pc=pc)
    for overlap in (True, False):
        got = spgemm_coo_sharded(a, b, mesh, "ring", dist_plan=dps,
                                 overlap=overlap, check=True)
        assert_bit_identical(got, ref)
print("OK")
""", timeout=600)


def test_summa_warm_numeric_and_facade():
    """Warm numeric phase under schedule='summa' (and 'auto' reading the
    structure's cached 2D pick) reproduces the cold product exactly
    (small-int values ⇒ order-exact sums), overlap on/off identical; the
    facade threads schedule/overlap through, and 'cstat' — meaningless
    without a resident C block — is rejected."""
    run_with_devices(_PRELUDE + """
import repro
from repro.core.distributed import spgemm_coo_sharded_numeric
from repro.plan import make_structure
A, B = int_sparse(32, 32, 0.25), int_sparse(32, 32, 0.25)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
ref = spgemm_coo(a, b, out_cap="auto")
st = make_structure(a, b, n_dev=8, schedules=("summa", "ring"))
for sched in ("auto", "ring", "summa"):
    for overlap in (True, False):
        got = spgemm_coo_sharded_numeric(a, b, mesh, "ring", st,
                                         schedule=sched, overlap=overlap,
                                         check=True)
        np.testing.assert_array_equal(np.asarray(got.to_dense()), A @ B)
        assert int(got.ngroups) == int(ref.ngroups)
got_f = repro.spgemm(a, b, mesh=mesh, axis="ring", structure=st,
                     schedule="summa", overlap=False, check=True)
np.testing.assert_array_equal(np.asarray(got_f.to_dense()), A @ B)
try:
    spgemm_coo_sharded_numeric(a, b, mesh, "ring", st, schedule="cstat")
    raise SystemExit("cstat should be rejected on the numeric path")
except ValueError:
    pass
print("OK")
""", timeout=600)


def test_summa_poison_crosses_grid_axes():
    """Truncation inside individual grid cells must poison the replicated
    result: the overflow psum runs over the full flat axis, so a drop at any
    (row, column) coordinate of the logical grid surfaces on every device —
    under both factorizations and their transposes."""
    run_with_devices(_PRELUDE + """
A, B = int_sparse(32, 32, 0.5), int_sparse(32, 32, 0.5)
a = ell_rows_from_dense(jnp.array(A), 20)
b = ell_cols_from_dense(jnp.array(B), 20)
dp = make_dist_plan(a, b, n_dev=8, schedule="summa")
got_ok = spgemm_coo_sharded(a, b, mesh, "ring", dist_plan=dp, check=True)
assert not bool(got_ok.overflowed())
for pr, pc in ((2, 4), (4, 2)):
    tiny = dataclasses.replace(dp, pr=pr, pc=pc, local_cap=128)
    got = spgemm_coo_sharded(a, b, mesh, "ring", dist_plan=tiny)
    assert bool(got.overflowed()), (pr, pc, int(got.ngroups))
    try:
        spgemm_coo_sharded(a, b, mesh, "ring", dist_plan=tiny, check=True)
        raise SystemExit("check=True should have raised")
    except AccumulatorOverflow:
        pass
print("OK")
""", timeout=600)
