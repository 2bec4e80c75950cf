"""``merge_sorted`` packs its run heads by one two-key (row, col) sort
instead of ``segment_min`` plus two gathers. Checked against the gather-based merge it replaces, kept here as
the oracle: coordinates and group counts equal, values to float32
rounding, on random sorted streams and at the edges of the contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accumulate import merge_sorted, sort_by_coords
from repro.core.formats import INVALID

I32_MAX = int(jnp.iinfo(jnp.int32).max)


def _merge_by_gather(row, col, val, out_cap, n_rows, n_cols):
    """The oracle: run heads found by ``segment_min`` over the run ids and
    their coordinates gathered from the stream."""
    valid = row >= 0
    new_grp = jnp.logical_or(row != jnp.roll(row, 1), col != jnp.roll(col, 1))
    new_grp = new_grp.at[0].set(True)
    new_grp = jnp.logical_and(new_grp, valid)
    seg = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    seg = jnp.where(valid, seg, out_cap)
    seg = jnp.clip(seg, 0, out_cap)
    sums = jax.ops.segment_sum(val, seg, num_segments=out_cap + 1)[:out_cap]
    first = jnp.where(new_grp, jnp.arange(row.shape[0]), row.shape[0] - 1)
    first_idx = jax.ops.segment_min(first, seg,
                                    num_segments=out_cap + 1)[:out_cap]
    ngroups = jnp.sum(new_grp)
    slot_ok = jnp.arange(out_cap) < ngroups
    out_row = jnp.where(slot_ok, row[first_idx], INVALID).astype(jnp.int32)
    out_col = jnp.where(slot_ok, col[first_idx], INVALID).astype(jnp.int32)
    return out_row, out_col, jnp.where(slot_ok, sums, 0), ngroups


def _stream(seed, n_unique, per_output, n_rows, n_cols, n_invalid=0):
    """A sorted stream: ``n_unique`` distinct coordinates, each repeated
    1 + Poisson(per_output − 1) times, with ``n_invalid`` INVALID lanes,
    shuffled and put through the accumulate's own coordinate sort."""
    rng = np.random.default_rng(seed)
    flat = rng.choice(n_rows * n_cols, size=n_unique, replace=False)
    reps = 1 + rng.poisson(per_output - 1, size=flat.size)
    flat = np.repeat(flat, reps)
    row = np.concatenate([flat // n_cols, np.full(n_invalid, -1)])
    col = np.concatenate([flat % n_cols, np.full(n_invalid, -1)])
    val = rng.standard_normal(row.size).astype(np.float32)
    perm = rng.permutation(row.size)
    return sort_by_coords(jnp.asarray(row[perm], jnp.int32),
                          jnp.asarray(col[perm], jnp.int32),
                          jnp.asarray(val[perm]), n_rows)


def _check(row, col, val, out_cap, n_rows, n_cols):
    got = merge_sorted(row, col, val, out_cap, n_rows, n_cols)
    w_row, w_col, w_val, w_ng = _merge_by_gather(row, col, val, out_cap,
                                                 n_rows, n_cols)
    assert got.row.shape == got.col.shape == got.val.shape == (out_cap,)
    assert got.shape == (n_rows, n_cols)
    np.testing.assert_array_equal(np.asarray(got.row), np.asarray(w_row))
    np.testing.assert_array_equal(np.asarray(got.col), np.asarray(w_col))
    assert int(got.ngroups) == int(w_ng)
    scale = float(jnp.sum(jnp.abs(val))) or 1.0
    np.testing.assert_allclose(np.asarray(got.val), np.asarray(w_val),
                               rtol=1e-6, atol=1e-6 * scale)
    return got


# (n_rows, n_cols): a small space, the largest whose row·n_cols + col keys
# fit int32 (n_rows·n_cols = 2³¹ − 2), the first that does not (2³¹ − 1)
# and a square one past it
SPACES = {"small": (997, 1009), "int32_edge": (2, (1 << 30) - 1),
          "past_int32_edge": (1, I32_MAX), "past_int32": (46_341, 46_341)}


@pytest.mark.parametrize("space", sorted(SPACES))
@pytest.mark.parametrize("per_output", [1.05, 6.0])
def test_random_sorted_streams_match_gather_merge(space, per_output):
    n_rows, n_cols = SPACES[space]
    row, col, val = _stream(7, 3000, per_output, n_rows, n_cols,
                            n_invalid=500)
    got = _check(row, col, val, 3200, n_rows, n_cols)
    assert int(got.ngroups) == 3000


@pytest.mark.parametrize("space", ["small", "past_int32"])
def test_truncation_keeps_first_groups_and_true_count(space):
    n_rows, n_cols = SPACES[space]
    row, col, val = _stream(11, 900, 3.0, n_rows, n_cols, n_invalid=64)
    got = _check(row, col, val, 256, n_rows, n_cols)
    assert int(got.ngroups) == 900 and bool(got.overflowed())
    assert bool(jnp.all(got.row >= 0))


@pytest.mark.parametrize("space", ["small", "past_int32"])
def test_all_invalid_stream(space):
    n_rows, n_cols = SPACES[space]
    row = jnp.full((128,), INVALID, jnp.int32)
    got = _check(row, row, jnp.zeros((128,), jnp.float32), 64, n_rows, n_cols)
    assert int(got.ngroups) == 0
    assert bool(jnp.all(got.row == INVALID)) and bool(jnp.all(got.val == 0))


@pytest.mark.parametrize("space", ["small", "int32_edge", "past_int32_edge"])
def test_single_group(space):
    n_rows, n_cols = SPACES[space]
    last = jnp.asarray([n_rows - 1] * 5 + [INVALID] * 3, jnp.int32)
    col = jnp.asarray([n_cols - 1] * 5 + [INVALID] * 3, jnp.int32)
    got = _check(last, col, jnp.arange(8, dtype=jnp.float32), 4,
                 n_rows, n_cols)
    assert int(got.ngroups) == 1
    assert (int(got.row[0]), int(got.col[0])) == (n_rows - 1, n_cols - 1)
    assert float(got.val[0]) == 10.0


@pytest.mark.parametrize("space", ["small", "past_int32"])
def test_out_cap_larger_than_stream(space):
    n_rows, n_cols = SPACES[space]
    row, col, val = _stream(5, 40, 2.0, n_rows, n_cols, n_invalid=3)
    got = _check(row, col, val, 4 * row.shape[0], n_rows, n_cols)
    assert int(got.ngroups) == 40
    assert bool(jnp.all(got.row[40:] == INVALID))
