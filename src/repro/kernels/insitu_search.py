"""Pallas TPU kernel: the paper's Algorithm 1 — bit-serial in-situ minima
search — executed literally on bit-planes, plus the batched emission and
coordinate-alignment primitives the ``'search'`` accumulation backend is
built from.

The ReRAM array finds all rows holding the minimal value by scanning one bit
column per step, high→low, keeping only active rows whose current bit is 0
(unless none are — then the '1' rows survive, exactly the paper's
"if no row's CB stores '1', row DRVs' activation remains the same").

On TPU the word-line parallelism maps to VREG lanes: each of the 32 steps is
one vectorized mask update over the (n/128, 128) tile in VMEM.
``_minima_kernel`` is the *faithful* Alg. 1 (mask of argmin rows + iterated
extraction); ``emit_sorted_unique`` batches its emission: one XLA sort of
the stream and a run-head compaction produce the same sorted-unique key
list (Fig. 11c) in one pass instead of nnz_C scans, in a program whose
size does not depend on the stream's length.
Alignment is the second half of the paper's in-situ search: every product
coordinate is located in that sorted list (a CAM lookup on hardware).
``emit_ranked`` sorts (key, lane) so the rank of each sorted lane among
the unique keys falls out of the run heads, and ``align_ranked`` hands each
product its slot back through the sort's permutation: no search at all.
``align_keys`` locates keys it did not emit itself (the faithful path's):
a gather-free broadcast compare on short lists, ``searchsorted`` beyond.

Realization selection follows the repo-wide ``resolve_mode`` contract:
``interpret=None`` (the default) runs the compiled Pallas kernels on TPU and
the bit-identical XLA realization elsewhere — never the interpreter, which
explicit ``interpret=True`` reserves for kernel-correctness tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitonic_merge import LANES, next_pot
from .platform import resolve_mode

KEY_INVALID = jnp.iinfo(jnp.int32).max

# Alignment kernel blocking: product lanes per grid step, structure keys
# compared per inner loop iteration (both VMEM-tile sized).
_ALIGN_TILE = 512
_ALIGN_CHUNK = 512
# The CAM-style kernel compares every product with every unique key, so it
# serves short unique lists only; longer lists take the O(log u)
# ``searchsorted`` realization on every backend.
ALIGN_MAX_KEYS = 1 << 14


def _minima_kernel(v_ref, mask_ref):
    v = v_ref[...]
    # masks ride as int32 0/1: Mosaic carries no boolean vectors in loops
    active = (v != KEY_INVALID).astype(jnp.int32)     # all valid rows (line 3)

    def bit_step(i, active):
        bit = 30 - i                                  # non-negative int32 keys
        zero_bit = active * (1 - jnp.bitwise_and(v >> bit, 1))
        any_zero = jnp.max(zero_bit) > 0
        # Alg. 1 line 8: keep '0'-bit rows iff some row had a '0' here
        return jnp.where(any_zero, zero_bit, active)

    mask_ref[...] = jax.lax.fori_loop(0, 31, bit_step, active)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _minima_mask_pallas_jit(v: jax.Array, *, interpret: bool) -> jax.Array:
    (n,) = v.shape
    npad = (-n) % LANES
    v2 = jnp.pad(v, (0, npad), constant_values=KEY_INVALID).reshape(-1, LANES)
    mask = pl.pallas_call(
        _minima_kernel,
        out_shape=jax.ShapeDtypeStruct(v2.shape, jnp.int32),
        interpret=interpret,
    )(v2)
    return mask.reshape(-1)[:n] != 0


@jax.jit
def minima_mask_xla(v: jax.Array) -> jax.Array:
    """XLA realization of the bit-serial minima search's exact contract:
    boolean mask of the active rows holding min(v). The 31-step bit scan
    selects precisely the argmin rows, so one vectorized min + compare
    reproduces it bit-for-bit."""
    active = v != KEY_INVALID
    vmin = jnp.min(jnp.where(active, v, KEY_INVALID))
    return jnp.logical_and(active, v == vmin)


def minima_mask_pallas(v: jax.Array, *,
                       interpret: bool | None = None) -> jax.Array:
    """Boolean mask of the rows holding min(v). v: (n,) int32 ≥ 0;
    KEY_INVALID marks consumed/invalid rows (the flipped sign bit).
    ``interpret=None`` auto-selects: compiled Pallas on TPU, XLA off-TPU."""
    mode = resolve_mode(interpret)
    if mode == "xla":
        return minima_mask_xla(v)
    return _minima_mask_pallas_jit(v, interpret=mode == "interpret")


def search_emit_sorted(v: jax.Array, max_unique: int,
                       *, interpret: bool | None = None):
    """Iterated Alg. 1 (Fig. 11): repeatedly emit the minimal value and
    invalidate its rows — produces the sorted unique values, the hardware's
    emission order. O(u · 32) scans, u = number of unique values.

    Returns (values (max_unique,), counts (max_unique,)); empty slots carry
    KEY_INVALID / 0. The mode is resolved once, outside the scan, so the
    loop body never re-consults the backend.
    """
    mode = resolve_mode(interpret)
    if mode == "xla":
        mask_fn = minima_mask_xla
    else:
        mask_fn = functools.partial(_minima_mask_pallas_jit,
                                    interpret=mode == "interpret")

    def step(carry, _):
        v_cur = carry
        mask = mask_fn(v_cur)
        any_left = jnp.any(mask)
        val = jnp.min(jnp.where(mask, v_cur, KEY_INVALID))
        cnt = jnp.sum(mask)
        # flip consumed rows to invalid (the paper sets the sign bit)
        v_next = jnp.where(mask, KEY_INVALID, v_cur)
        out_val = jnp.where(any_left, val, KEY_INVALID)
        out_cnt = jnp.where(any_left, cnt, 0)
        return v_next, (out_val, out_cnt.astype(jnp.int32))

    _, (vals, counts) = jax.lax.scan(step, v, None, length=max_unique)
    return vals, counts


# ---------------------------------------------------------------------------
# Batched emission: the sorted-unique key list from one sort of the stream
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("out_cap",))
def emit_ranked(key: jax.Array, *, out_cap: int):
    """The batched emission, with what alignment needs: one sort of (key,
    lane) gives the sorted unique keys and, for every sorted lane, its rank
    among them, so each product's slot is read off the sort's own
    permutation instead of searched for.

    The run heads (the first lane of every equal-key run, exactly the
    emission order of the iterated Alg. 1 scan) are packed densely by a
    key-only sort that sends every other lane to the KEY_INVALID tail: on
    TPU a sort of the stream costs a third of a scatter of it. Returns
    ``(uk, nnz, perm, rank)``: ``uk`` and ``nnz`` as
    ``emit_sorted_unique``; ``perm[i]`` the stream lane at sorted position
    ``i``; ``rank[i]`` the number of unique keys below that lane's key
    (``nnz`` for KEY_INVALID lanes). XLA sorts whatever the stream length,
    so the compiled program does not grow with it."""
    (n,) = key.shape
    ks, perm = jax.lax.sort((key, jnp.arange(n, dtype=jnp.int32)),
                            num_keys=1, is_stable=False)
    prev = jnp.concatenate([jnp.full((1,), -1, ks.dtype), ks[:-1]])
    valid = ks != KEY_INVALID
    head = jnp.logical_and(ks != prev, valid)
    run = jnp.cumsum(head, dtype=jnp.int32)
    uk = jnp.sort(jnp.where(head, ks, KEY_INVALID))
    uk = jnp.pad(uk, (0, max(0, out_cap - n)),
                 constant_values=KEY_INVALID)[:out_cap]
    return uk, run[-1], perm, run - valid.astype(jnp.int32)


def emit_sorted_unique(key: jax.Array, out_cap: int, *,
                       interpret: bool | None = None,
                       faithful: bool = False):
    """The ``'search'`` backend's emission phase: the sorted unique keys of
    a packed product stream — the paper's "sorted list of the output
    matrix" (Fig. 11c) that every product is subsequently aligned against.

    Returns ``(uk, nnz)``: ``uk`` (out_cap,) ascending with KEY_INVALID
    padding, ``nnz`` the true unique-key count (``nnz > out_cap`` flags
    truncation — the first ``out_cap`` unique keys are kept, matching the
    'sort' backend's truncation order).

    The batched emission is ``emit_ranked``'s, on every backend: its
    program is the same size at any stream length. ``faithful=True`` runs
    the literal iterated Alg. 1 scan (O(out_cap·32) minima searches, the
    Pallas kernel on TPU) instead — the two are bit-identical; the
    faithful path's ``nnz`` reports ``out_cap + 1`` when truncated (a
    floor: the scan stops emitting at ``out_cap``, but any leftover active
    row still flags the overflow).
    """
    if not faithful:
        return emit_ranked(key, out_cap=out_cap)[:2]
    mode = resolve_mode(interpret)
    if mode == "xla":
        mask_fn = minima_mask_xla
    else:
        mask_fn = functools.partial(_minima_mask_pallas_jit,
                                    interpret=mode == "interpret")

    def step(v_cur, _):
        mask = mask_fn(v_cur)
        any_left = jnp.any(mask)
        val = jnp.min(jnp.where(mask, v_cur, KEY_INVALID))
        v_next = jnp.where(mask, KEY_INVALID, v_cur)
        return v_next, jnp.where(any_left, val, KEY_INVALID)

    v_final, uk = jax.lax.scan(step, key, None, length=out_cap)
    emitted = jnp.sum(uk != KEY_INVALID).astype(jnp.int32)
    leftover = jnp.any(v_final != KEY_INVALID)
    return uk, emitted + leftover.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Alignment: every product key's slot in the sorted unique list
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("out_cap",))
def align_ranked(perm: jax.Array, rank: jax.Array, *, out_cap: int):
    """``align_keys``'s contract for the stream ``emit_ranked`` sorted, from
    its permutation: the rank of each sorted lane, clipped to ``out_cap``,
    goes back to the lane it came from by one sort keyed on the
    permutation (on TPU half the time of scattering it there). Returns
    ``(slot, hit)`` in stream order; a lane's key is among the first
    ``out_cap`` unique keys exactly when its rank is below ``out_cap``."""
    _, slot = jax.lax.sort((perm, jnp.minimum(rank, out_cap)), num_keys=1,
                           is_stable=False)
    return slot, slot < out_cap


def _make_align_kernel(u: int, chunk: int):
    def kernel(pk_ref, uk_ref, slot_ref, hit_ref):
        pk = pk_ref[...]                              # (bt, 1) products

        def body(j, carry):
            slot, hit = carry
            ukc = uk_ref[:, pl.ds(pl.multiple_of(j * chunk, chunk), chunk)]
            # CAM-style broadcast compare: no gathers, the (bt, chunk)
            # compare matrix lives entirely in VREGs
            lt = jnp.sum((ukc < pk).astype(jnp.int32), axis=1, keepdims=True)
            eq = jnp.max((ukc == pk).astype(jnp.int32), axis=1,
                         keepdims=True)
            return slot + lt, jnp.maximum(hit, eq)

        zero = jnp.zeros(pk.shape, jnp.int32)
        slot, hit = jax.lax.fori_loop(0, u // chunk, body, (zero, zero))
        slot_ref[...] = slot
        hit_ref[...] = hit
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _align_keys_pallas_jit(pk: jax.Array, uk: jax.Array, *, interpret: bool):
    (n,) = pk.shape
    (u,) = uk.shape
    bt = min(_ALIGN_TILE, n)
    chunk = min(_ALIGN_CHUNK, u)
    col = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    slot, hit = pl.pallas_call(
        _make_align_kernel(u, chunk),
        grid=(n // bt,),
        in_specs=[col, pl.BlockSpec((1, u), lambda i: (0, 0))],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32)],
        interpret=interpret,
    )(pk.reshape(n, 1), uk.reshape(1, u))
    return slot.reshape(n), hit.reshape(n) != 0


@jax.jit
def align_keys_xla(pk: jax.Array, uk: jax.Array):
    """XLA realization of the alignment contract: ``searchsorted`` into the
    ascending unique keys (side='left' ⇒ slot = #{uk < pk}, identical to
    the kernel's broadcast count) plus a clipped membership probe."""
    u = uk.shape[0]
    slot = jnp.searchsorted(uk, pk, side="left").astype(jnp.int32)
    hit = jnp.take(uk, jnp.minimum(slot, u - 1), mode="clip") == pk
    return slot, hit


def align_keys(pk: jax.Array, uk: jax.Array, *,
               interpret: bool | None = None):
    """Locate every product key in the sorted unique list ``uk``.

    Returns ``(slot, hit)``: ``slot[i] = #{j : uk[j] < pk[i]}`` (the
    product's output slot when present) and ``hit[i] = pk[i] ∈ uk``. This
    is the in-situ search half of the paper's accumulation — on hardware a
    CAM lookup per product, here one vectorized gather-free pass per
    realization. KEY_INVALID padding in ``uk`` is harmless by construction
    (it is never < a valid key, and only KEY_INVALID product lanes — which
    callers mask — can equal it). The broadcast-compare kernel costs
    O(n·u), so lists longer than ``ALIGN_MAX_KEYS`` take ``searchsorted``
    on TPU as well."""
    mode = resolve_mode(interpret)
    if mode == "xla" or (mode == "pallas" and uk.shape[0] > ALIGN_MAX_KEYS):
        return align_keys_xla(pk, uk)
    (n,) = pk.shape
    bt = min(_ALIGN_TILE, next_pot(max(1, n)))
    npad = (-n) % bt
    pkp = jnp.pad(pk, (0, npad), constant_values=KEY_INVALID) if npad else pk
    (u,) = uk.shape
    chunk = min(_ALIGN_CHUNK, next_pot(max(1, u)))
    upad = (-u) % chunk
    ukp = jnp.pad(uk, (0, upad), constant_values=KEY_INVALID) if upad else uk
    slot, hit = _align_keys_pallas_jit(pkp, ukp,
                                       interpret=mode == "interpret")
    return slot[:n], hit[:n]
