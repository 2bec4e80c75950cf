"""End-to-end SPLIM SpGEMM: SCCP multiply → in-situ-search-style accumulate.

Public entry points:

  * ``spgemm_coo``      — C = A·B as sorted COO (the paper's output format).
                          Six accumulation backends: ``'sort'`` (global
                          ``jax.lax.sort``), ``'tiled'`` (multi-tile bitonic
                          merge tree, kernels.ops.sort_merge), ``'bucket'``
                          (propagation blocking, kernels.radix_bucket),
                          ``'hash'`` (per-row-block open addressing,
                          kernels.hash_accum), ``'stream'`` (slab-scan
                          multiply→compact→merge, core.streaming — the only
                          one that never materializes the (k_a, n, k_b)
                          product stream) and ``'search'`` (the paper's own
                          in-situ-search accumulation, kernels.insitu_search:
                          emit the sorted unique keys, align every product
                          against them — Alg. 1 / Fig. 11);
                          ``accumulator='auto'`` / ``out_cap='auto'`` route
                          through the planner (repro.plan), and
                          ``check=True`` raises on any truncation or backend
                          drop.
  * ``spgemm_dense``    — C dense (oracle / small-n convenience).
  * ``spgemm_streaming``— scan over A slabs so the intermediate working set is
                          O(n·k_b) (paper's Fig. 8 iteration + BSS memory
                          argument), scatter-accumulating into dense C.
  * ``spgemm_coo_batched`` / ``spgemm_dense_batched`` — vmap over a leading
                          batch axis of both ELLPACK operands (all shapes /
                          caps shared across the batch).
  * ``spmm_ell_dense``  — ELLPACK × dense matrix (powers MoE dispatch and
                          SparseLinear in the LM stack).

All are jittable with static k / caps, and the single-matrix entry points
are vmap-able (the batched wrappers are exactly that).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import platform
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs

from .accumulate import accumulate, scatter_dense
from .formats import (INVALID, Coo, EllCols, EllRows, ell_cols_from_dense,
                      ell_rows_from_dense)
from .sccp import sccp_multiply, sccp_multiply_slab


def _plan_key(plan, n_rows: int, n_cols: int) -> str:
    """Metrics-ledger key for est-vs-measured joins: the plan fingerprint
    when available, else a shape tag."""
    fp = getattr(plan, "fp", None)
    return fp[:12] if fp else f"shape:{n_rows}x{n_cols}"


def _coo_from_merged(key: jax.Array, tot: jax.Array, out_cap: int,
                     n_rows: int, n_cols: int) -> Coo:
    """Compact a sort_merge stream (sorted keys, run-tail totals) to COO.

    O(n) scatter — tails are already in ascending key order, so a cumsum
    gives each one its output slot directly (no global sort: that would
    reintroduce the monolithic pass the merge tree exists to avoid).
    Non-tail lanes and overflow groups park in the discarded dump slot.
    """
    from repro.kernels.bitonic_merge import KEY_INVALID
    nxt = jnp.concatenate([key[1:], jnp.full((1,), KEY_INVALID - 1, key.dtype)])
    tail = jnp.logical_and(key != nxt, key != KEY_INVALID)
    ngroups = jnp.sum(tail)
    dst = jnp.where(tail, jnp.cumsum(tail) - 1, out_cap)
    dst = jnp.minimum(dst, out_cap)
    row = (jnp.full((out_cap + 1,), INVALID, jnp.int32)
           .at[dst].set((key // n_cols).astype(jnp.int32)))[:out_cap]
    col = (jnp.full((out_cap + 1,), INVALID, jnp.int32)
           .at[dst].set((key % n_cols).astype(jnp.int32)))[:out_cap]
    val = jnp.zeros((out_cap + 1,), tot.dtype).at[dst].set(tot)[:out_cap]
    return Coo(row=row, col=col, val=val, shape=(n_rows, n_cols),
               ngroups=ngroups.astype(jnp.int32))


def _poison_overflow(coo: Coo, dropped: jax.Array) -> Coo:
    """Fold a backend's dropped-product count into the overflow contract:
    any drop pushes ``ngroups`` past ``cap`` so ``overflowed()`` flags it and
    ``check_no_overflow`` raises — dropped products mean lost values, which
    must never pass for a clean result."""
    ng = coo.ngroups + jnp.where(dropped > 0,
                                 jnp.int32(coo.row.shape[-1] + 1),
                                 jnp.int32(0))
    return Coo(row=coo.row, col=coo.col, val=coo.val, shape=coo.shape,
               ngroups=ng)


def _count_accumulate(backend: str) -> None:
    """One accumulate call on ``backend``, for the planner's share of
    ``'search'`` (counted only while tracing is on)."""
    _obs_metrics.inc("spgemm.accumulate.calls")
    if backend == "search":
        _obs_metrics.inc("spgemm.accumulate.search_calls")


def accumulate_stream(row: jax.Array, col: jax.Array, val: jax.Array,
                      out_cap: int, n_rows: int, n_cols: int, *,
                      backend: str = "sort", tile: int = 4096,
                      plan=None) -> Coo:
    """Run one accumulation backend over a raw product stream → sorted COO.

    The backend-dispatch half of ``spgemm_coo``, factored out so any
    producer of an (row, col, val) product stream — the single-device SCCP
    multiply, or a device-local slab stream inside the distributed ring —
    accumulates through the identical six backends. ``plan`` (repro.plan
    ``Plan``) supplies bucket/table blocking sizes; dropped products poison
    ``Coo.ngroups`` exactly as in ``spgemm_coo``.

    ``backend='stream'`` scans the stream tile-by-tile (3-D input: by its
    slab axis, bit-identical to the never-materialized ``spgemm_coo``
    stream path; flat input: by ``tile``-lane chunks) so the sort working
    set stays one tile — but the caller already paid for materializing the
    stream; ``spgemm_coo(accumulator='stream')`` avoids even that.

    Instrumented (repro.obs): a ``spgemm.accumulate`` span with a device
    sync, whose measured µs feeds the planner est-vs-measured ledger, and
    the counters ``spgemm.accumulate.calls`` and, on the ``'search'``
    backend, ``spgemm.accumulate.search_calls`` — disabled tracing takes the
    bare dispatch path untouched.
    """
    if not _obs.is_enabled():
        return _accumulate_impl(row, col, val, out_cap, n_rows, n_cols,
                                backend=backend, tile=tile, plan=plan)
    _count_accumulate(backend)
    with _obs.span("spgemm.accumulate", backend=backend,
                   lanes=int(row.size), out_cap=int(out_cap)) as sp:
        coo = _accumulate_impl(row, col, val, out_cap, n_rows, n_cols,
                               backend=backend, tile=tile, plan=plan)
        _obs.sync(coo.val)
        if not isinstance(coo.ngroups, jax.core.Tracer):
            ng = int(coo.ngroups)
            sp.set(nnz=ng)
            if ng > out_cap and backend in ("bucket", "hash"):
                # backend drop → _poison_overflow stamped ngroups past cap
                _obs_metrics.inc("spgemm.poison_events")
                _obs.instant("spgemm.poison", backend=backend, ngroups=ng,
                             cap=int(out_cap))
    if sp.dur_us is not None and not isinstance(row, jax.core.Tracer):
        _obs_metrics.record_backend_us(_plan_key(plan, n_rows, n_cols),
                                       backend, sp.dur_us)
    return coo


def _accumulate_impl(row: jax.Array, col: jax.Array, val: jax.Array,
                     out_cap: int, n_rows: int, n_cols: int, *,
                     backend: str, tile: int, plan) -> Coo:
    if backend == "sort":
        return accumulate(row, col, val, out_cap, n_rows, n_cols)
    if backend == "stream":
        from .streaming import accumulate_products_stream
        scap = plan.stream_cap if plan is not None else None
        grp = plan.stream_group if plan is not None else 1
        return accumulate_products_stream(row, col, val, out_cap, n_rows,
                                          n_cols, chunk=tile,
                                          stream_cap=scap, group=grp)
    from repro.kernels import ops
    if backend == "tiled":
        key, tot = ops.sort_merge(row, col, val, n_rows, n_cols, tile=tile)
        return _coo_from_merged(key, tot, out_cap, n_rows, n_cols)
    if backend == "search":
        # Paper Alg. 1 / Fig. 11: emit the sorted unique keys, align every
        # product against them, land the values (kernels.insitu_search; the
        # spans spgemm.accumulate.emit/.align/.land) — values are never
        # sorted. Truncation keeps the first out_cap unique keys and flags
        # via nnz > out_cap, exactly the 'sort' backend's contract; the
        # backend never internally drops, so no poisoning applies.
        uk, sums, nnz = ops.search_merge(row, col, val, n_rows, n_cols,
                                         out_cap=out_cap)
        return _coo_from_slots(uk, sums, nnz, out_cap=out_cap,
                               n_rows=n_rows, n_cols=n_cols)
    if backend == "bucket":
        kw = dict(n_buckets=plan.n_buckets, bucket_cap=plan.bucket_cap) \
            if plan is not None else {}
        key, tot, dropped = ops.bucket_merge(row, col, val, n_rows,
                                             n_cols, **kw)
        return _poison_overflow(
            _coo_from_merged(key, tot, out_cap, n_rows, n_cols), dropped)
    if backend == "hash":
        kw = dict(n_blocks=plan.n_blocks, block_cap=plan.block_cap,
                  max_probes=plan.max_probes) if plan is not None else {}
        key, tot, dropped = ops.hash_merge(row, col, val, n_rows,
                                           n_cols, **kw)
        return _poison_overflow(
            _coo_from_merged(key, tot, out_cap, n_rows, n_cols), dropped)
    raise ValueError(f"unknown accumulator {backend!r}")


def _validate_plan_fp(plan, a: EllRows, b: EllCols) -> None:
    """Raise on a stale caller-supplied plan: its sparsity fingerprint must
    match the operands'. Skipped for tracers (no bytes to hash — the host
    call that built the plan already validated) and for batched operands
    (reusing a representative-slice plan across a batch is the documented
    pattern). ``dataclasses.replace(plan, fp=None)`` opts out for deliberate
    reuse across similar patterns."""
    fp = getattr(plan, "fp", None)
    if fp is None or a.val.ndim != 2:
        return
    if isinstance(a.val, jax.core.Tracer) or isinstance(b.val, jax.core.Tracer):
        return
    from repro.plan.structure import fingerprint
    got = fingerprint(a, b)
    if got != fp:
        raise ValueError(
            f"stale plan: operands' sparsity fingerprint {got[:12]}… differs "
            f"from the plan's {fp[:12]}… — the pattern the plan's capacities "
            "were sized for changed, which silently truncates or poisons the "
            "output. Rebuild with plan.make_plan/make_dist_plan on the new "
            "operands, or opt out for deliberate cross-pattern reuse with "
            "dataclasses.replace(plan, fp=None) (size slack accordingly)")


def spgemm_coo(a: EllRows, b: EllCols, out_cap="auto", *,
               accumulator: str | None = None, tile: int | None = None,
               check: bool = False, plan=None) -> Coo:
    """Sorted-COO SpGEMM (paper Fig. 7-11 pipeline, single device).

    Prefer ``repro.spgemm(a, b, ...)`` — the unified front door (core/api.py)
    delegates here with identical kwargs.

    ``out_cap`` — static output capacity, or ``'auto'`` to derive it from
    the symbolic phase (plan/symbolic; requires concrete operands).
    ``accumulator`` — ``'sort' | 'tiled' | 'bucket' | 'hash' | 'stream' |
    'search'`` pick a backend directly; ``'auto'`` lets ``plan.make_plan``
    choose one
    (concrete operands). ``'stream'`` skips the monolithic SCCP multiply
    entirely and scans A slabs (core.streaming), bounding the intermediate
    working set to O(n·k_b + stream_cap). A pre-built ``plan`` (repro.plan.Plan) supplies out_cap,
    backend and all blocking sizes — explicitly passed arguments still win —
    and keeps this call jit/vmap-compatible: every Plan field is a Python
    int. With neither plan nor accumulator given the backend defaults to
    ``'sort'`` even when ``out_cap='auto'`` sizes the output symbolically;
    only an explicit ``'auto'`` (or a plan) opts into backend selection.
    ``check=True`` routes the result through ``check_no_overflow`` (host
    sync; call outside jit) so truncation or backend drops raise instead of
    returning silently-wrong output.
    """
    if plan is not None:
        _validate_plan_fp(plan, a, b)
    if plan is None and (out_cap == "auto" or accumulator == "auto"):
        if isinstance(a.val, jax.core.Tracer):
            raise ValueError(
                "out_cap='auto'/accumulator='auto' plan from operand VALUES, "
                "which jit/vmap abstract away — build the plan outside the "
                "trace (plan.make_plan on concrete operands) and pass plan=, "
                "or pass a concrete out_cap")
        from repro.plan import make_plan
        # Oversized coordinate spaces force the unpacked 'sort' path below;
        # request that from the planner too so sizing-only calls with a
        # pinned packed-key backend don't spuriously reject.
        oversized = a.n_rows * b.n_cols >= jnp.iinfo(jnp.int32).max
        plan = make_plan(
            a, b,
            out_cap=None if out_cap == "auto" else out_cap,
            backend=("sort" if accumulator is None or oversized else
                     None if accumulator == "auto" else accumulator))
    if plan is not None:
        out_cap = plan.out_cap if out_cap == "auto" else out_cap
        accumulator = plan.backend if accumulator in (None, "auto") \
            else accumulator
        tile = plan.tile if tile is None else tile
    accumulator = accumulator or "sort"
    tile = tile or 4096
    if accumulator not in ("sort", "tiled", "bucket", "hash", "stream",
                           "search"):
        raise ValueError(f"unknown accumulator {accumulator!r}")
    if a.n_rows * b.n_cols >= jnp.iinfo(jnp.int32).max:
        # Packed int32 keys can't span this coordinate space (the tiled /
        # bucket / hash / stream / search backends all key on
        # row*n_cols+col); the two-key lexicographic sort path is the only
        # lossless realization.
        accumulator = "sort"

    if accumulator == "stream":
        # The whole point: never materialize the (k_a, n, k_b) stream.
        from .streaming import spgemm_coo_stream
        scap = plan.stream_cap if plan is not None else None
        grp = plan.stream_group if plan is not None else 1
        if _obs.is_enabled():
            _count_accumulate("stream")
            with _obs.span("spgemm.accumulate", backend="stream",
                           lanes=a.k * a.n_cols * b.k,
                           out_cap=int(out_cap)) as sp:
                coo = spgemm_coo_stream(a, b, out_cap, stream_cap=scap,
                                        group=grp)
                _obs.sync(coo.val)
            if sp.dur_us is not None \
                    and not isinstance(a.val, jax.core.Tracer):
                _obs_metrics.record_backend_us(
                    _plan_key(plan, a.n_rows, b.n_cols), "stream", sp.dur_us)
        else:
            coo = spgemm_coo_stream(a, b, out_cap, stream_cap=scap, group=grp)
    elif _obs.is_enabled():
        with _obs.span("spgemm.multiply", backend=accumulator,
                       k_a=a.k, k_b=b.k, n=a.n_cols):
            val, row, col = sccp_multiply(a, b)
            _obs.sync(val)
        coo = accumulate_stream(row, col, val, out_cap, a.n_rows, b.n_cols,
                                backend=accumulator, tile=tile, plan=plan)
    else:
        val, row, col = sccp_multiply(a, b)
        coo = accumulate_stream(row, col, val, out_cap, a.n_rows, b.n_cols,
                                backend=accumulator, tile=tile, plan=plan)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


def spgemm_dense(a: EllRows, b: EllCols) -> jax.Array:
    """Dense-output SpGEMM via the same structured multiply."""
    val, row, col = sccp_multiply(a, b)
    return scatter_dense(row, col, val, a.n_rows, b.n_cols)


def spgemm_streaming(a: EllRows, b: EllCols) -> jax.Array:
    """Scan over A slabs (one Fig.-8 iteration per step) accumulating dense C.

    Matches the hardware schedule: each ring step materializes only the
    (n, k_b) intermediate of the current slab pair batch.
    """
    n_rows, n_cols = a.n_rows, b.n_cols

    def step(c_acc, i):
        val, row, col = sccp_multiply_slab(a, b, i)
        c_acc = c_acc + scatter_dense(row, col, val, n_rows, n_cols)
        return c_acc, ()

    init = jnp.zeros((n_rows, n_cols), a.val.dtype)
    c, _ = jax.lax.scan(step, init, jnp.arange(a.k))
    return c


def spgemm_coo_batched(a: EllRows, b: EllCols, out_cap="auto", *,
                       accumulator: str | None = None, tile: int | None = None,
                       check: bool = False, plan=None) -> Coo:
    """Batched C[i] = A[i]·B[i]: ELLPACK planes carry a leading batch axis
    (shared n_rows/n_cols/k/caps). Prefer ``repro.spgemm`` — it detects the
    batch axis and delegates here with identical kwargs. Returns a ``Coo``
    whose leaves — including
    ``ngroups`` — have the batch as their leading axis. ``accumulator`` must
    be a concrete backend or come from a ``plan`` (built with
    ``plan.make_plan`` on a representative slice): 'auto' planning inspects
    operand values, which vmap abstracts away. ``check`` runs once on the
    batched result (host sync, outside the vmap)."""
    if plan is None and (accumulator == "auto" or out_cap == "auto"):
        raise ValueError("batched spgemm needs a concrete out_cap/backend: "
                         "build one with plan.make_plan on a representative "
                         "slice and pass plan=")
    fn = partial(spgemm_coo, out_cap=out_cap, accumulator=accumulator,
                 tile=tile, plan=plan)
    coo = jax.vmap(fn)(a, b)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


def _coo_from_slots(key: jax.Array, sums: jax.Array, nnz: jax.Array, *,
                    out_cap: int, n_rows: int, n_cols: int) -> Coo:
    """Dress segment-summed slot values in the sorted-COO output contract:
    coordinates come straight from the precomputed unique keys, pad slots
    (beyond the structure's true nnz) get the row = col = -1 / val = 0
    convention, and ``ngroups`` is the structure's exact group count."""
    ok = jnp.arange(out_cap, dtype=jnp.int32) < nnz
    row = jnp.where(ok, (key // n_cols).astype(jnp.int32), INVALID)
    col = jnp.where(ok, (key % n_cols).astype(jnp.int32), INVALID)
    val = jnp.where(ok, sums, 0)
    return Coo(row=row, col=col, val=val, shape=(n_rows, n_cols),
               ngroups=nnz.astype(jnp.int32))


@partial(jax.jit, static_argnames=("out_cap",))
def _search_slots(key: jax.Array, pk: jax.Array, valid: jax.Array, *,
                  out_cap: int):
    """Slot of each packed product key among the structure's sorted unique
    keys, under the ``numeric.search`` scope. Invalid lanes and keys absent
    from the structure go to the dump slot ``out_cap``; returns the slots
    and the number of valid misses. Jitted on its own, the search is a
    function of its own in the lowered module, and so in the key of the
    persistent compile cache, which leaves scope names out: a program
    compiled without the scope is never served in its place."""
    with jax.named_scope("numeric.search"):
        slot = platform.searchsorted(key, pk).astype(jnp.int32)
        miss = jnp.logical_or(~valid, jnp.take(
            key, jnp.minimum(slot, out_cap - 1), mode="clip") != pk)
        n_miss = jnp.sum(jnp.logical_and(valid, miss)).astype(jnp.int32)
        return jnp.where(miss, out_cap, slot), n_miss


def _lanes(a_idx: jax.Array, b_idx: jax.Array):
    """Flat ``(row, col)`` of every product lane of the index planes
    ``a_idx`` (k_a, n) and ``b_idx`` (n, k_b), in ``sccp_multiply``'s
    ``(k_a, n, k_b)`` lane order (-1 where a plane slot is empty)."""
    k_a, n = a_idx.shape
    k_b = b_idx.shape[1]
    return (jnp.broadcast_to(a_idx[:, :, None], (k_a, n, k_b)).reshape(-1),
            jnp.broadcast_to(b_idx[None, :, :], (k_a, n, k_b)).reshape(-1))


def _find_slots(row, col, key, *, n_cols: int, out_cap: int):
    """``_search_slots`` of the flat product lanes ``(row, col)``."""
    with jax.named_scope("numeric.key"):
        valid = jnp.logical_and(row >= 0, col >= 0)
        pk = jnp.where(valid,
                       row.astype(jnp.int32) * n_cols + col.astype(jnp.int32),
                       0)
    return _search_slots(key, pk, valid, out_cap=out_cap)


@partial(jax.jit, static_argnames=("n_cols", "out_cap"))
def lane_slots(a_idx: jax.Array, b_idx: jax.Array, key: jax.Array, *,
               n_cols: int, out_cap: int) -> jax.Array:
    """Output slot of every product lane of the index planes among the
    sorted unique keys ``key``, flat in ``sccp_multiply``'s lane order: the
    search a numeric call without cached slots makes, made once for a
    structure (``plan.structure``). Invalid lanes and absent keys get
    ``out_cap``."""
    return _find_slots(*_lanes(a_idx, b_idx), key, n_cols=n_cols,
                       out_cap=out_cap)[0]


@partial(jax.jit, static_argnames=("out_cap", "n_rows", "n_cols"))
def _numeric_scatter(val: jax.Array, row, col, key: jax.Array,
                     nnz: jax.Array, cached=None, *, out_cap: int,
                     n_rows: int, n_cols: int):
    """Numeric-phase core: the output slot of each product of
    ``sccp_multiply``'s ``(val, row, col)``, then one segment-sum into the
    slots. No planning, no coordinate sort. Returns the ``Coo`` and
    whether the cached slots were taken.

    Without ``cached``, each product's packed key is binary-searched in the
    sorted unique keys, O(p log u). ``cached`` is ``(slot, a_idx, b_idx,
    a_seen, b_seen)``: a structure's per-lane slots (as long as ``val``)
    and the planes it found them from, with the operands' index planes,
    which stand for ``row`` and ``col`` (then ``None``); where the
    operands' planes equal the structure's on the device the slots are
    ``slot``, else the search runs on the planes' lanes. Invalid lanes land
    in the discarded dump slot; a VALID product whose key is absent from
    the structure (a stale structure used with ``validate=False``) lands
    there too, and its value is lost — so such misses poison
    ``Coo.ngroups`` past ``out_cap`` exactly like a backend drop, never
    passing for a clean result. Each step runs under a ``jax.named_scope``
    (``numeric.key``, ``.search`` — the plane compare and the choice
    included —, ``.scatter``, ``.emit``): the names reach the ops' HLO
    metadata only, so a device trace can charge every op to its step."""
    val = val.reshape(-1)
    if cached is None:
        row, col = row.reshape(-1), col.reshape(-1)
        slot, n_miss = _find_slots(row, col, key, n_cols=n_cols,
                                   out_cap=out_cap)
        hit = jnp.bool_(False)
    else:
        slot, a_idx, b_idx, a_seen, b_seen = cached
        with jax.named_scope("numeric.search"):
            hit = jnp.logical_and(jnp.array_equal(a_idx, a_seen),
                                  jnp.array_equal(b_idx, b_seen))
            # the lanes are built inside the branch: as operands of the
            # cond they would be materialized on every call
            slot, n_miss = jax.lax.cond(
                hit, lambda: (slot, jnp.int32(0)),
                lambda: _find_slots(*_lanes(a_idx, b_idx), key,
                                    n_cols=n_cols, out_cap=out_cap))
        row, col = _lanes(a_idx, b_idx)
    with jax.named_scope("numeric.scatter"):
        valid = jnp.logical_and(row >= 0, col >= 0)
        sums = jax.ops.segment_sum(jnp.where(valid, val, 0), slot,
                                   num_segments=out_cap + 1)[:out_cap]
    with jax.named_scope("numeric.emit"):
        coo = _coo_from_slots(key, sums, nnz, out_cap=out_cap,
                              n_rows=n_rows, n_cols=n_cols)
        return _poison_overflow(coo, n_miss), hit


@partial(jax.jit, static_argnames=("out_cap", "n_rows", "n_cols", "group"))
def _numeric_stream(a_val, a_idx, b_val, b_idx, key, nnz, *, out_cap: int,
                    n_rows: int, n_cols: int, group: int) -> Coo:
    """Numeric phase for stream-planned structures: scan A slab groups,
    searching/summing each group's products into the slot accumulator — the
    (k_a, n, k_b) stream is never materialized, working set is
    O(group·n·k_b + out_cap), matching the cold stream path's memory
    contract while skipping its compact/merge machinery entirely."""
    from repro.kernels.ops import pad_to
    a_val = pad_to(a_val, 0, group, 0)
    a_idx = pad_to(a_idx, 0, group, INVALID)
    n = a_val.shape[1]
    k_b = b_val.shape[1]

    def step(carry, g):
        acc, nm = carry
        av = jax.lax.dynamic_slice_in_dim(a_val, g * group, group, axis=0)
        ai = jax.lax.dynamic_slice_in_dim(a_idx, g * group, group, axis=0)
        v = (av[:, :, None] * b_val[None, :, :]).reshape(-1)
        r = jnp.broadcast_to(ai[:, :, None], (group, n, k_b)).reshape(-1)
        c = jnp.broadcast_to(b_idx[None, :, :], (group, n, k_b)).reshape(-1)
        valid = jnp.logical_and(r >= 0, c >= 0)
        pk = jnp.where(valid, r * n_cols + c, 0).astype(jnp.int32)
        slot, miss = _search_slots(key, pk, valid, out_cap=out_cap)
        nm = nm + miss
        acc = acc + jax.ops.segment_sum(jnp.where(valid, v, 0), slot,
                                        num_segments=out_cap + 1)
        return (acc, nm), ()

    init = (jnp.zeros((out_cap + 1,),
                      jnp.result_type(a_val.dtype, b_val.dtype)),
            jnp.int32(0))
    (acc, n_miss), _ = jax.lax.scan(step, init,
                                    jnp.arange(a_val.shape[0] // group))
    coo = _coo_from_slots(key, acc[:out_cap], nnz, out_cap=out_cap,
                          n_rows=n_rows, n_cols=n_cols)
    return _poison_overflow(coo, n_miss)


def spgemm_coo_numeric(a: EllRows, b: EllCols, structure, *,
                       check: bool = False, validate: bool = True) -> Coo:
    """Numeric phase of the two-phase SpGEMM: multiply + scatter into a
    precomputed ``SpgemmStructure`` (plan.make_structure), skipping planning
    and coordinate sorting entirely. Prefer ``repro.spgemm(a, b,
    structure=st)`` — the unified front door delegates here.

    The result is bit-identical to the cold ``spgemm_coo`` on the operands
    the structure was built from, up to floating-point summation order (the
    slot segment-sum fixes one canonical order; backends differ only in
    rounding). Repeat calls with the same shapes hit XLA's compile cache —
    the intended serving pattern: one symbolic call, thousands of numeric
    calls. A structure from ``make_structure`` holds each product lane's
    output slot (``slot``, 4 B per lane, found once at build time for every
    backend but ``'stream'``) with the index planes it was found from: the
    call compares the operands' planes with those on the device and, where
    they are equal, sums into the cached slots; otherwise — or where the
    structure has no slots, or slots for another lane count — it searches
    every product's key among the structure's keys. Structures from
    stream-backed plans scan A slab groups so the product stream is never
    materialized (same memory contract as the cold stream path).
    ``validate=False`` skips the fingerprint check (e.g. under jit, or
    deliberate reuse across value-only updates — which is exactly what the
    fingerprint permits anyway); a stale structure then routes unknown
    keys to the discarded overflow slot AND poisons ``Coo.ngroups`` past
    ``out_cap`` — their values are lost, so ``overflowed()`` flags it and
    ``check=True`` raises instead of returning silently-wrong output.
    ``check=True`` otherwise runs the usual overflow check for API parity
    (a correctly built structure cannot overflow or miss).

    Instrumented (repro.obs): ``spgemm.validate`` around the fingerprint
    check, then ``spgemm.numeric`` with ``spgemm.multiply`` inside it; each
    call counts ``spgemm.numeric.slot_hits`` where it took the cached
    slots, else ``spgemm.numeric.slot_searches``."""
    if validate:
        with _obs.span("spgemm.validate"):
            structure.validate(a, b)
    if a.val.ndim != 2:
        raise ValueError("batched operands: use spgemm_coo_numeric_batched "
                         "with a structure from make_structure_batched")
    st = structure
    plan = st.plan
    backend = plan.backend if plan is not None else "sort"
    sp = (_obs.span("spgemm.numeric", backend=backend, out_cap=st.out_cap,
                    n_rows=st.n_rows, n_cols=st.n_cols)
          if _obs.is_enabled() else _obs.NULL_SPAN)
    with sp:
        if plan is not None and plan.backend == "stream":
            grp = max(1, min(plan.stream_group, a.val.shape[0]))
            coo = _numeric_stream(a.val, a.idx, b.val, b.idx, st.key, st.nnz,
                                  out_cap=st.out_cap, n_rows=st.n_rows,
                                  n_cols=st.n_cols, group=grp)
            hit = False
        else:
            with _obs.span("spgemm.multiply", backend=backend, k_a=a.k,
                           k_b=b.k, n=a.n_cols):
                val, row, col = _obs.sync(sccp_multiply(a, b))
            cached = None
            if st.slot is not None and st.slot.shape == (val.size,):
                cached = (st.slot, a.idx, b.idx, st.a_idx, st.b_idx)
                row = col = None
            coo, hit = _numeric_scatter(val, row, col, st.key, st.nnz,
                                        cached, out_cap=st.out_cap,
                                        n_rows=st.n_rows, n_cols=st.n_cols)
        _obs.sync(coo.val)
        if _obs.is_enabled() and not isinstance(coo.ngroups, jax.core.Tracer):
            ng, hit = jax.device_get((coo.ngroups, hit))
            ng = int(ng)
            _obs_metrics.inc("spgemm.numeric.slot_hits" if hit
                             else "spgemm.numeric.slot_searches")
            sp.set(nnz=ng)
            if ng > st.out_cap:
                # structure-miss drop → _poison_overflow stamped ngroups
                _obs_metrics.inc("spgemm.poison_events")
                _obs.instant("spgemm.poison", backend=backend, ngroups=ng,
                             cap=int(st.out_cap))
    if sp.dur_us is not None and not isinstance(a.val, jax.core.Tracer):
        _obs_metrics.observe(f"numeric_us.{backend}", sp.dur_us)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


def spgemm_coo_numeric_batched(a: EllRows, b: EllCols, structure, *,
                               check: bool = False,
                               validate: bool = True) -> Coo:
    """Batched numeric phase: vmap the slot scatter over the leading batch
    axis of both operands and of the structure's per-element keys/nnz
    (plan.make_structure_batched). Prefer ``repro.spgemm(a, b,
    structure=st)`` — it detects batched structures and delegates here.
    Shares ``spgemm_coo_numeric``'s
    contract; ``check`` runs once on the batched result."""
    if validate:
        structure.validate(a, b)
    if not structure.batched:
        raise ValueError("structure is unbatched — build one with "
                         "plan.make_structure_batched for batched operands")
    st = structure

    def one(a_i, b_i, key, nnz):
        val, row, col = sccp_multiply(a_i, b_i)
        return _numeric_scatter(val, row, col, key, nnz, out_cap=st.out_cap,
                                n_rows=st.n_rows, n_cols=st.n_cols)[0]

    coo = jax.vmap(one)(a, b, st.key, st.nnz)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


def spgemm_dense_batched(a: EllRows, b: EllCols) -> jax.Array:
    """Batched dense-output SpGEMM over a leading batch axis."""
    return jax.vmap(spgemm_dense)(a, b)


@partial(jax.jit, static_argnames=("k_a", "k_b", "out_cap"))
def spgemm_from_dense(a_dense: jax.Array, b_dense: jax.Array,
                      k_a: int, k_b: int, out_cap: int) -> Coo:
    """Convenience: dense inputs → ELLPACK → SPLIM SpGEMM → sorted COO."""
    a = ell_rows_from_dense(a_dense, k_a)
    b = ell_cols_from_dense(b_dense, k_b)
    return spgemm_coo(a, b, out_cap)


def spmm_ell_dense(a: EllRows, x: jax.Array) -> jax.Array:
    """C = A @ X with A in row-wise ELLPACK and X dense (n, d).

    The structured-multiply half of SCCP with a *structured* output: each
    product lane A.val[s, c] * X[c, :] scatter-adds into output row
    A.idx[s, c]. One segment-sum per slab; no decompression of A.
    This is the op behind MoE dispatch/combine (models/moe.py) and
    SparseLinear. kernels/ell_spmm.py is the Pallas version.
    """
    k, n = a.val.shape
    d = x.shape[-1]
    rows = jnp.where(a.idx >= 0, a.idx, a.n_rows).reshape(-1)        # (k*n,)
    contrib = (a.val[:, :, None] * x[None, :, :]).reshape(-1, d)      # (k*n, d)
    out = jax.ops.segment_sum(contrib, rows, num_segments=a.n_rows + 1)
    return out[: a.n_rows]


def spmm_dense_ell(x: jax.Array, b: EllCols) -> jax.Array:
    """C = X @ B with X dense (d, n) and B in column-wise ELLPACK."""
    n, k = b.val.shape
    d = x.shape[0]
    cols = jnp.where(b.idx >= 0, b.idx, b.n_cols).reshape(-1)         # (n*k,)
    contrib = (x[:, :, None] * b.val[None, :, :]).reshape(d, -1)      # (d, n*k)
    out = jax.ops.segment_sum(contrib.T, cols, num_segments=b.n_cols + 1)
    return out[: b.n_cols].T
