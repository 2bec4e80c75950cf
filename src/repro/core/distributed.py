"""Distributed SPLIM SpGEMM — sparse-native ring schedules on the ICI torus.

Paper Fig. 6(c): B column-vectors rotate array→array (2-step RowClone) while
A row-vectors stay put; every array multiplies its resident A slabs against
the visiting B slabs; intermediate results never cross arrays (§VI-D:
"SPLIM circumvents the need for cross-PE transfer of intermediate results").

TPU mapping: the array ring is a mesh-axis ring, RowClone is
``jax.lax.ppermute`` (one ICI hop, no shared-bus conflicts at all — stronger
than the paper's 2-phase odd/even RowClone schedule), and the per-array
multiply is the SCCP slab product.  What happens *after* the multiply is the
point of this module: partial products are accumulated **device-locally and
sparsely** (the planner's sort/tiled/bucket/hash/stream backends), and only
**COO triples binned by output-row owner** ever cross the mesh — a
propagation-blocking exchange in the spirit of Gu et al. (arXiv 2002.11302)
— so no path here materializes a dense ``n_rows × n_cols`` array.

Three schedules (selected by ``plan.make_dist_plan``):

  * ``'ring'``  — B-stationary ring (paper Fig. 6c): A slabs stay sharded,
    B slabs rotate; each device accumulates its slab-pair product stream
    into a local sorted COO, then a ``ring_all_to_all`` exchanges the
    partials binned by the row-block owner, who merges them.
  * ``'cstat'`` — C-stationary row-block ownership: every device masks A to
    the output rows it owns and merges each visiting-B-slab product stream
    straight into its resident C block — intermediates *never* cross the
    mesh (only operand slabs rotate), at the price of replicating A.
  * ``'summa'`` — communication-avoiding 2D schedule (SUMMA-style; Gu &
    Azad arXiv 2002.11302, Deveci et al.): the device axis is factored into
    a logical ``pr × pc`` grid; each device assembles its grid row's A slab
    panel over ``pc−1`` neighbour hops along the row ring, then rotates B
    panels ``pr−1`` hops along the column ring — per-device operand motion
    is ``(pc−1)/p`` of A plus ``(pr−1)/p`` of B, ~``1/√p`` of the 1D ring's
    full-B volume — and finishes with the same owner-binned COO exchange as
    ``'ring'``. Both 1D schedules rotate over the whole ring; 2D exchanges
    along mesh rows/columns only, which is what survives large meshes.

All three support ``overlap=True`` double-buffering: each stage's
``ppermute`` prefetch of the *next* operand panel is issued before the
current stage's products are accumulated, and the pair is rejoined with
``jax.lax.optimization_barrier`` — on hardware with an async ICI the
exchange hides entirely behind the accumulation scan, and numerics are
bit-identical either way (the barrier only pins scheduling).

Output stays ``Coo`` end to end; ``ngroups`` overflow poisoning (local-cap
truncation, full exchange bins, block-cap truncation) is ``psum``-reduced
across the collective so ``check_no_overflow`` sees every device's drops.

``ring_spgemm`` (dense per-device partial C + final ``psum``) is kept as the
explicit dense baseline the sparse path replaces — it is what COO-SPLIM/
GraphR-style decompression would do, and the distributed benchmark suite
measures its per-device partial-memory cost against ``spgemm_coo_sharded``.

The same ring schedule is reused by the LM stack for MoE token exchange
(models/moe.py, ``ring_all_to_all``) — SPLIM's communication pattern promoted
to a first-class collective strategy.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import platform
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs

from .accumulate import accumulate, scatter_dense
from .formats import Coo, EllCols, EllRows, INVALID


# ---------------------------------------------------------------------------
# Slab padding (ISSUE: validate-and-pad instead of opaque reshape errors)
# ---------------------------------------------------------------------------

def pad_slabs_a(a: EllRows, mult: int) -> EllRows:
    """Pad A's slab axis to a multiple of ``mult`` with INVALID lanes.

    Padding slabs carry ``idx = -1`` / ``val = 0`` so they contribute no
    products — the distributed schedules shard the slab axis over the mesh
    ring and require it divisible by the ring size.
    """
    if a.val.shape[-2] % mult == 0:          # slab axis (batched-safe)
        return a
    from repro.kernels.ops import pad_to
    return EllRows(val=pad_to(a.val, -2, mult, 0),
                   idx=pad_to(a.idx, -2, mult, INVALID), n_rows=a.n_rows)


def pad_slabs_b(b: EllCols, mult: int) -> EllCols:
    """Pad B's slab axis to a multiple of ``mult`` with INVALID lanes."""
    if b.val.shape[-1] % mult == 0:          # slab axis (batched-safe)
        return b
    from repro.kernels.ops import pad_to
    return EllCols(val=pad_to(b.val, -1, mult, 0),
                   idx=pad_to(b.idx, -1, mult, INVALID), n_cols=b.n_cols)


# ---------------------------------------------------------------------------
# Shared building blocks
# ---------------------------------------------------------------------------

def _slab_products(a_val, a_idx, b_val, b_idx):
    """Resident-A × visiting-B slab products (works with leading batch dims).

    Returns ``(val, row, col)`` of shape ``(..., ka_loc, n, kb_loc)`` with
    invalid lanes carrying row = col = -1 and val = 0.
    """
    val = a_val[..., :, :, None] * b_val[..., None, :, :]
    row = jnp.broadcast_to(a_idx[..., :, :, None], val.shape)
    col = jnp.broadcast_to(b_idx[..., None, :, :], val.shape)
    ok = (row >= 0) & (col >= 0)
    return (jnp.where(ok, val, 0),
            jnp.where(ok, row, INVALID),
            jnp.where(ok, col, INVALID))


def _bin_by_owner(row: jax.Array, col: jax.Array, val: jax.Array,
                  n_dev: int, rows_per_dev: int, bin_cap: int):
    """Scatter a row-sorted local COO into per-owner exchange bins.

    Entries are already (row, col)-sorted with invalid lanes parked at the
    tail (every accumulation backend's output contract), so each owner's
    entries form one contiguous run: rank-in-bin = position − run start.
    Returns ``(n_dev, bin_cap)`` row/col/val planes plus the number of
    entries dropped to full bins (0 under a ``make_dist_plan`` sizing).
    """
    cap = row.shape[0]
    valid = row >= 0
    owner = jnp.where(valid, row // rows_per_dev, n_dev)
    counts = jax.ops.segment_sum(jnp.ones((cap,), jnp.int32), owner,
                                 num_segments=n_dev + 1)
    start = jnp.cumsum(counts) - counts                  # exclusive prefix
    rank = jnp.arange(cap, dtype=jnp.int32) - start[owner]
    keep = valid & (rank < bin_cap)
    dropped = jnp.sum(valid & ~keep).astype(jnp.int32)
    o = jnp.where(keep, owner, n_dev)                    # dump bin n_dev
    r = jnp.where(keep, rank, 0)
    buf_row = (jnp.full((n_dev + 1, bin_cap), INVALID, jnp.int32)
               .at[o, r].set(jnp.where(keep, row, INVALID)))
    buf_col = (jnp.full((n_dev + 1, bin_cap), INVALID, jnp.int32)
               .at[o, r].set(jnp.where(keep, col, INVALID)))
    buf_val = (jnp.zeros((n_dev + 1, bin_cap), val.dtype)
               .at[o, r].set(jnp.where(keep, val, 0)))
    return buf_row[:n_dev], buf_col[:n_dev], buf_val[:n_dev], dropped


def _compact_sorted(row: jax.Array, col: jax.Array, val: jax.Array,
                    out_cap: int, shape: Tuple[int, int],
                    ngroups: jax.Array) -> Coo:
    """Dense-pack a globally sorted, gappy COO stream into ``Coo(out_cap)``.

    The per-device row blocks arrive owner-ordered (ascending row ranges)
    and block-sorted, so valid entries are already in global (row, col)
    order — an O(n) cumsum scatter packs them without re-sorting. Valid
    entries beyond ``out_cap`` land in the discarded dump slot; the caller's
    ``ngroups`` (true global group count, possibly poisoned) flags that.
    """
    valid = row >= 0
    dst = jnp.minimum(jnp.where(valid, jnp.cumsum(valid) - 1, out_cap),
                      out_cap)
    out_row = (jnp.full((out_cap + 1,), INVALID, jnp.int32)
               .at[dst].set(jnp.where(valid, row, INVALID)))[:out_cap]
    out_col = (jnp.full((out_cap + 1,), INVALID, jnp.int32)
               .at[dst].set(jnp.where(valid, col, INVALID)))[:out_cap]
    out_val = (jnp.zeros((out_cap + 1,), val.dtype)
               .at[dst].set(jnp.where(valid, val, 0)))[:out_cap]
    return Coo(row=out_row, col=out_col, val=out_val, shape=shape,
               ngroups=ngroups)


# ---------------------------------------------------------------------------
# Sparse-native distributed SpGEMM
# ---------------------------------------------------------------------------

def spgemm_coo_sharded(a: EllRows, b: EllCols, mesh: Mesh, axis: str,
                       out_cap="auto", *, accumulator: str = "auto",
                       schedule: str = "auto", dist_plan=None,
                       structure=None, overlap: bool = True,
                       check: bool = False) -> Coo:
    """C = A·B as sorted COO with slabs sharded over the mesh axis ``axis``.

    Prefer ``repro.spgemm(a, b, mesh=mesh, axis=axis, ...)`` — the unified
    front door (core/api.py) delegates here with identical kwargs.

    Sparse end to end: each ring step feeds the SCCP slab product into a
    device-local planned accumulator, and only COO triples cross the mesh
    (see module docstring for the three schedules — ``'ring'``/``'cstat'``
    1D rotations and the communication-avoiding 2D ``'summa'`` grid). The
    result is replicated
    and bit-compatible with single-device ``spgemm_coo``: same sorted
    coordinate stream, same padding, same true-``ngroups`` overflow
    contract — with any device's drops poisoning the global count.

    ``overlap=True`` (default) double-buffers every schedule's operand
    rotation: the next panel's ``ppermute`` is issued *before* the current
    panel's products are accumulated and the two are rejoined with
    ``jax.lax.optimization_barrier``, hiding the exchange behind compute on
    async-ICI hardware. Purely a scheduling hint — results are bit-identical
    with ``overlap=False`` (which restores accumulate-then-rotate order).

    ``out_cap`` / ``accumulator`` / ``schedule`` accept ``'auto'`` (requires
    concrete operands — planning inspects values); a prebuilt ``dist_plan``
    (``plan.make_dist_plan``) supplies all capacities and keeps the call
    jit/vmap-friendly; a ``structure`` (``plan.make_structure(...,
    n_dev=...)``) supplies its cached per-schedule DistPlan the same way, so
    repeat calls on one pattern never re-plan. A caller-supplied dist_plan
    is fingerprint-validated against the operands (see ``Plan.fp``); stale
    plans raise instead of silently truncating. Batched operands (leading
    batch axis on all four
    ELLPACK planes) are supported with an explicit ``dist_plan`` built on a
    representative slice. ``check=True`` raises ``AccumulatorOverflow`` on
    any truncation anywhere in the pipeline (host sync; call outside jit).

    Coordinate spaces with ``n_rows·n_cols ≥ 2³¹`` reroute the device-local
    accumulation to the unpacked two-key ``'sort'`` path regardless of the
    requested backend — the same automatic, lossless rerouting
    ``spgemm_coo`` applies (packed int32 keys cannot span such spaces).

    ``accumulator='stream'`` moves accumulation *inside* the ring scan
    (core.streaming): each step's slab products are compacted and merged
    into a running sorted buffer immediately, so the per-device peak
    intermediate is one (ka_loc, n, kb_loc) step tile plus the buffer —
    the other backends stack all ``n_dev`` steps' products before
    accumulating.
    """
    n_dev = mesh.shape[axis]
    batched = a.val.ndim == 3
    if dist_plan is None and structure is not None:
        # Per-schedule DistPlan reuse: a SpgemmStructure built with n_dev=
        # caches one DistPlan per schedule — repeated sharded calls on the
        # same pattern skip make_dist_plan entirely.
        dist_plan = structure.dist_plan(
            None if schedule == "auto" else schedule)
        if out_cap == "auto":
            out_cap = structure.out_cap
    if dist_plan is None:
        if isinstance(a.val, jax.core.Tracer) or batched:
            raise ValueError(
                "spgemm_coo_sharded needs a dist_plan under jit/vmap or with "
                "batched operands — build one with plan.make_dist_plan on a "
                "representative (concrete, unbatched) slice and pass "
                "dist_plan=")
        from repro.plan import make_dist_plan
        dist_plan = make_dist_plan(
            a, b, n_dev=n_dev,
            out_cap=None if out_cap == "auto" else int(out_cap),
            backend=None if accumulator == "auto" else accumulator,
            schedule=None if schedule == "auto" else schedule)
    dp = dist_plan
    if dp.n_dev != n_dev:
        raise ValueError(f"dist_plan built for {dp.n_dev} devices but mesh "
                         f"axis {axis!r} has {n_dev}")
    from .spgemm import _validate_plan_fp
    _validate_plan_fp(dp, a, b)
    out_cap = dp.out_cap if out_cap == "auto" else int(out_cap)
    sched = dp.schedule if schedule == "auto" else schedule
    if sched not in ("ring", "cstat", "summa"):
        raise ValueError(f"unknown schedule {sched!r}")
    pr, pc = dp.pr, dp.pc
    if sched == "summa" and pr * pc != n_dev:
        # hand-built or pre-grid DistPlan: derive the factorization here
        # (capacities stay safe — local_cap covers both 1D and 2D histograms
        # under make_dist_plan, and hand caps are the caller's contract)
        from repro.plan.planner import best_grid
        pr, pc = best_grid(n_dev, a.val.shape[-2], b.val.shape[-1],
                           allow_degenerate=True)
    backend = dp.base.backend if accumulator == "auto" else accumulator
    if a.n_rows * b.n_cols >= jnp.iinfo(jnp.int32).max:
        backend = "sort"                     # only unpacked keys span this
    a = pad_slabs_a(a, n_dev)
    b = pad_slabs_b(b, n_dev)
    n_rows, n_cols = a.n_rows, b.n_cols
    rpd, local_cap = dp.rows_per_dev, dp.local_cap
    bin_cap, block_cap = dp.bin_cap, dp.block_cap
    from .spgemm import accumulate_stream
    from . import streaming
    base = dp.base
    use_stream = backend == "stream"

    def acc_local(r, c, v):
        return accumulate_stream(r.reshape(-1), c.reshape(-1), v.reshape(-1),
                                 local_cap, n_rows, n_cols, backend=backend,
                                 tile=base.tile, plan=base)

    def merge_step(r, c, v):
        return accumulate_stream(r, c, v, block_cap, n_rows, n_cols,
                                 backend=backend, tile=base.tile, plan=None)

    def absorb(st, r, c, v):
        # one ring step's (ka_loc, n, kb_loc) products as a single tile:
        # the step already materialized it, so per-device peak intermediate
        # is that tile + the running buffer, never the stacked n_dev-step
        # stream the non-stream path collects before accumulating.
        from repro.kernels.bitonic_merge import next_pot
        return streaming.absorb_products(
            st, r.reshape(-1), c.reshape(-1), v.reshape(-1), n_cols=n_cols,
            stream_cap=next_pot(r.size))

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    vb = (lambda f: jax.vmap(f)) if batched else (lambda f: f)
    # device-stacked scan outputs / exchange buffers carry the mesh axis
    # first and the batch axis (if any) second; flatten per matrix.
    flat = ((lambda x: jnp.moveaxis(x, 1, 0).reshape(x.shape[1], -1))
            if batched else (lambda x: x.reshape(-1)))

    def rotate(bv, bi, p):
        return (jax.lax.ppermute(bv, axis, p),
                jax.lax.ppermute(bi, axis, p))

    def exchange_tail(local, poison):
        # owner-binned COO exchange + per-owner block merge, shared by the
        # B-stationary 1D ring and the 2D summa grid (owners are flat device
        # ids over the full axis either way)
        poison = poison + (local.ngroups > local_cap).astype(jnp.int32)
        br, bc, bv_, dropped = vb(partial(
            _bin_by_owner, n_dev=n_dev, rows_per_dev=rpd,
            bin_cap=bin_cap))(local.row, local.col, local.val)
        poison = poison + (dropped > 0).astype(jnp.int32)
        if batched:                          # exchange wants the mesh axis first
            br, bc, bv_ = (jnp.moveaxis(t, 1, 0) for t in (br, bc, bv_))
        got_i = ring_all_to_all(jnp.stack([br, bc], axis=-1), axis)
        got_v = ring_all_to_all(bv_, axis)
        block = vb(partial(accumulate, out_cap=block_cap, n_rows=n_rows,
                           n_cols=n_cols))(
            flat(got_i[..., 0]), flat(got_i[..., 1]), flat(got_v))
        poison = poison + (block.ngroups > block_cap).astype(jnp.int32)
        ng = (jax.lax.psum(block.ngroups, axis)
              + jnp.where(jax.lax.psum(poison, axis) > 0,
                          jnp.int32(out_cap + 1), jnp.int32(0)))
        return block.row[None], block.col[None], block.val[None], ng

    def rotating_products(av, ai, b_val, b_idx, p, steps, lead):
        """Run ``steps`` rotation stages of resident(av, ai) × visiting B,
        accumulating device-locally; returns the local sorted Coo.

        With ``overlap`` the next panel's ppermute is issued before this
        panel's products are accumulated; ``jax.lax.optimization_barrier``
        rejoins the prefetched buffers with the accumulation result so XLA
        cannot sink the transfer below the compute it should hide behind.
        """
        if use_stream:
            st0 = streaming.stream_init(streaming.buffer_cap(local_cap),
                                        av.dtype, lead=lead)

            def step(carry, _):
                bv, bi, st = carry
                if overlap:
                    nbv, nbi = rotate(bv, bi, p)
                    v, r, c = _slab_products(av, ai, bv, bi)
                    st = vb(absorb)(st, r, c, v)
                    (nbv, nbi), st = jax.lax.optimization_barrier(
                        ((nbv, nbi), st))
                else:
                    v, r, c = _slab_products(av, ai, bv, bi)
                    st = vb(absorb)(st, r, c, v)
                    nbv, nbi = rotate(bv, bi, p)
                return (nbv, nbi, st), ()
            (_, _, st), _ = jax.lax.scan(step, (b_val, b_idx, st0), None,
                                         length=steps)
            return vb(partial(streaming.finalize, out_cap=local_cap,
                              n_rows=n_rows, n_cols=n_cols))(st)

        def step(carry, _):
            bv, bi = carry
            if overlap:
                nxt = rotate(bv, bi, p)
                prod = _slab_products(av, ai, bv, bi)
                nxt, prod = jax.lax.optimization_barrier((nxt, prod))
                return nxt, prod
            prod = _slab_products(av, ai, bv, bi)
            return rotate(bv, bi, p), prod
        # vs/rs/cs: (steps, [batch,] ka_loc, n, kb_loc) — the device-local
        # product stream, stacked (the materialized-path cost the 'stream'
        # branch above avoids).
        _, (vs, rs, cs) = jax.lax.scan(step, (b_val, b_idx), None,
                                       length=steps)
        return vb(acc_local)(flat(rs), flat(cs), flat(vs))

    def shard_ring(a_val, a_idx, b_val, b_idx):
        local = rotating_products(a_val, a_idx, b_val, b_idx, perm, n_dev,
                                  a_val.shape[:-2])
        return exchange_tail(local, jnp.int32(0))

    def shard_summa(a_val, a_idx, b_val, b_idx):
        # Logical pr × pc grid over the flat axis: device d = (r, c) with
        # r = d // pc, c = d % pc. Row panel r owns A shard-blocks
        # [r·pc, (r+1)·pc) (contiguous under the 1D slab sharding); column
        # panel c owns B shard-blocks {r'·pc + c} (stride-pc). Cells
        # partition the (A-slab, B-slab) product pairs disjointly, so the
        # exchange tail sees exactly the same global product stream as ring.
        row_perm = [(q * pc + j, q * pc + (j + 1) % pc)
                    for q in range(pr) for j in range(pc)]
        col_perm = [(q * pc + j, ((q + 1) % pr) * pc + j)
                    for q in range(pr) for j in range(pc)]
        # Phase 1 — assemble the grid row's A slab panel: pc−1 neighbour
        # hops along the row ring (a ppermute pipeline, never an
        # all-gather). Panel order doesn't matter: coordinates are absolute
        # and accumulation sorts.
        panels_v, panels_i, av, ai = [a_val], [a_idx], a_val, a_idx
        for _ in range(pc - 1):
            av, ai = rotate(av, ai, row_perm)
            panels_v.append(av)
            panels_i.append(ai)
        panel_val = jnp.concatenate(panels_v, axis=-2)
        panel_idx = jnp.concatenate(panels_i, axis=-2)
        # Phase 2 — rotate B panels pr−1 hops along the column ring, each
        # stage multiplying the full A panel against the visiting B shard.
        local = rotating_products(panel_val, panel_idx, b_val, b_idx,
                                  col_perm, pr, a_val.shape[:-2])
        return exchange_tail(local, jnp.int32(0))

    def shard_cstat(a_val, a_idx, b_val, b_idx):
        me = jax.lax.axis_index(axis)
        lo = me * rpd
        own = (a_idx >= lo) & (a_idx < lo + rpd)
        av = jnp.where(own, a_val, 0)
        ai = jnp.where(own, a_idx, INVALID)
        lead = (a_val.shape[0],) if batched else ()
        if use_stream:
            st0 = streaming.stream_init(streaming.buffer_cap(block_cap),
                                        a_val.dtype, lead=lead)

            def step(carry, _):
                bv, bi, st = carry
                if overlap:
                    nbv, nbi = rotate(bv, bi, perm)
                    v, r, c = _slab_products(av, ai, bv, bi)
                    st = vb(absorb)(st, r, c, v)
                    (nbv, nbi), st = jax.lax.optimization_barrier(
                        ((nbv, nbi), st))
                else:
                    v, r, c = _slab_products(av, ai, bv, bi)
                    st = vb(absorb)(st, r, c, v)
                    nbv, nbi = rotate(bv, bi, perm)
                return (nbv, nbi, st), ()
            (_, _, st), _ = jax.lax.scan(step, (b_val, b_idx, st0), None,
                                         length=n_dev)
            blk = vb(partial(streaming.finalize, out_cap=block_cap,
                             n_rows=n_rows, n_cols=n_cols))(st)
            row_b, col_b, val_b, ng_b = blk.row, blk.col, blk.val, blk.ngroups
            poison = (blk.ngroups > block_cap).astype(jnp.int32)
        else:
            buf_r = jnp.full(lead + (block_cap,), INVALID, jnp.int32)
            buf_v = jnp.zeros(lead + (block_cap,), a_val.dtype)
            zero = jnp.zeros(lead, jnp.int32)

            def step(carry, _):
                bv, bi, row_b, col_b, val_b, ng, poison = carry
                if overlap:
                    nbv, nbi = rotate(bv, bi, perm)
                v, r, c = _slab_products(av, ai, bv, bi)
                sq = lambda x: x.reshape(lead + (-1,))
                blk = vb(merge_step)(
                    jnp.concatenate([row_b, sq(r)], axis=-1),
                    jnp.concatenate([col_b, sq(c)], axis=-1),
                    jnp.concatenate([val_b, sq(v)], axis=-1))
                poison = poison + (blk.ngroups > block_cap).astype(jnp.int32)
                if overlap:
                    (nbv, nbi), poison = jax.lax.optimization_barrier(
                        ((nbv, nbi), poison))
                else:
                    nbv, nbi = rotate(bv, bi, perm)
                return (nbv, nbi, blk.row, blk.col, blk.val, blk.ngroups,
                        poison), ()
            (_, _, row_b, col_b, val_b, ng_b, poison), _ = jax.lax.scan(
                step, (b_val, b_idx, buf_r, buf_r, buf_v, zero, zero), None,
                length=n_dev)
        ng = (jax.lax.psum(ng_b, axis)
              + jnp.where(jax.lax.psum(poison, axis) > 0,
                          jnp.int32(out_cap + 1), jnp.int32(0)))
        return row_b[None], col_b[None], val_b[None], ng

    from repro.parallel.sharding import spgemm_operand_specs
    spec_a, spec_b = spgemm_operand_specs(axis, schedule=sched,
                                          batched=batched)
    blk_spec = P(axis, *([None] * (1 + int(batched))))
    body = {"ring": shard_ring, "cstat": shard_cstat,
            "summa": shard_summa}[sched]
    # One compiled program (a shard_map called outside jit runs op by op).
    # Unchecked: the device-local accumulators call Pallas kernels, whose
    # outputs (and the interpreter off-TPU) carry no varying-axis types.
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_a, spec_a, spec_b, spec_b),
        out_specs=(blk_spec, blk_spec, blk_spec, P()), check_vma=False))
    if _obs.is_enabled():
        # per-step spans can't escape the shard_map/scan body (it traces
        # once), so the exchange is observed at the dispatch boundary with
        # the DistPlan's modeled per-device comm bytes attached
        comm = float(dp.est.get(f"{sched}_comm_bytes", 0.0))
        steps = (pc - 1) + pr if sched == "summa" else n_dev
        span_kw = dict(schedule=sched, backend=backend, n_dev=n_dev,
                       steps=steps, overlap=overlap,
                       comm_bytes_per_dev=comm)
        if sched == "summa":
            span_kw["grid"] = f"{pr}x{pc}"
        with _obs.span("dist.exchange", **span_kw) as _sp:
            row_g, col_g, val_g, ngroups = fn(a.val, a.idx, b.val, b.idx)
            _obs.sync(val_g)
        _obs_metrics.inc(f"dist.comm_bytes.{sched}", comm * n_dev)
        _obs_metrics.inc("dist.calls")
        if overlap:
            # modeled fraction of the rotation traffic that fits under the
            # device-local accumulation (12 B/product read-modify-write):
            # 1.0 = the exchange hides entirely behind compute
            work = 12.0 * float(dp.est.get("flops", 0.0)) / max(1, n_dev)
            _obs_metrics.gauge(
                "dist.overlap_efficiency",
                1.0 if comm <= 0 else min(1.0, work / comm))
    else:
        row_g, col_g, val_g, ngroups = fn(a.val, a.idx, b.val, b.idx)
    compact = partial(_compact_sorted, out_cap=out_cap,
                      shape=(n_rows, n_cols))
    if batched:
        coo = jax.vmap(lambda r, c, v, g: compact(r, c, v, ngroups=g))(
            flat(row_g), flat(col_g), flat(val_g), ngroups)
    else:
        coo = compact(flat(row_g), flat(col_g), flat(val_g), ngroups=ngroups)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


def spgemm_coo_sharded_batched(a: EllRows, b: EllCols, mesh: Mesh, axis: str,
                               *, dist_plan, schedule: str = "auto",
                               overlap: bool = True,
                               check: bool = False) -> Coo:
    """Batched sharded SpGEMM: ELLPACK planes carry a leading batch axis
    (shared shapes/caps across the batch). Prefer ``repro.spgemm(a, b,
    mesh=mesh, axis=axis, dist_plan=dp)`` — the unified front door detects
    the batch axis and delegates here. Requires a ``dist_plan`` built
    with ``plan.make_dist_plan`` on a representative slice — 'auto' planning
    inspects operand values, which a batch makes ambiguous. Returns a
    ``Coo`` whose leaves (including ``ngroups``) lead with the batch axis.
    """
    if a.val.ndim != 3 or b.val.ndim != 3:
        raise ValueError("batched operands need a leading batch axis on all "
                         f"ELLPACK planes; got A {a.val.ndim}D, B {b.val.ndim}D")
    return spgemm_coo_sharded(a, b, mesh, axis, dist_plan=dist_plan,
                              schedule=schedule, overlap=overlap,
                              check=check)


def spgemm_coo_sharded_numeric(a: EllRows, b: EllCols, mesh: Mesh, axis: str,
                               structure, *, schedule: str = "auto",
                               overlap: bool = True, check: bool = False,
                               validate: bool = True) -> Coo:
    """Distributed numeric phase: rotate B slabs (1D ring or 2D summa grid),
    binary-search each
    step's slab products into the precomputed structure slots, ``psum`` the
    slot accumulators. Prefer ``repro.spgemm(a, b, mesh=mesh, axis=axis,
    structure=st)`` — the unified front door delegates here. No planning, no device-local sort, no owner-binned
    COO exchange — the only cross-device traffic is the operand rotation plus
    one ``(out_cap + 1)`` accumulator reduction, and the per-device peak
    intermediate is a single slab-pair product tile plus that accumulator.

    ``schedule`` accepts ``'auto'`` (the structure's cached DistPlan pick
    when one exists and it chose ``'summa'``, else ``'ring'``), ``'ring'``,
    or ``'summa'`` (2D grid operand motion; the final reduction stays one
    psum). ``'cstat'`` has no meaning here — there is no resident C block —
    and raises. ``overlap=True`` applies the same prefetch-before-accumulate
    double-buffering as the cold path; numerics are unaffected.

    ``structure`` comes from ``plan.make_structure`` on the same (global,
    unbatched) operands; it does **not** need ``n_dev`` — the slot scatter
    replaces the DistPlan machinery entirely (cold repeat calls that still
    want the exchange pipeline reuse cached DistPlans via
    ``spgemm_coo_sharded(..., structure=)`` instead). Output is replicated
    sorted COO, the same contract as ``spgemm_coo_sharded``, equal to the
    cold result up to floating-point summation order."""
    if validate:
        structure.validate(a, b)
    if a.val.ndim != 2:
        raise ValueError("spgemm_coo_sharded_numeric is unbatched — vmap "
                         "spgemm_coo_numeric for batched operands")
    st = structure
    n_dev = mesh.shape[axis]
    if schedule not in ("auto", "ring", "summa"):
        raise ValueError(
            f"unknown numeric-path schedule {schedule!r} — the warm numeric "
            "phase supports 'auto', 'ring', or 'summa' (no resident C block, "
            "so 'cstat' does not apply)")
    sched, pr, pc = schedule, 1, 1
    cached = None
    if st.dist_plans:
        dp = st.dist_plan(None)
        if dp.n_dev == n_dev:
            cached = dp
    if sched == "auto":
        sched = ("summa" if cached is not None and cached.schedule == "summa"
                 else "ring")
    if sched == "summa":
        if cached is not None and cached.pr * cached.pc == n_dev:
            pr, pc = cached.pr, cached.pc
        else:
            from repro.plan.planner import best_grid
            pr, pc = best_grid(n_dev, a.val.shape[-2], b.val.shape[-1],
                               allow_degenerate=True)
    a = pad_slabs_a(a, n_dev)
    b = pad_slabs_b(b, n_dev)
    n_rows, n_cols, out_cap = st.n_rows, st.n_cols, st.out_cap
    ring_perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    row_perm = [(q * pc + j, q * pc + (j + 1) % pc)
                for q in range(pr) for j in range(pc)]
    col_perm = [(q * pc + j, ((q + 1) % pr) * pc + j)
                for q in range(pr) for j in range(pc)]
    acc_dtype = jnp.result_type(a.val.dtype, b.val.dtype)

    def shard_fn(a_val, a_idx, b_val, b_idx, key):
        if sched == "summa":
            # assemble the grid row's A slab panel (pc−1 row-ring hops),
            # then rotate B along the column ring — same 2D stage structure
            # as the cold path, minus the exchange tail
            pv, pi, av, ai = [a_val], [a_idx], a_val, a_idx
            for _ in range(pc - 1):
                av = jax.lax.ppermute(av, axis, row_perm)
                ai = jax.lax.ppermute(ai, axis, row_perm)
                pv.append(av)
                pi.append(ai)
            res_val = jnp.concatenate(pv, axis=-2)
            res_idx = jnp.concatenate(pi, axis=-2)
            perm, steps = col_perm, pr
        else:
            res_val, res_idx, perm, steps = a_val, a_idx, ring_perm, n_dev

        def absorb(acc, nm, bv, bi):
            v, r, c = _slab_products(res_val, res_idx, bv, bi)
            v, r, c = v.reshape(-1), r.reshape(-1), c.reshape(-1)
            valid = r >= 0
            pk = jnp.where(valid, r * n_cols + c, 0).astype(jnp.int32)
            slot = platform.searchsorted(key, pk).astype(jnp.int32)
            miss = jnp.logical_or(
                ~valid, jnp.take(key, jnp.minimum(slot, out_cap - 1),
                                 mode="clip") != pk)
            slot = jnp.where(miss, out_cap, slot)
            # valid products missing from the structure lose their value to
            # the dump slot — counted and psum'd so the result is poisoned
            nm = nm + jnp.sum(jnp.logical_and(valid, miss)).astype(jnp.int32)
            acc = acc + jax.ops.segment_sum(jnp.where(valid, v, 0), slot,
                                            num_segments=out_cap + 1)
            return acc, nm

        def step(carry, _):
            bv, bi, acc, nm = carry
            if overlap:
                nbv = jax.lax.ppermute(bv, axis, perm)
                nbi = jax.lax.ppermute(bi, axis, perm)
                acc, nm = absorb(acc, nm, bv, bi)
                (nbv, nbi), (acc, nm) = jax.lax.optimization_barrier(
                    ((nbv, nbi), (acc, nm)))
            else:
                acc, nm = absorb(acc, nm, bv, bi)
                nbv = jax.lax.ppermute(bv, axis, perm)
                nbi = jax.lax.ppermute(bi, axis, perm)
            return (nbv, nbi, acc, nm), ()

        init = (b_val, b_idx,
                jax.lax.pcast(jnp.zeros((out_cap + 1,), acc_dtype), axis,
                              to="varying"),
                jax.lax.pcast(jnp.zeros((), jnp.int32), axis, to="varying"))
        (_, _, acc, nm), _ = jax.lax.scan(step, init, None, length=steps)
        return jax.lax.psum(acc, axis), jax.lax.psum(nm, axis)

    fn = jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                               in_specs=(P(axis, None), P(axis, None),
                                         P(None, axis), P(None, axis), P()),
                               out_specs=(P(), P())))
    sums, n_miss = fn(a.val, a.idx, b.val, b.idx, st.key)
    from .spgemm import _coo_from_slots, _poison_overflow
    coo = _coo_from_slots(st.key, sums[:out_cap], st.nnz, out_cap=out_cap,
                          n_rows=n_rows, n_cols=n_cols)
    coo = _poison_overflow(coo, n_miss)
    if check:
        from .accumulate import check_no_overflow
        coo = check_no_overflow(coo)
    return coo


# ---------------------------------------------------------------------------
# Dense-psum baseline (what the sparse path replaces) + ring collective
# ---------------------------------------------------------------------------

def _local_multiply_accumulate(a_val, a_idx, b_val, b_idx, n_rows, n_cols, c_acc):
    """One ring step: resident A slabs × visiting B slabs → dense partial C."""
    val, row, col = _slab_products(a_val, a_idx, b_val, b_idx)
    return c_acc + scatter_dense(row, col, val, n_rows, n_cols)


def ring_spgemm(a: EllRows, b: EllCols, mesh: Mesh, axis: str) -> jax.Array:
    """C = A·B with slabs sharded over ``axis`` and B-slabs ring-rotated.

    The **dense baseline**: every device scatters partials into a dense
    per-device C and a final ``psum`` merges them — per-device partial
    memory is O(n_rows·n_cols) regardless of sparsity, which is exactly the
    scaling failure ``spgemm_coo_sharded`` exists to fix (its partials stay
    COO and scale ~1/devices). Kept for verification and as the measured
    baseline of the distributed benchmark suite.

    Slab counts that don't divide the ring size are padded with INVALID
    lanes (``pad_slabs_a``/``pad_slabs_b``) rather than rejected.
    """
    n_dev = mesh.shape[axis]
    a = pad_slabs_a(a, n_dev)
    b = pad_slabs_b(b, n_dev)
    n_rows, n_cols = a.n_rows, b.n_cols

    def shard_fn(a_val, a_idx, b_val, b_idx):
        def step(carry, _):
            b_val_c, b_idx_c, c_acc = carry
            c_acc = _local_multiply_accumulate(
                a_val, a_idx, b_val_c, b_idx_c, n_rows, n_cols, c_acc)
            # ring-rotate the visiting B slabs to the next device (RowClone)
            perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
            b_val_c = jax.lax.ppermute(b_val_c, axis, perm)
            b_idx_c = jax.lax.ppermute(b_idx_c, axis, perm)
            return (b_val_c, b_idx_c, c_acc), ()

        init = (b_val, b_idx,
                jax.lax.pcast(jnp.zeros((n_rows, n_cols), a_val.dtype), axis,
                              to="varying"))
        (b_val, b_idx, c_acc), _ = jax.lax.scan(step, init, None, length=n_dev)
        return jax.lax.psum(c_acc, axis)

    spec_a = P(axis, None)
    spec_b = P(None, axis)
    fn = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec_a, spec_a, spec_b, spec_b),
        out_specs=P()))
    return fn(a.val, a.idx, b.val, b.idx)


def ring_all_to_all(x: jax.Array, axis: str) -> jax.Array:
    """SPLIM-style ring alternative to ``all_to_all`` (inside shard_map).

    ``x``: (n_dev, chunk, ...) — chunk i is destined for device i. Rotates
    the whole buffer around the ring, each device peeling off its chunk; uses
    n_dev-1 ppermutes of shrinking usefulness but only neighbour links (no
    global crossbar pressure), matching the paper's C/A-conflict-free
    RowClone argument. Used by MoE when ``moe_comm='ring'`` and by the
    B-stationary schedule's owner-binned COO exchange.
    """
    n_dev = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    out = jnp.zeros_like(x)
    out = out.at[me].set(x[me])

    def step(carry, i):
        buf, out = carry
        perm = [(d, (d + 1) % n_dev) for d in range(n_dev)]
        buf = jax.lax.ppermute(buf, axis, perm)
        src = (me - i - 1) % n_dev          # whose buffer is visiting now
        out = out.at[src].set(buf[me])
        return (buf, out), ()

    (x, out), _ = jax.lax.scan(step, (x, out), jnp.arange(n_dev - 1))
    return out
