"""Workload-adaptive accumulation planning for SPLIM SpGEMM.

SPLIM's thesis splits SpGEMM into a *structured* multiply (SCCP — always the
same dataflow) and an *unstructured* accumulation, and the accumulation is
where one size does not fit all: the SpGEMM literature picks sort-, bin-, or
hash-based accumulators per matrix (Gu et al. propagation blocking; Nagasaka
et al. hash vs heap on KNL). This module is that selection step for our six
backends:

  sort    — global ``jax.lax.sort`` + segmented sum (core/accumulate)
  tiled   — multi-tile bitonic merge tree (kernels/bitonic_merge)
  bucket  — propagation blocking: bin by row range, per-bucket bitonic
            (kernels/radix_bucket)
  hash    — per-row-block open-addressing tables (kernels/hash_accum)
  stream  — slab-scan multiply→compact→merge (core/streaming): the only
            backend that never materializes the (k_a, n, k_b) product
            stream; its intermediate is O(n·k_b + stream_cap)
  search  — the paper's in-situ-search accumulation (kernels/insitu_search):
            emission of the sorted unique coordinates by one sort of
            (key, lane), every product aligned to its slot through that
            sort's permutation, one segment-sum landing the values, which
            are never sorted

The model is also **memory-aware**: every backend's modeled intermediate
bytes go into ``Plan.est`` (``interm_*`` — the materialized un-accumulated
product lanes, the quantity SpGEMM is bound by per Liu & Vinter / Nagasaka
et al.), and when the op-count winner's intermediate exceeds
``mem_budget`` bytes the planner overrides it with the cheapest backend
that fits — ``'stream'``, whose intermediate does not grow with ``k_a``,
when none of the materializing ones does.

``make_plan`` runs the symbolic phase (plan/symbolic) on concrete operands,
derives ``out_cap`` and every backend's blocking sizes from *exact*
histograms (so the planned bucket/hash paths can never drop products), scores
the backends with an operation-count cost model fed by ``hwmodel.MatrixStats``
(``hwmodel.stats_from_ell`` is the ELL-side variant of ``stats_from_scipy``),
and returns a frozen ``Plan`` whose fields are all Python ints — the plan
itself is jit/vmap-compatible even though planning is a host-side step.

Cost model: all backends first pay the SCCP stream ``S`` (padded to a power
of two); they differ in what they do per stream element and in how much of
the work runs inside Pallas networks. Off-TPU the Pallas kernels execute in
interpreter mode (orders of magnitude slower than XLA's fused sort), so the
model carries an interpreter penalty on Pallas terms — on CPU hosts the
planner therefore honestly prefers ``sort``, while the op counts govern on
real TPUs. ``benchmarks/microbench.accum_backends_micro`` validates the
choice against measured times.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import numpy as np

from repro.core.formats import EllCols, EllRows
from repro.core.hwmodel import MatrixStats, splim_latency, stats_from_ell
from repro.kernels import platform
from repro.kernels.bitonic_merge import next_pot as _pot
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs

from . import symbolic

BACKENDS = ("sort", "tiled", "bucket", "hash", "stream", "search")

# Cost-model constants (relative vector-op units per element).
XLA_SORT_C = 1.0        # XLA fused sort, per element per log2 level
CE_C = 1.0              # one bitonic compare-exchange step
BIN_C = 2.0             # binning scan + scatter, per element
PROBE_C = 3.0           # one probe round: 2 gathers + 1 scatter-min
SEGSUM_C = 1.0          # segment_sum per element
INTERPRET_PENALTY = 50.0   # Pallas interpret-mode slowdown off-TPU
# 'sort' pays 12 B/lane over three operands with a two-key comparator; the
# streaming engine's packed single-key tile sorts move 8 B/lane with a
# scalar comparator (STREAM_SORT_C scales its per-element unit down).
SORT_TRAFFIC = 1.5
STREAM_SORT_C = 0.5
# 'search' runs three single-key sorts of the padded stream — (key, lane)
# for its emission, the run heads' keys for the compaction, (lane, rank)
# for the alignment — and one segment-sum for its landing. Fitted on a TPU
# v5e at the HPCG 27-point stencil's A·Aᵀ (32×32×40 grid: S = 2^25 lanes,
# 5.88 products per output), in the units of 'sort''s term there: the
# 'sort' accumulate took 0.990 s for its SORT_TRAFFIC · S · log2 S units;
# the emission 0.167 s, the alignment 0.075 s, the landing (a segment-sum
# dearer per lane than any sort) 0.369 s. The constants stand off the chip
# too, where nothing was fitted. Only the 'sort' and 'search' terms share
# these units: the other backends' constants, SEGSUM_C of 'hash''s same
# segment-sum among them, are the older relative vector-op units and were
# never fitted on the chip. The term has no nnz(C) part, since each of its
# four passes runs over the padded stream, so past about 2^13 lanes
# 'search' ranks below 'sort' at any duplicate ratio; it was measured at
# 5.88 products per output only.
SEARCH_SORT_C = 0.25    # emission, per element per log2 level
ALIGN_C = 0.11          # alignment, per element per log2 level
LAND_C = 14.0           # landing, per element
# Fixed per-scan-step floor of the streaming engine (dispatch + carry +
# compaction bookkeeping), in the same per-element units — measured ≈ a
# few hundred µs off-TPU. This is what the planner's stream_group
# amortizes; it also keeps 'stream' from being chosen on tiny streams
# where the monolithic sort is dispatch-free.
SCAN_STEP_C = 16384.0
# Off-TPU a scan step's tile should be big enough to amortize SCAN_STEP_C:
# stream_group targets this many lanes per tile, subject to the streamed
# intermediate staying ≥ STREAM_INTERM_MARGIN× under the materialized
# stream (the whole point of streaming — and the bench's evidence gate).
STREAM_TILE_TARGET = 32768
STREAM_INTERM_MARGIN = 4.0

# Intermediate-bytes budget on backends that report no device memory (the
# CPU host): 1 GiB of materialized product lanes comfortably fits host RAM
# for the toy suites, while genuinely large k_a·n·k_b streams blow past it.
DEFAULT_MEM_BUDGET = 1 << 30
# Where the device reports its memory (TPU HBM), the budget is this share
# of it: the modeled intermediate counts only the un-accumulated product
# lanes, and the sort that consumes them needs about as much again in
# temporaries, plus the output buffers.
DEVICE_MEM_SHARE = 4


def default_mem_budget() -> int:
    """Intermediate-bytes budget for the default device."""
    stats = jax.devices()[0].memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"]) // DEVICE_MEM_SHARE
    return DEFAULT_MEM_BUDGET


def _net_cost(n: int, length: int) -> float:
    """Compare-exchange count of a full bitonic sort of ``n`` elements in
    power-of-2 rows of ``length`` (all rows ride one network)."""
    lt = max(1, int(math.log2(max(2, length))))
    return n * lt * (lt + 1) / 2 * CE_C


@dataclasses.dataclass(frozen=True)
class Plan:
    """A fully static accumulation plan (safe to close over under jit/vmap).

    ``fp`` is the sparsity fingerprint of the operands the plan was sized
    for (``plan.structure.fingerprint``); ``spgemm_coo(plan=)`` validates it
    against the actual operands and raises on mismatch instead of silently
    producing garbage or poisoned overflow. ``dataclasses.replace(plan,
    fp=None)`` opts a plan out of validation for deliberate reuse across
    similarly-sparse patterns (pair with ``slack`` > 1 headroom). ``stats``
    and ``est`` are advisory (excluded from equality/hash so plans stay
    usable as static jit aux data).
    """

    backend: str                      # one of BACKENDS
    out_cap: int
    tile: int = 4096                  # 'tiled' merge-tree tile
    stream_cap: Optional[int] = None  # 'stream' per-tile compaction width
    stream_group: int = 1             # 'stream' A slabs per scan step
    # Blocking sizes: make_plan fills all four from exact histograms. Leaving
    # them None (hand-built plans) resolves to the ops-layer safe default —
    # ONE stream-sized bucket/table, not an n-way split of stream-sized ones.
    n_buckets: Optional[int] = None   # 'bucket' row-range partitions
    bucket_cap: Optional[int] = None  # per-bucket slots (pow2)
    n_blocks: Optional[int] = None    # 'hash' row-range partitions
    block_cap: Optional[int] = None   # per-block table slots (pow2)
    max_probes: Optional[int] = None  # None = full probe cycle (never spuriously drops)
    fp: Optional[str] = None          # operand sparsity fingerprint
    stats: Optional[MatrixStats] = dataclasses.field(default=None,
                                                     compare=False)
    est: Dict[str, float] = dataclasses.field(default_factory=dict,
                                              compare=False)


def _backend_costs(s: MatrixStats, stream_pot: int, tile: int,
                   n_buckets: int, bucket_cap: int,
                   n_blocks: int, block_cap: int,
                   n_steps: int, tile_lanes: int, stream_cap: int,
                   buf_cap: int, on_tpu: bool) -> Dict[str, float]:
    S = float(stream_pot)
    ls = max(1.0, math.log2(S))
    pal = 1.0 if on_tpu else INTERPRET_PENALTY

    cost = {"sort": SORT_TRAFFIC * XLA_SORT_C * S * ls}

    lt = math.log2(tile)
    tree_ce = S * (lt * (lt + 1) / 2 + sum(range(int(lt) + 1, int(ls) + 1)))
    cost["tiled"] = pal * tree_ce * CE_C

    cost["bucket"] = (pal * (BIN_C * S * (1 + n_buckets / 64)
                             + _net_cost(n_buckets * bucket_cap, bucket_cap)))

    load = min(0.95, s.nnz_c / max(1, n_blocks * block_cap))
    probes = 1.0 / max(0.05, 1.0 - load)
    cost["hash"] = (PROBE_C * S * probes + SEGSUM_C * S
                    + pal * _net_cost(n_blocks * block_cap, block_cap))

    # stream: n_steps sequential steps of (group-tile packed sort, merge
    # with the 2·buf_cap buffer pair) plus the fixed per-step dispatch
    # floor (which also covers the cheap compactions). The tile sort is
    # XLA's fused sort off-TPU and the fused in-VMEM network on TPU —
    # never interpret-mode Pallas, so no interpreter penalty applies.
    t = float(_pot(tile_lanes))
    ltile = max(1.0, math.log2(max(2.0, t)))
    tile_sort = (_net_cost(t, int(t)) if on_tpu
                 else STREAM_SORT_C * XLA_SORT_C * t * ltile)
    mrg = float(2 * buf_cap)
    merge = CE_C * mrg * (math.log2(mrg) + 1)
    cost["stream"] = n_steps * (tile_sort + merge + SCAN_STEP_C)

    # search: the emission's and the alignment's sorts + one segment-sum,
    # all XLA programs on every platform, so no interpreter penalty.
    cost["search"] = ((SEARCH_SORT_C + ALIGN_C) * XLA_SORT_C * S * ls
                      + LAND_C * S)
    return cost


def _stream_interm_bytes(tile_lanes: int, stream_cap: int) -> float:
    """Streaming engine's peak materialized intermediate: the packed
    (key+val, 8 B/lane) sorted tile plus the compacted ``stream_cap``
    lanes. The raw 12 B/lane product tile never materializes — on TPU it
    lives in the fused kernel's VMEM registers, off-TPU the element-wise
    multiply→mask→pack chain fuses into the sort-operand computation."""
    return 8.0 * (_pot(tile_lanes) + stream_cap)


def _backend_interm_bytes(stream_lanes: int, stream_pot: int,
                          tile_lanes: int, stream_cap: int,
                          n_buckets: int, bucket_cap: int,
                          n_blocks: int, block_cap: int,
                          out_cap: int) -> Dict[str, float]:
    """Modeled peak *materialized intermediate* bytes per backend — the
    un-accumulated product lanes alive at once (the SpGEMM working-set
    bound of Liu & Vinter / Nagasaka et al.), not the output buffer all
    backends share via ``out_cap``. Every materialized backend first pays
    the full 12 B/lane (val+row+col) SCCP stream; the packed-key ones add
    an 8 B/lane (key+val) copy, blocking adds its bins/tables. The stream
    backend's intermediate (``_stream_interm_bytes``) is independent of
    ``k_a``."""
    raw = 12.0 * stream_lanes
    packed = 8.0 * stream_pot
    return {
        "sort": raw,
        "tiled": raw + packed,
        "bucket": raw + packed + 8.0 * n_buckets * bucket_cap,
        "hash": raw + packed + 8.0 * n_blocks * block_cap,
        "stream": _stream_interm_bytes(tile_lanes, stream_cap),
        # per padded lane: the packed key, the sort's permutation and each
        # lane's rank and slot (16 B), and the landing's segment-sum
        # temporaries (about 20 B: its indices and values sorted); plus
        # the unique keys and their sums
        "search": raw + 36.0 * stream_pot + 8.0 * out_cap,
    }


def make_plan(a: EllRows, b: EllCols, *, out_cap: Optional[int] = None,
              backend: Optional[str] = None, exact: bool = True,
              tile: int = 4096, slack: float = 1.0,
              mem_budget: Optional[int] = None) -> Plan:
    """Symbolic phase + backend selection on concrete (non-traced) operands.

    ``out_cap``/``backend`` pin the respective decision while the planner
    still derives the rest (e.g. ``backend='hash'`` with auto table sizes).
    ``exact=False`` degrades the symbolic phase to the cheap row-flop upper
    bound (sizes stay safe: caps come from product histograms, which
    dominate unique-coordinate histograms). ``mem_budget`` bounds the
    modeled materialized-intermediate bytes (default ``default_mem_budget``:
    a share of the device's memory, 1 GiB where none is reported): when the
    op-count winner would materialize more, the cheapest backend within the
    budget is chosen instead — ``'stream'`` (whose intermediate is O(n·k_b),
    not O(k_a·n·k_b)) when no materializing backend fits.

    Instrumented (repro.obs): the whole of it is one ``spgemm.plan`` span,
    with ``spgemm.symbolic`` as its child.
    """
    with _obs.span("spgemm.plan", backend=backend or "auto",
                   n_rows=a.n_rows, n_cols=b.n_cols):
        return _make_plan(a, b, out_cap=out_cap, backend=backend,
                          exact=exact, tile=tile, slack=slack,
                          mem_budget=mem_budget)


def _make_plan(a: EllRows, b: EllCols, *, out_cap: Optional[int],
               backend: Optional[str], exact: bool, tile: int, slack: float,
               mem_budget: Optional[int]) -> Plan:
    if mem_budget is None:
        mem_budget = default_mem_budget()
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    n_rows, n_cols, n = a.n_rows, b.n_cols, a.n_cols
    if n_rows * n_cols >= 2 ** 31 - 1 and backend not in (None, "sort"):
        raise ValueError(
            f"backend {backend!r} needs packed int32 coordinate keys but the "
            f"output space is {n_rows}x{n_cols}; only 'sort' (unpacked "
            "two-key path) spans it")
    stream = a.k * n * b.k
    stream_pot = _pot(stream)
    on_tpu = platform.on_tpu()
    slab_lanes = n * b.k

    # --- symbolic phase -----------------------------------------------------
    # The exact unique-coordinate pass costs one coordinate-only stream sort;
    # run it only when something consumes tight uniques: out_cap sizing, or
    # table sizing for a possible hash backend. Bound-based sizing stays safe
    # (the clipped row-flop bound dominates the true per-row uniques).
    exact = exact and (out_cap is None or backend in (None, "hash"))
    with _obs.span("spgemm.symbolic", backend=backend or "auto", exact=exact,
                   n_rows=n_rows, n_cols=n_cols):
        products_per_row, unique_per_row = symbolic.per_row_counts(
            a, b, exact=exact)
        products_per_row = jax.device_get(products_per_row)   # host sync
        unique_per_row = jax.device_get(unique_per_row)
    nnz_c = int(unique_per_row.sum())
    if out_cap is None:
        cap = -(-int(max(1, nnz_c) * slack) // symbolic.LANE) * symbolic.LANE
        out_cap = max(symbolic.LANE, cap)

    # --- blocking sizes from exact histograms (never-drop guarantee) --------
    n_buckets = min(64, max(2, _pot(stream_pot // 4096)))
    n_blocks = n_buckets
    rpb = -(-n_rows // n_buckets)
    pad = n_buckets * rpb - n_rows
    prod_hist = np.pad(np.asarray(products_per_row),
                       (0, pad)).reshape(n_buckets, rpb).sum(axis=1)
    uniq_hist = np.pad(np.asarray(unique_per_row),
                       (0, pad)).reshape(n_blocks, rpb).sum(axis=1)
    bucket_cap = min(stream_pot, max(128, _pot(int(prod_hist.max()))))
    block_cap = min(stream_pot, max(128, _pot(2 * int(uniq_hist.max()))))
    # stream sizing. stream_cap: per-tile compaction width from the exact
    # per-slab product histogram — a group tile's uniques never exceed its
    # products, which are bounded by group · the largest slab count, so
    # this cap never drops (full-tile fallback when slabs are empty).
    # stream_group: on TPU the fused VMEM kernel wants single slabs; off
    # TPU take the largest group that amortizes the per-step dispatch
    # floor (STREAM_TILE_TARGET lanes) while the streamed intermediate
    # stays ≥ STREAM_INTERM_MARGIN× under the materialized stream.
    max_slab = int(jax.device_get(symbolic.max_slab_products(a, b)))

    def _scap(g: int) -> int:
        return min(_pot(g * slab_lanes), max(128, _pot(g * max_slab)))

    group = 1
    if not on_tpu:
        group = max(1, min(a.k, STREAM_TILE_TARGET // max(1, slab_lanes)))
        while group > 1 and (STREAM_INTERM_MARGIN
                             * _stream_interm_bytes(group * slab_lanes,
                                                    _scap(group))
                             > 12.0 * stream):
            group -= 1
    tile_lanes = group * slab_lanes
    n_steps = -(-a.k // group)
    stream_cap = _scap(group)
    buf_cap = _pot(max(int(out_cap), 128))

    # --- backend selection --------------------------------------------------
    # Pinned backend = sizing-only request: skip the stats pass and the cost
    # model whose output would be discarded (bare spgemm_coo(a, b) pins
    # 'sort' and pays only the symbolic phase above).
    if backend is not None:
        s, est, chosen = None, {}, backend
    else:
        s = stats_from_ell(a, b, nnz_c=nnz_c)
        costs = _backend_costs(s, stream_pot, tile, n_buckets, bucket_cap,
                               n_blocks, block_cap, n_steps, tile_lanes,
                               stream_cap, buf_cap, on_tpu)
        interm = _backend_interm_bytes(stream, stream_pot, tile_lanes,
                                       stream_cap, n_buckets, bucket_cap,
                                       n_blocks, block_cap, int(out_cap))
        chosen = min(costs, key=costs.get)
        # memory-aware override: a winner that must materialize more
        # intermediate bytes than the budget loses to the cheapest backend
        # within it, else to the smallest working set (the streaming
        # engine's, which does not grow with k_a).
        if interm[chosen] > mem_budget:
            fits = [k for k in costs if interm[k] <= mem_budget]
            chosen = (min(fits, key=costs.get) if fits
                      else min(interm, key=interm.get))
        if n_rows * n_cols >= 2 ** 31 - 1:
            chosen = "sort"                 # only unpacked keys span the space
        est = {f"cost_{k}": v for k, v in costs.items()}
        est.update({f"interm_{k}": v for k, v in interm.items()})
        est["mem_budget"] = float(mem_budget)
        est["splim_model_s"] = splim_latency(s)["total"]
    from .structure import fingerprint   # lazy: structure imports this module
    fp = fingerprint(a, b)
    if _obs.is_enabled():
        # planner-evidence ledger: est costs now, measured µs arrive from
        # the instrumented accumulate spans keyed by the same fingerprint
        _obs_metrics.record_plan(fp[:12], chosen, est)
        _obs.instant("plan.decision", backend=chosen, out_cap=int(out_cap),
                     pinned=backend is not None)
    return Plan(backend=chosen, out_cap=int(out_cap), tile=tile,
                stream_cap=stream_cap, stream_group=group,
                n_buckets=n_buckets, bucket_cap=bucket_cap,
                n_blocks=n_blocks, block_cap=block_cap, max_probes=None,
                fp=fp, stats=s, est=est)


# ---------------------------------------------------------------------------
# Distributed planning (core/distributed.spgemm_coo_sharded)
# ---------------------------------------------------------------------------

SCHEDULES = ("ring", "cstat", "summa")


def _lane_pad(x: int) -> int:
    return max(symbolic.LANE, -(-int(x) // symbolic.LANE) * symbolic.LANE)


def grid_candidates(n_dev: int):
    """Non-degenerate ``(pr, pc)`` factorizations of ``n_dev`` (both ≥ 2).

    A factorization with a side of 1 degenerates to a 1D schedule — its
    communication is the ring/cstat model, so modeling it as "2D" would
    invent phantom column-traffic savings (the 2-device-mesh bug this
    function exists to prevent). Degenerate grids are therefore never
    candidates for ``schedule='auto'``; an *explicit* ``schedule='summa'``
    on a prime mesh still runs (``best_grid(allow_degenerate=True)``) but
    is modeled with 1D bytes.
    """
    return [(pr, n_dev // pr) for pr in range(2, n_dev)
            if n_dev % pr == 0 and n_dev // pr >= 2]


def best_grid(n_dev: int, k_a: int, k_b: int, *,
              allow_degenerate: bool = False):
    """Least-operand-motion ``(pr, pc)`` grid for a SUMMA-style schedule.

    Per-device operand motion is ``k_a·(pc−1) + k_b·(pr−1)`` slab-lanes
    (A hops along the grid row, B along the grid column), so non-square
    operand widths want non-square grids. Returns ``None`` when no
    non-degenerate factorization exists (prime or 2-device meshes) unless
    ``allow_degenerate`` — then the better of ``(n_dev, 1)`` / ``(1,
    n_dev)`` is returned so an explicit ``schedule='summa'`` still runs.
    """
    cands = grid_candidates(n_dev)
    if not cands:
        if not allow_degenerate:
            return None
        cands = [(n_dev, 1), (1, n_dev)] if n_dev > 1 else [(1, 1)]
    return min(cands, key=lambda g: k_a * (g[1] - 1) + k_b * (g[0] - 1))


@dataclasses.dataclass(frozen=True)
class DistPlan:
    """A fully static distributed-SpGEMM plan (Python ints — safe to close
    over under jit/shard_map). Capacities come from exact per-shard/per-block
    histograms, so a planned run never drops partials:

      local_cap — device-local accumulation width, ≥ the unique coordinates
                  any one device's slab-product stream produces (exact
                  per-shard AND per-grid-cell product counts ∧ global
                  nnz(C) — the max of both histograms, so one plan stays
                  safe under ``dataclasses.replace(dp, schedule=...)``);
      bin_cap   — per-destination COO-exchange bin, ≥ any (device, owner)
                  partial count (bounded by both of the above);
      block_cap — per-owner row-block output width, ≥ the exact block nnz.

    ``(pr, pc)`` is the logical 2D grid the ``'summa'`` schedule factors the
    device axis into (``pr·pc == n_dev``); it is always populated with the
    best factorization so replacing the schedule on an existing plan works.
    """

    schedule: str             # 'ring' | 'cstat' | 'summa' (2D grid)
    n_dev: int
    rows_per_dev: int         # owner(r) = r // rows_per_dev
    local_cap: int
    bin_cap: int
    block_cap: int
    out_cap: int              # final global COO capacity
    base: Plan                # device-local accumulation backend + sizes
    fp: Optional[str] = None  # operand sparsity fingerprint (see Plan.fp)
    pr: int = 1               # 'summa' grid rows (A panels hop along rows)
    pc: int = 1               # 'summa' grid cols (B panels hop along cols)
    est: Dict[str, float] = dataclasses.field(default_factory=dict,
                                              compare=False)


def make_dist_plan(a: EllRows, b: EllCols, *, n_dev: int,
                   schedule: Optional[str] = None,
                   out_cap: Optional[int] = None,
                   backend: Optional[str] = None,
                   tile: int = 4096, slack: float = 1.0) -> DistPlan:
    """Distributed symbolic phase + schedule selection (concrete operands).

    Extends ``make_plan`` across a mesh axis of ``n_dev`` devices: the base
    plan supplies the device-local accumulation backend and the global
    ``out_cap``; per-shard / per-grid-cell product counts and per-row-block
    nnz histograms (plan/symbolic) size the exchange. Schedule choice weighs
    the per-device communication volume (hwmodel-style byte counting, mesh
    size included): the B-stationary ring pays full-B rotation plus an
    owner-binned COO exchange of the partial results, the C-stationary
    schedule pays full A replication instead, and the 2D ``'summa'``
    schedule hops A panels along grid rows and B panels along grid columns
    — ~``1/√p`` of either operand's 1D volume — plus the same COO exchange
    as ``'ring'``. The grid factorization is chosen per operand widths
    (``best_grid``); meshes with no non-degenerate factorization (2 devices,
    primes) fall back to the 1D model and are never auto-picked as 2D.
    ``schedule=`` pins it, otherwise the cheapest wins.
    """
    if schedule is not None and schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected {SCHEDULES}")
    if n_dev < 1:
        raise ValueError(f"n_dev must be >= 1, got {n_dev}")
    base = make_plan(a, b, out_cap=out_cap, backend=backend, tile=tile,
                     slack=slack)
    n_rows, n_cols, n = a.n_rows, b.n_cols, a.n_cols
    rpd = -(-n_rows // n_dev)
    block_uniq = np.asarray(jax.device_get(
        symbolic.per_block_nnz(a, b, n_dev)))
    shard_prod = np.asarray(jax.device_get(
        symbolic.per_shard_products(a, b, n_dev)))
    grid = best_grid(n_dev, a.k, b.k, allow_degenerate=True)
    pr, pc = grid
    # cap sizing covers EVERY factorization (incl. both degenerate
    # orientations), not just the chosen grid, so a plan stays never-drop
    # under dataclasses.replace(dp, schedule=..., pr=..., pc=...)
    grid_cell_max = max(
        int(np.asarray(jax.device_get(
            symbolic.per_grid_products(a, b, gr, gc))).max())
        for gr, gc in (grid_candidates(n_dev) or []) + [(1, n_dev)])
    nnz_c = int(block_uniq.sum())
    block_cap = _lane_pad(int(block_uniq.max()))
    # max over BOTH partitions (1D shards, 2D grid cells) so one plan stays
    # never-drop under any schedule it may be replaced into
    local_cap = _lane_pad(min(max(1, nnz_c),
                              max(int(shard_prod.max()), grid_cell_max)))
    # entries device d sends owner o ≤ min(d's local uniques, o's block nnz)
    bin_cap = _lane_pad(min(local_cap, block_cap))
    flops = int(shard_prod.sum())
    # per-device communication bytes (8 B/lane of val+idx operand motion,
    # 12 B/triple COO partial exchange): 'ring' rotates all of B and
    # exchanges partials, 'cstat' rotates B and replicates A, 'summa' hops
    # each operand only along its grid dimension — (pc−1)/p of A plus
    # (pr−1)/p of B — and pays the same partial exchange as 'ring'.
    rotate_b = 8.0 * n * b.k
    exchange = 12.0 * min(nnz_c, max(1, flops // n_dev))
    ring_bytes = rotate_b + exchange
    cstat_bytes = rotate_b + 8.0 * n * a.k
    degenerate = min(pr, pc) < 2
    if degenerate:
        # a 1-wide grid degenerates to a 1D schedule: model it with the 1D
        # bytes so 'auto' can never be lured by phantom column traffic
        summa_bytes = ring_bytes
    else:
        summa_bytes = (8.0 * n * (a.k * (pc - 1) + b.k * (pr - 1)) / n_dev
                       + exchange)
    est = dict(base.est)
    est.update({"ring_comm_bytes": ring_bytes,
                "cstat_comm_bytes": cstat_bytes,
                "summa_comm_bytes": summa_bytes,
                "summa_pr": float(pr), "summa_pc": float(pc),
                "nnz_c": float(nnz_c), "flops": float(flops)})
    if schedule is None:
        schedule = "cstat" if cstat_bytes < ring_bytes else "ring"
        if not degenerate and summa_bytes < est[f"{schedule}_comm_bytes"]:
            schedule = "summa"
    if _obs.is_enabled():
        _obs.instant("plan.dist_decision", schedule=schedule, n_dev=n_dev,
                     pr=pr, pc=pc, ring_comm_bytes=ring_bytes,
                     cstat_comm_bytes=cstat_bytes,
                     summa_comm_bytes=summa_bytes)
    return DistPlan(schedule=schedule, n_dev=n_dev, rows_per_dev=rpd,
                    local_cap=local_cap, bin_cap=bin_cap, block_cap=block_cap,
                    out_cap=base.out_cap, base=base, fp=base.fp,
                    pr=pr, pc=pc, est=est)


def plan_spmm_format(w, candidates=None):
    """Route a pruned dense weight to its SpMM storage format.

    The weights-side twin of ``make_plan``'s accumulation choice: inspects
    the (host-side, one-time) sparsity pattern of a pruned ``(d_in, d_out)``
    weight and returns ``("nm", (n, m))`` when some candidate N:M window
    balances every column's reduction windows — the gather-free
    kernels/nm_spmm.py fast path — or ``("ellpack", None)`` otherwise
    (structured SpMM via ``spmm_dense_ell`` / kernels/ell_spmm.py, which
    tolerates arbitrary patterns at worst-row slab width). Bit-identical
    results either way; models/sparse.SparseLinear consumes the decision.
    """
    from repro.core.nm import NM_CANDIDATES, detect_nm
    shape = detect_nm(w, NM_CANDIDATES if candidates is None else candidates)
    if _obs.is_enabled():
        _obs.instant("plan.spmm_format",
                     fmt="nm" if shape else "ellpack",
                     nm=str(shape) if shape else "")
    if shape is not None:
        return ("nm", shape)
    return ("ellpack", None)
