"""Symbolic phase, reified: the output *structure* of C = A·B as a value.

Classic two-phase SpGEMM (Deveci et al. 2018; Nagasaka et al. 2018) splits
the multiply into a **symbolic** pass — which output coordinates exist, how
many per row — and a **numeric** pass that only computes values. Production
sparse workloads (GNN layers, iterative graph algorithms, repeated sparse
layer applies at serve time) multiply the *same sparsity pattern* thousands
of times, so the symbolic result is worth keeping: this module computes it
once and packages it as an immutable :class:`SpgemmStructure` pytree that
``core.spgemm.spgemm_coo_numeric`` consumes to skip planning and coordinate
sorting entirely on every repeat call.

A structure is keyed by a cheap sparsity **fingerprint** — a hash of the
ELLPACK *index* planes plus shapes and value dtype, values excluded — so a
value-only change (new weights, new iteration of a fixed-pattern solver)
reuses the cached structure while any pattern change misses.  The companion
cache layer lives in ``plan.cache``.

Contents of a structure:

  * ``key``      — the sorted unique packed output coordinates of C
                   (``row·n_cols + col``), padded to ``out_cap`` with
                   ``KEY_INVALID``: a numeric call without ``slot`` maps
                   every product to its output slot by one
                   ``searchsorted`` against this.
  * ``row_nnz``  — per-row unique-coordinate counts of C.
  * ``seg``      — row segment boundaries (exclusive prefix sum of
                   ``row_nnz``), CSR-style ``indptr`` of the output.
  * ``nnz``      — the true unique count (becomes ``Coo.ngroups``).
  * ``plan``     — the single-device :class:`~repro.plan.planner.Plan`.
  * ``dist_plans`` — optional per-schedule
                   :class:`~repro.plan.planner.DistPlan` entries (built when
                   ``make_structure(..., n_dev=...)`` is given; any of
                   ``'ring' | 'cstat' | 'summa'`` via ``schedules=``), so the
                   distributed path reuses planning per schedule too — the
                   warm numeric path also reads the cached pick (and its
                   ``pr × pc`` grid) to choose its rotation schedule.
  * ``slot``     — each product lane's output slot, int32 of shape
                   ``(k_a·n·k_b,)`` in the lane order of ``sccp_multiply``
                   (``(k_a, n, k_b)`` flattened); invalid lanes hold the
                   dump slot ``out_cap``. ``make_structure`` finds it once,
                   with the numeric phase's own search, for every backend
                   but ``'stream'`` (whose plans exist so that no
                   per-lane plane is ever held): 4 B per product lane,
                   0.925 GB at bcsstk32's 2.31·10⁸ lanes.
  * ``a_idx``, ``b_idx`` — the index planes ``slot`` was found from. The
                   numeric phase compares the operands' planes with them
                   on the device and takes ``slot`` only where they are
                   equal; otherwise it searches ``key`` as without
                   ``slot``, so a stale structure used with
                   ``validate=False`` still poisons ``ngroups``.

``slot``, ``a_idx`` and ``b_idx`` are ``None`` on batched structures, on
stream-planned ones and on any built by the constructor without them.

Packed int32 keys require ``n_rows·n_cols < 2³¹`` — the same structural
precondition every packed-key backend carries; larger coordinate spaces stay
on the cold unpacked two-key ``'sort'`` path (``spgemm_coo`` routes there
automatically).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import EllCols, EllRows
from repro.kernels.bitonic_merge import KEY_INVALID
from repro.obs import trace as _obs

from . import symbolic
from .planner import DistPlan, Plan, SCHEDULES, make_dist_plan, make_plan


def fingerprint(a: EllRows, b: EllCols) -> str:
    """Sparsity fingerprint of an operand pair: a hash over the ELLPACK
    *index* planes, logical shapes and value dtypes — values excluded.

    Two operand pairs share a fingerprint iff they have identical sparsity
    patterns (same coordinates in the same slots) and value dtypes, which is
    exactly the condition under which a cached :class:`SpgemmStructure` (and
    any :class:`Plan`) transfers losslessly. Requires concrete operands —
    jit/vmap tracers carry no index bytes to hash.
    """
    if isinstance(a.val, jax.core.Tracer) or isinstance(b.val, jax.core.Tracer):
        raise ValueError(
            "fingerprint needs concrete operands; under jit/vmap the index "
            "planes are abstract — fingerprint outside the trace (where the "
            "structure/plan is built) and close over the result")
    h = hashlib.sha1()
    for idx, logical in ((a.idx, a.n_rows), (b.idx, b.n_cols)):
        arr = np.ascontiguousarray(np.asarray(jax.device_get(idx)))
        h.update(repr((arr.shape, int(logical), arr.dtype.str)).encode())
        h.update(arr.tobytes())
    h.update(repr((np.dtype(a.val.dtype).str,
                   np.dtype(b.val.dtype).str)).encode())
    return h.hexdigest()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SpgemmStructure:
    """Immutable symbolic-phase result of C = A·B (see module docstring).

    A registered pytree: the coordinate arrays are leaves (so a structure
    can be passed straight through ``jit``/``vmap`` boundaries), everything
    else — shapes, caps, fingerprint, plans — is static aux data, hashable
    so jitted numeric functions taking a structure argument cache compiles
    per pattern. Batched structures (from ``make_structure_batched``) carry
    a leading batch axis on every leaf, including ``nnz``.
    """

    key: jax.Array       # (out_cap,) int32 sorted unique packed coords
    row_nnz: jax.Array   # (n_rows,) int32 per-row unique counts
    seg: jax.Array       # (n_rows + 1,) int32 row segment boundaries
    nnz: jax.Array       # () int32 true unique count (→ Coo.ngroups)
    n_rows: int
    n_cols: int
    out_cap: int
    fp: Optional[str]
    plan: Plan
    dist_plans: Tuple[Tuple[str, DistPlan], ...] = ()
    slot: Optional[jax.Array] = None    # (k_a·n·k_b,) int32 lane → slot
    a_idx: Optional[jax.Array] = None   # (k_a, n) planes slot was found from
    b_idx: Optional[jax.Array] = None   # (n, k_b)

    def tree_flatten(self):
        return ((self.key, self.row_nnz, self.seg, self.nnz, self.slot,
                 self.a_idx, self.b_idx),
                (self.n_rows, self.n_cols, self.out_cap, self.fp,
                 self.plan, self.dist_plans))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        key, row_nnz, seg, nnz, slot, a_idx, b_idx = leaves
        return cls(key, row_nnz, seg, nnz, *aux, slot=slot, a_idx=a_idx,
                   b_idx=b_idx)

    @property
    def batched(self) -> bool:
        return self.key.ndim == 2

    def dist_plan(self, schedule: Optional[str] = None) -> DistPlan:
        """The cached :class:`DistPlan` for ``schedule`` (or the only one /
        the planner's pick when ``None``). Raises with a rebuild hint when
        the structure was made without ``n_dev``."""
        if not self.dist_plans:
            raise ValueError(
                "structure holds no distributed plans — rebuild with "
                "make_structure(..., n_dev=mesh.shape[axis]) (optionally "
                "schedules=('ring', 'cstat', 'summa')) to cache them")
        plans = dict(self.dist_plans)
        if schedule is None:
            return plans[self.dist_plans[0][0]]
        if schedule not in plans:
            raise ValueError(
                f"structure caches no {schedule!r} DistPlan (has "
                f"{tuple(plans)}); rebuild with make_structure(..., "
                f"schedules=({schedule!r},))")
        return plans[schedule]

    def validate(self, a: EllRows, b: EllCols) -> None:
        """Raise ``ValueError`` when ``(a, b)``'s sparsity fingerprint does
        not match the one this structure was built for (silent reuse of a
        stale structure would scatter values into the wrong coordinates).
        Tracer operands skip the content hash — cheap shape checks still
        apply."""
        if a.n_rows != self.n_rows or b.n_cols != self.n_cols:
            raise ValueError(
                f"structure built for a {self.n_rows}x{self.n_cols} output "
                f"but operands produce {a.n_rows}x{b.n_cols}")
        if (self.fp is not None
                and not isinstance(a.val, jax.core.Tracer)
                and not isinstance(b.val, jax.core.Tracer)):
            got = fingerprint(a, b)
            if got != self.fp:
                raise ValueError(
                    "stale structure: operands' sparsity fingerprint "
                    f"{got[:12]}… differs from the structure's "
                    f"{self.fp[:12]}… — the sparsity pattern changed, so "
                    "cached output coordinates no longer apply. Rebuild "
                    "with make_structure (or fetch through "
                    "plan.cache.StructureCache, which keys on the "
                    "fingerprint and re-derives automatically)")


def _check_packable(n_rows: int, n_cols: int) -> None:
    if n_rows * n_cols >= jnp.iinfo(jnp.int32).max:
        raise ValueError(
            f"coordinate space {n_rows}x{n_cols} exceeds packed int32 keys; "
            "the structure/numeric fast path cannot span it — use the cold "
            "spgemm_coo path (its unpacked two-key 'sort' route handles "
            "such spaces automatically)")


@functools.partial(jax.jit, static_argnames=("n_rows", "n_cols", "out_cap"))
def _structure_arrays(a_idx: jax.Array, b_idx: jax.Array, *, n_rows: int,
                      n_cols: int, out_cap: int):
    """Coordinate-only symbolic pass → (key, row_nnz, seg, nnz).

    One packed-key sort of the broadcast coordinate planes (no value
    multiply, no value sort — the same pass ``symbolic.exact_nnz_rows``
    runs, extended to *keep* the sorted unique keys), then a cumsum scatter
    compacts the run heads into ``out_cap`` slots.
    """
    k_a, n = a_idx.shape
    k_b = b_idx.shape[1]
    row = jnp.broadcast_to(a_idx[:, :, None], (k_a, n, k_b)).reshape(-1)
    col = jnp.broadcast_to(b_idx[None, :, :], (k_a, n, k_b)).reshape(-1)
    ok = jnp.logical_and(row >= 0, col >= 0)
    key = jnp.where(ok, row * n_cols + col, KEY_INVALID).astype(jnp.int32)
    key = jax.lax.sort(key, dimension=0, is_stable=False)
    head = (key != jnp.roll(key, 1)).at[0].set(True)
    head = jnp.logical_and(head, key != KEY_INVALID)
    nnz = jnp.sum(head).astype(jnp.int32)
    dst = jnp.minimum(jnp.where(head, jnp.cumsum(head) - 1, out_cap), out_cap)
    uniq = (jnp.full((out_cap + 1,), KEY_INVALID, jnp.int32)
            .at[dst].set(jnp.where(head, key, KEY_INVALID)))[:out_cap]
    rid = jnp.where(head, key // n_cols, n_rows)
    row_nnz = jax.ops.segment_sum(head.astype(jnp.int32),
                                  jnp.minimum(rid, n_rows),
                                  num_segments=n_rows + 1)[:n_rows]
    seg = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(row_nnz).astype(jnp.int32)])
    return uniq, row_nnz, seg, nnz


def make_structure(a: EllRows, b: EllCols, *, out_cap: Optional[int] = None,
                   backend: Optional[str] = None, tile: int = 4096,
                   slack: float = 1.0, n_dev: Optional[int] = None,
                   schedules: Optional[Tuple[str, ...]] = None,
                   plan: Optional[Plan] = None) -> SpgemmStructure:
    """Run the symbolic phase once on concrete operands → ``SpgemmStructure``.

    Computes C's sorted unique output coordinates, per-row nnz and segment
    boundaries, plus a :class:`Plan` (``plan=`` supplies a prebuilt one,
    e.g. an autotuned winner; otherwise ``make_plan`` runs with the given
    ``out_cap``/``backend``/``tile``/``slack``). With ``n_dev`` set, a
    :class:`DistPlan` is additionally built and cached per entry of
    ``schedules`` (default: the planner's preferred schedule only), so
    distributed repeat calls skip ``make_dist_plan`` too.

    The result is keyed by ``fingerprint(a, b)`` and is valid for any
    operand pair with the identical sparsity pattern regardless of values.
    """
    _check_packable(a.n_rows, b.n_cols)
    fp = fingerprint(a, b)
    if plan is None:
        plan = make_plan(a, b, out_cap=out_cap, backend=backend, tile=tile,
                         slack=slack)
    out_cap = plan.out_cap
    with _obs.span("structure.build", fp=fp[:12], out_cap=out_cap,
                   backend=plan.backend):
        key, row_nnz, seg, nnz = _structure_arrays(
            a.idx, b.idx, n_rows=a.n_rows, n_cols=b.n_cols, out_cap=out_cap)
        _obs.sync(key)
    if int(jax.device_get(nnz)) > out_cap:
        raise ValueError(
            f"out_cap={out_cap} smaller than nnz(C)={int(jax.device_get(nnz))}"
            " — a structure must hold every output coordinate (pass a larger"
            " out_cap or let make_plan size it)")
    dist_plans: Tuple[Tuple[str, DistPlan], ...] = ()
    if n_dev is not None:
        if schedules is None:
            dp = make_dist_plan(a, b, n_dev=n_dev, out_cap=out_cap,
                                backend=plan.backend, tile=tile, slack=slack)
            dist_plans = ((dp.schedule, dp),)
        else:
            for s in schedules:
                if s not in SCHEDULES:
                    raise ValueError(
                        f"unknown schedule {s!r}; expected {SCHEDULES}")
            dist_plans = tuple(
                (s, make_dist_plan(a, b, n_dev=n_dev, schedule=s,
                                   out_cap=out_cap, backend=plan.backend,
                                   tile=tile, slack=slack))
                for s in schedules)
    return with_slots(
        SpgemmStructure(key=key, row_nnz=row_nnz, seg=seg, nnz=nnz,
                        n_rows=a.n_rows, n_cols=b.n_cols, out_cap=out_cap,
                        fp=fp, plan=plan, dist_plans=dist_plans), a, b)


def with_slots(st: SpgemmStructure, a: EllRows,
               b: EllCols) -> SpgemmStructure:
    """``st`` with each product lane's output slot for ``(a, b)``'s index
    planes, and the planes (module docstring); ``st`` unchanged where its
    plan streams. Found once by the numeric phase's own search, so the
    cached slots are the ones every call would find."""
    if st.plan is not None and st.plan.backend == "stream":
        return st
    from repro.core.spgemm import lane_slots
    with _obs.span("structure.slots", lanes=int(a.idx.size * b.idx.shape[1])):
        slot = _obs.sync(lane_slots(a.idx, b.idx, st.key, n_cols=st.n_cols,
                                    out_cap=st.out_cap))
    return dataclasses.replace(st, slot=slot, a_idx=a.idx, b_idx=b.idx)


def make_structure_batched(a: EllRows, b: EllCols, *,
                           out_cap: Optional[int] = None,
                           backend: Optional[str] = None, tile: int = 4096,
                           slack: float = 1.0) -> SpgemmStructure:
    """Per-batch-element symbolic phase over a leading batch axis.

    Every element gets its own sorted-key plane (patterns may differ across
    the batch); ``out_cap`` and the plan are shared — sized on the widest
    element so no element overflows. Leaves carry the batch axis first,
    matching ``spgemm_coo_batched``'s ``Coo`` layout; consume with
    ``spgemm_coo_numeric_batched``.
    """
    if a.val.ndim != 3 or b.val.ndim != 3:
        raise ValueError("batched operands need a leading batch axis on all "
                         f"ELLPACK planes; got A {a.val.ndim}D, "
                         f"B {b.val.ndim}D")
    _check_packable(a.n_rows, b.n_cols)
    bsz = a.val.shape[0]
    slices_a = [EllRows(a.val[i], a.idx[i], a.n_rows) for i in range(bsz)]
    slices_b = [EllCols(b.val[i], b.idx[i], b.n_cols) for i in range(bsz)]
    fp = fingerprint(a, b)
    if out_cap is None:
        caps = [symbolic.out_cap_auto(ai, bi, slack=slack)
                for ai, bi in zip(slices_a, slices_b)]
        out_cap = max(caps)
    plan = make_plan(slices_a[0], slices_b[0], out_cap=out_cap,
                     backend=backend, tile=tile, slack=slack)
    parts = [_structure_arrays(ai.idx, bi.idx, n_rows=a.n_rows,
                               n_cols=b.n_cols, out_cap=out_cap)
             for ai, bi in zip(slices_a, slices_b)]
    key, row_nnz, seg, nnz = (jnp.stack([p[i] for p in parts])
                              for i in range(4))
    if int(jax.device_get(nnz).max()) > out_cap:
        raise ValueError(
            f"out_cap={out_cap} smaller than the widest batch element's "
            f"nnz(C)={int(jax.device_get(nnz).max())}")
    return SpgemmStructure(key=key, row_nnz=row_nnz, seg=seg, nnz=nnz,
                           n_rows=a.n_rows, n_cols=b.n_cols, out_cap=out_cap,
                           fp=fp, plan=plan)
