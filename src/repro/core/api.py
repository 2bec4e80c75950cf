"""The unified SpGEMM front door: one ``spgemm()`` for every variant.

The SpGEMM surface grew to ~12 entry points across ``core/spgemm.py``,
``core/streaming.py`` and ``core/distributed.py`` (cold / numeric / batched
/ streaming / sharded crosses). They all remain as thin, stable wrappers,
but ``repro.spgemm(a, b, ...)`` is the preferred spelling: it dispatches on
*what you hand it* — a prebuilt structure routes to the warm numeric phase,
a mesh+axis to the sharded path, 3-D operand planes to the vmapped batched
variants — so call sites never hard-code a variant name.

Auto-select semantics, in one place (every wrapper follows these rules):

``out_cap``
    Static output capacity. ``"auto"`` (default everywhere, including the
    stream path) runs the symbolic phase via ``plan.make_plan`` on concrete
    operands; under jit/vmap pass an int or a prebuilt ``plan=``.
``accumulator``
    Accumulation backend: ``'sort' | 'tiled' | 'bucket' | 'hash' | 'stream'
    | 'search'``. ``None`` defaults to ``'sort'``; only an explicit
    ``'auto'`` (or a ``plan=`` / ``structure=``) opts into the planner's
    cost-model choice. ``'stream'`` is the only backend that never
    materializes the product stream.
``schedule``
    Distributed schedules (mesh paths only): ``'ring'`` (B-stationary) |
    ``'cstat'`` (C-stationary) | ``'summa'`` (communication-avoiding 2D
    grid). ``"auto"`` lets ``plan.make_dist_plan``
    weigh the per-device communication volume (including the 2D grid's).
``overlap``
    Mesh paths only: ``True`` (default) double-buffers operand rotation —
    each stage's ``ppermute`` prefetch is issued before the current stage's
    accumulation and rejoined with ``jax.lax.optimization_barrier``, hiding
    communication behind compute. Bit-identical either way.
``interpret`` / kernel mode
    Pallas kernels resolve via ``kernels.platform.resolve_mode``:
    ``None`` → compiled on TPU, XLA realization elsewhere; ``True`` forces
    the interpreter (debug), ``False`` forces compiled Pallas.
``batched``
    ``"auto"`` (default) detects a leading batch axis on the ELLPACK value
    planes (``a.val.ndim == 3``); ``True``/``False`` force it.

Warm-path contract: pass ``structure=`` (from ``plan.make_structure`` /
``plan.cache.StructureCache.get``) and only the numeric phase runs —
coordinates are never re-sorted, and misses against the frozen pattern
poison ``ngroups`` exactly like accumulator overflow (``check=True`` or
``core.check_no_overflow`` to raise).
"""
from __future__ import annotations

from typing import Optional

from repro.obs import trace as _obs

from .formats import Coo, EllCols, EllRows


def spgemm(a: EllRows, b: EllCols, *, structure=None, mesh=None,
           axis: Optional[str] = None, batched="auto", out_cap="auto",
           accumulator: Optional[str] = None, schedule: str = "auto",
           tile: Optional[int] = None, plan=None, dist_plan=None,
           overlap: bool = True, stream_cap: Optional[int] = None,
           group: Optional[int] = None, check: bool = False,
           validate: bool = True) -> Coo:
    """C = A·B as sorted COO — dispatches to the right SpGEMM variant.

    Routing (first match wins):

    * ``mesh``/``axis`` set → the sharded paths (``core.distributed``):
      with ``structure`` the device-local numeric phase
      (``spgemm_coo_sharded_numeric``; batched structures route through
      ``spgemm_coo_sharded`` with the structure's cached dist plan),
      otherwise the cold ``spgemm_coo_sharded`` (``schedule``/``dist_plan``
      select the exchange schedule).
    * ``structure`` set → warm numeric phase (``spgemm_coo_numeric`` /
      ``_numeric_batched``); stream-planned structures take the slab-scan
      numeric realization automatically.
    * otherwise → cold single-device path (``spgemm_coo`` /
      ``spgemm_coo_batched``); ``accumulator='stream'`` with explicit
      ``stream_cap``/``group`` routes through ``spgemm_coo_stream``.

    Kwargs not consumed by the selected variant (e.g. ``schedule`` without a
    mesh) are ignored only when they hold their defaults; see the module
    docstring for the shared auto-select semantics.

    Instrumented (repro.obs): one ``spgemm.call`` root span per call, with
    the product-stream ``lanes`` and, for a concrete result, its ``nnz``.
    """
    kw = dict(structure=structure, mesh=mesh, axis=axis, batched=batched,
              out_cap=out_cap, accumulator=accumulator, schedule=schedule,
              tile=tile, plan=plan, dist_plan=dist_plan, overlap=overlap,
              stream_cap=stream_cap, group=group, check=check,
              validate=validate)
    if not _obs.is_enabled():
        return _route(a, b, **kw)
    import jax
    import numpy as np
    with _obs.call("spgemm.call",
                   lanes=int(a.val.size * b.val.shape[-1])) as sp:
        out = _route(a, b, **kw)
        if out.ngroups is not None and not isinstance(out.ngroups,
                                                      jax.core.Tracer):
            sp.set(nnz=int(np.sum(jax.device_get(out.ngroups))))
    return out


def _route(a: EllRows, b: EllCols, *, structure, mesh, axis, batched,
           out_cap, accumulator, schedule, tile, plan, dist_plan, overlap,
           stream_cap, group, check, validate) -> Coo:
    if axis is not None and mesh is None:
        raise ValueError("axis= requires mesh= (a jax.sharding.Mesh)")
    if mesh is not None and axis is None:
        raise ValueError("mesh= requires axis= (the mesh axis name)")
    if batched == "auto":
        is_batched = a.val.ndim == 3
    else:
        is_batched = bool(batched)
        if is_batched and a.val.ndim != 3:
            raise ValueError("batched=True needs 3-D ELLPACK planes "
                             f"(got a.val.ndim={a.val.ndim})")

    if mesh is not None:
        from .distributed import (spgemm_coo_sharded,
                                  spgemm_coo_sharded_batched,
                                  spgemm_coo_sharded_numeric)
        if structure is not None and not is_batched:
            return spgemm_coo_sharded_numeric(a, b, mesh, axis, structure,
                                              schedule=schedule,
                                              overlap=overlap,
                                              check=check, validate=validate)
        if is_batched and structure is None and dist_plan is not None:
            return spgemm_coo_sharded_batched(a, b, mesh, axis,
                                              dist_plan=dist_plan,
                                              schedule=schedule,
                                              overlap=overlap,
                                              check=check)
        return spgemm_coo_sharded(a, b, mesh, axis, out_cap,
                                  accumulator=accumulator or "auto",
                                  schedule=schedule, dist_plan=dist_plan,
                                  structure=structure, overlap=overlap,
                                  check=check)

    if structure is not None:
        from .spgemm import spgemm_coo_numeric, spgemm_coo_numeric_batched
        if is_batched:
            return spgemm_coo_numeric_batched(a, b, structure, check=check,
                                              validate=validate)
        return spgemm_coo_numeric(a, b, structure, check=check,
                                  validate=validate)

    if accumulator == "stream" and (stream_cap is not None
                                    or group is not None):
        from .streaming import spgemm_coo_stream
        if is_batched:
            raise ValueError("batched stream SpGEMM: pass a plan= built "
                             "with backend='stream' instead of explicit "
                             "stream_cap/group")
        return spgemm_coo_stream(a, b, out_cap, stream_cap=stream_cap,
                                 group=group)

    from .spgemm import spgemm_coo, spgemm_coo_batched
    fn = spgemm_coo_batched if is_batched else spgemm_coo
    return fn(a, b, out_cap, accumulator=accumulator, tile=tile,
              check=check, plan=plan)
