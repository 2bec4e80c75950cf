"""Fingerprint-keyed structure cache: first call pays, the fleet rides free.

:class:`StructureCache` fronts ``plan.structure.make_structure`` with an
in-process LRU keyed by the operands' sparsity fingerprint (index planes +
shapes + value dtype, values excluded — see ``plan.structure.fingerprint``),
so repeated multiplies over the same pattern (GNN layers, iterative solvers,
serve-time sparse FFN applies) run the symbolic phase once and the numeric
phase (``core.spgemm.spgemm_coo_numeric``) forever after.

Three optional layers on top of the LRU:

  * **Disk persistence** (``cache_dir=``): every built structure is written
    as ``<fingerprint>.npz`` (coordinate arrays + a JSON metadata blob
    carrying the Plan/DistPlan statics), so a fresh process — or a fleet of
    them sharing a filesystem — warm-starts without re-running the symbolic
    phase. Writes are atomic (tmp + rename); a corrupt or stale file is
    treated as a miss, never an error. The file holds no per-lane slots:
    a structure read from it finds them again from the operands, as
    ``make_structure`` does.
  * **Measured autotune** (``autotune=True``): on first build the planner's
    cost-model backend choice is validated against short timed probes of
    every candidate backend on the real operands; the measured winner's plan
    is cached (probe timings recorded in ``plan.est['autotune_us']``).
  * **Stats** (:meth:`StructureCache.stats`): hit / miss / eviction /
    disk-hit / autotune counters for capacity planning and tests.

Memory: a cached structure that is not stream-planned holds each product
lane's output slot, 4 B per product lane (0.925 GB at bcsstk32's
2.31·10⁸ lanes), besides its output coordinates, and keeps the operands'
index planes it was built from alive.

Thread-safe: lookups and LRU mutation hold an internal lock; the expensive
build runs outside it (concurrent first calls on the same pattern may both
build — idempotent, last insert wins).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.formats import EllCols, EllRows
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs

from .planner import BACKENDS, DistPlan, Plan
from .structure import (SpgemmStructure, fingerprint, make_structure,
                        with_slots)

_FORMAT_VERSION = 1


def _plan_to_dict(plan: Plan) -> dict:
    d = {f.name: getattr(plan, f.name) for f in dataclasses.fields(plan)}
    d.pop("stats", None)  # MatrixStats is derivable, not worth serializing
    try:
        json.dumps(d.get("est"))
    except (TypeError, ValueError):
        d["est"] = {}
    return d


def _dist_plan_to_dict(dp: DistPlan) -> dict:
    d = {f.name: getattr(dp, f.name) for f in dataclasses.fields(dp)}
    d["base"] = _plan_to_dict(dp.base)
    try:
        json.dumps(d.get("est"))
    except (TypeError, ValueError):
        d["est"] = {}
    return d


def _plan_from_dict(d: dict) -> Plan:
    return Plan(**d)


def _dist_plan_from_dict(d: dict) -> DistPlan:
    d = dict(d)
    d["base"] = _plan_from_dict(d["base"])
    return DistPlan(**d)


class StructureCache:
    """LRU cache of :class:`~repro.plan.structure.SpgemmStructure` entries
    keyed by sparsity fingerprint (see module docstring).

    ``capacity`` bounds the in-memory entry count (least-recently-used
    evicted first; disk copies, if enabled, survive eviction).
    ``cache_dir`` enables on-disk persistence. ``autotune=True`` replaces
    the cost model's backend choice with a measured winner on first build;
    ``autotune_backends`` restricts the probed candidates and
    ``probe_iters`` sets the timed repetitions per candidate.
    """

    def __init__(self, capacity: int = 64, cache_dir: Optional[str] = None,
                 autotune: bool = False,
                 autotune_backends: Optional[Tuple[str, ...]] = None,
                 probe_iters: int = 3):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.cache_dir = cache_dir
        self.autotune = autotune
        self.autotune_backends = tuple(autotune_backends or BACKENDS)
        self.probe_iters = probe_iters
        self._lock = threading.RLock()
        self._entries: "OrderedDict[str, SpgemmStructure]" = OrderedDict()
        self._stats: Dict[str, int] = dict(hits=0, misses=0, evictions=0,
                                           disk_hits=0, autotuned=0)
        if cache_dir is not None:
            os.makedirs(cache_dir, exist_ok=True)

    # ------------------------------------------------------------- lookup

    def get(self, a: EllRows, b: EllCols, **make_kwargs) -> SpgemmStructure:
        """The structure for ``(a, b)``'s sparsity pattern — from memory,
        then disk, then a fresh symbolic-phase build (optionally autotuned).
        ``make_kwargs`` forward to ``make_structure`` on a build (``out_cap``,
        ``backend``, ``n_dev``, ``schedules``, ...); they do not affect the
        cache key, so callers sharing a cache should agree on them."""
        fp = fingerprint(a, b)
        with self._lock:
            st = self._entries.get(fp)
            if st is not None:
                self._entries.move_to_end(fp)
                self._stats["hits"] += 1
                hit = True
            else:
                hit = False
        if hit:
            _obs_metrics.inc("structure_cache.hits")
            return st
        if self.cache_dir is not None:
            st = self._load_disk(fp)
            if st is not None:
                st = with_slots(st, a, b)
                with self._lock:
                    self._stats["disk_hits"] += 1
                _obs_metrics.inc("structure_cache.disk_hits")
                self._insert(fp, st, write_disk=False)
                return st
        with self._lock:
            self._stats["misses"] += 1
        _obs_metrics.inc("structure_cache.misses")
        if self.autotune:
            make_kwargs = dict(make_kwargs)
            make_kwargs["plan"] = self._autotune_plan(a, b, make_kwargs)
        with _obs.span("structure_cache.build", fp=fp[:12]):
            st = make_structure(a, b, **make_kwargs)
        self._insert(fp, st, write_disk=True)
        return st

    def stats(self) -> Dict[str, int]:
        """Counters snapshot: hits, misses, evictions, disk_hits, autotuned,
        plus the current ``size``. Cheap under contention: only the raw
        counter reads happen under the LRU lock; the returned dict is built
        outside it."""
        with self._lock:
            items = tuple(self._stats.items())
            size = len(self._entries)
        out = dict(items)
        out["size"] = size
        return out

    def clear(self) -> None:
        """Drop every in-memory entry (disk copies are kept) and zero the
        counters."""
        with self._lock:
            self._entries.clear()
            for k in self._stats:
                self._stats[k] = 0

    # ------------------------------------------------------------ internals

    def _insert(self, fp: str, st: SpgemmStructure, *,
                write_disk: bool) -> None:
        with self._lock:
            self._entries[fp] = st
            self._entries.move_to_end(fp)
            evicted = 0
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self._stats["evictions"] += 1
                evicted += 1
        if evicted:
            _obs_metrics.inc("structure_cache.evictions", evicted)
        if write_disk and self.cache_dir is not None:
            self._save_disk(fp, st)

    def _autotune_plan(self, a: EllRows, b: EllCols,
                       make_kwargs: dict) -> Plan:
        """Short timed probes of each candidate backend on the real
        operands; the measured winner's plan is returned with per-backend
        timings recorded in ``est['autotune_us']``."""
        from repro.core.spgemm import spgemm_coo
        from .planner import make_plan
        kw = dict(out_cap=make_kwargs.get("out_cap"),
                  tile=make_kwargs.get("tile", 4096),
                  slack=make_kwargs.get("slack", 1.0))
        if kw["tile"] is None:
            kw["tile"] = 4096
        times: Dict[str, float] = {}
        plans: Dict[str, Plan] = {}
        packable = a.n_rows * b.n_cols < 2 ** 31 - 1
        for bk in self.autotune_backends:
            if bk != "sort" and not packable:
                continue  # packed-key backend cannot span this space
            p = make_plan(a, b, backend=bk, **kw)
            run = lambda: jax.block_until_ready(
                spgemm_coo(a, b, plan=p).val)
            run()  # compile + warm
            t0 = time.perf_counter()
            for _ in range(self.probe_iters):
                run()
            times[bk] = (time.perf_counter() - t0) / self.probe_iters
            plans[bk] = p
        if not times:
            return make_plan(a, b, **kw)
        winner = min(times, key=times.get)
        with self._lock:
            self._stats["autotuned"] += 1
        _obs_metrics.inc("structure_cache.autotuned")
        est = dict(plans[winner].est)
        est["autotune_us"] = {k: v * 1e6 for k, v in times.items()}
        return dataclasses.replace(plans[winner], est=est)

    # ----------------------------------------------------------------- disk

    def _path(self, fp: str) -> str:
        return os.path.join(self.cache_dir, f"{fp}.npz")

    def _save_disk(self, fp: str, st: SpgemmStructure) -> None:
        meta = dict(version=_FORMAT_VERSION, n_rows=st.n_rows,
                    n_cols=st.n_cols, out_cap=st.out_cap, fp=st.fp,
                    plan=_plan_to_dict(st.plan),
                    dist_plans=[[s, _dist_plan_to_dict(dp)]
                                for s, dp in st.dist_plans])
        path = self._path(fp)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, key=np.asarray(jax.device_get(st.key)),
                         row_nnz=np.asarray(jax.device_get(st.row_nnz)),
                         seg=np.asarray(jax.device_get(st.seg)),
                         nnz=np.asarray(jax.device_get(st.nnz)),
                         meta=np.frombuffer(json.dumps(meta).encode(),
                                            dtype=np.uint8))
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def _load_disk(self, fp: str) -> Optional[SpgemmStructure]:
        path = self._path(fp)
        if not os.path.exists(path):
            return None
        try:
            with np.load(path) as z:
                meta = json.loads(bytes(z["meta"]).decode())
                if meta.get("version") != _FORMAT_VERSION \
                        or meta.get("fp") != fp:
                    return None
                import jax.numpy as jnp
                return SpgemmStructure(
                    key=jnp.asarray(z["key"]),
                    row_nnz=jnp.asarray(z["row_nnz"]),
                    seg=jnp.asarray(z["seg"]),
                    nnz=jnp.asarray(z["nnz"]),
                    n_rows=meta["n_rows"], n_cols=meta["n_cols"],
                    out_cap=meta["out_cap"], fp=meta["fp"],
                    plan=_plan_from_dict(meta["plan"]),
                    dist_plans=tuple(
                        (s, _dist_plan_from_dict(d))
                        for s, d in meta.get("dist_plans", [])))
        except Exception:  # corrupt / partial / foreign file → plain miss
            return None
