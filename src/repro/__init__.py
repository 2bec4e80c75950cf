"""SPLIM reproduction — structured in-situ SpGEMM, planned and served.

One import surface for the whole stack (lazily resolved, so ``import
repro`` stays free until a name is touched — the model zoo and serving
engine never tax a kernels-only user):

    import repro
    c = repro.spgemm(a, b)                      # unified SpGEMM front door
    st = repro.make_structure(a, b)             # two-phase symbolic step
    c = repro.spgemm(a, b, structure=st)        # warm numeric path
    layer = repro.SparseLinear(w, sparsity=0.9) # N:M / ELLPACK routed
    eng = repro.ServingEngine(model, params, repro.ServeConfig())

``repro.spgemm`` (core/api.py) documents the shared auto-select semantics;
the legacy per-variant entry points under ``repro.core`` remain stable thin
wrappers.
"""
from __future__ import annotations

import importlib

# name -> module that defines it (resolved lazily, PEP 562)
_NAMES = {
    # unified front door + planning
    "spgemm": "repro.core.api",
    "spgemm_dense": "repro.core.spgemm",
    "make_plan": "repro.plan",
    "make_dist_plan": "repro.plan",
    "make_structure": "repro.plan",
    "make_structure_batched": "repro.plan",
    "make_mesh": "repro.launch.mesh",
    "plan_spmm_format": "repro.plan",
    "fingerprint": "repro.plan",
    "Plan": "repro.plan",
    "DistPlan": "repro.plan",
    "SpgemmStructure": "repro.plan",
    "StructureCache": "repro.plan",
    # formats + converters + overflow contract
    "Coo": "repro.core.formats",
    "EllCols": "repro.core.formats",
    "EllRows": "repro.core.formats",
    "coo_from_dense": "repro.core.formats",
    "ell_cols_from_dense": "repro.core.formats",
    "ell_rows_from_dense": "repro.core.formats",
    "AccumulatorOverflow": "repro.core.accumulate",
    "check_no_overflow": "repro.core.accumulate",
    "count_products": "repro.core.sccp",
    # N:M fast path
    "NmWeights": "repro.core.nm",
    "nm_from_dense": "repro.core.nm",
    "detect_nm": "repro.core.nm",
    "nm_spmm": "repro.kernels.nm_spmm",
    # models + serving
    "SparseLinear": "repro.models.sparse",
    "SparseMLP": "repro.models.ffn",
    "magnitude_prune": "repro.models.sparse",
    "magnitude_prune_nm": "repro.models.sparse",
    "ServeConfig": "repro.serve.engine",
    "ServingEngine": "repro.serve.engine",
    "SparseGemmBatcher": "repro.serve.engine",
}

# submodules reachable as repro.<name> without deep-importing repro.core.*
_MODULES = {
    "core": "repro.core",
    "hwmodel": "repro.core.hwmodel",
    "hybrid": "repro.core.hybrid",
    "sccp": "repro.core.sccp",
    "kernels": "repro.kernels",
    "plan": "repro.plan",
    "models": "repro.models",
    "serve": "repro.serve",
    "configs": "repro.configs",
    "obs": "repro.obs",
}

__all__ = sorted(set(_NAMES) | set(_MODULES))


def __getattr__(name: str):
    if name in _NAMES:
        return getattr(importlib.import_module(_NAMES[name]), name)
    if name in _MODULES:
        return importlib.import_module(_MODULES[name])
    raise AttributeError(f"module 'repro' has no attribute {name!r}")


def __dir__():
    return __all__
