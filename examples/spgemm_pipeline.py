"""End-to-end driver of the paper's kind: the A·Aᵀ SpGEMM suite.

Runs the full SPLIM pipeline (hybrid split → SCCP multiply → in-situ-search
merge) over scaled-down versions of the 16 Table-I matrices, validates every
result against scipy, and reports modeled PUM latency/energy + measured
wall time. The ``plan`` column shows what the adaptive planner (repro.plan)
would run for the sorted-COO output: its chosen accumulation backend and
the symbolically derived ``out_cap`` — the planned path is validated
against the oracle as well.

    PYTHONPATH=src python examples/spgemm_pipeline.py [--scale 64]
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import scipy.sparse as sp

from benchmarks.common import TABLE1
from repro import (ell_cols_from_dense, ell_rows_from_dense, hwmodel, hybrid,
                   make_plan, spgemm)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=256,
                    help="downscale factor for executable validation")
    args = ap.parse_args()

    print(f"{'matrix':>18s} {'dim':>6s} {'nnz':>8s} {'k':>4s} "
          f"{'wall_ms':>8s} {'model_us':>9s} {'model_uJ':>9s} "
          f"{'plan':>14s}  ok")
    for mid, name, dim, nnz, nnz_av, sigma in TABLE1:
        n = max(64, dim // args.scale)
        density = min(0.5, nnz / dim / dim * args.scale)
        rng = np.random.default_rng(mid)
        a = ((rng.random((n, n)) < density)
             * rng.standard_normal((n, n))).astype(np.float32)
        at = a.T.copy()
        k = hybrid.ell_width_rule((a != 0).sum(0))
        ha = hybrid.split_rows_hybrid(jnp.array(a), k, coo_cap=4 * n)
        hb = hybrid.split_cols_hybrid(jnp.array(at), k, coo_cap=4 * n)
        f = jax.jit(hybrid.hybrid_spgemm_dense)
        c = np.asarray(f(ha, hb))           # compile
        t0 = time.perf_counter()
        c = np.asarray(f(ha, hb))
        wall = (time.perf_counter() - t0) * 1e3
        ref = a @ at
        ok = np.allclose(c, ref, atol=1e-2)
        counts = (a != 0).sum(0)
        s = hwmodel.MatrixStats(
            n=n, nnz_a=int(counts.sum()), nnz_b=int(counts.sum()),
            k_a=k, k_b=k,
            valid_products=int((counts.astype(np.int64) ** 2).sum()),
            nnz_c=int((np.abs(ref) > 1e-7).sum()),
            sigma=float(counts.std()))
        lat = hwmodel.splim_latency(s)["total"] * 1e6
        en = hwmodel.splim_energy(s)["total"] * 1e6
        # Adaptive planner on the lossless ELL pair: symbolic out_cap +
        # backend choice, validated on the planned sorted-COO path.
        ka = max(1, int((a != 0).sum(0).max()))
        kb = max(1, int((at != 0).sum(1).max()))
        ea = ell_rows_from_dense(jnp.array(a), ka)
        eb = ell_cols_from_dense(jnp.array(at), kb)
        plan = make_plan(ea, eb)
        coo = spgemm(ea, eb, out_cap="auto", accumulator="auto",
                     plan=plan, check=True)
        ok_plan = np.allclose(np.asarray(coo.to_dense()), ref, atol=1e-2)
        print(f"{name:>18s} {n:6d} {s.nnz_a:8d} {k:4d} "
              f"{wall:8.1f} {lat:9.2f} {en:9.2f} "
              f"{plan.backend:>8s}/{plan.out_cap:<5d}  "
              f"{'✓' if ok and ok_plan else '✗'}")
        assert ok and ok_plan, name
    print("\nall 16 validated against scipy/numpy oracle")

    # Distributed: the sparse-native ring engine, when this host has a mesh
    # (fake one with XLA_FLAGS=--xla_force_host_platform_device_count=8).
    n_dev = len(jax.devices())
    if n_dev > 1:
        from repro import make_dist_plan, make_mesh
        rng = np.random.default_rng(0)
        n = 128
        a = ((rng.random((n, n)) < 0.05)
             * rng.standard_normal((n, n))).astype(np.float32)
        at = a.T.copy()
        ea = ell_rows_from_dense(jnp.array(a), max(1, int((a != 0).sum(0).max())))
        eb = ell_cols_from_dense(jnp.array(at), max(1, int((at != 0).sum(1).max())))
        mesh = make_mesh((n_dev,), ("ring",))
        dp = make_dist_plan(ea, eb, n_dev=n_dev)
        coo = spgemm(ea, eb, mesh=mesh, axis="ring", dist_plan=dp, check=True)
        ok = np.allclose(np.asarray(coo.to_dense()), a @ at, atol=1e-2)
        print(f"distributed A·Aᵀ on {n_dev} devices "
              f"({dp.schedule} schedule, {dp.base.backend} accumulator): "
              f"{'✓' if ok else '✗'}")
        assert ok


if __name__ == "__main__":
    main()
