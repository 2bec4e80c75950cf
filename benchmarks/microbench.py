"""Measured (wall-clock) benchmarks of the executable JAX/Pallas pieces.

These complement the modeled paper figures with real timings of our own
implementation on this host: SPLIM SpGEMM vs scipy vs dense matmul, the
Pallas kernels in interpret mode, MoE dispatch variants, and a smoke-scale
LM train step.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, float]


def _timeit(fn, n: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6   # µs


def spgemm_micro() -> List[Row]:
    import scipy.sparse as sp
    from repro.core import (ell_cols_from_dense, ell_rows_from_dense,
                            spgemm_coo, spgemm_dense)
    rows = []
    rng = np.random.default_rng(0)
    for n, dens in [(256, 0.05), (1024, 0.01), (2048, 0.005)]:
        a_s = sp.random(n, n, dens, random_state=1, format="csr", dtype=np.float32)
        b_s = sp.random(n, n, dens, random_state=2, format="csr", dtype=np.float32)
        A = jnp.asarray(a_s.toarray())
        B = jnp.asarray(b_s.toarray())
        k = max(1, int(np.diff(a_s.tocsc().indptr).max()))
        kb = max(1, int(np.diff(b_s.indptr).max()))
        a = ell_rows_from_dense(A, k)
        b = ell_cols_from_dense(B, kb)
        f_splim = jax.jit(spgemm_dense)
        f_splim(a, b).block_until_ready()
        t_splim = _timeit(lambda: f_splim(a, b).block_until_ready())
        t_scipy = _timeit(lambda: a_s @ b_s)
        f_dense = jax.jit(lambda x, y: x @ y)
        f_dense(A, B).block_until_ready()
        t_dense = _timeit(lambda: f_dense(A, B).block_until_ready())
        rows.append((f"micro/spgemm_splim/n{n}", round(t_splim, 1),
                     round(t_dense / t_splim, 3)))
        rows.append((f"micro/spgemm_scipy/n{n}", round(t_scipy, 1), 0.0))
    return rows


def kernels_micro() -> List[Row]:
    from repro.kernels import ops
    rows = []
    rng = np.random.default_rng(1)
    ka, n, kb = 8, 2048, 8
    a_val = jnp.asarray(rng.standard_normal((ka, n)), jnp.float32)
    a_idx = jnp.asarray(rng.integers(0, n, (ka, n)), jnp.int32)
    b_val = jnp.asarray(rng.standard_normal((n, kb)), jnp.float32)
    b_idx = jnp.asarray(rng.integers(0, n, (n, kb)), jnp.int32)
    t = _timeit(lambda: jax.block_until_ready(
        ops.sccp_multiply(a_val, a_idx, b_val, b_idx)), n=3, warmup=1)
    rows.append(("micro/pallas_sccp_interp/2048", round(t, 1), ka * n * kb))
    key = jnp.asarray(rng.integers(0, 1 << 20, 4096), jnp.int32)
    val = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    from repro.kernels import platform
    from repro.kernels.bitonic_merge import bitonic_merge_pallas
    t = _timeit(lambda: jax.block_until_ready(
        bitonic_merge_pallas(key, val, interpret=not platform.on_tpu())),
        n=3, warmup=1)
    rows.append(("micro/pallas_bitonic_interp/4096", round(t, 1), 4096))
    x = jnp.asarray(rng.standard_normal((n, 128)), jnp.float32)
    t = _timeit(lambda: jax.block_until_ready(
        ops.ell_spmm(a_val, a_idx, x, 1024)), n=3, warmup=1)
    rows.append(("micro/pallas_ellspmm_interp/2048x128", round(t, 1), 0.0))
    return rows


def sort_merge_micro() -> List[Row]:
    """Accumulation engines head-to-head on one product stream: the global
    ``jax.lax.sort`` path (core/accumulate.accumulate) vs the tiled bitonic
    merge tree (kernels/ops.sort_merge). Streams are 2^16 and 2^18 products
    over a 64×64 coordinate space — the multi-tile regime the tree exists
    for. ``derived`` column = speedup of the tree over the global sort
    (off-TPU the kernels run in interpret mode, where XLA's fused sort wins;
    the tree's point is VMEM-resident blocking on real TPU)."""
    from repro.core.accumulate import accumulate
    from repro.kernels import ops
    rows = []
    rng = np.random.default_rng(2)
    n_rows = n_cols = 64
    for logn in (16, 18):
        n = 1 << logn
        row = jnp.asarray(rng.integers(0, n_rows, n), jnp.int32)
        col = jnp.asarray(rng.integers(0, n_cols, n), jnp.int32)
        val = jnp.asarray(rng.standard_normal(n), jnp.float32)
        out_cap = n_rows * n_cols

        f_sort = jax.jit(lambda r, c, v: accumulate(r, c, v, out_cap,
                                                    n_rows, n_cols))
        jax.block_until_ready(f_sort(row, col, val))
        t_sort = _timeit(lambda: jax.block_until_ready(
            f_sort(row, col, val)), n=3, warmup=1)

        f_tree = jax.jit(lambda r, c, v: ops.sort_merge(r, c, v, n_rows,
                                                        n_cols, tile=4096))
        jax.block_until_ready(f_tree(row, col, val))
        t_tree = _timeit(lambda: jax.block_until_ready(
            f_tree(row, col, val)), n=3, warmup=1)

        rows.append((f"micro/accum_global_sort/2^{logn}", round(t_sort, 1), 0.0))
        rows.append((f"micro/accum_merge_tree/2^{logn}", round(t_tree, 1),
                     round(t_sort / t_tree, 3)))

        # streaming engine over the same (already materialized) stream:
        # chunk-scan compact→merge, sort working set one 4096-lane tile
        from repro.core import accumulate_stream
        f_stream = jax.jit(lambda r, c, v: accumulate_stream(
            r, c, v, out_cap, n_rows, n_cols, backend="stream").val)
        jax.block_until_ready(f_stream(row, col, val))
        t_stream = _timeit(lambda: jax.block_until_ready(
            f_stream(row, col, val)), n=3, warmup=1)
        rows.append((f"micro/accum_stream_flat/2^{logn}", round(t_stream, 1),
                     round(t_sort / t_stream, 3)))
    return rows


def accum_backends_micro() -> List[Row]:
    """All six accumulation backends head-to-head on planner-relevant
    shapes, plus a validation row per shape: did the planner's choice land
    within 2× of the best measured backend?

    Shapes span the regimes the backends are built for: a sparse mid-size
    SpGEMM (sort's home turf off-TPU), a duplication-heavy small coordinate
    space (hash's and search's), a DENSE duplicate-dominated stream
    (``n48_dup_heavy`` — the paper's alignment-beats-resorting case the
    'search' backend exists for), a skewed row distribution (bucket's), and
    a padding-heavy ELLPACK (oversized k, mostly INVALID lanes) where the
    streaming engine's per-tile compaction pays off. ``derived`` column =
    speedup vs the 'sort' baseline for backend rows, and
    best_time/chosen_time (≥ 0.5 passes the 2× criterion) for 'planner'
    rows. Tiny shapes on purpose — this doubles as the CI smoke suite
    feeding BENCH_accum.json.

    Dup-heavy shapes additionally log a ``search_alignment_win`` evidence
    row (us = measured 'search' time, derived = t_sort/t_search) so the
    BENCH file records whether in-situ alignment beat the full re-sort on
    the host that produced it — the paper's prediction, checkable per run.

    Per shape two memory-evidence rows make the compaction win visible:
    ``stream_density`` (us column = valid SCCP products, derived =
    valid / k_a·n·k_b lane density — how much of the materialized stream is
    ELLPACK-padding dead weight) and ``interm_bytes_{sort,stream}`` (the
    planner's modeled peak materialized-intermediate bytes; the stream
    row's derived = sort_bytes / stream_bytes reduction factor).
    """
    import dataclasses
    from functools import partial
    import repro.obs as obs
    from repro.core import (ell_cols_from_dense, ell_rows_from_dense,
                            spgemm_coo)
    from repro.core.sccp import count_products
    from repro.plan import make_plan
    rows: List[Row] = []
    rng = np.random.default_rng(7)
    shapes = [                              # tag, n, density, skew, k_force
        ("n128_sparse", 128, 0.05, 0.0, None),
        ("n64_dup", 64, 0.25, 0.0, None),
        # half-dense 48×48: the product stream carries ~20× duplicates per
        # unique coordinate — alignment against nnz(C) keys vs re-sorting
        # the whole stream is exactly the paper's in-situ-search bet
        ("n48_dup_heavy", 48, 0.5, 0.0, None),
        ("n96_skew", 96, 0.05, 0.5, None),
        ("n64_pad", 64, 0.04, 0.0, 16),     # k ≫ nnz: dead-lane dominated
        # k_a·n·k_b = 2^18 lanes at ~1% valid density: the regime the
        # streaming engine exists for (intermediate-bound, tiny nnz(C))
        ("n256_pad", 256, 0.008, 0.0, 32),
    ]
    for tag, n, dens, skew, k_force in shapes:
        a = ((rng.random((n, n)) < dens)
             * rng.standard_normal((n, n))).astype(np.float32)
        b = ((rng.random((n, n)) < dens)
             * rng.standard_normal((n, n))).astype(np.float32)
        if skew:
            hot = rng.choice(n, n // 8, replace=False)
            a[hot] = (rng.standard_normal((len(hot), n))
                      * (rng.random((len(hot), n)) < skew)).astype(np.float32)
        ka = k_force or max(1, int((a != 0).sum(0).max()))
        kb = k_force or max(1, int((b != 0).sum(1).max()))
        ea = ell_rows_from_dense(jnp.asarray(a), ka)
        eb = ell_cols_from_dense(jnp.asarray(b), kb)
        plan = make_plan(ea, eb)
        lanes = ka * n * kb
        valid = int(count_products(ea, eb))
        rows.append((f"micro/stream_density/{tag}", float(valid),
                     round(valid / lanes, 4)))
        i_sort, i_stream = plan.est["interm_sort"], plan.est["interm_stream"]
        rows.append((f"micro/interm_bytes_sort/{tag}", round(i_sort, 1), 1.0))
        rows.append((f"micro/interm_bytes_stream/{tag}", round(i_stream, 1),
                     round(i_sort / i_stream, 2)))
        if obs.is_enabled():
            from repro.core.spgemm import spgemm_coo_numeric
            from repro.plan import make_structure
            structure = make_structure(ea, eb, plan=plan)
        times = {}
        for backend in ("sort", "tiled", "bucket", "hash", "stream",
                        "search"):
            p = dataclasses.replace(plan, backend=backend)
            f = jax.jit(partial(spgemm_coo, out_cap=plan.out_cap,
                                accumulator=backend, plan=p))
            jax.block_until_ready(f(ea, eb).val)
            times[backend] = _timeit(
                lambda: jax.block_until_ready(f(ea, eb).val), n=3, warmup=1)
            rows.append((f"micro/accum_{backend}/{tag}",
                         round(times[backend], 1),
                         round(times["sort"] / times[backend], 3)))
            if obs.is_enabled():
                # one eager (unjitted) pass per backend so the trace carries
                # real per-phase spans with device syncs — multiply +
                # accumulate (feeding the est-vs-measured ledger) and the
                # numeric phase against the shared structure
                jax.block_until_ready(spgemm_coo(
                    ea, eb, out_cap=plan.out_cap, accumulator=backend,
                    plan=p).val)
                st = dataclasses.replace(structure, plan=p)
                jax.block_until_ready(spgemm_coo_numeric(
                    ea, eb, st, validate=False).val)
        if "dup" in tag:
            # evidence row (outside the accum_ regression regex): did the
            # paper's alignment beat the full re-sort on this host?
            rows.append((f"micro/search_alignment_win/{tag}",
                         round(times["search"], 1),
                         round(times["sort"] / times["search"], 3)))
        best = min(times.values())
        rows.append((f"micro/accum_planner_{plan.backend}/{tag}",
                     round(times[plan.backend], 1),
                     round(best / times[plan.backend], 3)))
    return rows


def plan_cache_micro() -> List[Row]:
    """Two-phase SpGEMM: what the fingerprint-keyed structure cache buys.

    Per shape three rows:
      * ``micro/plan_cache_cold/<tag>`` — the one-phase call as an uncached
        user pays it: host-side planning (exact symbolic pass) + coordinate
        sort + accumulation, every call.
      * ``micro/plan_cache_warm/<tag>`` — the realistic warm call: a
        ``StructureCache.get`` (fingerprint hash + LRU hit) followed by
        ``spgemm_coo_numeric`` (scatter into the precomputed structure, no
        planning, no sort). ``derived`` = cold/warm speedup — the CI gate
        asserts ≥ 1.5×.
      * ``micro/plan_cache_hitrate/<tag>`` — evidence row: 16 calls cycling
        4 sparsity patterns through one cache; ``us_per_call`` is the
        amortized per-call time (4 symbolic builds + 12 numeric-only) and
        ``derived`` the measured hit rate (0.75 by construction).
    """
    from repro.core import (ell_cols_from_dense, ell_rows_from_dense,
                            spgemm_coo)
    from repro.core.spgemm import spgemm_coo_numeric
    from repro.plan import StructureCache
    rows: List[Row] = []
    rng = np.random.default_rng(13)
    for tag, n, dens in [("n128", 128, 0.05), ("n256", 256, 0.02)]:
        def mk_a():
            ad = ((rng.random((n, n)) < dens)
                  * rng.standard_normal((n, n))).astype(np.float32)
            ka = max(1, int((ad != 0).sum(0).max()))
            return ell_rows_from_dense(jnp.asarray(ad), ka)
        bd = ((rng.random((n, n)) < dens)
              * rng.standard_normal((n, n))).astype(np.float32)
        kb = max(1, int((bd != 0).sum(1).max()))
        b = ell_cols_from_dense(jnp.asarray(bd), kb)
        a = mk_a()

        t_cold = _timeit(lambda: jax.block_until_ready(
            spgemm_coo(a, b).val), n=5, warmup=2)

        cache = StructureCache(capacity=8)
        cache.get(a, b)                       # symbolic phase paid once here

        def warm():
            st = cache.get(a, b)              # fingerprint hash + LRU hit
            jax.block_until_ready(spgemm_coo_numeric(
                a, b, st, validate=False).val)
        t_warm = _timeit(warm, n=5, warmup=2)
        rows.append((f"micro/plan_cache_cold/{tag}", round(t_cold, 1), 1.0))
        rows.append((f"micro/plan_cache_warm/{tag}", round(t_warm, 1),
                     round(t_cold / t_warm, 3)))

        pats = [a] + [mk_a() for _ in range(3)]
        mixed = StructureCache(capacity=8)
        for p in pats:                        # trace/compile outside timing
            jax.block_until_ready(spgemm_coo_numeric(
                p, b, mixed.get(p, b), validate=False).val)
        mixed.clear()
        t0 = time.perf_counter()
        calls = 16
        for i in range(calls):
            p = pats[i % len(pats)]
            jax.block_until_ready(spgemm_coo_numeric(
                p, b, mixed.get(p, b), validate=False).val)
        us = (time.perf_counter() - t0) / calls * 1e6
        s = mixed.stats()
        rows.append((f"micro/plan_cache_hitrate/{tag}", round(us, 1),
                     round(s["hits"] / (s["hits"] + s["misses"]), 3)))
    return rows


def serve_sparse_micro() -> List[Row]:
    """Sparse-serving suite (the PR-9 acceptance benchmark).

    Per shape tag, a decode-shaped SpMM ``y = x @ W`` with a 2:4-style
    magnitude-pruned weight, three execution paths on identical math:

      * ``micro/serve_sparse_dense/<tag>`` — pruned-but-dense matmul
        baseline (the in-file normalizer for the regression gate);
      * ``micro/serve_sparse_ell/<tag>`` — general column-wise ELLPACK
        (``sparse_linear_apply``, gather/segment-sum);
      * ``micro/serve_sparse_nm/<tag>`` — the gather-free N:M condensed
        path (``nm_spmm``: M masked matmuls on R = d_in·N/M rows).

    ``derived`` on those rows = requests/s at the measured latency (T
    activation rows per call). Two extra rows:

      * ``micro/nm_vs_ell_win/<tag>`` — ``us`` is the N:M time, ``derived``
        the ELLPACK/N:M speedup; CI requires ≥ 1 on at least one 2:4 tag.
      * ``micro/serve_sparse_batched/<tag>`` — one engine
        ``SparseGemmBatcher`` flush of 4 heterogeneous-pattern requests
        through ``spgemm_coo_numeric_batched`` slots; ``derived`` = the
        4-sequential-numeric-calls time over the batched flush time.
    """
    from repro.core.formats import ell_cols_from_dense, ell_rows_from_dense
    from repro.core.spgemm import spgemm_coo_numeric
    from repro.models.sparse import (ell_from_pruned, magnitude_prune_nm,
                                     nm_linear_apply, sparse_linear_apply)
    from repro.core.nm import nm_from_dense
    from repro.plan import StructureCache
    from repro.serve import SparseGemmBatcher
    rows: List[Row] = []
    rng = np.random.default_rng(17)
    for tag, t_rows, d_in, d_out, (nn, mm) in [
            ("t64_d256_2to4", 64, 256, 256, (2, 4)),
            ("t32_d128_2to4", 32, 128, 128, (2, 4))]:
        w = jnp.asarray(rng.standard_normal((d_in, d_out)), jnp.float32)
        wp = magnitude_prune_nm(w, nn, mm)
        x = jnp.asarray(rng.standard_normal((t_rows, d_in)), jnp.float32)
        w_ell = ell_from_pruned(wp)
        w_nm = nm_from_dense(wp, nn, mm)

        f_dense = jax.jit(lambda xx, ww: xx @ ww)
        jax.block_until_ready(f_dense(x, wp))
        t_dense = _timeit(lambda: jax.block_until_ready(f_dense(x, wp)))
        f_ell = jax.jit(sparse_linear_apply)
        jax.block_until_ready(f_ell(x, w_ell))
        t_ell = _timeit(lambda: jax.block_until_ready(f_ell(x, w_ell)))
        f_nm = jax.jit(nm_linear_apply)
        jax.block_until_ready(f_nm(x, w_nm))
        t_nm = _timeit(lambda: jax.block_until_ready(f_nm(x, w_nm)))
        for variant, t in (("dense", t_dense), ("ell", t_ell), ("nm", t_nm)):
            rows.append((f"micro/serve_sparse_{variant}/{tag}", round(t, 1),
                         round(t_rows / (t / 1e6), 1)))
        rows.append((f"micro/nm_vs_ell_win/{tag}", round(t_nm, 1),
                     round(t_ell / t_nm, 3)))

    # engine-style slot batching: 4 same-shape, different-pattern SpGEMMs
    tag = "n96x4"
    n = 96
    def mk_pair(seed):
        r = np.random.default_rng(seed)
        ad = ((r.random((n, n)) < 0.04)
              * r.standard_normal((n, n))).astype(np.float32)
        bd = ((r.random((n, n)) < 0.04)
              * r.standard_normal((n, n))).astype(np.float32)
        ka = max(1, int((ad != 0).sum(0).max()))
        kb = max(1, int((bd != 0).sum(1).max()))
        # shared slab counts so the batcher groups all four into one wave
        return (ell_rows_from_dense(jnp.asarray(ad), max(ka, 8)),
                ell_cols_from_dense(jnp.asarray(bd), max(kb, 8)))
    pairs = [mk_pair(s) for s in range(4)]
    cache = StructureCache(capacity=16)
    bt = SparseGemmBatcher(cache, max_slots=4)
    for a, b in pairs:                       # symbolic + compile outside timing
        bt.submit(a, b)
    bt.flush()
    sts = [cache.get(a, b) for a, b in pairs]
    for (a, b), st in zip(pairs, sts):
        jax.block_until_ready(spgemm_coo_numeric(a, b, st, validate=False).val)

    def seq():
        for (a, b), st in zip(pairs, sts):
            jax.block_until_ready(
                spgemm_coo_numeric(a, b, st, validate=False).val)
    t_seq = _timeit(seq, n=5, warmup=1)

    def batched():
        for a, b in pairs:
            bt.submit(a, b)
        bt.flush()
    t_batch = _timeit(batched, n=5, warmup=1)
    # 'seq' is the in-file normalizer for this group (no dense variant of a
    # 4-request SpGEMM wave exists); derived on 'batched' = the wave speedup
    rows.append((f"micro/serve_sparse_seq/{tag}", round(t_seq, 1), 1.0))
    rows.append((f"micro/serve_sparse_batched/{tag}", round(t_batch, 1),
                 round(t_seq / t_batch, 3)))
    return rows


def moe_dispatch_micro() -> List[Row]:
    """ELLPACK one-hot dispatch vs SPLIM sort dispatch (measured FLOP proxy
    via wall-time on CPU; dry-run flops recorded in §Perf)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import build_model
    rows = []
    base = get_config("granite-moe-3b-a800m").reduced()
    toks = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0, base.vocab)
    for disp in ("ellpack", "sort"):
        cfg = dataclasses.replace(
            base, moe=dataclasses.replace(base.moe, dispatch=disp))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        f = jax.jit(lambda p, t: m.loss(p, {"tokens": t}))
        f(params, toks).block_until_ready()
        t = _timeit(lambda: f(params, toks).block_until_ready(), n=5)
        rows.append((f"micro/moe_dispatch_{disp}", round(t, 1), 0.0))
    return rows


def lm_step_micro() -> List[Row]:
    from repro.configs import get_config
    from repro.launch.steps import make_train_step
    from repro.models import build_model
    from repro.optim import AdamWConfig, adamw_init
    rows = []
    for arch in ("qwen2-0.5b", "granite-moe-3b-a800m", "falcon-mamba-7b"):
        cfg = get_config(arch).reduced()
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        step = jax.jit(make_train_step(m, AdamWConfig()), donate_argnums=(0, 1))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64),
                                              0, cfg.vocab)}
        params, opt, metrics = step(params, opt, batch)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        N = 3
        for _ in range(N):
            params, opt, metrics = step(params, opt, batch)
        jax.block_until_ready(metrics["loss"])
        us = (time.perf_counter() - t0) / N * 1e6
        toks_s = 4 * 64 / (us / 1e6)
        rows.append((f"micro/train_step/{arch}-smoke", round(us, 1),
                     round(toks_s, 0)))
    return rows


def dist_spgemm_micro() -> List[Row]:
    """Distributed SpGEMM: sparse-native ``spgemm_coo_sharded`` (both
    schedules) against the dense-psum ``ring_spgemm`` baseline.

    Meaningful with several devices — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    ``tests-multidevice`` job does; a 1-device run degenerates to a 1-ring).
    ``derived`` = modeled per-device peak partial-result bytes of the dense
    baseline over the sparse path: the dense path scatters into a full
    n_rows×n_cols accumulator per device, the sparse path's partials are the
    device-local product stream (~stream/n_dev) plus its COO capacities, so
    the ratio growing with the mesh is exactly the paper's "intermediate
    results never cross arrays" scaling claim made measurable.
    """
    import dataclasses
    from repro.core import ell_cols_from_dense, ell_rows_from_dense
    from repro.core.distributed import (pad_slabs_a, pad_slabs_b, ring_spgemm,
                                        spgemm_coo_sharded)
    from repro.launch.mesh import make_mesh
    from repro.plan import make_dist_plan
    rows: List[Row] = []
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("ring",))
    rng = np.random.default_rng(11)
    for tag, n, dens in [("n256", 256, 0.02), ("n512", 512, 0.005)]:
        A = ((rng.random((n, n)) < dens)
             * rng.standard_normal((n, n))).astype(np.float32)
        B = ((rng.random((n, n)) < dens)
             * rng.standard_normal((n, n))).astype(np.float32)
        ka = max(1, int((A != 0).sum(0).max()))
        kb = max(1, int((B != 0).sum(1).max()))
        a = ell_rows_from_dense(jnp.asarray(A), ka)
        b = ell_cols_from_dense(jnp.asarray(B), kb)
        dense_bytes = 4 * n * n                      # per-device dense partial C
        f_dense = jax.jit(lambda av, bv: ring_spgemm(av, bv, mesh, "ring"))
        jax.block_until_ready(f_dense(a, b))
        t = _timeit(lambda: jax.block_until_ready(f_dense(a, b)), n=3, warmup=1)
        rows.append((f"micro/dist_densepsum/{tag}_dev{n_dev}", round(t, 1), 1.0))
        dp = make_dist_plan(a, b, n_dev=n_dev)
        ap, bp = pad_slabs_a(a, n_dev), pad_slabs_b(b, n_dev)
        stream_loc = ap.k * n * bp.k // n_dev        # device-local product lanes
        for sched in ("ring", "cstat"):
            dps = dataclasses.replace(dp, schedule=sched)
            f = jax.jit(lambda av, bv: spgemm_coo_sharded(
                av, bv, mesh, "ring", dist_plan=dps).val)
            jax.block_until_ready(f(a, b))
            t = _timeit(lambda: jax.block_until_ready(f(a, b)), n=3, warmup=1)
            caps = (dp.local_cap + n_dev * dp.bin_cap if sched == "ring"
                    else 0) + dp.block_cap
            sparse_bytes = 12 * (stream_loc + caps)  # val+row+col per lane
            rows.append((f"micro/dist_sparse_{sched}/{tag}_dev{n_dev}",
                         round(t, 1), round(dense_bytes / sparse_bytes, 3)))
    return rows


def dist2d_micro() -> List[Row]:
    """Communication-avoiding 2D schedule evidence (``--only dist-2d``).

    Two row groups, both registered with ``check_regression`` (unknown
    ``dist2d_*`` names are a hard failure there):

      * ``dist2d_comm_bytes_{ring,cstat,summa}/<tag>_devN`` — the DistPlan's
        modeled **per-device comm bytes** at N ∈ {2, 4, 8} (the value column
        carries bytes, not µs — evidence rows, ignored by the timing gate).
        ``derived`` = bytes / same-mesh ring bytes. The 1D schedules rotate
        all of B (or replicate all of A) through every device no matter the
        mesh size, so their per-device volume stays ~flat-to-growing; the 2D
        grid moves ``(pc−1)/p`` of A + ``(pr−1)/p`` of B, shrinking ~1/√p —
        summa's derived falling below 1.0 as N grows is the paper-adjacent
        communication-avoiding claim made measurable. CI gates fresh-run
        summa ≤ ring at 8 devices. At N=2 there is no pr,pc ≥ 2
        factorization, so summa is modeled (and gated) as exactly ring.
      * ``dist2d_overlap_{on,off}/<tag>_devN`` — wall-clock of the summa
        schedule with/without double-buffered prefetch (``derived`` on the
        'on' row = off/on speedup). Fake host devices make the ppermute a
        memcpy, so ≈1 here; async-ICI hardware is where the prefetch pays.
    """
    import dataclasses
    from jax.sharding import Mesh
    from repro.core import ell_cols_from_dense, ell_rows_from_dense
    from repro.core.distributed import spgemm_coo_sharded
    from repro.plan import make_dist_plan
    rows: List[Row] = []
    devs = jax.devices()
    rng = np.random.default_rng(13)
    n, dens, tag = 256, 0.02, "n256"
    A = ((rng.random((n, n)) < dens)
         * rng.standard_normal((n, n))).astype(np.float32)
    B = ((rng.random((n, n)) < dens)
         * rng.standard_normal((n, n))).astype(np.float32)
    ka = max(1, int((A != 0).sum(0).max()))
    kb = max(1, int((B != 0).sum(1).max()))
    a = ell_rows_from_dense(jnp.asarray(A), ka)
    b = ell_cols_from_dense(jnp.asarray(B), kb)
    for nd in (2, 4, 8):
        if nd > len(devs):
            continue
        dp = make_dist_plan(a, b, n_dev=nd)
        ring_b = dp.est["ring_comm_bytes"]
        for sched in ("ring", "cstat", "summa"):
            v = dp.est[f"{sched}_comm_bytes"]
            rows.append((f"micro/dist2d_comm_bytes_{sched}/{tag}_dev{nd}",
                         round(v, 1), round(v / max(ring_b, 1.0), 3)))
    nd = max(d for d in (2, 4, 8) if d <= len(devs))
    mesh = Mesh(np.array(devs[:nd]), ("ring",))
    dps = dataclasses.replace(make_dist_plan(a, b, n_dev=nd),
                              schedule="summa")
    ts = {}
    for ov in (True, False):
        f = jax.jit(lambda av, bv, _ov=ov: spgemm_coo_sharded(
            av, bv, mesh, "ring", dist_plan=dps, overlap=_ov).val)
        jax.block_until_ready(f(a, b))
        ts[ov] = _timeit(lambda: jax.block_until_ready(f(a, b)),
                         n=3, warmup=1)
    rows.append((f"micro/dist2d_overlap_off/{tag}_dev{nd}",
                 round(ts[False], 1), 1.0))
    rows.append((f"micro/dist2d_overlap_on/{tag}_dev{nd}",
                 round(ts[True], 1),
                 round(ts[False] / max(ts[True], 1e-9), 3)))
    return rows
