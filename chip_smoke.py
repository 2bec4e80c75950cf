"""Smoke run of the system's main paths on a TPU chip.

    python chip_smoke.py [--seed 0]           # one chip
    python chip_smoke.py --chips 4 [--seed 0] # distributed SpGEMM on four

One process drives everything; data and weights come from ``--seed``.
Phases, in order (one chip):

  1. device  — the default JAX backend must be a TPU; there is no CPU
     fallback, so anywhere else the script exits non-zero.
  2. cold    — C = A·Aᵀ on the ``bcsstk32``-matched Table-I matrix
     (benchmarks/common.py: n = 45,000, nnz 2.0 M) through
     ``repro.spgemm(out_cap="auto", accumulator="auto", check=True)``,
     checked against scipy on the host: identical coordinates, float32-close
     values.
  3. warm    — ``repro.make_structure`` on the same pattern, then the
     numeric-only call with new B values, checked against scipy.
  4. model   — qwen2-0.5b at its published widths (bf16, random weights)
     served through ``ServingEngine.generate_batch`` under the same mesh
     and sharding rules as ``launch/serve.py``; the engine's first decode
     step is checked against an uncached forward pass over the same prefix.

``--chips 4`` runs only the distributed path: the ring, cstat and 2×2
summa schedules over a four-chip mesh on the same matrix (small-integer
values, so every float32 sum is exact), each checked bit-for-bit against
the single-device result and against scipy.

Times printed here are smoke times (first call includes compilation), not
benchmark numbers. The last line of standard output is one JSON object,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

MATRIX = "bcsstk32"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def ready(coo):
    coo.val.block_until_ready()
    return coo


def build_operands(seed: int, integer_values: bool):
    """A (Table-I ``bcsstk32`` pattern, values from ``seed``) as ELLPACK
    A and Bᵀ-as-columns operands for C = A·Aᵀ."""
    import numpy as np
    from benchmarks.common import bench_matrices, build_scipy
    import repro
    from repro.core.formats import (np_ell_cols_from_scipy,
                                    np_ell_rows_from_scipy)

    m = next(x for x in bench_matrices() if x.name == MATRIX)
    a = build_scipy(m).tocsr()
    rng = np.random.default_rng(seed)
    a.data = ((rng.integers(1, 5, a.nnz) * rng.choice([-1, 1], a.nnz))
              if integer_values else rng.standard_normal(a.nnz)
              ).astype(np.float32)
    a_csc = a.tocsc()
    k = int(np.diff(a_csc.indptr).max())       # widest column: lossless ELL
    av, ai = np_ell_rows_from_scipy(a_csc, k)
    bv, bi = np_ell_cols_from_scipy(a.T.tocsr(), k)
    ea = repro.EllRows(val=av, idx=ai, n_rows=a.shape[0])
    eb = repro.EllCols(val=bv, idx=bi, n_cols=a.shape[0])
    log(f"[data] {MATRIX}: n={a.shape[0]} nnz={a.nnz} k_a=k_b={k} "
        f"product lanes={k * a.shape[0] * k}")
    return a, ea, eb


def to_device(ell):
    import dataclasses
    import jax.numpy as jnp
    return dataclasses.replace(ell, val=jnp.asarray(ell.val),
                               idx=jnp.asarray(ell.idx))


def check_against_scipy(coo, a, b, label: str):
    """C = a·b checked against scipy on the host: the same coordinate set as
    the structural product (scipy drops entries whose products cancel to an
    exact zero, so coordinates come from the product of the patterns) and
    float32-close values."""
    import numpy as np
    import scipy.sparse as sp
    pa, pb = (abs(x).astype(np.float32) for x in (a, b))
    pat = (pa @ pb).tocoo()                  # positive: nothing cancels
    order = np.lexsort((pat.col, pat.row))
    nnz = int(coo.ngroups)
    if nnz != pat.nnz:
        raise AssertionError(f"{label}: nnz(C)={nnz} but scipy has {pat.nnz}")
    row = np.asarray(coo.row[:nnz])
    col = np.asarray(coo.col[:nnz])
    val = np.asarray(coo.val[:nnz])
    if not (np.array_equal(row, pat.row[order])
            and np.array_equal(col, pat.col[order])):
        raise AssertionError(f"{label}: coordinates differ from scipy")
    ref = (a @ b).tocsr()
    ours = sp.csr_matrix((val, (row, col)), shape=ref.shape)
    scale = float(np.abs(ref.data).max())
    err = float(abs(ours - ref).max())
    # float32-close: the two sides sum each entry's products in different
    # orders
    if not err <= 1e-4 * scale:
        raise AssertionError(f"{label}: max |C - scipy| = {err} "
                             f"(max |scipy| = {scale})")
    log(f"[{label}] nnz(C)={nnz} == scipy; coordinates identical; "
        f"max |C - scipy| = {err:.3e} (max |C| = {scale:.3e})")


def phase_cold(a, ea, eb):
    import repro
    c, t1 = timed(lambda: ready(repro.spgemm(
        ea, eb, out_cap="auto", accumulator="auto", check=True)))
    _, t2 = timed(lambda: ready(repro.spgemm(
        ea, eb, out_cap="auto", accumulator="auto", check=True)))
    plan = repro.make_plan(ea, eb)
    log(f"[cold] planner chose backend={plan.backend} out_cap={plan.out_cap}")
    log(f"[cold] smoke times: first call {t1:.2f} s (with compile), "
        f"second call {t2:.2f} s")
    check_against_scipy(c, a, a.T, "cold")


def phase_warm(a, ea, eb, seed: int):
    import numpy as np
    import repro
    a2 = a.copy()
    a2.data = np.random.default_rng(seed + 1).standard_normal(
        a.nnz).astype(np.float32)
    # same pattern, new values: rebuild Bᵀ's ELL planes from A2
    from repro.core.formats import np_ell_cols_from_scipy
    eb2_val, eb2_idx = np_ell_cols_from_scipy(a2.T.tocsr(), eb.val.shape[1])
    if not np.array_equal(eb2_idx, np.asarray(eb.idx)):
        raise AssertionError("warm: B2 pattern differs from B")
    eb2 = to_device(repro.EllCols(val=eb2_val, idx=eb2_idx, n_cols=eb.n_cols))
    st, tb = timed(lambda: repro.make_structure(ea, eb))
    c, t1 = timed(lambda: ready(repro.spgemm(ea, eb2, structure=st)))
    _, t2 = timed(lambda: ready(repro.spgemm(ea, eb2, structure=st)))
    log(f"[warm] structure built in {tb:.2f} s (backend "
        f"{st.plan.backend}); numeric smoke times: first {t1:.2f} s, "
        f"second {t2:.2f} s")
    check_against_scipy(c, a, a2.T, "warm")


def phase_model(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model, transformer
    from repro.parallel.sharding import sharding_rules
    from repro.serve import ServeConfig, ServingEngine

    cfg = get_config("qwen2-0.5b")
    model = build_model(cfg)
    mesh = make_host_mesh()
    rng = np.random.default_rng(seed)
    n_req, plen, max_new = 4, 12, 16
    prompts = [rng.integers(3, cfg.vocab, size=plen).astype(np.int32)
               for _ in range(n_req)]
    with sharding_rules(mesh), mesh:
        params = model.init(jax.random.PRNGKey(seed))
        eng = ServingEngine(model, params, ServeConfig(max_new_tokens=max_new))
        outs, t_gen = timed(lambda: eng.generate_batch(prompts))
        toks = jnp.asarray(np.stack(prompts))
        # the engine's own compiled prefill/decode, replayed for the logits
        # of the first decode step (generate_batch only returns tokens)
        _, cache = eng._prefill(params, {"tokens": toks})
        first = jnp.asarray([o[0] for o in outs], jnp.int32)[:, None]
        dec_logits, _ = eng._decode(params, cache, first)
        full, _, _ = jax.jit(lambda p, t: transformer.decoder_forward(
            p, t, cfg))(params, jnp.concatenate([toks, first], axis=1))
    dec = np.asarray(dec_logits, np.float32).reshape(n_req, -1)
    ref = np.asarray(full[:, plen], np.float32)
    if len(outs) != n_req or not all(1 <= len(o) <= max_new for o in outs):
        raise AssertionError(f"model: expected {n_req} requests of "
                             f"1..{max_new} tokens, got "
                             f"{[len(o) for o in outs]}")
    if not (np.isfinite(dec).all() and np.isfinite(ref).all()):
        raise AssertionError("model: non-finite logits")
    err = float(np.abs(dec - ref).max())
    scale = float(np.abs(ref).max())
    agree = float(np.mean(dec.argmax(-1) == ref.argmax(-1)))
    log(f"[model] {cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
        f"vocab={cfg.vocab}; {n_req} requests, {sum(map(len, outs))} "
        f"tokens in {t_gen:.2f} s (smoke time, with compile)")
    log(f"[model] first decode step vs uncached forward: max |Δlogit| = "
        f"{err:.4f} (max |logit| = {scale:.4f}), argmax agreement {agree:.2f}")
    if not err <= 0.05 * scale:          # bf16 tolerance: 5% of logit range
        raise AssertionError(f"model: decode logits differ by {err}")


def phase_distributed(a, ea, eb, n_dev: int):
    import dataclasses
    import numpy as np
    import repro
    mesh = repro.make_mesh((n_dev,), ("ring",))
    single, t = timed(lambda: ready(repro.spgemm(
        ea, eb, out_cap="auto", accumulator="auto", check=True)))
    log(f"[dist] single-device reference in {t:.2f} s")
    check_against_scipy(single, a, a.T, "dist/single")
    want = [np.asarray(x) for x in (single.row, single.col, single.val)]
    # one plan for all three schedules: its caps cover every schedule
    dp, t = timed(lambda: repro.make_dist_plan(ea, eb, n_dev=n_dev))
    log(f"[dist] DistPlan in {t:.2f} s: planner's schedule {dp.schedule}, "
        f"local backend {dp.base.backend}, summa grid {dp.pr}x{dp.pc}")
    for sched in ("ring", "cstat", "summa"):
        plan = dataclasses.replace(dp, schedule=sched)
        c, t = timed(lambda: ready(repro.spgemm(
            ea, eb, mesh=mesh, axis="ring", dist_plan=plan, check=True)))
        got = [np.asarray(x) for x in (c.row, c.col, c.val)]
        same = (all(np.array_equal(g, w) for g, w in zip(got, want))
                and int(c.ngroups) == int(single.ngroups))
        devs = sorted({d.id for d in c.val.sharding.device_set})
        log(f"[dist] {sched}: {t:.2f} s (with compile), result on devices "
            f"{devs}, bit-identical to single device: {same}")
        if not same:
            raise AssertionError(f"dist: {sched} differs from single device")
        check_against_scipy(c, a, a.T, f"dist/{sched}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (default backend is "
              f"{devs[0].platform}); nothing run", file=sys.stderr)
        return 1
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} found",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[cache] compile cache: {enable_compile_cache()}")
    clock = CompileClock()

    t0 = time.perf_counter()
    a, ea, eb = build_operands(args.seed, integer_values=args.chips > 1)
    ea, eb = to_device(ea), to_device(eb)
    log(f"[data] built on host and placed in {time.perf_counter() - t0:.2f} s")
    if args.chips > 1:
        phase_distributed(a, ea, eb, args.chips)
    else:
        phase_cold(a, ea, eb)
        phase_warm(a, ea, eb, args.seed)
        phase_model(args.seed)
    log(f"[compile] backend compile {clock.seconds:.1f} s in this process; "
        f"{clock.hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
