"""FFN variants: SwiGLU, GELU MLP, and SPLIM-dispatch MoE.

MoE is where the paper's technique is a first-class LM feature (DESIGN.md
§3): a top-k routing matrix **is** a row-wise ELLPACK matrix — every token
row has exactly ``k`` non-zero slots, zero padding waste. Dispatch
(``Xᵉ = Rᵀ·X``) and combine (``Y = R·E(Xᵉ)``) are ELLPACK×dense SpMMs.
On TPU the scatter is realized as a one-hot × MXU matmul per tile — exactly
kernels/ell_spmm.py — here expressed as the whole-array einsum so XLA SPMD
can shard it (the Pallas kernel is the single-device tile body; the einsum
is its distributed form).

Three dispatch strategies (config ``moe.dispatch``):
  * 'ellpack' — one-hot dispatch/combine einsums (GShard-style, baseline).
  * 'sort'    — SPLIM-accumulation-style: tokens sorted by expert id (our
    in-situ-search dual), ragged segments, no (T,E,C) one-hot tensor.
    Used by the §Perf hillclimb; ~E× fewer dispatch FLOPs.
  * 'spmm'    — the routing planes feed the SpGEMM stack's structured SpMM
    directly (core.spgemm.spmm_ell_dense off-TPU, kernels/ell_spmm.py's
    one-hot MXU tiles on TPU): dispatch/combine as two ELLPACK×dense
    products, no (T,E,C) tensor, per-layer obs spans from the kernel path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import trace as _obs
from repro.parallel.sharding import maybe_shard

from .params import Spec


# ---------------------------------------------------------------------------
# Dense FFNs
# ---------------------------------------------------------------------------

def swiglu_specs(cfg, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w_gate": Spec((d, f), ("fsdp", "ff")),
        "w_up": Spec((d, f), ("fsdp", "ff")),
        "w_down": Spec((f, d), ("ff", "fsdp")),
    }


def swiglu_apply(p, x, dtype):
    h = jax.nn.silu(x @ p["w_gate"].astype(dtype)) * (x @ p["w_up"].astype(dtype))
    axes = ("batch",) + (None,) * (h.ndim - 2) + ("ff",)
    h = maybe_shard(h, *axes)
    return h @ p["w_down"].astype(dtype)


def gelu_mlp_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_in": Spec((d, f), ("fsdp", "ff")),
        "b_in": Spec((f,), ("ff",), init="zeros"),
        "w_out": Spec((f, d), ("ff", "fsdp")),
        "b_out": Spec((d,), (None,), init="zeros"),
    }


def gelu_mlp_apply(p, x, dtype):
    h = jax.nn.gelu(x @ p["w_in"].astype(dtype) + p["b_in"].astype(dtype))
    h = maybe_shard(h, "batch", None, "ff")
    return h @ p["w_out"].astype(dtype) + p["b_out"].astype(dtype)


# ---------------------------------------------------------------------------
# MoE with ELLPACK dispatch
# ---------------------------------------------------------------------------

def moe_specs(cfg) -> dict:
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    # NO "fsdp" on expert weights: they are already sharded over the model
    # axis (expert and/or expert_ff); adding a data-axis shard would force a
    # per-layer all-gather over data — measured 1.6→0.6e13 collective bytes
    # on deepseek train_4k (§Perf cell B, iteration 4). Optimizer state still
    # shards over data via the ZeRO-1 "opt_shard" rule.
    s = {
        "router": Spec((d, m.n_experts), (None, "expert")),
        "w_gate": Spec((m.n_experts, d, fe), ("expert", None, "expert_ff")),
        "w_up": Spec((m.n_experts, d, fe), ("expert", None, "expert_ff")),
        "w_down": Spec((m.n_experts, fe, d), ("expert", "expert_ff", None)),
    }
    if m.n_shared:
        s["shared"] = swiglu_specs(cfg, d_ff=m.n_shared * fe)
    return s


def _topk_routing(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Returns (weights (T,k) fp32 normalized, expert ids (T,k) int32).

    The (ids, weights) pair is precisely a row-wise ELLPACK representation of
    the T×E routing matrix: k slots per row, idx plane = expert ids.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, ids.astype(jnp.int32)


def _moe_ellpack(p, x_grp, cfg, dtype):
    """One-hot (ELLPACK) dispatch: GShard-style capacity-bounded einsums,
    *grouped* — x_grp: (G, T_g, d) with G aligned to the data shards, so the
    (G, T_g, E, C_g) dispatch tensor and its einsums shard over "batch" and
    C_g shrinks by G× vs an ungrouped dispatch (§Perf cell A, iteration 1)."""
    m = cfg.moe
    g, tg, d = x_grp.shape
    e, k = m.n_experts, m.top_k
    cap = max(1, int(tg * m.capacity_factor * k / e))
    logits = x_grp @ p["router"].astype(dtype)              # (G,Tg,E)
    w, ids = _topk_routing(logits, k)                       # ELLPACK planes
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)      # (G,Tg,k,E)
    # position of each (token, slot) within its expert's capacity buffer
    pos = jnp.cumsum(onehot.reshape(g, tg * k, e), axis=1).reshape(
        g, tg, k, e) - 1.0
    keep = (pos < cap) & (onehot > 0)
    pos = jnp.where(keep, pos, 0).astype(jnp.int32)
    disp = (keep.astype(jnp.float32)[..., None]
            * jax.nn.one_hot(pos, cap, dtype=jnp.float32))  # (G,Tg,k,E,C)
    comb = disp * w[..., None, None]
    disp = disp.sum(2)                                      # (G,Tg,E,C)
    comb = comb.sum(2)
    disp = maybe_shard(disp, "batch", None, "expert", None)
    xe = jnp.einsum("gtec,gtd->gecd", disp.astype(dtype), x_grp)
    xe = maybe_shard(xe, "batch", "expert", None, None)
    h = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(dtype))
    u = jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(dtype))
    h = maybe_shard(jax.nn.silu(h) * u, "batch", "expert", None, "expert_ff")
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dtype))
    y = jnp.einsum("gtec,gecd->gtd", comb.astype(dtype), ye)
    # load-balancing aux loss (Switch): mean prob per expert × token share
    me = jnp.mean(onehot.sum(2), axis=(0, 1))
    pe = jnp.mean(jax.nn.softmax(logits.astype(jnp.float32), -1), axis=(0, 1))
    aux = e * jnp.sum(me * pe)
    return y, aux


def _spmm_ell_auto(a, x):
    """ELLPACK×dense SpMM through the kernel stack: compiled Pallas one-hot
    MXU tiles on TPU (kernels/ell_spmm.py via ops.ell_spmm), the XLA
    segment-sum realization elsewhere — the resolve_mode convention applied
    to the structured multiply."""
    from repro.kernels import ops, platform
    if platform.on_tpu():
        return ops.ell_spmm(a.val, a.idx, x, a.n_rows)
    from repro.core.spgemm import spmm_ell_dense
    return spmm_ell_dense(a, x)


def _moe_spmm(p, x_grp, cfg, dtype):
    """SpGEMM-stack dispatch: the top-k routing planes (ids, weights) *are*
    a row-wise ELLPACK matrix (``_topk_routing``), so dispatch and combine
    run as two structured ELLPACK×dense SpMMs through ``_spmm_ell_auto`` —
    the same op behind SparseLinear — instead of materializing the
    (T, E, C) one-hot tensor. Dispatch scatters token rows into per-expert
    capacity slots (k slabs, slot coordinate = expert·cap + rank); combine
    gathers them back with the routing weights as a 1-slab ELLPACK over the
    slot axis (each slot holds at most one pair). Numerically equivalent to
    'ellpack' up to float summation order."""
    m = cfg.moe
    g, tg, d = x_grp.shape
    e, k = m.n_experts, m.top_k
    cap = max(1, int(tg * m.capacity_factor * k / e))
    logits = x_grp @ p["router"].astype(dtype)              # (G,Tg,E)
    w, ids = _topk_routing(logits, k)                       # ELLPACK planes
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)      # (G,Tg,k,E)
    pos = jnp.cumsum(onehot.reshape(g, tg * k, e), axis=1).reshape(
        g, tg, k, e) - 1.0
    keep = (pos < cap) & (onehot > 0)
    rank = jnp.where(keep, pos, 0).sum(-1).astype(jnp.int32)  # (G,Tg,k)
    kept = keep.any(-1)                                       # (G,Tg,k)
    slot = ids * cap + rank                                   # in [0, E·C)

    from repro.core.formats import EllRows

    def one_group(x_g, slot_g, kept_g, w_g):
        # dispatch: k-slab ELLPACK, columns = tokens, rows = E·C slots
        disp = EllRows(
            val=kept_g.astype(dtype).T,                       # (k, Tg)
            idx=jnp.where(kept_g, slot_g, -1).T.astype(jnp.int32),
            n_rows=e * cap)
        xe = _spmm_ell_auto(disp, x_g).reshape(e, cap, d)
        h = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dtype))
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                        p["w_down"].astype(dtype)).reshape(e * cap, d)
        # combine: invert slot→(token, weight); ranks are unique per expert
        # so every slot holds ≤ 1 pair and the scatter is deterministic
        flat = jnp.where(kept_g, slot_g, e * cap).reshape(-1)
        tok = jnp.broadcast_to(
            jnp.arange(tg, dtype=jnp.int32)[:, None], (tg, k)).reshape(-1)
        tok_of = jnp.full((e * cap + 1,), -1, jnp.int32) \
            .at[flat].set(tok)[: e * cap]
        w_of = jnp.zeros((e * cap + 1,), dtype) \
            .at[flat].set(w_g.reshape(-1).astype(dtype))[: e * cap]
        comb = EllRows(val=w_of[None], idx=tok_of[None], n_rows=tg)
        return _spmm_ell_auto(comb, ye)                       # (Tg, d)

    y = jax.vmap(one_group)(x_grp, slot, kept, w)
    me = jnp.mean(onehot.sum(2), axis=(0, 1))
    pe = jnp.mean(jax.nn.softmax(logits.astype(jnp.float32), -1), axis=(0, 1))
    aux = e * jnp.sum(me * pe)
    return y, aux


def _moe_sort(p, x_grp, cfg, dtype):
    """SPLIM-style sorted dispatch (grouped): sort (token,slot) pairs by
    expert id — the in-situ-search dual (equal coordinates grouped by
    sorting) — then gather/scatter into per-expert capacity buffers. No
    (T,E,C) one-hot tensor is ever materialized; dispatch cost drops from
    O(T·E·C·d) to O(T·k·d + sort). §Perf cell A, iteration 2.

    The whole dispatch→expert→combine region runs under a *full-manual*
    shard_map: GSPMD cannot prove that each group's dispatch indices stay
    inside that group's slice and falls back to replicate+all-reduce of the
    full (T·k, d) buffers (measured 48 GiB f32 all-reduces per layer on
    deepseek). Inside shard_map every gather/scatter is group-local; expert
    weights arrive pre-sliced over the model axis (expert dim when it
    divides, hidden dim otherwise) and one psum over "model" merges the
    partial combine. §Perf iteration 5."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import current_rules
    m = cfg.moe
    g, tg, d = x_grp.shape
    e, fe = m.n_experts, m.d_ff_expert

    rules = current_rules()
    if rules is None or rules.mesh is None:
        return _moe_sort_body(x_grp, p["router"], p["w_gate"], p["w_up"],
                              p["w_down"], cfg, dtype, (), ())

    mesh = rules.mesh
    gspec = rules.resolve(("batch", None, None), x_grp.shape)
    gaxes = (() if gspec[0] is None else
             (gspec[0] if isinstance(gspec[0], tuple) else (gspec[0],)))
    wg_spec = rules.resolve(("expert", None, "expert_ff"), (e, d, fe))
    wd_spec = rules.resolve(("expert", "expert_ff", None), (e, fe, d))
    # model-axis handle for the expert offset / final psum
    model_axes = tuple(ax for ax in ("model",) if ax in mesh.shape)

    def body(x_loc, router, wg, wu, wd):
        return _moe_sort_body(x_loc, router, wg, wu, wd, cfg, dtype,
                              gaxes, model_axes)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(gspec[0], None, None), P(), wg_spec, wg_spec, wd_spec),
        out_specs=(P(gspec[0], None, None), P()),
        check_vma=False)
    return fn(x_grp, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_sort_body(x_grp, router, w_gate, w_up, w_down, cfg, dtype,
                   gaxes, model_axes):
    """Manual (device-local) sort dispatch. Expert weights may arrive sliced
    on the expert dim (e_loc < E) or the hidden dim; in either case the
    combine is partial and one psum over the model axis completes it."""
    m = cfg.moe
    g, tg, d = x_grp.shape
    e, k = m.n_experts, m.top_k
    cap = max(1, int(tg * m.capacity_factor * k / e))
    e_loc = w_gate.shape[0]
    if model_axes and e_loc < e:
        e_off = jax.lax.axis_index(model_axes[0]) * e_loc
    else:
        e_off = jnp.zeros((), jnp.int32)

    logits = x_grp @ router.astype(dtype)                   # (G,Tg,E)
    w, ids = _topk_routing(logits, k)

    # per-group sort along axis 1 (lax.sort dimension=1): every group sorts
    # its own (token, slot) pairs by expert id in parallel — the G dim stays
    # explicit so GSPMD keeps all dispatch structures data-sharded. Integers
    # only: the differentiable payload is gathered afterwards by permutation,
    # so autodiff never sees the sort.
    npg = tg * k                                             # pairs per group
    tok_of = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tg, dtype=jnp.int32), k)[None], (g, npg))
    iota_g = jnp.broadcast_to(jnp.arange(npg, dtype=jnp.int32)[None], (g, npg))
    s_ids, s_tok, perm = jax.lax.sort(
        (ids.reshape(g, npg), tok_of, iota_g),
        dimension=1, num_keys=1, is_stable=True)
    goff_p = (jnp.arange(g, dtype=jnp.int32) * npg)[:, None]
    s_w = w.reshape(g * npg)[(perm + goff_p).reshape(-1)].reshape(g, npg)
    # rank within each (group, expert) run
    same = jnp.concatenate(
        [jnp.zeros((g, 1), jnp.int32),
         (s_ids[:, 1:] == s_ids[:, :-1]).astype(jnp.int32)], axis=1)
    idx = jnp.broadcast_to(jnp.arange(npg)[None], (g, npg))
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(same == 0, idx, 0), axis=1)
    rank = idx - run_start
    keep = rank < cap
    slot = s_ids * cap + jnp.where(keep, rank, 0)            # (G, npg) in E·C
    # gather tokens (flat indices carry the group sharding)
    goff_t = (jnp.arange(g, dtype=jnp.int32) * tg)[:, None]
    gathered = x_grp.reshape(g * tg, d)[((s_tok + goff_t)).reshape(-1)]
    gathered = (gathered.reshape(g, npg, d)
                * keep[..., None].astype(dtype))
    # scatter-add into per-group expert capacity buffers
    goff_s = (jnp.arange(g, dtype=jnp.int32) * (e * cap))[:, None]
    flat_slot = jnp.where(keep, slot + goff_s, g * e * cap).reshape(-1)
    xe = jax.ops.segment_sum(gathered.reshape(g * npg, d), flat_slot,
                             num_segments=g * e * cap + 1)[:-1]
    xe = xe.reshape(g, e, cap, d)
    # slice to the experts whose weights live on this device
    xe_loc = jax.lax.dynamic_slice_in_dim(xe, e_off, e_loc, axis=1)
    h = jnp.einsum("gecd,edf->gecf", xe_loc, w_gate.astype(dtype))
    u = jnp.einsum("gecd,edf->gecf", xe_loc, w_up.astype(dtype))
    ye = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u,
                    w_down.astype(dtype))
    # combine only the pairs whose expert is local; psum completes the rest
    loc_slot = slot - e_off * cap
    in_range = jnp.logical_and(loc_slot >= 0, loc_slot < e_loc * cap)
    loc_slot = jnp.clip(loc_slot, 0, e_loc * cap - 1)
    goff_l = (jnp.arange(g, dtype=jnp.int32) * (e_loc * cap))[:, None]
    back = (ye.reshape(g * e_loc * cap, d)[(loc_slot + goff_l).reshape(-1)]
            .reshape(g, npg, d)
            * (s_w * keep * in_range).astype(dtype)[..., None])
    y = jax.ops.segment_sum(back.reshape(g * npg, d),
                            ((s_tok + goff_t)).reshape(-1),
                            num_segments=g * tg).reshape(g, tg, d)
    # psum only when the model axis actually partitioned the expert compute
    # (expert dim or hidden dim sliced) — otherwise y is already complete
    partitioned = (e_loc < e) or (w_gate.shape[2] < m.d_ff_expert)
    if model_axes and partitioned:
        y = jax.lax.psum(y, model_axes)
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)
    me = jnp.mean(onehot.sum(2), axis=(0, 1))
    pe = jnp.mean(jax.nn.softmax(logits.astype(jnp.float32), -1), axis=(0, 1))
    aux = e * jnp.sum(me * pe)
    if gaxes:
        aux = jax.lax.pmean(aux, gaxes)
    return y, aux


class SparseMLP:
    """Pruned two-layer MLP whose layers pool one structure cache.

    Both :class:`~repro.models.sparse.SparseLinear` layers share a single
    ``plan.cache.StructureCache``: a serving loop that applies the MLP to
    recurring sparse-activation patterns pays the symbolic SpGEMM phase once
    per (pattern, layer) and runs numeric-only afterwards, with one shared
    LRU/stats surface for the whole block (pass ``cache=`` to pool wider,
    e.g. the engine-level cache in serve/engine.py).
    """

    def __init__(self, w_in: jax.Array, w_out: jax.Array, sparsity: float, *,
                 cache=None, cache_capacity: int = 16, nm="auto"):
        from repro.plan.cache import StructureCache
        from .sparse import SparseLinear
        self.cache = cache if cache is not None \
            else StructureCache(capacity=cache_capacity)
        self.fc_in = SparseLinear(w_in, sparsity, cache=self.cache, nm=nm)
        self.fc_out = SparseLinear(w_out, sparsity, cache=self.cache, nm=nm)

    def __call__(self, x: jax.Array) -> jax.Array:
        """Dense activations: x @ W_in → GELU → @ W_out (structured SpMMs)."""
        with _obs.span("sparse_mlp.apply"):
            return _obs.sync(self.fc_out(jax.nn.gelu(self.fc_in(x))))

    def cache_stats(self):
        """Hit/miss/eviction counters of the shared structure cache."""
        return self.cache.stats()


def moe_apply(p, x, cfg, dtype) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y, aux_loss). Tokens are grouped by data shard (GShard
    groups) so dispatch structures shard over "batch" and per-group capacity
    stays constant as the fleet scales."""
    from repro.parallel.sharding import axis_size
    b, s, d = x.shape
    t = b * s
    groups = max(1, min(axis_size("batch"), b))
    x_grp = x.reshape(groups, t // groups, d)
    with _obs.span("moe.dispatch", strategy=cfg.moe.dispatch,
                   tokens=t, experts=cfg.moe.n_experts):
        if cfg.moe.dispatch == "sort":
            y, aux = _moe_sort(p, x_grp, cfg, dtype)
        elif cfg.moe.dispatch == "spmm":
            y, aux = _moe_spmm(p, x_grp, cfg, dtype)
        else:
            y, aux = _moe_ellpack(p, x_grp, cfg, dtype)
        _obs.sync(y)
    if cfg.moe.n_shared:
        y = y + swiglu_apply(p["shared"], x_grp, dtype)
    return y.reshape(b, s, d), aux
