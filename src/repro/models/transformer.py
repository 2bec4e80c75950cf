"""Generic decoder-only LM: composes attention/FFN/SSM/RG-LRU blocks.

An architecture is a *segment plan*: a list of (unit, repeats) where a unit
is a tuple of block kinds (e.g. recurrentgemma's ("rec","rec","local")).
Homogeneous repeats are stacked and scanned (compact HLO, fixed per-layer
memory); heterogeneous remainders unroll. The same plan drives parameter
construction, the forward/loss path, prefill, and cached decode, so every
(arch × shape) cell lowers from one code path.

Block kinds:
  attn       full-attention GQA + SwiGLU          (dense archs)
  attn_moe   GQA + SPLIM-dispatch MoE             (granite)
  mla_dense  MLA + SwiGLU                         (deepseek layer 0)
  mla_moe    MLA + MoE(+shared)                   (deepseek)
  mamba      Mamba-1 mixer only                   (falcon-mamba)
  rec        RG-LRU + SwiGLU                      (recurrentgemma)
  local      windowed GQA + SwiGLU                (recurrentgemma 1-in-3)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import maybe_shard

from . import attention as attn
from . import ffn, rglru, ssm
from .common import embed_lookup, embed_specs, next_token_loss, rmsnorm, unembed
from .params import Spec, stack

# ---------------------------------------------------------------------------
# Segment planning
# ---------------------------------------------------------------------------

def segment_plan(cfg) -> List[Tuple[Tuple[str, ...], int]]:
    L = cfg.n_layers
    if cfg.family == "ssm":
        return [(("mamba",), L)]
    if cfg.family == "hybrid":
        unit = tuple("local" if k == "attn" else k for k in cfg.griffin.pattern)
        reps, rem = divmod(L, len(unit))
        plan = [(unit, reps)]
        if rem:
            plan.append((unit[:rem], 1))
        return plan
    if cfg.moe is not None and cfg.mla is not None:
        fd = cfg.moe.first_dense_layers
        plan = []
        if fd:
            plan.append((("mla_dense",), fd))
        plan.append((("mla_moe",), L - fd))
        return plan
    if cfg.moe is not None:
        return [(("attn_moe",), L)]
    return [(("attn",), L)]


# ---------------------------------------------------------------------------
# Block specs / apply / cache
# ---------------------------------------------------------------------------

def _norm_spec(cfg):
    return Spec((cfg.d_model,), (None,), init="ones")


def block_specs(cfg, kind: str) -> Dict[str, Any]:
    s: Dict[str, Any] = {"ln1": _norm_spec(cfg)}
    if kind in ("attn", "attn_moe", "local"):
        s["attn"] = attn.gqa_specs(cfg)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = ffn.moe_specs(cfg) if kind == "attn_moe" else ffn.swiglu_specs(cfg)
    elif kind in ("mla_dense", "mla_moe"):
        s["attn"] = attn.mla_specs(cfg)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = ffn.moe_specs(cfg) if kind == "mla_moe" else ffn.swiglu_specs(cfg)
    elif kind == "mamba":
        s["mixer"] = ssm.mamba_specs(cfg)
    elif kind == "rec":
        s["rec"] = rglru.rglru_specs(cfg)
        s["ln2"] = _norm_spec(cfg)
        s["ffn"] = ffn.swiglu_specs(cfg)
    else:
        raise ValueError(kind)
    return s


def _ffn_apply(p, x, cfg, kind, dtype):
    if kind in ("attn_moe", "mla_moe"):
        return ffn.moe_apply(p, x, cfg, dtype)
    y = ffn.swiglu_apply(p, x, dtype)
    return y, jnp.zeros((), jnp.float32)


def block_apply_full(p, x, cfg, kind: str, dtype,
                     want_cache: bool, s_max: int = 0):
    """Full-seq path. Returns (x, aux_loss, cache_slice_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind in ("attn", "attn_moe", "local"):
        window = cfg.griffin.window if kind == "local" else cfg.attn_window
        out, kv = attn.gqa_full(p["attn"], h, cfg, dtype, window=window,
                                return_kv=want_cache)
        x = x + out
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        y, aux = _ffn_apply(p["ffn"], h2, cfg, kind, dtype)
        x = x + y
        if want_cache:
            k, v = kv
            if kind == "local":                 # ring buffer: last W slots
                w = cfg.griffin.window
                s = x.shape[1]
                if s >= w:
                    # slot layout must match decode's pos % w indexing
                    shift = s % w
                    k, v = k[:, -w:], v[:, -w:]
                    k = jnp.roll(k, shift, axis=1)
                    v = jnp.roll(v, shift, axis=1)
                    slot_pos = jnp.roll(jnp.arange(s - w, s), shift)
                else:
                    pad = w - s
                    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    slot_pos = jnp.concatenate(
                        [jnp.arange(s), jnp.full((pad,), -1, jnp.int32)])
            else:
                pad = s_max - k.shape[1]
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                # pin the cache shards at construction — otherwise the
                # per-layer stacked (L,B,S_max,kv,hd) prefill buffer
                # materializes replicated before the jit-boundary sharding
                k = maybe_shard(k, "batch", "seq_shard", None, None)
                v = maybe_shard(v, "batch", "seq_shard", None, None)
                slot_pos = jnp.where(jnp.arange(s_max) < x.shape[1],
                                     jnp.arange(s_max), -1)
            cache = {"k": k, "v": v, "slot_pos": slot_pos}
    elif kind in ("mla_dense", "mla_moe"):
        out, kv = attn.mla_full(p["attn"], h, cfg, dtype, return_kv=want_cache)
        x = x + out
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        y, aux = _ffn_apply(p["ffn"], h2, cfg, kind, dtype)
        x = x + y
        if want_cache:
            latent, krope = kv
            pad = s_max - latent.shape[1]
            cache = {"latent": jnp.pad(latent, ((0, 0), (0, pad), (0, 0))),
                     "krope": jnp.pad(krope, ((0, 0), (0, pad), (0, 0)))}
    elif kind == "mamba":
        out, st = ssm.mamba_apply_full(p["mixer"], h, cfg, dtype,
                                       return_state=want_cache)
        x = x + out
        if want_cache:
            cache = {"conv": st[0], "ssm": st[1]}
    elif kind == "rec":
        out, st = rglru.rglru_apply_full(p["rec"], h, cfg, dtype,
                                         return_state=want_cache)
        x = x + out
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        y, _ = _ffn_apply(p["ffn"], h2, cfg, kind, dtype)
        x = x + y
        if want_cache:
            cache = {"conv": st[0], "h": st[1]}
    else:
        raise ValueError(kind)
    return x, aux, cache


def block_cache_zeros(cfg, kind: str, batch: int, s_max: int, dtype):
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    if kind in ("attn", "attn_moe"):
        return {"k": jnp.zeros((batch, s_max, kv, hd), dtype),
                "v": jnp.zeros((batch, s_max, kv, hd), dtype),
                "slot_pos": jnp.full((s_max,), -1, jnp.int32)}
    if kind == "local":
        w = cfg.griffin.window
        return {"k": jnp.zeros((batch, w, kv, hd), dtype),
                "v": jnp.zeros((batch, w, kv, hd), dtype),
                "slot_pos": jnp.full((w,), -1, jnp.int32)}
    if kind in ("mla_dense", "mla_moe"):
        m = cfg.mla
        return {"latent": jnp.zeros((batch, s_max, m.kv_lora_rank), dtype),
                "krope": jnp.zeros((batch, s_max, m.rope_head_dim), dtype)}
    if kind == "mamba":
        di = cfg.ssm.expand * cfg.d_model
        return {"conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, di), dtype),
                "ssm": jnp.zeros((batch, di, cfg.ssm.d_state), jnp.float32)}
    if kind == "rec":
        w = rglru._width(cfg)
        return {"conv": jnp.zeros((batch, cfg.griffin.conv_width - 1, w), dtype),
                "h": jnp.zeros((batch, w), jnp.float32)}
    raise ValueError(kind)


def block_apply_decode(p, x, cfg, kind: str, dtype, cache, pos):
    """One-token path. Returns (x, new_cache)."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind in ("attn", "attn_moe", "local"):
        if kind == "local":
            w = cfg.griffin.window
            slot = pos % w
            out, ck, cv = attn.gqa_decode_ring(
                p["attn"], h, cfg, dtype, cache["k"], cache["v"],
                cache["slot_pos"], pos, slot, w)
            new_slot_pos = cache["slot_pos"].at[slot].set(pos)
            cache = {"k": ck, "v": cv, "slot_pos": new_slot_pos}
        else:
            out, ck, cv = attn.gqa_decode(p["attn"], h, cfg, dtype,
                                          cache["k"], cache["v"], pos)
            cache = {"k": ck, "v": cv, "slot_pos": cache["slot_pos"].at[pos].set(pos)}
        x = x + out
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        y, _ = _ffn_apply(p["ffn"], h2, cfg, kind, dtype)
        x = x + y
    elif kind in ("mla_dense", "mla_moe"):
        out, cl, ckr = attn.mla_decode(p["attn"], h, cfg, dtype,
                                       cache["latent"], cache["krope"], pos)
        cache = {"latent": cl, "krope": ckr}
        x = x + out
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        y, _ = _ffn_apply(p["ffn"], h2, cfg, kind, dtype)
        x = x + y
    elif kind == "mamba":
        out, conv, st = ssm.mamba_decode(p["mixer"], h, cfg, dtype,
                                         cache["conv"], cache["ssm"])
        cache = {"conv": conv, "ssm": st}
        x = x + out
    elif kind == "rec":
        out, conv, hst = rglru.rglru_decode(p["rec"], h, cfg, dtype,
                                            cache["conv"], cache["h"])
        cache = {"conv": conv, "h": hst}
        x = x + out
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        y, _ = _ffn_apply(p["ffn"], h2, cfg, kind, dtype)
        x = x + y
    else:
        raise ValueError(kind)
    return x, cache


# ---------------------------------------------------------------------------
# Whole-model spec / apply
# ---------------------------------------------------------------------------

def decoder_specs(cfg) -> Dict[str, Any]:
    segs = []
    for unit, reps in segment_plan(cfg):
        unit_specs = {f"u{i}": block_specs(cfg, kind)
                      for i, kind in enumerate(unit)}
        segs.append(stack(unit_specs, reps) if reps > 1 else unit_specs)
    return {
        "embed": embed_specs(cfg),
        "segments": segs,
        "ln_f": _norm_spec(cfg),
    }


def _remat_factor(n: int):
    """Balanced (outer, inner) factoring for hierarchical remat."""
    a = int(n ** 0.5)
    while a > 1 and n % a:
        a -= 1
    return (a, n // a) if a > 1 else (1, n)


def _maybe_remat(f, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(f)
    if cfg.remat == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return f


def decoder_forward(params, tokens, cfg, *, prefix_embed=None,
                    want_cache: bool = False, s_max: int = 0,
                    return_hidden: bool = False):
    """Full-seq forward. tokens: (B,S) int32. prefix_embed: optional
    (B,P,d) continuous prefix (VLM patch embeddings stub).

    Returns (logits, aux_loss, cache_or_None).
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    x = embed_lookup(params["embed"], tokens, dtype)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(dtype), x], axis=1)
    s_max = s_max or x.shape[1]
    aux_total = jnp.zeros((), jnp.float32)
    caches = []

    for seg_params, (unit, reps) in zip(params["segments"], segment_plan(cfg)):
        def seg_body(x, p_slice):
            # barrier pins per-iteration consumption of the remat-saved carry
            # so XLA cannot hoist a whole-stack fp32 convert out of the
            # backward loop (16.5 GiB/device on mistral-123b; §Perf iter 1);
            # its VJP applies the barrier to the cotangent as well
            x = jax.lax.optimization_barrier(x)
            aux_seg = jnp.zeros((), jnp.float32)
            cache_u = {}
            for i, kind in enumerate(unit):
                x, aux, c = block_apply_full(p_slice[f"u{i}"], x, cfg, kind,
                                             dtype, want_cache, s_max)
                aux_seg = aux_seg + aux
                if want_cache:
                    cache_u[f"u{i}"] = c
            # Megatron-SP: residual stream sharded (batch, seq) between blocks
            x = maybe_shard(x, "batch", "seq_act", None)
            return x, (aux_seg, cache_u)

        if reps > 1:
            body = _maybe_remat(seg_body, cfg)
            outer, inner = _remat_factor(reps) if cfg.remat == "full" else (1, reps)
            if outer > 1 and not want_cache:
                # Hierarchical (√-style) remat: only outer-group carries are
                # saved across the whole backward (outer × (B,S,d) instead of
                # reps ×); inner layers re-save transiently during one
                # group's backward. Cuts the saved-stack (and XLA's hoisted
                # fp32 copy of it) by ~inner×. §Perf iteration 3.
                grouped = jax.tree.map(
                    lambda a: a.reshape((outer, inner) + a.shape[1:]), seg_params)

                # (§Perf cell C, iteration 3 — REFUTED: dropping the
                # per-layer remat inside groups cut FLOPs 16% but the inner
                # backward then saves full layer internals: temp 27→78 GiB.
                # Per-layer remat inside checkpointed groups it is.)
                @jax.checkpoint
                def group_body(xc, p_group):
                    xc, (auxs, _) = jax.lax.scan(body, xc, p_group)
                    return xc, (auxs, {})

                x, (auxs, cache_seg) = jax.lax.scan(group_body, x, grouped)
                cache_seg = None
            else:
                x, (auxs, cache_seg) = jax.lax.scan(body, x, seg_params)
            aux_total = aux_total + jnp.sum(auxs)
        else:
            body = _maybe_remat(seg_body, cfg)
            x, (aux1, cache_seg) = body(x, seg_params)
            aux_total = aux_total + aux1
        caches.append(cache_seg)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    if return_hidden:
        return x, aux_total, (caches if want_cache else None)
    logits = unembed(params["embed"], x, dtype)
    return logits, aux_total, (caches if want_cache else None)


def decoder_loss(params, tokens, cfg, prefix_embed=None) -> jax.Array:
    """LM loss via the sequence-sharded softmax-xent (§Perf iteration 2)."""
    from .common import sharded_softmax_xent
    dtype = jnp.dtype(cfg.compute_dtype)
    hidden, aux, _ = decoder_forward(params, tokens, cfg,
                                     prefix_embed=prefix_embed,
                                     return_hidden=True)
    if prefix_embed is not None:
        hidden = hidden[:, prefix_embed.shape[1]:]
    if "out" in params["embed"]:
        w_out = params["embed"]["out"].astype(dtype)
    else:
        w_out = params["embed"]["tok"].astype(dtype).T
    loss = sharded_softmax_xent(hidden, w_out, tokens)
    return loss + 0.01 * aux


def decoder_prefill(params, tokens, cfg, s_max: int, prefix_embed=None):
    dtype = jnp.dtype(cfg.compute_dtype)
    # unembed only the final position — full-sequence prefill logits would
    # materialize (B·S, V) fp32 (22.6 GiB/device on internvl2 prefill_32k)
    hidden, _, caches = decoder_forward(params, tokens, cfg,
                                        prefix_embed=prefix_embed,
                                        want_cache=True, s_max=s_max,
                                        return_hidden=True)
    logits = unembed(params["embed"], hidden[:, -1:], dtype)
    pos = jnp.array(tokens.shape[1] + (prefix_embed.shape[1] if prefix_embed is not None else 0),
                    jnp.int32)
    return logits[:, 0], {"layers": caches, "pos": pos}


def decoder_cache_zeros(cfg, batch: int, s_max: int):
    dtype = jnp.dtype(cfg.compute_dtype)
    caches = []
    for unit, reps in segment_plan(cfg):
        cache_u = {f"u{i}": block_cache_zeros(cfg, kind, batch, s_max, dtype)
                   for i, kind in enumerate(unit)}
        if reps > 1:
            cache_u = jax.tree.map(
                lambda c: jnp.broadcast_to(c[None], (reps,) + c.shape), cache_u)
        caches.append(cache_u)
    return {"layers": caches, "pos": jnp.zeros((), jnp.int32)}


def decoder_decode_step(params, cache, tokens, cfg):
    """tokens: (B,1). Returns (logits (B,V), new_cache)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    pos = cache["pos"]
    x = embed_lookup(params["embed"], tokens, dtype)
    new_caches = []
    for seg_params, seg_cache, (unit, reps) in zip(
            params["segments"], cache["layers"], segment_plan(cfg)):
        def seg_body(x, pc):
            p_slice, c_slice = pc
            new_c = {}
            for i, kind in enumerate(unit):
                x, nc = block_apply_decode(p_slice[f"u{i}"], x, cfg, kind,
                                           dtype, c_slice[f"u{i}"], pos)
                new_c[f"u{i}"] = nc
            return x, new_c

        if reps > 1:
            x, new_seg = jax.lax.scan(seg_body, x, (seg_params, seg_cache))
        else:
            x, new_seg = seg_body(x, (seg_params, seg_cache))
        new_caches.append(new_seg)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, dtype)
    return logits[:, 0], {"layers": new_caches, "pos": pos + 1}
