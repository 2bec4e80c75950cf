"""Pallas TPU kernel: N:M balanced-sparsity SpMM (gather-free, MXU path).

Y[t, :] = Σ_r  V[r, :] · X[t, M·(r // N) + O[r, :]]

``V``/``O`` are the nmSPARSE-style condensed planes of a weight whose
reduction dimension is exactly N-in-M balanced: ``V`` holds the N surviving
values of every M-wide window as dense rows (R = d_in·N/M of them) and ``O``
the within-window offsets (log2(M)-bit payload, stored int8). The balance
guarantee is what makes the kernel gather-free: instead of indexing X with
``O`` (a gather TPUs hate), each of the M possible offsets is handled as a
*masked dense matmul* —

    Y = Σ_{m < M}  X[:, windows·M + m] (repeated N×)  @  where(O == m, V, 0)

so the MXU sees M static (BT, BR) @ (BR, D) products per tile pair and the
offset planes only ever feed a vectorized compare. Per-window balance means
every condensed row carries real work: tiles are conflict-free and perfectly
load-balanced, which unstructured ELLPACK/COO paths cannot guarantee
(nmSPARSE's central observation, applied to SPLIM's structured multiply).

Grid: (t_tiles, r_tiles); the offset loop (M, small & static) is unrolled.
Output tile (BT, D) is revisited across r_tiles and accumulated in place.
BT = BR = 128 (MXU native); BR covers BR//N windows, so the X tile is
(BT, BR·M/N) — the dense columns those windows read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import pad_to
from .platform import resolve_mode

BT = 128   # token tile
BR = 128   # condensed-row tile (must be a multiple of N)


def _nm_spmm_kernel(x_ref, val_ref, off_ref, o_ref, *, n: int, m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                          # (BT, BR·m/n) dense window cols
    val = val_ref[...]                      # (BR, D) condensed values
    off = off_ref[...].astype(jnp.int32)    # (BR, D) within-window offsets
    bx = x.shape[1]
    # window column of every condensed row: selection matrices on the MXU
    # stand in for the strided relayout Mosaic cannot lower
    col = jax.lax.broadcasted_iota(jnp.int32, (bx, BR), 0)
    base = (jax.lax.broadcasted_iota(jnp.int32, (bx, BR), 1) // n) * m
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for s in range(m):                      # static unroll over offsets
        # window column s, repeated N× to line up with condensed rows
        # (exact: one 1 per selection column)
        xs = jnp.dot(x, (col == base + s).astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(x.dtype)
        vs = jnp.where(off == s, val.astype(jnp.float32), 0.0)
        acc = acc + jnp.dot(xs, vs, preferred_element_type=jnp.float32)
    o_ref[...] += acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "d_in", "interpret"))
def nm_spmm_pallas(x: jax.Array, val: jax.Array, off: jax.Array,
                   *, n: int, m: int, d_in: int,
                   interpret: bool) -> jax.Array:
    """X(t, d_in) × condensed N:M planes (R, d_out) -> (t, d_out).

    t % BT == 0, R % BR == 0 (window-aligned), handled by nm_spmm padding.
    """
    t, di = x.shape
    r, d_out = val.shape
    assert di == d_in and off.shape == val.shape
    assert t % BT == 0 and r % BR == 0 and BR % n == 0
    assert d_in == r * m // n
    bx = BR * m // n                        # dense cols one row tile reads
    grid = (t // BT, r // BR)
    kern = functools.partial(_nm_spmm_kernel, n=n, m=m)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BT, bx), lambda i, j: (i, j)),
            pl.BlockSpec((BR, d_out), lambda i, j: (j, 0)),
            pl.BlockSpec((BR, d_out), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BT, d_out), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d_out), x.dtype),
        interpret=interpret,
    )(x, val, off)


@functools.partial(jax.jit, static_argnames=("n", "m"))
def nm_spmm_xla(x: jax.Array, val: jax.Array, off: jax.Array,
                *, n: int, m: int) -> jax.Array:
    """XLA realization of the same masked-matmul sum (off-TPU default)."""
    t, d_in = x.shape
    r, d_out = val.shape
    windows = d_in // m
    xw = x.reshape(t, windows, m)
    off32 = off.astype(jnp.int32)
    acc = jnp.zeros((t, d_out), jnp.float32)
    for s in range(m):
        xs = jnp.broadcast_to(xw[:, :, s][:, :, None],
                              (t, windows, n)).reshape(t, r)
        vs = jnp.where(off32 == s, val.astype(jnp.float32), 0.0)
        acc = acc + jnp.dot(xs, vs, preferred_element_type=jnp.float32)
    return acc.astype(x.dtype)


def nm_spmm(x: jax.Array, val: jax.Array, off: jax.Array,
            *, n: int, m: int, interpret: bool | None = None) -> jax.Array:
    """Y = X @ W for an N:M-condensed W; pads and picks the realization.

    ``interpret`` follows the repo-wide :func:`resolve_mode` convention:
    ``None`` → compiled Pallas on TPU, XLA elsewhere; ``True``/``False``
    force the interpreter / compiled Pallas (kernel tests off-TPU).
    """
    t, d_in = x.shape
    r, d_out = val.shape
    if d_in * n != r * m:
        raise ValueError(f"condensed rows {r} != d_in*N/M = {d_in}*{n}/{m}")
    mode = resolve_mode(interpret)
    if mode == "xla":
        return nm_spmm_xla(x, val, off, n=n, m=m)
    # pad tokens to BT, condensed rows to BR (window-aligned since BR % n
    # == 0 and off pads with 0 → reads padded-zero x columns, adds nothing)
    x_p = pad_to(pad_to(x, 0, BT, 0), 1, BR * m // n, 0)
    val_p = pad_to(val, 0, BR, 0)
    off_p = pad_to(off, 0, BR, 0)
    outs = []
    for lo in range(0, d_out, 512):         # chunk D like ops.ell_spmm
        y = nm_spmm_pallas(x_p, val_p[:, lo:lo + 512], off_p[:, lo:lo + 512],
                           n=n, m=m, d_in=x_p.shape[1],
                           interpret=(mode == "interpret"))
        outs.append(y)
    out = jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
    return out[:t]
