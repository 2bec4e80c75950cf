"""Pallas TPU kernels for SPLIM's compute hot-spots (validated interpret=True).

  sccp_multiply   — structured slab-pair multiply (paper Fig. 8), VMEM-tiled
  fused_sccp_stream — one streaming step fused: slab multiply + packed-key
                    bitonic sort entirely in VMEM (feeds core/streaming)
  bitonic_merge   — sort + segmented-sum: the in-situ search's batched dual
  radix_bucket    — propagation-blocking accumulation (bin by row range,
                    per-bucket bitonic sort/reduce)
  hash_accum      — per-row-block open-addressing hash accumulation
  insitu_search   — the paper's Algorithm 1 itself (bit-serial minima search)
  ell_spmm        — ELLPACK × dense via one-hot MXU tiles (MoE/SparseLinear)
  ops             — jit'd public wrappers (padding, realization choice)
  platform        — the one TPU predicate every realization choice reads
  ref             — pure-jnp oracles for every kernel
"""
from . import ops, ref

__all__ = ["ops", "ref"]
