"""The Pallas kernels compile for a TPU v5e chip.

Interpret-mode tests check what a kernel computes; only the chip's own
compiler refuses primitives Mosaic cannot lower, blocks that break its
tiling and kernels that outgrow VMEM. Each test compiles one kernel at the
widest block its wrapper admits on TPU, against a described ``v5e:2x2``
topology — nothing runs, so no chip is needed, only the TPU compiler.

The topology is described inside a module fixture (never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file), and tests skip where it cannot be described.
JAX's persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (bitonic_merge, ell_spmm, fused_sccp_stream,
                           hash_accum, insitu_search, nm_spmm, radix_bucket)

I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


# name -> (function of array args, [(shape, dtype), ...]); shapes are the
# widest blocks the TPU dispatch sends each kernel
CASES = {
    # 'tiled' / bucket / hash tiles (Plan.tile default), one grid step each
    "sort_tiles": (functools.partial(bitonic_merge.sort_tiles_pallas,
                                     tile=4096, interpret=False),
                   [((1 << 18,), I32), ((1 << 18,), F32)]),
    # one streaming step: an (n, k_b) slab tile packed and sorted in VMEM
    "fused_slab_sort": (functools.partial(
        fused_sccp_stream.fused_slab_sort_pallas, n_cols=45_000,
        interpret=False),
        [((512,), F32), ((512,), I32), ((512, 8), F32), ((512, 8), I32)]),
    "align_keys": (functools.partial(insitu_search._align_keys_pallas_jit,
                                     interpret=False),
                   [((1 << 16,), I32), ((insitu_search.ALIGN_MAX_KEYS,),
                                        I32)]),
    "bin_ranks": (functools.partial(radix_bucket.bin_ranks_pallas,
                                    n_buckets=64, interpret=False),
                  [((1 << 16,), I32)]),
    "hash_accum": (functools.partial(hash_accum.hash_merge, n_blocks=64,
                                     block_cap=4096, keys_per_block=1 << 20,
                                     interpret=False),
                   [((1 << 20,), I32), ((1 << 20,), F32)]),
    # MoE dispatch widths: 8 routing slabs over 1024 tokens, d chunk 512
    "ell_spmm": (functools.partial(ell_spmm.ell_spmm_pallas, n_rows=1024,
                                   interpret=False),
                 [((8, 1024), F32), ((8, 1024), I32), ((1024, 512), F32)]),
    # qwen2-0.5b FFN down-projection at 2:4: d_ff 4864 -> d_model 896
    "nm_spmm": (functools.partial(nm_spmm.nm_spmm, n=2, m=4,
                                  interpret=False),
                [((128, 4864), BF16), ((2432, 896), BF16),
                 ((2432, 896), jnp.int8)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = CASES[name]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("emit", ["emit_sorted_unique", "emit_ranked"])
def test_emission_code_does_not_grow_with_stream(emit, one_chip):
    """The 'search' emission compiles to one program whose generated code
    stays the same size as the stream grows (an unrolled merge tree adds a
    level per doubling, and its code and compile memory with it)."""
    fn = getattr(insitu_search, emit)

    def code_bytes(log2_keys):
        out_cap = 1 << (log2_keys - 2)
        spec = jax.ShapeDtypeStruct((1 << log2_keys,), I32, sharding=one_chip)
        lowered = jax.jit(lambda k: fn(k, out_cap=out_cap)).lower(spec)
        return len(lowered.compile().as_text())

    assert code_bytes(18) <= 2 * code_bytes(14)
