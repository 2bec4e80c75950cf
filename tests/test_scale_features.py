"""Large-scale-runnability features: elastic restore, long-context decode,
dry-run entry point, hwmodel properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # offline: fixed-seed shim
    from _propcheck import given, settings, strategies as st

from conftest import run_with_devices


def test_elastic_checkpoint_restore_new_sharding(tmp_path):
    """A checkpoint written unsharded restores onto a different mesh
    topology (elastic re-mesh after failures)."""
    run_with_devices(f"""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager

mgr = CheckpointManager("{tmp_path}")
params = {{"w": jnp.arange(64.0).reshape(8, 8)}}
opt = {{"step": jnp.array(3, jnp.int32)}}
mgr.save(1, params, opt)

# restore onto a 4x2 mesh with the leaf sharded over 'a'
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("a", "b"))
sh = {{"w": NamedSharding(mesh, P("a", "b"))}}
osh = {{"step": NamedSharding(mesh, P())}}
p2, o2, _ = mgr.restore(1, params, opt, shardings=(sh, osh))
assert p2["w"].sharding == sh["w"], p2["w"].sharding
np.testing.assert_allclose(np.asarray(p2["w"]), np.arange(64.0).reshape(8,8))
print("OK")
""", n_devices=8)


def test_long_context_ring_decode_mamba_and_rg():
    """Decode far past the window/prefill length: O(1)-state paths stay
    finite and the ring cache wraps correctly."""
    from repro.configs import get_config
    from repro.models import build_model
    for arch in ("falcon-mamba-7b", "recurrentgemma-9b"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 12),
                                              3, cfg.vocab)}
        logits, cache = model.prefill(params, batch, s_max=64)
        step = jax.jit(model.decode_step)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        # decode 3x the local-attention window (window=8 in reduced config)
        for _ in range(30):
            logits, cache = step(params, cache, tok)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        assert np.isfinite(np.asarray(logits)).all(), arch


def test_ring_cache_wraps_consistently():
    """After wrapping, ring-decode still matches a full forward pass."""
    from repro.configs import get_config
    from repro.models import build_model, transformer
    cfg = get_config("recurrentgemma-9b").reduced()   # window = 8
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    s = 24                                            # 3x window
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, s), 3, cfg.vocab)
    full_logits, _, _ = transformer.decoder_forward(params, toks, cfg)
    logits, cache = model.prefill(params, {"tokens": toks[:, :4]}, s_max=s + 2)
    for t in range(4, s):
        logits, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(full_logits[:, t], np.float32), rtol=0.15, atol=0.05,
            err_msg=f"pos {t}")


def test_dryrun_entrypoint_single_cell(tmp_path):
    """The dry-run driver itself works end-to-end from a fresh process
    (cheapest cell: falcon-mamba long_500k, batch 1, decode)."""
    import os, subprocess, sys, json
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    # the dry run compiles for fake host devices: it pins JAX_PLATFORMS=cpu
    # itself, and the child is given it too so it never reaches for a chip
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # dryrun sets its own device count
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "falcon-mamba-7b", "--shape", "long_500k", "--single-pod-only",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads((tmp_path / "falcon-mamba-7b__long_500k__pod16x16.json")
                     .read_text())
    assert rec["n_devices"] == 256
    assert rec["hlo_flops_tc"] > 0


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1_000, 200_000), k=st.integers(2, 200),
       pes=st.sampled_from([8, 16, 32]))
def test_hwmodel_monotonic(n, k, pes):
    """Latency grows with k and shrinks with PEs, for any matrix shape."""
    from repro.core import hwmodel
    s = hwmodel.MatrixStats(n=n, nnz_a=n * k // 2, nnz_b=n * k // 2,
                            k_a=k, k_b=k, valid_products=n * k * k // 4,
                            nnz_c=min(n * k, n * n), sigma=1.0)
    cfg = dataclasses.replace(hwmodel.SplimConfig(), n_pes=pes)
    lat = hwmodel.splim_latency(s, cfg)
    t = lat["total"]
    s2 = dataclasses.replace(s, k_a=k + 8, k_b=k + 8,
                             valid_products=int(s.valid_products * 1.2))
    assert hwmodel.splim_latency(s2, cfg)["total"] > t
    # more PEs speed up the compute/merge terms; the ring term (2T RowClones)
    # legitimately *grows* with T, so compare totals net of ring — tiny
    # matrices can be ring-dominated (over-parallelization, physically real)
    cfg2 = dataclasses.replace(cfg, n_pes=pes * 2)
    lat2 = hwmodel.splim_latency(s, cfg2)
    assert (lat2["total"] - lat2["ring"]) < (t - lat["ring"])
    assert hwmodel.splim_energy(s, cfg)["total"] > 0
