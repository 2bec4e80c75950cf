"""End-to-end behaviour tests: training improves loss, resume works, the
loss implementations agree, MoE dispatch variants agree, and hwmodel
reproduces the paper's headline means."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig


def test_training_reduces_loss(tmp_path):
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    tcfg = TrainerConfig(steps=30, log_every=5, ckpt_every=100,
                         ckpt_dir=str(tmp_path), global_batch=8, seq_len=64)
    out = Trainer(model, tcfg, AdamWConfig(lr=3e-3, warmup_steps=5)).run(
        resume=False)
    losses = [h["loss"] for h in out["history"]]
    assert losses[-1] < losses[0] - 0.2, losses


def test_checkpoint_resume_continues(tmp_path):
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    t1 = TrainerConfig(steps=10, log_every=2, ckpt_every=10,
                       ckpt_dir=str(tmp_path), global_batch=4, seq_len=32)
    Trainer(model, t1, AdamWConfig(lr=1e-3)).run(resume=False)
    # second run extends to 14 steps and must resume from step 10
    t2 = dataclasses.replace(t1, steps=14)
    trainer = Trainer(model, t2, AdamWConfig(lr=1e-3))
    out = trainer.run(resume=True)
    steps = [h["step"] for h in out["history"]]
    assert min(steps) >= 10, f"should resume at step 10, got {steps}"


def test_sharded_loss_matches_naive():
    from repro.models.common import next_token_loss, sharded_softmax_xent
    rng = jax.random.PRNGKey(0)
    b, s, d, v = 2, 8, 16, 32
    x = jax.random.normal(rng, (b, s, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v)) * 0.1
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)
    naive = next_token_loss((x @ w)[:, :, :], toks, z_loss=0.0)
    shard = sharded_softmax_xent(x, w, toks, z_loss=0.0)
    np.testing.assert_allclose(float(naive), float(shard), rtol=1e-5)


def test_moe_dispatch_variants_agree():
    """'ellpack' (one-hot), 'sort' (SPLIM-style) and 'spmm' (routing matrix
    as row-wise ELLPACK through the SpGEMM stack) dispatch must agree when
    capacity is ample (no token drops)."""
    base = get_config("granite-moe-3b-a800m").reduced()
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, base.vocab)
    losses = {}
    for disp in ("ellpack", "sort", "spmm"):
        cfg = dataclasses.replace(
            base, moe=dataclasses.replace(base.moe, dispatch=disp,
                                          capacity_factor=4.0))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        losses[disp] = float(model.loss(params, {"tokens": toks}))
    np.testing.assert_allclose(losses["ellpack"], losses["sort"], rtol=1e-3)
    np.testing.assert_allclose(losses["ellpack"], losses["spmm"], rtol=1e-3)


def test_hwmodel_reproduces_paper_means():
    from benchmarks.common import all_stats
    from repro.core import hwmodel
    stats = all_stats()
    cal = hwmodel.calibrate(stats)
    t_splim = np.array([hwmodel.splim_latency(s)["total"] for s in stats])
    t_gpu = np.array([hwmodel.gpu_latency(s) * cal["gpu_perf"] for s in stats])
    assert np.mean(t_gpu / t_splim) == pytest.approx(275.74, rel=1e-3)
    e_splim = np.array([hwmodel.splim_energy(s)["total"] for s in stats])
    e_gpu = np.array([hwmodel.gpu_energy(s) * cal["gpu_energy"] for s in stats])
    assert np.mean(e_gpu / e_splim) == pytest.approx(687.19, rel=1e-3)


def test_hwmodel_sensitivity_directions():
    """Paper §VI-C: sparser ⇒ faster; smaller σ ⇒ faster; more PEs ⇒ faster."""
    import math
    from benchmarks.common import all_stats
    from benchmarks.paper_figures import _scaled_stats
    from repro.core import hwmodel
    s = all_stats()[0]
    t1 = hwmodel.splim_latency(s)["total"]
    assert hwmodel.splim_latency(_scaled_stats(s, 0.5))["total"] < t1
    k_small = max(1, int(math.ceil(s.nnz_a / s.n + s.sigma / 3)))
    s_sig = dataclasses.replace(s, k_a=k_small, k_b=k_small)
    assert hwmodel.splim_latency(s_sig)["total"] < t1
    cfg8 = dataclasses.replace(hwmodel.SplimConfig(), n_pes=8)
    assert hwmodel.splim_latency(s, cfg8)["total"] > t1


def test_splim_beats_coo_splim_everywhere():
    """§IV-C: the SCCP paradigm dominates the decompression paradigm."""
    from benchmarks.common import all_stats
    from repro.core import hwmodel
    for s in all_stats():
        t = hwmodel.splim_latency(s)["total"]
        t_coo = hwmodel.coo_splim_latency(s)["total"]
        assert t < t_coo, (s.n, t, t_coo)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_location(tmp_path, env_dir):
    """Entry points keep compiled programs where JAX_COMPILATION_CACHE_DIR
    says, else in ``<repo>/.jax_cache``; importing repro sets no cache."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(repo / ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax, repro\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "used = enable_compile_cache()\n"
            "print(before, used, jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    before, used, after = out.stdout.split()
    assert used == after == want
    assert before == (want if env_dir else "None")
