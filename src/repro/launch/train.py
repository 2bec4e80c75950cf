"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the real Trainer (checkpoint/restart, fault tolerance) on whatever
devices this host offers. On a CPU box use a reduced (``--smoke``) config;
on a TPU slice point it at the production mesh with --model-parallel.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.parallel.sharding import sharding_rules
from repro.runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    name = args.arch + ("-smoke" if args.smoke else "")
    cfg = get_config(name)
    model = build_model(cfg)
    mesh = make_host_mesh(args.model_parallel)
    print(f"[train] arch={cfg.name} params={model.n_params():,} "
          f"mesh={dict(mesh.shape)}", flush=True)

    def extra(step):
        import numpy as np
        import jax.numpy as jnp
        rng = np.random.default_rng(step)
        if cfg.family == "audio":
            return {"frames": jnp.asarray(rng.standard_normal(
                (args.batch, cfg.encoder_seq, cfg.d_model), dtype=np.float32))}
        if cfg.family == "vlm":
            return {"patches": jnp.asarray(rng.standard_normal(
                (args.batch, cfg.n_vision_tokens, cfg.d_model), dtype=np.float32))}
        return {}

    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         global_batch=args.batch, seq_len=args.seq)
    with sharding_rules(mesh), mesh:
        trainer = Trainer(model, tcfg, AdamWConfig(lr=args.lr),
                          extra_batch_fn=extra if cfg.family in ("audio", "vlm") else None)
        out = trainer.run(resume=not args.no_resume)
    print(f"[train] done. final loss "
          f"{out['history'][-1]['loss']:.4f}", flush=True)


if __name__ == "__main__":
    main()
