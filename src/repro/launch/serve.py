"""Serving launcher: ``python -m repro.launch.serve --arch <id> --smoke``.

Batched prefill+decode with the continuous-batching engine.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.parallel.sharding import sharding_rules
from repro.serve import ServeConfig, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    model = build_model(cfg)
    mesh = make_host_mesh(args.model_parallel)
    rng = np.random.default_rng(0)
    with sharding_rules(mesh), mesh:
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, ServeConfig(
            max_new_tokens=args.max_new))
        waves = [args.requests // eng.cfg.max_batch or 1]
        served = 0
        while served < args.requests:
            n = min(eng.cfg.max_batch, args.requests - served)
            prompts = [rng.integers(3, cfg.vocab, size=rng.integers(4, 16))
                       .astype(np.int32) for _ in range(n)]
            outs = eng.generate_batch(prompts)
            served += n
        s = eng.stats
        print(f"[serve] {s['requests']} reqs, {s['tokens']} tokens, "
              f"decode {s['tokens']/max(s['decode_s'],1e-9):.1f} tok/s",
              flush=True)


if __name__ == "__main__":
    main()
