"""Persistent XLA compilation cache for the program's entry points.

Called by the launchers (``launch/serve.py``, ``launch/train.py``), the
benchmark runner and ``chip_smoke.py`` — never on ``import repro``, so a
library user's process keeps whatever cache policy it set.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory used.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and it
    is left alone. Otherwise the cache lives at ``<repo>/.jax_cache``: a
    fixed path, since a directory that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
