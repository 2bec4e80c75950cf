"""Production mesh construction (DESIGN.md §5).

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes.

    ``jax.make_mesh`` builds Explicit axes by default, under which gathers
    inside ``shard_map`` bodies and ``with_sharding_constraint`` on
    logical-axis rules are refused. Every shard_map body and sharding rule
    in this repo is written for Auto axes (GSPMD propagation), so every
    mesh is built here.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host offers (tests / examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
