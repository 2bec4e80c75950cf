"""The one platform predicate every realization choice reads.

Kernels, the streaming engine, the planner's cost model and the MoE
dispatch pick between a compiled Pallas kernel and its bit-identical XLA
realization from this module only, so a test can steer all of them by
monkeypatching ``on_tpu``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU. Resolved at trace time, so
    jitted callers bake in the choice for the backend they compile for."""
    return jax.default_backend() == "tpu"


def searchsorted(sorted_arr: jax.Array, query: jax.Array, *,
                 side: str = "left") -> jax.Array:
    """``jnp.searchsorted`` in the method the platform suits: on TPU a
    sort-based rank (two sorts), since the default binary search issues one
    dependent gather pass per level over every query; the binary search
    elsewhere."""
    return jnp.searchsorted(sorted_arr, query, side=side,
                            method="sort" if on_tpu() else "scan")


def resolve_mode(interpret: bool | None) -> str:
    """Realization of a kernel that has an XLA twin.

    ``None`` → ``'pallas'`` (compiled) on TPU, ``'xla'`` elsewhere — never
    the interpreter, which is the debug path. Explicit ``True``/``False``
    force ``'interpret'``/``'pallas'`` (kernel correctness tests exercise
    the interpreter off-TPU this way). Resolved in non-jitted wrappers so a
    backend change never hits a stale jit cache.
    """
    if interpret is None:
        return "pallas" if on_tpu() else "xla"
    return "interpret" if interpret else "pallas"
