"""Random weights for a served model, made on the device from the seed.

One jitted call makes every leaf of the program's parameter tree in the
dtype it is served in. A matrix leaf is ``N(0, 0.02)``; a norm weight is
``1 + N(0, 0.1)``, so the norms' scales are exercised too. Each leaf's key
is folded from its path, so a leaf does not change when another is added.
"""

from __future__ import annotations

import zlib
from typing import Any

import jax
import jax.numpy as jnp

MATRIX_STD, NORM_STD = 0.02, 0.1


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _is_norm(path: tuple) -> bool:
    return any(str(getattr(k, "key", k)) in ("ln1", "ln2", "final_norm") for k in path)


def make(shapes: Any, seed: int, std: float = MATRIX_STD) -> Any:
    """Arrays shaped like ``shapes`` (a tree of ShapeDtypeStruct). ``std``
    suits the published widths; a model a few dozen wide needs more."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for path, s in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = jax.random.normal(k, s.shape, jnp.float32)
            x = 1.0 + NORM_STD * x if _is_norm(path) else std * x
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
