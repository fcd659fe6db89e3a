"""Parameter initializers for the functional module system.

Params are plain nested dicts of jnp arrays. Sharding is attached by
name-based logical-axis rules (see ``repro.distributed.sharding``), so init
stays trivially simple and scan-stackable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def trunc_normal(key, shape, dtype=jnp.float32, stddev=0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


def dense_init(key, d_in, d_out, dtype=jnp.float32, scale=None):
    stddev = scale if scale is not None else (1.0 / (d_in ** 0.5))
    return trunc_normal(key, (d_in, d_out), dtype, stddev)


def zeros(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


def split(key, n):
    return list(jax.random.split(key, n))


def cast_floating(tree, dtype):
    """Cast every floating leaf of ``tree`` to ``dtype`` (ints untouched)."""
    dtype = jnp.dtype(dtype)
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)
