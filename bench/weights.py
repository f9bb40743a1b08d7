"""Seeded random weights of a configuration, made on the device.

Every tensor is drawn from the run's seed and its own place in the model
(layer, name), so the program's parameter tree and the plain reference,
which builds one layer at a time, draw exactly the same numbers without
either handing the other anything. An architecture (``bench/arch/``) names
its tensors in draw order; its sizes object gives each one's ``shape(name)``
and the ``dtype`` it is served in. This module imports nothing of the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
NORM_STD = 0.1


def base_key(seed: int) -> jax.Array:
    """A key from a whole-number seed of any size (64 bits kept)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xffffffff),
                              seed >> 32)


def _draw(key, name: str, shape, dtype):
    if name.endswith('_scale'):
        x = 1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
    elif name == 'embed':
        x = EMBED_STD * jax.random.normal(key, shape, jnp.float32)
    else:           # projection, scaled by its fan-in (axis -2: a stack
        # of experts (E, in, out) draws each as one projection)
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return x.astype(dtype)


def layer_weights(key: jax.Array, i, dims, names: tuple) -> dict:
    """Layer ``i``'s tensors ``names`` (``i`` may be traced, so this
    vmaps)."""
    k = jax.random.fold_in(key, 1000 + i)
    return {n: _draw(jax.random.fold_in(k, j), n, dims.shape(n),
                     jnp.dtype(dims.dtype))
            for j, n in enumerate(names)}


def top_weights(key: jax.Array, dims, names: tuple) -> dict:
    """The tensors outside the layers: embedding, final norm, head."""
    return {n: _draw(jax.random.fold_in(key, j), n, dims.shape(n),
                     jnp.dtype(dims.dtype))
            for j, n in enumerate(names)}
