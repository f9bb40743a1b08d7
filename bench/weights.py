"""Seeded random weights of a configuration, made on the device.

Every tensor is drawn from the run's seed and its own place in the model
(layer, name), so the program's parameter tree (``bench/model.py``) and the
plain reference (``bench/reference.py``), which builds one layer at a time,
draw exactly the same numbers without either handing the other anything.
This module imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))

# tensors per layer and at the top, in draw order; shapes from Dims.shape
LAYER_TENSORS = ('ln1_scale', 'wq', 'wk', 'wv', 'wo', 'ln2_scale', 'w_up',
                 'w_gate', 'w_down')
TOP_TENSORS = ('embed', 'final_scale', 'lm_head')
EMBED_STD = 0.02
NORM_STD = 0.1


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration, as run: a Mistral-family decoder
    (serial blocks, RMSNorm, SwiGLU, grouped-query attention)."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str

    def shape(self, name: str):
        d, q, e = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return {'ln1_scale': (d,), 'ln2_scale': (d,), 'final_scale': (d,),
                'wq': (d, q), 'wk': (d, e), 'wv': (d, e), 'wo': (q, d),
                'w_up': (d, self.ff), 'w_gate': (d, self.ff),
                'w_down': (self.ff, d), 'embed': (self.vocab, d),
                'lm_head': (d, self.vocab)}[name]


def load_spec(name: str) -> dict:
    with open(os.path.join(HERE, 'configs', f'{name}.json')) as f:
        return json.load(f)


def dims_of(spec: dict) -> Dims:
    """Dims from a configuration file's published keys, as run."""
    if spec['model_type'] != 'mistral':
        raise ValueError(f'no weights for model_type {spec["model_type"]!r}')
    d, h = spec['hidden_size'], spec['num_attention_heads']
    return Dims(spec['num_hidden_layers'], d, h, spec['num_key_value_heads'],
                spec.get('head_dim', d // h), spec['intermediate_size'],
                spec['vocab_size'], float(spec['rope_theta']),
                float(spec['rms_norm_eps']), spec['torch_dtype'])


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
    else:                                   # fan-in scaled projection
        x = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
    return x.astype(dtype)


def layer_weights(key: jax.Array, i, dims: Dims) -> dict:
    """Layer ``i``'s weights (``i`` may be traced, so this vmaps)."""
    k = jax.random.fold_in(key, 1000 + i)
    return {n: _draw(jax.random.fold_in(k, j), n, dims.shape(n),
                     jnp.dtype(dims.dtype))
            for j, n in enumerate(LAYER_TENSORS)}


def top_weights(key: jax.Array, dims: Dims) -> dict:
    """Embedding, final norm and output head."""
    return {n: _draw(jax.random.fold_in(key, j), n, dims.shape(n),
                     jnp.dtype(dims.dtype))
            for j, n in enumerate(TOP_TENSORS)}
