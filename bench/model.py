"""The system under test, built from a configuration file.

``bench/configs/<config>.json`` holds a model's published ``config.json``
keys with the values as run, plus the engine geometry. This module turns
them into the program's ``ModelConfig`` (``dataclasses.replace`` over the
repository's Mistral config), lays the seeded weights of
``bench/weights.py`` out as the program's parameter tree in one jitted call,
and builds the serving engine the way ``repro.launch.serve`` does, on the
deployment path: layer 0 from the precomputed table with the fused
gather->RoPE kernel, the paged KV pool with the radix prefix cache, chunked
prefill and the Pallas attention backend, named and checked.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import weights as W

# What the program computes where a configuration key has no option in it:
# a file that states another value is refused rather than silently served.
# The reference has no sliding window either.
FIXED = {'model_type': 'mistral', 'hidden_act': 'silu', 'rms_norm_eps': 1e-6,
         'sliding_window': None}


class ConfigError(ValueError):
    """A configuration file states something the program does not run."""


def model_config(name: str, spec: dict):
    """-> the program's ModelConfig for configuration ``name``."""
    from repro.configs import get_config
    for key, want in FIXED.items():
        if spec.get(key) != want:
            raise ConfigError(f'{name}: {key}={spec.get(key)!r}, but the '
                              f'program runs {want!r}')
    dims = W.dims_of(spec)
    cfg = dataclasses.replace(
        get_config(spec['repro_config']), name=name,
        num_layers=dims.layers, d_model=dims.d, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.ff,
        vocab_size=dims.vocab, rope_theta=dims.rope_theta,
        pattern=('global',), window=0,
        max_seq_len=spec['max_position_embeddings'],
        tie_embeddings=bool(spec['tie_word_embeddings']), dtype=dims.dtype)
    want = ('serial', 'rmsnorm', True, 'silu')
    got = (cfg.block_type, cfg.norm, cfg.glu, cfg.act)
    if got != want or cfg.tie_embeddings:
        raise ConfigError(f'{name}: repo config {spec["repro_config"]} '
                          f'gives {got}, expected {want}, untied')
    cfg.validate()
    return cfg


def _block(p: dict) -> dict:
    return {'ln1': {'scale': p['ln1_scale']}, 'ln2': {'scale': p['ln2_scale']},
            'ffn': {n: {'w': p[n]} for n in ('w_up', 'w_gate', 'w_down')},
            'attn': {n: {'w': p[n]} for n in ('wq', 'wk', 'wv', 'wo')}}


def program_params(model, dims: W.Dims, seed: int):
    """The seeded weights as the program's parameter tree, made on the
    device in one jitted call, in the dtype they are served in. Checked
    leaf by leaf against the program's own schema."""
    def make(key):
        top = W.top_weights(key, dims)
        bb = {'layer0': _block(W.layer_weights(key, 0, dims))}
        if dims.layers > 1:
            body = jax.vmap(lambda i: W.layer_weights(key, i, dims))(
                jnp.arange(1, dims.layers))
            bb['body'] = [_block(body)]
        return {'embed': {'table': top['embed']}, 'backbone': bb,
                'lm_head': {'w': top['lm_head']},
                'final_norm': {'scale': top['final_scale']}}

    key = W.base_key(seed)
    want = jax.eval_shape(model.init, key)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ConfigError('the seeded weights do not match the program\'s '
                          f'parameter schema:\n{want}\n{got}')
    return jax.jit(make)(key)


def engine_options(geometry: dict) -> dict:
    """Engine keywords of the deployment path; the geometry is data."""
    return dict(max_slots=geometry['max_slots'], max_seq=geometry['max_seq'],
                chunk_size=geometry['chunk_size'],
                page_size=geometry['page_size'],
                num_pages=geometry['num_pages'], prefix_cache=True,
                attn_backend='pallas', fused_gather_rope=True)


def check_fast_path(eng) -> None:
    """The engine runs the path it was asked for: nothing was dropped
    (fused gather->RoPE turns itself off for ineligible configs)."""
    from repro.kernels import ops
    problems = []
    if eng.attn_backend.name != 'pallas':
        problems.append(f'attention backend is {eng.attn_backend.name!r}')
    if not eng.fused_gather_rope:
        problems.append('fused gather->RoPE is off')
    if not (eng.paged and eng.chunk_size > 1):
        problems.append('the paged chunked path is off')
    if eng.precomputed is None:
        problems.append('layer 0 does not read the precomputed table')
    if jax.default_backend() == 'tpu' and ops._interpret():
        problems.append('Pallas kernels would run interpreted')
    if problems:
        raise ConfigError('; '.join(problems))


def build(name: str, spec: dict, seed: int, *, telemetry=False):
    """-> (engine, cfg): weights, table and engine for one run."""
    from repro.launch.serve import build_engine
    from repro.models.model import Model
    cfg = model_config(name, spec)
    model = Model(cfg)
    params = program_params(model, W.dims_of(spec), seed)
    table = model.build_table(params)
    eng = build_engine(model, params, table, seed=seed,
                       telemetry=telemetry, **engine_options(spec['engine']))
    check_fast_path(eng)
    return eng, cfg
