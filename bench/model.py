"""The system under test, built from a configuration file.

``bench/configs/<config>.json`` holds a model's published ``config.json``
keys with the values as run, plus the engine geometry. The configuration's
architecture (``bench/arch/<model_type>.py``) turns them into the program's
``ModelConfig`` and lays the seeded weights out as the program's parameter
tree. This module holds every architecture to the same deployment path: it
checks the tree against the program's own schema and builds the serving
engine the way ``repro.launch.serve`` does, layer 0 from the precomputed
table with the fused gather->RoPE kernel, the paged KV pool with the radix
prefix cache, chunked prefill and the Pallas attention backend, named and
checked.
"""
from __future__ import annotations

import jax


class ConfigError(ValueError):
    """A configuration file states something the program does not run."""


def check_schema(model, params) -> None:
    """The parameter tree has the program's structure, shapes and dtypes,
    leaf by leaf."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           params)
        raise ConfigError('the seeded weights do not match the program\'s '
                          f'parameter schema:\n{want}\n{got}')


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


def build(arch, name: str, spec: dict, seed: int, *, telemetry=False):
    """-> (engine, cfg): weights, table and engine for one run of
    configuration ``name``, whose architecture module is ``arch``."""
    from repro.launch.serve import build_engine
    from repro.models.model import Model
    cfg = arch.model_config(name, spec)
    model = Model(cfg)
    params = arch.program_params(model, arch.dims_of(spec), seed)
    check_schema(model, params)
    table = model.build_table(params)
    eng = build_engine(model, params, table, seed=seed,
                       telemetry=telemetry, **engine_options(spec['engine']))
    check_fast_path(eng)
    return eng, cfg
