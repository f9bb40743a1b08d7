"""Production mesh + per-(arch, shape) sharding-rule selection.

Target hardware: TPU v5e pods — 256 chips/pod, 16x16 ('data','model');
multi-pod adds a leading 'pod' axis: (2,16,16) = 512 chips. The 'pod' axis
composes with 'data' for the batch dimension (pure DP across pods), so the
only cross-pod collective is the gradient all-reduce.

NOTE: importing this module never touches jax device state — meshes are built
inside functions, after the caller (dryrun.py) has set XLA_FLAGS.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.config import InputShape, ModelConfig
from repro.sharding import Rules, default_rules


def _auto_axes(n: int) -> tuple:
    """``jax.make_mesh`` makes Explicit axes by default; the sharding
    constraints of this code base (``with_sharding_constraint``) need the
    compiler-propagated Auto kind."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def batch_axes(mesh) -> tuple:
    return ('pod', 'data') if 'pod' in mesh.axis_names else ('data',)


def make_serving_mesh(spec, devices=None):
    """Build (or pass through) the serving engine's ``('pool','heads')`` mesh.

    ``spec`` accepts:
      - ``None`` / ``''`` / ``'1x1'`` -> ``None`` (single-device engine, the
        mesh machinery stays completely out of the hot path)
      - ``'PxH'`` string (e.g. ``'2x2'``, ``'4x1'``) or a ``(P, H)`` tuple ->
        a fresh ``jax.make_mesh((P, H), ('pool', 'heads'))``
      - an existing ``jax.sharding.Mesh`` -> validated and returned as-is

    Raises :class:`ValueError` (never asserts — these are user-facing CLI
    inputs) on malformed specs, non-positive factors, or a device product
    exceeding what the backend actually has. Emulated CPU meshes need
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before jax
    initialises its backend.
    """
    if spec is None or spec == '':
        return None
    if isinstance(spec, jax.sharding.Mesh):
        names = tuple(spec.axis_names)
        if names != ('pool', 'heads'):
            raise ValueError(f'serving mesh needs axes (pool, heads), got '
                             f'{names}')
        return spec
    if isinstance(spec, str):
        parts = spec.lower().replace('×', 'x').split('x')
        if len(parts) != 2:
            raise ValueError(f'mesh spec must look like "PxH" (e.g. "2x2"), '
                             f'got {spec!r}')
        try:
            shape = (int(parts[0]), int(parts[1]))
        except ValueError:
            raise ValueError(f'mesh spec must be two integers "PxH", got '
                             f'{spec!r}') from None
    else:
        shape = tuple(int(v) for v in spec)
        if len(shape) != 2:
            raise ValueError(f'mesh shape must be (pool, heads), got {spec!r}')
    p, h = shape
    if p < 1 or h < 1:
        raise ValueError(f'mesh factors must be positive, got {p}x{h}')
    if p * h == 1:
        return None
    avail = devices if devices is not None else jax.devices()
    if p * h > len(avail):
        raise ValueError(
            f'mesh {p}x{h} needs {p * h} devices but only {len(avail)} are '
            f'visible (on CPU, set XLA_FLAGS='
            f'--xla_force_host_platform_device_count={p * h} before jax '
            f'initialises)')
    return jax.make_mesh((p, h), ('pool', 'heads'),
                         devices=list(avail)[:p * h],
                         axis_types=_auto_axes(2))


# models big enough that train-mode params/optimizer must be FSDP-sharded
# over the data axis on top of tensor parallelism (ZeRO-3 style)
FSDP_ARCHS = {'llama3-405b', 'gemma3-27b', 'glm4-9b', 'mixtral-8x7b',
              'mixtral-8x7b-parallel', 'deepseek-v2-lite-16b', 'mistral-7b',
              'pythia-6.9b'}


def rules_for(cfg: ModelConfig, shape: InputShape, mesh, *,
              fsdp: Optional[bool] = None,
              shard_cache_seq: Optional[bool] = None) -> Rules:
    """Pick the sharding rules for one (architecture x input-shape) run."""
    model_size = dict(zip(mesh.axis_names,
                          mesh.devices.shape)).get('model', 1)
    if fsdp is None:
        # big models need params sharded over data x model in every mode
        # (inference included: 405B bf16 = 810 GB won't fit 16 chips' HBM)
        fsdp = cfg.name in FSDP_ARCHS
    kv_divisible = cfg.mla is None and cfg.num_kv_heads % model_size == 0
    if shard_cache_seq is None:
        # context-parallel decode whenever kv heads can't cover the model
        # axis, and always for batch=1 long-context decode
        shard_cache_seq = shape.mode == 'decode' and (
            not kv_divisible or shape.global_batch < 16)
    rules = default_rules(mesh, batch_axes=batch_axes(mesh), fsdp=fsdp,
                          shard_kv_heads=kv_divisible and not shard_cache_seq,
                          shard_cache_seq=shard_cache_seq)
    if shape.mode == 'train':
        # Megatron-style sequence-parallel residual stream: the scan carry
        # (and every saved activation) is sharded over 'model' on seq, which
        # divides the dominant train-memory term (saved per-rep carries) by
        # the model-axis size. Attention/FFN gather internally as needed.
        rules = rules.with_overrides(seq='model')
    if cfg.moe is not None:
        if cfg.moe.num_experts % model_size == 0:
            rules = rules.with_overrides(experts='model', expert_mlp=None)
        else:
            rules = rules.with_overrides(experts=None, expert_mlp='model')
    return rules


# --------------------------------------------------------- shape skip logic
FULL_ATTENTION_ARCHS = {
    # pure full-attention (or full-attn-equivalent) archs skip long_500k
    'llama3-405b': 'full causal attention at every layer',
    'glm4-9b': 'full causal attention at every layer',
    'deepseek-v2-lite-16b': 'MLA compresses the KV cache but attention is '
                            'still full-causal',
    'internvl2-1b': 'full causal attention at every layer',
    'whisper-tiny': 'enc-dec; 500k target positions out of family scope',
    'whisper-tiny-rope': 'enc-dec; 500k target positions out of family scope',
    'pythia-6.9b': 'full causal attention at every layer',
}


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == 'long_500k' and cfg.name in FULL_ATTENTION_ARCHS:
        return FULL_ATTENTION_ARCHS[cfg.name]
    return None
