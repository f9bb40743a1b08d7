"""The Mistral family: serial blocks, RMSNorm, SwiGLU, grouped-query
attention with half-split RoPE, untied head.

Its sizes, its seeded weights laid out as the program's parameter tree
(``dataclasses.replace`` over the repository's Mistral config), its
equations for the plain reference and its int8 control, and its work
counts. The program's departure from the published model (stated in the
configuration file: its RMSNorm epsilon) is followed by the reference too.
The interface is in ``bench/arch/__init__.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W
from bench import work
from bench.model import ConfigError

# tensors per layer and at the top, in draw order; shapes from Dims.shape
LAYER_TENSORS = ('ln1_scale', 'wq', 'wk', 'wv', 'wo', 'ln2_scale', 'w_up',
                 'w_gate', 'w_down')
TOP_TENSORS = ('embed', 'final_scale', 'lm_head')

# What the program computes where a configuration key has no option in it:
# a file that states another value is refused rather than silently served.
# The reference has no sliding window either.
FIXED = {'model_type': 'mistral', 'hidden_act': 'silu', 'rms_norm_eps': 1e-6,
         'sliding_window': None}

# the CPU tests' size (bench/tests/tiny.py)
SMALL = {'hidden_size': 64, 'intermediate_size': 128,
         'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 16,
         'num_hidden_layers': 2, 'vocab_size': 256,
         'max_position_embeddings': 256}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration, as run."""
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


def dims_of(spec: dict) -> Dims:
    """Dims from a configuration file's published keys, as run."""
    d, h = spec['hidden_size'], spec['num_attention_heads']
    return Dims(spec['num_hidden_layers'], d, h, spec['num_key_value_heads'],
                spec.get('head_dim', d // h), spec['intermediate_size'],
                spec['vocab_size'], float(spec['rope_theta']),
                float(spec['rms_norm_eps']), spec['torch_dtype'])


def small(spec: dict) -> dict:
    return {**spec, **SMALL}


# ------------------------------------------------------------- the program
def model_config(name: str, spec: dict):
    """-> the program's ModelConfig for configuration ``name``."""
    from repro.configs import get_config
    for key, want in FIXED.items():
        if spec.get(key) != want:
            raise ConfigError(f'{name}: {key}={spec.get(key)!r}, but the '
                              f'program runs {want!r}')
    dims = dims_of(spec)
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


def program_params(model, dims: Dims, seed: int):
    """The seeded weights as the program's parameter tree, made on the
    device in one jitted call, in the dtype they are served in."""
    def make(key):
        top = W.top_weights(key, dims, TOP_TENSORS)
        bb = {'layer0': _block(W.layer_weights(key, 0, dims, LAYER_TENSORS))}
        if dims.layers > 1:
            body = jax.vmap(lambda i: W.layer_weights(
                key, i, dims, LAYER_TENSORS))(jnp.arange(1, dims.layers))
            bb['body'] = [_block(body)]
        return {'embed': {'table': top['embed']}, 'backbone': bb,
                'lm_head': {'w': top['lm_head']},
                'final_norm': {'scale': top['final_scale']}}

    return jax.jit(make)(W.base_key(seed))


# ----------------------------------------------------------- the reference
def _norm(x, scale, dims: Dims):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + dims.norm_eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """Half-split rotary embedding over the whole head; x (B, S, h, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, dims: Dims):
    """Query head h on KV head h // (heads / kv_heads)."""
    B, S = q.shape[:2]
    KV, G, hd = dims.kv_heads, dims.heads // dims.kv_heads, dims.head_dim
    out = R.causal_attention(q.reshape(B, S, KV, G, hd), k, v, hd ** -0.5)
    return out.reshape(B, S, dims.heads * hd)


@functools.partial(jax.jit, static_argnames=('dims', 'int8'))
def _layer(w, h, dims: Dims, int8: bool):
    """One decoder layer on hidden states h (B, S, d) float32."""
    B, S, _ = h.shape
    lin = lambda x, n: R.linear(x, w[n], int8)
    xn = _norm(h, w['ln1_scale'], dims)
    q = _rope(lin(xn, 'wq').reshape(B, S, dims.heads, dims.head_dim),
              dims.rope_theta)
    k = _rope(lin(xn, 'wk').reshape(B, S, dims.kv_heads, dims.head_dim),
              dims.rope_theta)
    v = lin(xn, 'wv').reshape(B, S, dims.kv_heads, dims.head_dim)
    h = h + lin(_attention(q, k, v, dims), 'wo')
    x = _norm(h, w['ln2_scale'], dims)
    return h + lin(jax.nn.silu(lin(x, 'w_gate')) * lin(x, 'w_up'), 'w_down')


def _logits(top, h, int8: bool, dims: Dims):
    return R.linear(_norm(h, top['final_scale'], dims), top['lm_head'], int8)


def score(spec: dict, seed: int, seqs: List[np.ndarray], *,
          control: bool = False) -> List[dict]:
    """The reference (and with ``control`` the int8 control) over whole
    token sequences; rows as ``bench.reference.drive`` gives them."""
    dims = dims_of(spec)
    key = W.base_key(seed)
    top = jax.jit(W.top_weights, static_argnames=('dims', 'names'))(
        key, dims=dims, names=TOP_TENSORS)
    gen = jax.jit(W.layer_weights, static_argnames=('dims', 'names'))
    layers = (gen(key, i, dims=dims, names=LAYER_TENSORS)
              for i in range(dims.layers))
    return R.drive(seqs, top, layers, _layer, _logits, dims, control=control)


# ---------------------------------------------------------------- the work
# The weight accounting is that of ``repro.core.analysis`` (the paper's
# tables), written out again here so that no change to the program can
# change the yardstick; ``paper_accounting`` holds the two equal. Layer 0's
# projections that the precomputed table replaces (Q, K, V) are not
# counted: the served algorithm gathers them.
def q_size(d: Dims) -> int:
    return d.heads * d.head_dim


def kv_size(d: Dims) -> int:
    return d.kv_heads * d.head_dim


def layer_weights(d: Dims) -> dict:
    """Matmul weights of one layer, as in the paper's accounting."""
    return {'q_p': d.d * q_size(d) + q_size(d) * d.d,
            'k_v': 2 * d.d * kv_size(d),
            'ffn': 3 * d.d * d.ff}                  # SwiGLU: gate, up, down


def embed_weights(d: Dims) -> int:
    return 2 * d.d * d.vocab                      # untied input + output


def eliminated_weights(d: Dims) -> int:
    """Layer-0 weights whose work the precomputed table removes."""
    return d.d * q_size(d) + 2 * d.d * kv_size(d)


def row_width(d: Dims) -> int:
    """Values per precomputed-table row: [x | s, q, k, v]."""
    return d.d + q_size(d) + 2 * kv_size(d)


def matmul_flops_per_token(d: Dims) -> int:
    """Matmul FLOPs of one processed token through the layers, the
    table-replaced layer-0 projections left out."""
    per_layer = sum(layer_weights(d).values())
    return 2 * (d.layers * per_layer - eliminated_weights(d))


def attention_flops(d: Dims, keys: int) -> int:
    """Scores and value mix of one query token over ``keys`` keys, all
    layers: 2 FLOPs per multiply-add, two products per key and head dim."""
    return d.layers * 4 * d.heads * d.head_dim * keys


def head_flops(d: Dims) -> int:
    """The output head for one sampled token."""
    return 2 * d.d * d.vocab


def model_flops(d: Dims, lanes) -> int:
    return work.model_flops(lanes, token=matmul_flops_per_token(d),
                            head=head_flops(d),
                            attention=functools.partial(attention_flops, d))


def paged_attention_work(d: Dims, lanes) -> tuple:
    """K and V rows of each valid key and its stored position (int32);
    each query's row in and its output row out."""
    b = work.BYTES[d.dtype]
    return work.paged_attention_work(
        lanes, attention=functools.partial(attention_flops, d),
        key_bytes=d.layers * (2 * kv_size(d) * b + 4),
        query_bytes=d.layers * 2 * q_size(d) * b)


def paper_accounting(name: str, spec: dict) -> dict:
    """Each weight count here beside ``repro.core.analysis``'s."""
    from repro.core import analysis
    cfg = model_config(name, spec)
    dims = dims_of(spec)
    wc = analysis.weight_counts(cfg)
    lw = layer_weights(dims)
    return {'q_p': (lw['q_p'], wc.q_p_per_layer),
            'k_v': (lw['k_v'], wc.k_v_per_layer),
            'ffn': (lw['ffn'], wc.ffn_per_layer),
            'embed': (embed_weights(dims), wc.embed),
            'total': (dims.layers * sum(lw.values()) + embed_weights(dims),
                      wc.total),
            'eliminated': (eliminated_weights(dims),
                           analysis.eliminated_weights(cfg)),
            'row_width': (row_width(dims), analysis.analyze(cfg).row_width)}
