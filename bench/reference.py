"""Plain reference forward pass, and its int8 control.

The Mistral architecture's equations (serial blocks, RMSNorm, SwiGLU,
grouped-query attention with half-split RoPE, untied head) written out in
``jax.numpy`` and float32 at ``highest`` matmul precision: no kernels, no
cache, no table, no batching tricks. Weights come from ``bench/weights.py``,
drawn from the run's seed one layer at a time, never from the program. The
program's departure from the published model (stated in the configuration
file: its RMSNorm epsilon) is followed here too.

``score`` runs whole sequences (prompt + served tokens) layer by layer, in
blocks of query rows, and returns for every position the reference's best
logit and the logit of the token that followed. With ``control=True`` it
also runs the control, the same equations with every linear layer in int8
(weights per output channel, activations per token, int32 accumulation:
the W8A8 path a TPU v5e runs at twice the bf16 rate), and returns the
reference's logit of the control's first choice.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 256          # query rows per attention block
HEAD_BLOCK = 256       # positions per output-head block
PAD_TO = 256           # sequence lengths are padded to a multiple of this
HI = jax.lax.Precision.HIGHEST


def _quant(x, axis):
    """Symmetric int8 along ``axis`` -> (int8 values, float32 scales)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) \
        / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _linear(x, w, int8: bool):
    """x (..., i) float32 @ w (i, o)."""
    if not int8:
        return jnp.einsum('...i,io->...o', x, w.astype(jnp.float32),
                          precision=HI)
    xq, xs = _quant(x, -1)
    wq, ws = _quant(w.astype(jnp.float32), 0)
    y = jnp.einsum('...i,io->...o', xq, wq,
                   preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * xs * ws


def _norm(x, scale, dims: W.Dims):
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


def _attention(q, k, v, dims: W.Dims):
    """Causal attention, query head h on KV head h // (heads / kv_heads);
    blocks of query rows see only the keys up to their last row."""
    B, S = q.shape[:2]
    KV, G, hd = dims.kv_heads, dims.heads // dims.kv_heads, dims.head_dim
    q = q.reshape(B, S, KV, G, hd)
    out = []
    for q0 in range(0, S, Q_BLOCK):
        q1 = min(S, q0 + Q_BLOCK)
        s = jnp.einsum('bqkgd,bskd->bkgqs', q[:, q0:q1], k[:, :q1],
                       precision=HI) * hd ** -0.5
        mask = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum('bkgqs,bskd->bqkgd', p, v[:, :q1],
                              precision=HI))
    return jnp.concatenate(out, 1).reshape(B, S, dims.heads * hd)


@functools.partial(jax.jit, static_argnames=('dims', 'int8'))
def _layer(w, h, dims: W.Dims, int8: bool):
    """One decoder layer on hidden states h (B, S, d) float32."""
    B, S, _ = h.shape
    lin = lambda x, n: _linear(x, w[n], int8)
    xn = _norm(h, w['ln1_scale'], dims)
    q = _rope(lin(xn, 'wq').reshape(B, S, dims.heads, dims.head_dim),
              dims.rope_theta)
    k = _rope(lin(xn, 'wk').reshape(B, S, dims.kv_heads, dims.head_dim),
              dims.rope_theta)
    v = lin(xn, 'wv').reshape(B, S, dims.kv_heads, dims.head_dim)
    h = h + lin(_attention(q, k, v, dims), 'wo')
    x = _norm(h, w['ln2_scale'], dims)
    return h + lin(jax.nn.silu(lin(x, 'w_gate')) * lin(x, 'w_up'), 'w_down')


@functools.partial(jax.jit, static_argnames=('dims', 'int8'))
def _embed(top, tokens, dims: W.Dims, int8: bool):
    e = top['embed'].astype(jnp.float32)
    if int8:
        eq, es = _quant(e, -1)
        e = eq.astype(jnp.float32) * es
    return e[tokens]


@functools.partial(jax.jit, static_argnames=('dims',))
def _head(top, h_ref, h_ctl, nxt, dims: W.Dims):
    """Per position: the reference's best logit, its logit of the next
    token, and its logit of the control's first choice."""
    def logits(h, int8):
        x = _norm(h, top['final_scale'], dims)
        return _linear(x, top['lm_head'], int8)
    best, at_next, at_ctl = [], [], []
    for p0 in range(0, h_ref.shape[1], HEAD_BLOCK):
        sl = slice(p0, p0 + HEAD_BLOCK)
        ref = logits(h_ref[:, sl], False)
        best.append(ref.max(-1))
        at_next.append(jnp.take_along_axis(ref, nxt[:, sl, None], -1)[..., 0])
        if h_ctl is not None:
            pick = logits(h_ctl[:, sl], True).argmax(-1)
            at_ctl.append(
                jnp.take_along_axis(ref, pick[..., None], -1)[..., 0])
    cat = lambda xs: jnp.concatenate(xs, 1) if xs else None
    return cat(best), cat(at_next), cat(at_ctl)


def score(spec: dict, seed: int, seqs: List[np.ndarray], *,
          control: bool = False) -> List[dict]:
    """Run the reference (and with ``control`` the int8 control) over whole
    token sequences. Returns, per sequence, arrays over positions
    0..len-2: ``best`` (the reference's largest logit), ``next`` (its logit
    of the token at the following position) and, with ``control``,
    ``control`` (its logit of the control's first choice)."""
    dims = W.dims_of(spec)
    key = W.base_key(seed)
    n = max(len(s) for s in seqs)
    S = -(-n // PAD_TO) * PAD_TO
    toks = np.full((len(seqs), S), 3, np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = jnp.asarray(toks)
    top = jax.jit(W.top_weights, static_argnames='dims')(key, dims=dims)
    h_ref = _embed(top, toks, dims, False)
    h_ctl = _embed(top, toks, dims, True) if control else None
    gen = jax.jit(W.layer_weights, static_argnames='dims')
    for i in range(dims.layers):
        w = gen(key, i, dims=dims)
        h_ref = _layer(w, h_ref, dims, False)
        if control:
            h_ctl = _layer(w, h_ctl, dims, True)
        del w
    nxt = jnp.concatenate([toks[:, 1:], toks[:, -1:]], 1)
    best, at_next, at_ctl = jax.device_get(_head(top, h_ref, h_ctl, nxt,
                                                 dims))
    out = []
    for i, s in enumerate(seqs):
        m = len(s) - 1
        row = {'best': best[i, :m], 'next': at_next[i, :m]}
        if control:
            row['control'] = at_ctl[i, :m]
        out.append(row)
    return out
