"""What every architecture's plain reference shares.

A family (``bench/arch/``) writes its layer equations in ``jax.numpy`` and
float32 at ``highest`` matmul precision: no kernels, no cache, no table, no
batching tricks, weights drawn from the run's seed one layer at a time
(``bench/weights.py``), never taken from the program. This module holds
what those equations share: the int8 linear layer of the control, the
blocked causal softmax, the embedding, and ``drive``, which runs whole
padded sequences (prompt + served tokens) layer by layer and returns for
every position the reference's best logit and the logit of the token that
followed, through an output head taken in blocks of positions.

The control runs the same equations with every linear layer in int8
(weights per output channel, activations per token, int32 accumulation:
the W8A8 path a TPU v5e runs at twice the bf16 rate), and ``drive`` returns
the reference's logit of the control's first choice.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterable, List

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # query rows per attention block
HEAD_BLOCK = 256       # positions per output-head block
PAD_TO = 256           # sequence lengths are padded to a multiple of this
PAD_TOKEN = 3
HI = jax.lax.Precision.HIGHEST


def quant(x, axis):
    """Symmetric int8 along ``axis`` -> (int8 values, float32 scales)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) \
        / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def linear(x, w, int8: bool):
    """x (..., i) float32 @ w (i, o)."""
    if not int8:
        return jnp.einsum('...i,io->...o', x, w.astype(jnp.float32),
                          precision=HI)
    xq, xs = quant(x, -1)
    wq, ws = quant(w.astype(jnp.float32), 0)
    y = jnp.einsum('...i,io->...o', xq, wq,
                   preferred_element_type=jnp.int32)
    return y.astype(jnp.float32) * xs * ws


def causal_attention(q, k, v, scale: float):
    """Causal softmax attention. q (B, S, K, G, dq) are G query heads on
    each of K key heads, k (B, S, K, dq), v (B, S, K, dv) ->
    (B, S, K, G, dv). Blocks of query rows see only the keys up to their
    last row."""
    S = q.shape[1]
    out = []
    for q0 in range(0, S, Q_BLOCK):
        q1 = min(S, q0 + Q_BLOCK)
        s = jnp.einsum('bqkgd,bskd->bkgqs', q[:, q0:q1], k[:, :q1],
                       precision=HI) * scale
        mask = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum('bkgqs,bskd->bqkgd', p, v[:, :q1],
                              precision=HI))
    return jnp.concatenate(out, 1)


@functools.partial(jax.jit, static_argnames=('int8',))
def _embed(top, tokens, int8: bool):
    e = top['embed'].astype(jnp.float32)
    if int8:
        eq, es = quant(e, -1)
        e = eq.astype(jnp.float32) * es
    return e[tokens]


@functools.partial(jax.jit, static_argnames=('logits', 'dims'))
def _head(top, h_ref, h_ctl, nxt, logits, dims):
    """Per position: the reference's best logit, its logit of the next
    token, and its logit of the control's first choice."""
    best, at_next, at_ctl = [], [], []
    for p0 in range(0, h_ref.shape[1], HEAD_BLOCK):
        sl = slice(p0, p0 + HEAD_BLOCK)
        ref = logits(top, h_ref[:, sl], False, dims)
        best.append(ref.max(-1))
        at_next.append(jnp.take_along_axis(ref, nxt[:, sl, None], -1)[..., 0])
        if h_ctl is not None:
            pick = logits(top, h_ctl[:, sl], True, dims).argmax(-1)
            at_ctl.append(
                jnp.take_along_axis(ref, pick[..., None], -1)[..., 0])
    cat = lambda xs: jnp.concatenate(xs, 1) if xs else None
    return cat(best), cat(at_next), cat(at_ctl)


def drive(seqs: List[np.ndarray], top: dict, layers: Iterable[dict],
          layer: Callable, logits: Callable, dims, *,
          control: bool) -> List[dict]:
    """Run a family's equations over whole token sequences, padded to a
    multiple of ``PAD_TO``. ``top`` holds ``embed`` and what ``logits``
    reads; ``layers`` yields each layer's weights in turn (one at a time on
    the device); ``layer(w, h, dims, int8)`` maps hidden states (B, S, d)
    float32 through one layer and ``logits(top, h, int8, dims)`` through
    the final norm and head, both jitted or jittable with ``dims`` static.
    Returns, per sequence, arrays over positions 0..len-2: ``best`` (the
    reference's largest logit), ``next`` (its logit of the token at the
    following position) and, with ``control``, ``control`` (its logit of
    the control's first choice)."""
    n = max(len(s) for s in seqs)
    S = -(-n // PAD_TO) * PAD_TO
    toks = np.full((len(seqs), S), PAD_TOKEN, np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = jnp.asarray(toks)
    h_ref = _embed(top, toks, False)
    h_ctl = _embed(top, toks, True) if control else None
    for w in layers:
        h_ref = layer(w, h_ref, dims, False)
        if control:
            h_ctl = layer(w, h_ctl, dims, True)
        del w
    nxt = jnp.concatenate([toks[:, 1:], toks[:, -1:]], 1)
    best, at_next, at_ctl = jax.device_get(_head(top, h_ref, h_ctl, nxt,
                                                 logits, dims))
    out = []
    for i, s in enumerate(seqs):
        m = len(s) - 1
        row = {'best': best[i, :m], 'next': at_next[i, :m]}
        if control:
            row['control'] = at_ctl[i, :m]
        out.append(row)
    return out
