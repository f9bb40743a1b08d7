"""Single-token decode attention over a (ring) KV cache.

Since the unified attention-backend refactor this is the *identity-table,
T=1 case* of :mod:`repro.kernels.paged_attention`: the dense ``(B, Sc, ...)``
cache is viewed in place as ``Sc / block_s`` pages per slot (a free reshape),
the page table is ``table[b, j] = b * n + j``, and the shared kernel streams
each block HBM->VMEM with fp32 running-softmax scratch. Validity still comes
from the cache's stored positions (-1 = empty) via the shared
:func:`~repro.kernels.paged_attention.page_validity` helper, which makes
ring-buffer wraparound and sliding-window masking uniform.

The decode hot loop is memory-bandwidth-bound (the whole cache streams once
per step) — the same regime the paper's precompute targets for layer 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import (NEG_INF, dense_as_pages,
                                           dense_identity_table,
                                           page_validity, paged_attention)

__all__ = ['decode_attention', 'page_validity', 'NEG_INF']

# A window no query reaches past: it masks nothing more than window 0, and
# it keeps the kernel's walk over every block, which a cache holding any
# position at any index needs (the kernel ends a window-0 walk at the last
# block a linear cache can hold the query's history in).
_WHOLE_CACHE = 2 ** 30


@functools.partial(jax.jit, static_argnames=('window', 'block_s',
                                             'interpret'))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_pos: jax.Array, pos: jax.Array, *,
                     window: int = 0, block_s: int = 128,
                     interpret: bool | None = None) -> jax.Array:
    """q (B,H,d); k/v_cache (B,Sc,KH,d); cache_pos (B,Sc); pos (B,)
    -> (B,H,d). Sc % block_s == 0 (ops pads with pos=-1 slots)."""
    B, H, d = q.shape
    Sc, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    bs = min(block_s, Sc)
    qg = q.reshape(B, 1, KH, G, d)
    out = paged_attention(
        qg,
        dense_as_pages(k_cache, bs),
        dense_as_pages(v_cache, bs),
        dense_as_pages(cache_pos, bs),
        dense_identity_table(B, Sc, bs),
        pos.astype(jnp.int32),
        scale=d ** -0.5, window=window or _WHOLE_CACHE, interpret=interpret)
    return out.reshape(B, H, d)
