"""Public jit'd wrappers for the Pallas kernels.

Handle the alignment contracts (128-lane row widths, block-multiple sequence
lengths) by padding/unpadding, pick interpret mode automatically (interpret on
CPU — the kernel body runs in Python for validation; compiled on TPU), and
expose drop-in signatures matching the pure-jnp refs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.embed_gather import embed_gather
from repro.kernels.gather_rope import gather_rope, tile_rows
from repro.kernels.rmsnorm_qkv import rmsnorm_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.paged_attention import paged_attention


def _interpret() -> bool:
    """Single platform check for every Pallas entry point.

    Kernels compile on TPU and run interpreted elsewhere (CPU CI validates
    the kernel bodies in Python). Every kernel's ``interpret=None`` default
    resolves here, so no caller silently runs interpreted on real hardware.
    """
    return jax.default_backend() != 'tpu'


def on_every_device(call, *args):
    """Run one Pallas call whole on every device of the mesh being traced.

    The compiler cannot partition a Mosaic kernel, so a kernel traced for a
    mesh (``jax.set_mesh``) runs replicated inside a ``shard_map``: each
    device computes the full result from its (replicated) operands. Outside
    a mesh, or inside a ``shard_map`` body, it is the plain call.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.are_all_axes_manual:
        return call(*args)
    rep = jax.sharding.PartitionSpec()
    return jax.shard_map(call, mesh=mesh, in_specs=(rep,) * len(args),
                         out_specs=rep, check_vma=False)(*args)


def _pad_to(x: jax.Array, mult: int, axis: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def embed_gather_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Precomputed-row gather; any (V, W) table, any ids shape -> (*ids, W)."""
    W = table.shape[1]
    tp = _pad_to(table, 128, axis=1)
    flat = ids.reshape(-1).astype(jnp.int32)
    rows = embed_gather(tp, flat, interpret=_interpret())
    return rows[:, :W].reshape(*ids.shape, W)


def gather_rope_rows(table: jax.Array, ids: jax.Array, positions: jax.Array,
                     *, q_off: int, num_heads: int, k_off: int,
                     num_kv_heads: int, head_dim: int,
                     theta: float) -> jax.Array:
    """Fused precomputed-row gather + layer-0 RoPE on the q/k slices.

    Any (V, W) table, any matching ids/positions shape -> (*ids, W) rows
    whose q and k segments are already rotated for each token's position —
    the chunked-prefill serving fast path's first read.
    """
    segs = ((q_off, num_heads, head_dim), (k_off, num_kv_heads, head_dim))
    return gather_rope_rows_segs(table, ids, positions, segs=segs,
                                 theta=theta)


def gather_rope_rows_segs(table: jax.Array, ids: jax.Array,
                          positions: jax.Array, *, segs,
                          theta: float) -> jax.Array:
    """Fused row gather + RoPE over arbitrary static segments.

    ``segs`` is ``((offset, n_heads, head_dim), ...)`` in row-storage order;
    each segment is half-split-rotated for its token's position. This is the
    generic form behind :func:`gather_rope_rows`; MLA layouts use it with
    per-head rotary-slice segments (``[qk_nope | qk_rope]`` interleaving
    plus the shared ``k_pe`` slice).
    """
    W = table.shape[1]
    tp = _pad_to(_pad_to(table, 128, axis=1), tile_rows(table.dtype), axis=0)
    flat_ids = ids.reshape(-1).astype(jnp.int32)
    flat_pos = positions.reshape(-1).astype(jnp.int32)
    rows = gather_rope(tp, flat_ids, flat_pos, segs=tuple(segs),
                       theta=float(theta), interpret=_interpret())
    return rows[:, :W].reshape(*ids.shape, W)


def rmsnorm_qkv(x: jax.Array, scale: jax.Array, wq: jax.Array, wk: jax.Array,
                wv: jax.Array, *, eps: float = 1e-6
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused RMSNorm + QKV: x (..., d) -> q (..., Q), k (..., E), v (..., E)."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = _pad_to(x.reshape(-1, d), 128, axis=0)
    w = jnp.concatenate([wq, wk, wv], axis=1)
    wp = _pad_to(w, 128, axis=1)
    out = rmsnorm_matmul(xf, scale, wp, eps=eps, interpret=_interpret())
    n = int(jnp.prod(jnp.asarray(lead))) if lead else 1
    out = out[: (x.reshape(-1, d)).shape[0], : w.shape[1]]
    Q, E = wq.shape[1], wk.shape[1]
    out = out.reshape(*lead, w.shape[1])
    return out[..., :Q], out[..., Q:Q + E], out[..., Q + E:]


def flash_attention_bshd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         block: int = 128) -> jax.Array:
    """(B,S,H,d) x (B,S,KH,d)^2 -> (B,S,H,d); pads S to a block multiple."""
    S = q.shape[1]
    qp = _pad_to(q, block, axis=1)
    kp = _pad_to(k, block, axis=1)
    vp = _pad_to(v, block, axis=1)
    out = flash_attention(qp, kp, vp, causal=causal, window=window,
                          block_q=block, block_k=block,
                          interpret=_interpret())
    return out[:, :S]


def paged_attend(q: jax.Array, k_pages: jax.Array, v_pages, cpos_pages,
                 table: jax.Array, pos0: jax.Array, *, scale: float,
                 window: int = 0, k2_pages=None, k_scale_pages=None,
                 v_scale_pages=None, mla_split: int = 0) -> jax.Array:
    """In-place paged/chunked attention over the global KV pool.

    q (B,T,KV,G,dq) against page-pool storage (NP,ps,KV,·) through a
    per-slot (B,P) page table -> (B,T,KV,G,dv). Never gathers a dense
    virtual cache; see kernels/paged_attention.py for the variants
    (``mla_split``, int8 scales).
    """
    return paged_attention(q, k_pages, v_pages, cpos_pages, table, pos0,
                           scale=scale, window=window, k2_pages=k2_pages,
                           k_scale_pages=k_scale_pages,
                           v_scale_pages=v_scale_pages, mla_split=mla_split,
                           interpret=_interpret())


def decode_attention_cache(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, cache_pos: jax.Array,
                           pos: jax.Array, *, window: int = 0,
                           block: int = 128) -> jax.Array:
    """(B,H,d) against (B,Sc,KH,d) caches; pads Sc with empty (-1) slots."""
    kp = _pad_to(k_cache, block, axis=1)
    vp = _pad_to(v_cache, block, axis=1)
    cp = _pad_to(cache_pos, block, axis=1, value=-1)
    return decode_attention(q, kp, vp, cp, pos.astype(jnp.int32),
                            window=window, block_s=block,
                            interpret=_interpret())
