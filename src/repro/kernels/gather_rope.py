"""Pallas TPU kernel: fused precomputed-row gather + layer-0 RoPE.

The paper turns layer 0 into one table-row read per token; at serve time the
q/k slices of that row are immediately rotated by RoPE before attention. This
kernel fuses the two: the row is DMA'd HBM->VMEM via scalar-prefetched token
ids and the rotation happens in the same VMEM pass — the rows never
round-trip through HBM between gather and RoPE.

Token ids AND positions arrive via ``PrefetchScalarGridSpec`` so the row DMA
for step ``i`` can be issued before its body runs; the position is only
needed inside the body (sin/cos angles), never for addressing.

Mosaic tiles the last two dims of every block by (8, 128) (one sublane tile
of ``RB`` rows: 8 for 32-bit, 16 for 16-bit dtypes) unless they span the
whole array, so a single ``(1, W)`` row cannot be a block. Grid step ``i``
therefore DMAs the ``(RB, W)`` tile holding token i's row, picks the row out
in VMEM, and stores it into row ``i % RB`` of the ``(RB, W)`` output tile,
which stays resident for RB consecutive steps.

The rotated segments are described statically by
``segs = ((offset, n_heads, head_dim), ...)`` in row-storage order —
(q_offset, H, hd) and (k_offset, KV, hd) for the standard ``[x|s, q, k, v]``
layout. RoPE uses the half-split (llama) convention, matching
``models.layers.apply_rope``: the rotation is done on the whole row with two
lane rolls (the pair partner sits ``hd/2`` lanes away) and per-lane
frequency/sign vectors, so segment offsets need no alignment. The row width
must be 128-lane padded and the vocab a multiple of RB (the ops.py wrapper
pads both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tile_rows(dtype) -> int:
    """Rows in one (sublane, 128-lane) TPU tile of ``dtype``."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _lane_constants(W: int, segs, theta: float) -> jax.Array:
    """(3, W) fp32: per-lane inverse frequency, -1/+1 for lanes whose pair
    partner sits half a head to the right/left, and 1 on rotated lanes."""
    inv = jnp.zeros((W,), jnp.float32)
    sign = jnp.zeros((W,), jnp.float32)
    rot = jnp.zeros((W,), jnp.float32)
    for off, heads, hd in segs:
        half = hd // 2
        # the expression of models.layers.rope_freqs -> the same angles
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                 / hd))
        sl = slice(off, off + heads * hd)
        inv = inv.at[sl].set(jnp.tile(jnp.concatenate([freqs, freqs]), heads))
        sign = sign.at[sl].set(jnp.tile(jnp.repeat(
            jnp.asarray([-1.0, 1.0], jnp.float32), half), heads))
        rot = rot.at[sl].set(1.0)
    return jnp.stack([inv, sign, rot])


def _gather_rope_kernel(ids_ref, pos_ref, lane_ref, table_ref, out_ref, *,
                        half):
    i = pl.program_id(0)
    rb = table_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (rb, 1), 0)
    # pick token i's row out of its tile (exact: one term per column)
    row = jnp.sum(jnp.where(rows == ids_ref[i] % rb,
                            table_ref[...].astype(jnp.float32), 0.0),
                  axis=0, keepdims=True)                     # (1, W)
    W = row.shape[1]
    inv, sign, rot = (lane_ref[0:1, :], lane_ref[1:2, :], lane_ref[2:3, :])
    ang = pos_ref[i].astype(jnp.float32) * inv
    right = pltpu.roll(row, W - half, 1)        # row[j + half]
    left = pltpu.roll(row, half, 1)             # row[j - half]
    partner = jnp.where(sign < 0, -right, left)
    # first half: x1*cos + (-x2)*sin; second half: x2*cos + x1*sin
    out = jnp.where(rot > 0, row * jnp.cos(ang) + partner * jnp.sin(ang), row)
    out_ref[...] = jnp.where(rows == i % rb,
                             jnp.broadcast_to(out.astype(out_ref.dtype),
                                              out_ref.shape),
                             out_ref[...])


@functools.partial(jax.jit, static_argnames=('segs', 'theta', 'interpret'))
def gather_rope(table: jax.Array, ids: jax.Array, positions: jax.Array, *,
                segs, theta: float,
                interpret: bool | None = None) -> jax.Array:
    """table (V, W), ids (N,) int32, positions (N,) int32 -> rows (N, W)
    with each ``segs`` slice RoPE-rotated for its token's position. W must be
    128-aligned and V a multiple of :func:`tile_rows` (use
    ops.gather_rope_rows for the padding wrapper)."""
    from repro.kernels.ops import _interpret, on_every_device
    if interpret is None:
        interpret = _interpret()
    V, W = table.shape
    rb = tile_rows(table.dtype)
    assert W % 128 == 0 and V % rb == 0, (table.shape, rb)
    segs = tuple(sorted(segs))
    halves = {hd // 2 for _, _, hd in segs}
    assert len(halves) == 1, 'all rotated segments share one head_dim'
    for off, heads, hd in segs:
        assert hd % 2 == 0 and off + heads * hd <= W
    N = ids.shape[0]
    Np = -(-N // rb) * rb
    ids = jnp.pad(ids.astype(jnp.int32), (0, Np - N))
    positions = jnp.pad(positions.astype(jnp.int32), (0, Np - N))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # ids, positions
        grid=(Np,),
        in_specs=[
            pl.BlockSpec((3, W), lambda i, ids_ref, pos_ref: (0, 0)),
            pl.BlockSpec((rb, W),
                         lambda i, ids_ref, pos_ref: (ids_ref[i] // rb, 0)),
        ],
        out_specs=pl.BlockSpec((rb, W), lambda i, ids_ref, pos_ref:
                               (i // rb, 0)),
    )
    kernel = functools.partial(_gather_rope_kernel, half=halves.pop())
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Np, W), table.dtype),
        interpret=interpret,
        name='gather_rope',
    )
    out = on_every_device(call, ids, positions,
                          _lane_constants(W, segs, theta), table)
    return out[:N]
