"""Pallas TPU kernel: in-place paged/chunked decode attention.

The reference paged path first *gathers* each slot's pages into a
dense-shaped ``(B, Sc, ...)`` virtual cache per layer per step, then attends
over the copy. This kernel removes the copy: the grid runs over
``(batch, pages)`` and each step DMAs ONE physical page of the global pool
(all its kv heads) straight into VMEM through the per-slot page table (the
table is scalar-prefetched, so page ``j``'s DMA is issued before the body
runs).

Each slot's walk ends at its last reachable page on full-attention layers
(``window == 0``). Their table is linear — block ``j`` holds positions
``[j*ps, (j+1)*ps)`` or none — so no query of a chunk at ``pos0 .. pos0 +
T - 1`` attends past block ``min((pos0 + T - 1) // ps, P - 1)``. Grid steps
past it skip the body: their pages hold only later positions or ``-1``,
which would leave the running softmax exactly as it was, so the output is
bitwise that of the full walk. The index maps stay ``table[b, j]``: the
serving pool's tables hold the null page past a slot's allocation, whose
repeated block index issues no DMA, and clamping ``j`` in every map cost
~0.1 µs a grid step on a TPU v5e, more than the DMAs it would save.
Sliding-window layers read ring tables, whose blocks map to no fixed
positions, and walk every page.

It also removes the reference path's query-lane serialisation: all T query
lanes of a prefill chunk are batched into a single dispatch (one
``(T*G, page)`` score block per page) instead of a per-lane loop. fp32
running-softmax scratch persists across the page axis; entry validity comes
from the pool's stored positions (``-1`` = never written), which makes ring
wraparound, sliding windows, unaligned final pages and null-page table
entries all the same test — see :func:`page_validity`, shared with the
single-token dense kernel (``decode_attention.py`` is the identity-table
T=1 case of this kernel).

Variants (static flags):
- ``quant``: int8 K/V pages with per-(token, head) scales folded into the
  scores and the value mix, matching ``attention._attend_lanes``' order
  (the int8 rows themselves stay exact through a bf16 MXU pass).
- ``mla_split > 0``: MLA latent attention — query rows are
  ``[q_absorbed | q_pe]``, scores are ``q_abs·ckv^T + q_pe·kpe^T`` and the
  value mix re-reads the ckv pages (MLA caches no separate V).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30   # large-negative that survives bf16


def page_validity(cpos: jax.Array, qpos: jax.Array, window: int
                  ) -> jax.Array:
    """(1, ps) stored positions x (R, 1) query positions -> (R, ps) validity.

    A cache entry is attendable iff it was ever written (``pos >= 0``), is
    causal history for the query (``stored <= query``) and, on sliding-window
    layers, still inside the window. Ring wraparound, unaligned final pages
    and null-page reads need no special cases: all of them surface as
    ``pos == -1`` or out-of-window stored positions.
    """
    v = (cpos >= 0) & (cpos <= qpos)
    if window:
        v &= (qpos - cpos) < window
    return v


def _nt_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """a (M, d) . b (N, d)^T -> (M, N) in fp32, without materialising b^T."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _paged_kernel(pos0_ref, table_ref, roff_ref, q_ref, *refs, n_j, T, ps,
                  window, scale, quant, mla_split):
    refs = list(refs)
    k_ref = refs.pop(0)
    if mla_split:
        k2_ref = refs.pop(0)
        v_ref = k_ref                 # MLA: the value mix re-reads ckv
    else:
        v_ref = refs.pop(0)
    if quant:
        ks_ref = refs.pop(0)
        vs_ref = refs.pop(0)
    cpos_ref = refs.pop(0)
    o_ref, m_scr, l_scr, acc_scr = refs

    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # linear tables: a block whose first position is past the chunk's last
    # query is past the slot's last reachable page; the scratch stays as is
    @pl.when(True if window else j * ps <= pos0_ref[b] + T - 1)
    def _attend():
        qpos = pos0_ref[b] + roff_ref[...]                      # (R, 1)
        valid = page_validity(cpos_ref[0], qpos, window)        # (R, ps)
        if quant:                                   # (ps, KV) -> (KV, ps) rows
            ks_rows = ks_ref[0].astype(jnp.float32).T
            vs_rows = vs_ref[0].astype(jnp.float32).T

        for h in range(q_ref.shape[1]):       # every kv head of this page
            q = q_ref[0, h].astype(jnp.float32)                 # (R, dq)
            if mla_split:
                k1 = k_ref[0, :, h].astype(jnp.float32)         # (ps, r)
                k2 = k2_ref[0, :, h].astype(jnp.float32)        # (ps, dr)
                s = (_nt_dot(q[:, :mla_split], k1)
                     + _nt_dot(q[:, mla_split:], k2))
                v = k1
            else:
                k = k_ref[0, :, h].astype(jnp.float32)          # (ps, dk)
                v = v_ref[0, :, h].astype(jnp.float32)          # (ps, dv)
                s = _nt_dot(q, k)
                if quant:           # per-token scales on the scores
                    s = s * ks_rows[h:h + 1]
            s = jnp.where(valid, s * scale, NEG_INF)

            m_prev = m_scr[h]                                   # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if quant:               # ... and on the value mix
                p = p * vs_rows[h:h + 1]
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == n_j - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'window', 'mla_split',
                                             'interpret'))
def paged_attention(q: jax.Array, k_pages: jax.Array,
                    v_pages: jax.Array | None, cpos_pages: jax.Array,
                    table: jax.Array, pos0: jax.Array, *, scale: float,
                    window: int = 0, k2_pages: jax.Array | None = None,
                    k_scale_pages: jax.Array | None = None,
                    v_scale_pages: jax.Array | None = None,
                    mla_split: int = 0,
                    interpret: bool | None = None) -> jax.Array:
    """In-place paged attention of a whole query chunk.

    q           (B, T, KV, G, dq)   post-RoPE queries; lane t at pos0 + t
    k_pages     (NP, ps, KV, dk)    global pool (MLA: ckv with KV == 1)
    v_pages     (NP, ps, KV, dv)    global pool (None when ``mla_split``)
    cpos_pages  (NP, ps)            stored positions (-1 = empty)
    table       (B, P) int32        physical page of each slot's block
    pos0        (B,) int32          first query lane's position
    -> (B, T, KV, G, dv) context, dv = value width.

    ``mla_split = r`` switches to the MLA form: q rows are
    ``[q_abs (r) | q_pe (dr)]``, ``k2_pages`` holds the kpe pool and the
    value mix reads ``k_pages`` (ckv) again. ``k/v_scale_pages``
    (NP, ps, KV) enable the int8 pool. The kernel never materialises a
    gathered cache: page ``table[b, j]`` is read in place on grid step j,
    and attended up to the slot's last reachable block when
    ``window == 0``.

    Mosaic tiles the last two dims of every block by (8, 128) unless they
    span the whole array, so each grid step takes ALL kv heads of a page
    (page blocks end in the full ``(KV, d)``), the queries are laid out
    head-major as ``(B, KV, T*G, dq)`` (one row per (lane, group)), and the
    stored positions are read as ``(NP, 1, ps)`` rows.
    """
    from repro.kernels.ops import _interpret, on_every_device
    if interpret is None:
        interpret = _interpret()
    B, T, KV, G, dq = q.shape
    NP, ps = k_pages.shape[:2]
    P = table.shape[1]
    R = T * G
    quant = k_scale_pages is not None
    dv = mla_split if mla_split else v_pages.shape[-1]

    def page_spec(pages):
        return pl.BlockSpec((1, ps) + pages.shape[2:],
                            lambda b, j, p0, tab: (tab[b, j], 0, 0, 0))

    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, R, dq)
    roff = (jnp.arange(R, dtype=jnp.int32) // G)[:, None]   # row -> lane
    in_specs = [
        pl.BlockSpec((R, 1), lambda b, j, p0, tab: (0, 0)),
        pl.BlockSpec((1, KV, R, dq), lambda b, j, p0, tab: (b, 0, 0, 0)),
        page_spec(k_pages),
    ]
    operands = [roff, qh, k_pages]
    v_like = k2_pages if mla_split else v_pages
    in_specs.append(page_spec(v_like))
    operands.append(v_like)
    if quant:
        sc_spec = pl.BlockSpec((1, ps, KV),
                               lambda b, j, p0, tab: (tab[b, j], 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale_pages, v_scale_pages]
    in_specs.append(pl.BlockSpec((1, 1, ps),
                                 lambda b, j, p0, tab: (tab[b, j], 0, 0)))
    operands.append(cpos_pages.reshape(NP, 1, ps))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # pos0, table
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, R, dv),
                               lambda b, j, p0, tab: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, R, 1), jnp.float32),
            pltpu.VMEM((KV, R, 1), jnp.float32),
            pltpu.VMEM((KV, R, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, n_j=P, T=T, ps=ps,
                               window=window,
                               scale=float(scale), quant=quant,
                               mla_split=mla_split)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, dv), q.dtype),
        interpret=interpret,
        name='paged_attention',
    )
    out = on_every_device(call, pos0.astype(jnp.int32),
                          table.astype(jnp.int32), *operands)
    return out.reshape(B, KV, T, G, dv).transpose(0, 2, 1, 3, 4)


def sharded_paged_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array | None,
                            cpos_pages: jax.Array, table: jax.Array,
                            pos0: jax.Array, *, mesh, scale: float,
                            window: int = 0,
                            k2_pages: jax.Array | None = None,
                            k_scale_pages: jax.Array | None = None,
                            v_scale_pages: jax.Array | None = None,
                            mla_split: int = 0,
                            interpret: bool | None = None) -> jax.Array:
    """Head-parallel :func:`paged_attention` over a ``('pool','heads')`` mesh.

    Every kv head's running softmax is independent, so partitioning axis 2
    of the queries and the pools over the mesh's ``'heads'`` axis is
    embarrassingly parallel: each device runs the *identical* kernel on ``KV / nh`` heads
    and the results are concatenated. No reduction crosses the shard
    boundary, which is what keeps the sharded output **bitwise identical**
    to the single-device kernel — the contract the serving engine's parity
    tests pin down.

    Page tables and ``pos0`` are scalar-prefetch operands; they stay
    replicated (device-local) on every shard. The ``'pool'`` mesh axis only
    shards storage *at rest* — inside this call all operands are gathered
    over ``'pool'`` (specs never mention it), and with ``check_vma=False``
    the identical per-pool-shard outputs collapse back to one.

    Falls back to the plain kernel when there is nothing to shard: no mesh,
    a heads axis of size 1, MLA (``KV == 1`` latent head), or kv heads not
    divisible by the heads axis.
    """
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    nh = 1
    if mesh is not None:
        nh = dict(zip(mesh.axis_names, mesh.devices.shape)).get('heads', 1)
    KV = q.shape[2]
    if nh == 1 or mla_split or KV % nh:
        return paged_attention(q, k_pages, v_pages, cpos_pages, table, pos0,
                               scale=scale, window=window, k2_pages=k2_pages,
                               k_scale_pages=k_scale_pages,
                               v_scale_pages=v_scale_pages,
                               mla_split=mla_split, interpret=interpret)

    from jax.sharding import PartitionSpec as P

    sc_spec = P(None, None, 'heads')
    in_specs = [P(None, None, 'heads', None, None),    # q
                P(None, None, 'heads', None),          # k pages
                P(None, None, 'heads', None),          # v pages
                P(None, None),                         # cpos (replicated)
                P(None, None),                         # table (device-local)
                P(None)]                               # pos0
    operands = [q, k_pages, v_pages, cpos_pages, table.astype(jnp.int32),
                pos0.astype(jnp.int32)]
    if k_scale_pages is not None:
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale_pages, v_scale_pages]

    def body(q_, k_, v_, cp_, tab_, p0_, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q_, k_, v_, cp_, tab_, p0_, scale=scale,
                               window=window, k_scale_pages=ks,
                               v_scale_pages=vs, interpret=interpret)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(None, None, 'heads', None, None),
                         check_vma=False)(*operands)


def dense_page_split(Sc: int, max_page: int = 128) -> int:
    """Page size for viewing a dense (B, Sc, ...) cache as pages in place.

    Picks the largest power-of-two block <= ``max_page`` that divides Sc so
    the reshape to (B * Sc/ps, ps, ...) is free (no pad copy). Falls back to
    1 for odd ring lengths — still correct, just a deeper grid.
    """
    for bs in (max_page, 64, 32, 16, 8, 4, 2):
        if bs <= Sc and Sc % bs == 0:
            return bs
    return 1


def dense_as_pages(leaf: jax.Array, ps: int) -> jax.Array:
    """(B, Sc, ...) -> (B * Sc/ps, ps, ...) page view — a pure reshape."""
    B, Sc = leaf.shape[:2]
    return leaf.reshape((B * (Sc // ps), ps) + leaf.shape[2:])


def dense_identity_table(B: int, Sc: int, ps: int) -> jax.Array:
    """Page table mapping slot b's block j to physical page b * P + j."""
    P = Sc // ps
    return jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
