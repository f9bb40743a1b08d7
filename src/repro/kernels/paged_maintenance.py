"""Pallas TPU kernels: in-kernel paged-cache maintenance.

PR 5 moved the paged *read* (attend) in-kernel; this module moves the three
remaining per-layer paged writes in-kernel so a paged decode step touches
each pool page once:

- **chunk K/V scatter** — the reference path writes a chunk with an XLA
  flat-index scatter per leaf (``attention.paged_scatter``). Here the write
  is a job-list Pallas kernel: each grid step DMAs ONE physical page through
  a scalar-prefetched job table and merges the chunk rows that land in it.
- **clear-on-alloc** — freshly allocated pages used to be zeroed by a
  standalone XLA dispatch (``ServingEngine._clear_pages``). The engine now
  defers clears into ``PageTables.pending`` and they ride the same job list
  as first-write masking: a fresh page's unwritten rows get the fill value
  in the same pass that writes its new rows (mode 1), and pending pages not
  written this chunk get a whole-page clear job (mode 2).
- **copy-on-write** — partial-page COW at admission was an XLA gather+pad
  copy; :func:`cow_page_copy` is a page-to-page DMA kernel (one src page in,
  one dst page out, tail rows filled).

Job list (``NJ, 6`` int32, scalar-prefetched): ``[page, slot, delta, nv,
mode, vbase]``. Row ``r`` of the page holds chunk lane ``t = (delta + r)
mod Sc``; a row is written iff ``t < nv`` AND ``vbase + r < Sc`` (``vbase``
is the block's first virtual index — ring lengths need not be page
multiples, and the tail rows of the partial last page back no virtual index
at all). ``mode``: 0 = merge into existing page,
1 = merge into a fresh (pending) page — unwritten rows get the fill value,
2 = clear the whole page (``nv == 0`` so no row is written). The in-kernel
gather is a one-hot matmul ``(ps, T) x (T, F)``: every output row sums
exactly one chunk row (or none), so the result is BITWISE the XLA
scatter's — int8/bf16/int32 round-trip exactly through the fp32 MXU pass.

Write-hazard discipline (Pallas revisits of one output block are pipelined,
so two jobs may only target the same page if their writes are
byte-identical): real merge pages are slot-exclusive (COW guarantees it)
and distinct within a slot; every residual collision lands on the null
page 0, whose content equals the fill value, making all such jobs
idempotent no-ops. :func:`build_jobs` demotes a pending page's clear job to
page 0 when a merge job covers the same page (the merge's mode-1 fresh
masking subsumes the clear).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MODE_MERGE = 0
MODE_FRESH = 1
MODE_CLEAR = 2


def leaf_fill(name: str) -> int:
    """Clear value for a pool leaf: positions use -1 (= never written)."""
    return -1 if name == 'pos' else 0


def build_jobs(pos0: jax.Array, n_valid: jax.Array, table: jax.Array,
               Sc: int, ps: int, T: int, pending: jax.Array) -> jax.Array:
    """Static-shape job list for one chunk write + pending clears.

    pos0 (B,), n_valid (B,), table (B, P), pending (K,) int32 physical
    pages awaiting clear-on-alloc (0 = padding) -> jobs (K + B*NJm, 6).

    A chunk of T tokens touches at most ``T // ps + 3`` consecutive logical
    blocks (ring wrap included; +3 because a non-page-multiple ring's
    partial last block can hold as little as one row), so NJm candidate
    merge jobs per slot cover every written page; candidates beyond the
    written range become write-back no-ops via the in-kernel ``t < nv``
    mask. A candidate whose page is pending is marked fresh (mode 1) and
    its standalone clear job is demoted to the page-0 no-op, keeping the
    clear-set and merge-set disjoint per dispatch.
    """
    B, P = table.shape
    NJm = min(T // ps + 3, P)
    i = jnp.arange(NJm, dtype=jnp.int32)
    pos0 = pos0.astype(jnp.int32)
    start_blk = (pos0 % Sc) // ps                              # (B,)
    lb = (start_blk[:, None] + i[None, :]) % P                 # (B, NJm)
    page = jnp.take_along_axis(table.astype(jnp.int32), lb, axis=1)
    delta = (lb * ps - pos0[:, None]) % Sc
    nv = jnp.broadcast_to(n_valid.astype(jnp.int32)[:, None], (B, NJm))
    slot = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, NJm))
    pend = pending.astype(jnp.int32)                           # (K,)
    fresh = ((page[:, :, None] == pend[None, None, :])
             & (pend[None, None, :] > 0)).any(-1)
    mode = jnp.where(fresh, MODE_FRESH, MODE_MERGE)
    merge = jnp.stack([page, slot, delta, nv, mode, lb * ps], axis=-1) \
        .reshape(B * NJm, 6)

    covered = (pend[:, None] == page.reshape(-1)[None, :]).any(-1)
    cpage = jnp.where(covered, 0, pend)
    z = jnp.zeros_like(pend)
    clear = jnp.stack([cpage, z, z, z,
                       jnp.full_like(pend, MODE_CLEAR), z], axis=-1)
    return jnp.concatenate([clear, merge], axis=0)


def _scatter_kernel(jobs_ref, vals_ref, pool_ref, out_ref, *, Sc, fill):
    j = pl.program_id(0)
    delta = jobs_ref[j, 2]
    nv = jobs_ref[j, 3]
    mode = jobs_ref[j, 4]
    vbase = jobs_ref[j, 5]
    old = pool_ref[0]                                       # (ps, ...)
    v = vals_ref[0]                                         # (T, ...)
    ps = old.shape[0]
    T = v.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)[:, 0]
    t_r = delta + r
    t_r = jnp.where(t_r >= Sc, t_r - Sc, t_r)               # mod Sc, r < ps
    # mode 2: nv == 0. vbase + r >= Sc: tail rows of a non-page-multiple
    # ring's partial last page back no virtual index — never write them
    written = (t_r < nv) & (vbase + r < Sc)
    tt = jax.lax.broadcasted_iota(jnp.int32, (ps, T), 1)
    onehot = ((t_r[:, None] == tt) & written[:, None]).astype(jnp.float32)
    old2 = old.reshape(ps, -1).astype(jnp.float32)
    v2 = v.reshape(T, -1).astype(jnp.float32)
    # exactly one nonzero per output row -> bitwise the scattered value, as
    # long as the MXU keeps all of fp32: at the default precision the TPU
    # rounds fp32 operands to bf16, which moves stored positions past 256
    gathered = jnp.dot(onehot, v2, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    base = jnp.where(mode >= MODE_FRESH,
                     jnp.full_like(old2, float(fill)), old2)
    out2 = jnp.where(written[:, None], gathered, base)
    out_ref[0] = out2.reshape(old.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=('Sc', 'fill', 'interpret'))
def fused_page_write(pool: jax.Array, vals: jax.Array, jobs: jax.Array, *,
                     Sc: int, fill: int = 0,
                     interpret: bool | None = None) -> jax.Array:
    """Apply a :func:`build_jobs` job list to one pool leaf, in place.

    pool (NP, ps, ...), vals (B, T, ...) matching trailing dims, jobs
    (NJ, 6) int32 -> updated pool (donated/aliased: each grid step reads
    and writes exactly the one page its job names).
    """
    from repro.kernels.ops import _interpret, on_every_device
    if interpret is None:
        interpret = _interpret()
    if pool.ndim == 2:
        # Mosaic tiles a block's last two dims by (8, 128) unless they span
        # the array, which a (1, ps) page of an (NP, ps) leaf cannot; a
        # trailing unit axis makes the page block (1, ps, 1), whose last
        # two dims are the array's own
        return fused_page_write(pool[..., None], vals[..., None], jobs,
                                Sc=Sc, fill=fill,
                                interpret=interpret)[..., 0]
    NJ = jobs.shape[0]
    ps = pool.shape[1]
    T = vals.shape[1]
    tail = pool.shape[2:]
    assert vals.shape[2:] == tail, (pool.shape, vals.shape)
    zeros = (0,) * len(tail)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                               # jobs
        grid=(NJ,),
        in_specs=[
            pl.BlockSpec((1, T) + tail,
                         lambda j, jb, z=zeros: (jb[j, 1], 0) + z),
            pl.BlockSpec((1, ps) + tail,
                         lambda j, jb, z=zeros: (jb[j, 0], 0) + z),
        ],
        out_specs=pl.BlockSpec((1, ps) + tail,
                               lambda j, jb, z=zeros: (jb[j, 0], 0) + z),
    )
    kernel = functools.partial(_scatter_kernel, Sc=Sc, fill=fill)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},                         # pool -> out
        interpret=interpret,
        name='fused_page_write',
    )
    return on_every_device(call, jobs.astype(jnp.int32), vals, pool)


def fused_chunk_scatter(cache: dict, vals: dict, pos0: jax.Array,
                        n_valid: jax.Array, table: jax.Array, Sc: int,
                        pending: jax.Array) -> dict:
    """Fused equivalent of ``paged_scatter`` + deferred clear-on-alloc.

    Writes every ``vals`` leaf plus the derived absolute-position leaf, and
    executes the ``pending`` page clears against EVERY leaf of this cache —
    one Pallas dispatch per leaf, each touching each named page once.
    Bitwise identical to ``_clear_pages`` followed by ``paged_scatter``.
    """
    ps = cache['pos'].shape[1]
    T = next(iter(vals.values())).shape[1]
    jobs = build_jobs(pos0, n_valid, table, Sc, ps, T, pending)
    pos_t = pos0.astype(jnp.int32)[:, None] \
        + jnp.arange(T, dtype=jnp.int32)[None, :]
    vals = dict(vals, pos=pos_t)
    out = dict(cache)
    for name, pool in cache.items():
        v = vals.get(name)
        if v is None:
            # leaf gets no chunk data this step (defensive: all current
            # paged layouts write every leaf) — run its clear jobs with a
            # zero-lane dummy chunk by masking all writes off
            v = jnp.zeros((pos0.shape[0], T) + pool.shape[2:], pool.dtype)
            lj = jobs.at[:, 3].set(0)
        else:
            lj = jobs
        out[name] = fused_page_write(pool, v.astype(pool.dtype), lj,
                                     Sc=Sc, fill=leaf_fill(name))
    return out


def _cow_kernel(sdr_ref, src_ref, out_ref, *, fill):
    j = pl.program_id(0)
    rem = sdr_ref[j, 2]
    row = src_ref[0]                                         # (ps, ...)
    ps = row.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)[:, 0]
    keep = (r < rem)[:, None]
    r2 = row.reshape(ps, -1)
    out_ref[0] = jnp.where(keep, r2, jnp.full_like(r2, fill)) \
        .reshape(row.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=('fill', 'interpret'))
def cow_page_copy(pool: jax.Array, sdr: jax.Array, *, fill: int = 0,
                  interpret: bool | None = None) -> jax.Array:
    """Copy-on-write as a page-to-page DMA.

    pool (NP, ps, ...), sdr (NJ, 3) int32 rows ``[src, dst, rem]`` -> pool
    with each page dst = its src's first ``rem`` rows, tail rows filled.
    Each grid step streams one src page in and one dst page out — no
    dense gather, no standalone clear dispatch for the tail. Jobs must
    name distinct dst pages (the engine issues one per scan rep).
    """
    from repro.kernels.ops import _interpret, on_every_device
    if interpret is None:
        interpret = _interpret()
    if pool.ndim == 2:          # (NP, ps) leaf: see fused_page_write
        return cow_page_copy(pool[..., None], sdr, fill=fill,
                             interpret=interpret)[..., 0]
    NJ = sdr.shape[0]
    ps = pool.shape[1]
    tail = pool.shape[2:]
    zeros = (0,) * len(tail)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # rows of [src, dst, rem]
        grid=(NJ,),
        in_specs=[
            pl.BlockSpec((1, ps) + tail,
                         lambda j, s, z=zeros: (s[j, 0], 0) + z),
        ],
        out_specs=pl.BlockSpec((1, ps) + tail,
                               lambda j, s, z=zeros: (s[j, 1], 0) + z),
    )
    call = pl.pallas_call(
        functools.partial(_cow_kernel, fill=fill),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},                         # pool -> out
        interpret=interpret,
        name='cow_page_copy',
    )
    return on_every_device(call, sdr.astype(jnp.int32), pool)


def cow_copy_cache(cache: dict, src: jax.Array, dst: jax.Array,
                   rem: jax.Array) -> dict:
    """Run :func:`cow_page_copy` on every leaf of one paged cache dict."""
    sdr = jnp.stack([src.astype(jnp.int32), dst.astype(jnp.int32),
                     rem.astype(jnp.int32)])[None]
    return {name: cow_page_copy(pool, sdr, fill=leaf_fill(name))
            for name, pool in cache.items()}
