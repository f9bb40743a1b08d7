"""Decoder-only LM assembly.

Layer organisation (see DESIGN.md §5):

- **layer 0 is always unstacked** — this is what makes the paper's first-layer
  precompute a clean surgery: with a precomputed table, layer 0 consumes
  gathered ``[x|s, q, k, v, ...]`` rows instead of running its projections,
  and nothing inside the scanned stack changes.
- optional unstacked *head* layers (e.g. DeepSeek's leading dense-FFN layers),
- a ``lax.scan`` over repetitions of the arch's layer *pattern* (params stacked
  per pattern slot -> HLO size independent of depth; each slot keeps static
  structure such as sliding-window cache length),
- an unrolled *tail* for non-divisible depths.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.blocks import (block_apply_full, block_decode,
                                 block_make_state, block_paged_mask,
                                 block_schema, block_state_abstract,
                                 preproj_layout)
from repro.models.layers import ParamSpec


# ========================================================== layer organisation
@dataclasses.dataclass(frozen=True)
class LayerPlan:
    kinds: Tuple[str, ...]          # kind of every layer, in order
    use_moe: Tuple[bool, ...]       # per layer
    n_head: int                     # unstacked layers after layer 0
    reps: int                       # scan repetitions
    slots: Tuple[str, ...]          # rotated pattern (kind per scan slot)
    n_tail: int


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    P = len(cfg.pattern)
    kinds = tuple(cfg.pattern[i % P] for i in range(cfg.num_layers))
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    use_moe = tuple(cfg.moe is not None and i >= n_dense
                    for i in range(cfg.num_layers))
    n_head = max(0, n_dense - 1)            # layer 0 is peeled separately
    start = 1 + n_head
    remaining = cfg.num_layers - start
    slots = tuple(cfg.pattern[(start + s) % P] for s in range(P))
    reps = remaining // P
    n_tail = remaining - reps * P
    return LayerPlan(kinds, use_moe, n_head, reps, slots, n_tail)


def _slot_shardings(cfg: ModelConfig, plan: LayerPlan, body_moe: bool, rules):
    """Per-slot NamedShardings for the UNSTACKED layer params.

    Applied as with_sharding_constraint on the scan body's sliced params:
    this pins each layer's weights to their (FSDP-)sharded layout INSIDE the
    loop, so the SPMD partitioner cannot hoist the all-gather of the whole
    stacked parameter tree out of the scan (which would materialise every
    layer's gathered weights at once — 780 GiB/device for llama3-405b).
    """
    if rules is None or rules.mesh is None:
        return [None] * len(plan.slots)
    return [L.param_shardings(block_schema(cfg, k, body_moe), rules)
            for k in plan.slots]


def _constrain_params(prm, shardings):
    if shardings is None:
        return prm
    return jax.tree_util.tree_map(
        lambda x, sh: jax.lax.with_sharding_constraint(x, sh)
        if sh is not None else x, prm, shardings)


# ==================================================================== schema
def backbone_schema(cfg: ModelConfig) -> Dict:
    plan = layer_plan(cfg)
    sch: Dict[str, Any] = {
        'layer0': block_schema(cfg, plan.kinds[0], plan.use_moe[0])}
    if plan.n_head:
        sch['head'] = [block_schema(cfg, plan.kinds[1 + i], plan.use_moe[1 + i])
                       for i in range(plan.n_head)]
    if plan.reps:
        body_moe = plan.use_moe[1 + plan.n_head]
        sch['body'] = [L.stack_schema(block_schema(cfg, k, body_moe),
                                      plan.reps) for k in plan.slots]
    if plan.n_tail:
        sch['tail'] = [block_schema(cfg, plan.slots[i], plan.use_moe[-1])
                       for i in range(plan.n_tail)]
    return sch


def lm_schema(cfg: ModelConfig) -> Dict:
    sch: Dict[str, Any] = {
        'embed': L.embed_schema(cfg.vocab_size, cfg.d_model),
        'final_norm': L.norm_schema(cfg.d_model, cfg.norm),
        'backbone': backbone_schema(cfg),
    }
    if not cfg.tie_embeddings:
        sch['lm_head'] = L.dense_schema(cfg.d_model, cfg.vocab_size,
                                        ('embed', 'vocab'))
    if cfg.pos == 'learned':
        sch['pos_embed'] = ParamSpec((cfg.max_seq_len, cfg.d_model),
                                     (None, 'embed'), 'normal', 0.02)
    if cfg.num_meta_tokens:
        sch['meta'] = ParamSpec((cfg.num_meta_tokens, cfg.d_model),
                                (None, 'embed'), 'normal', 0.02)
    return sch


# ================================================================= embedding
def embed_tokens(params, tokens: jax.Array, cfg: ModelConfig,
                 positions: Optional[jax.Array] = None) -> jax.Array:
    h = L.embed_lookup(params['embed'], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    if cfg.pos == 'learned':
        assert positions is not None
        h = h + jnp.take(params['pos_embed'], positions, axis=0).astype(h.dtype)
    return h


def lm_head(params, h_normed: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Output projection only (callers may chunk over sequence)."""
    if cfg.tie_embeddings:
        logits = L.unembed(params['embed'], h_normed)
    else:
        logits = L.dense(params['lm_head'], h_normed)
    return L.softcap(logits, cfg.logit_softcap)


def lm_logits(params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    return lm_head(params, L.norm_apply(params['final_norm'], h, cfg.norm),
                   cfg)


# ================================================================== full seq
def backbone_apply(params, h: jax.Array, positions: jax.Array,
                   cfg: ModelConfig, *, rules=None, remat: bool = False,
                   pre0: Optional[Dict] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Run all layers on initial hidden states. pre0 = layer-0 precompute rows."""
    plan = layer_plan(cfg)

    def constrain(x):
        return rules.constrain(x, ('batch', 'seq', 'embed_act')) \
            if rules is not None else x

    def run_block(prm, hh, kind, moe, pre=None):
        f = lambda p, x: block_apply_full(p, x, positions, cfg, kind, moe,
                                          pre=pre, rules=rules)
        if remat:   # unstacked layers need remat too (15-layer hymba tail!)
            f = jax.checkpoint(f)
        return f(prm, hh)

    aux = jnp.zeros((), jnp.float32)
    h, a = run_block(params['layer0'], h, plan.kinds[0], plan.use_moe[0],
                     pre=pre0)
    h = constrain(h)
    aux += a
    for i in range(plan.n_head):
        h, a = run_block(params['head'][i], h, plan.kinds[1 + i],
                         plan.use_moe[1 + i])
        h = constrain(h)
        aux += a
    if plan.reps:
        body_moe = plan.use_moe[1 + plan.n_head]
        slot_shardings = _slot_shardings(cfg, plan, body_moe, rules)

        def one_block(kind):
            def f(prm, hh):
                return block_apply_full(prm, hh, positions, cfg, kind,
                                        body_moe, rules=rules)
            # nested remat: the scan-level checkpoint saves only the carry
            # per rep; the per-layer checkpoint bounds the backward's
            # recompute working set to ONE layer's intermediates
            return jax.checkpoint(f) if remat else f

        blocks = [one_block(k) for k in plan.slots]

        def body(carry, xs):
            hh, ax = carry
            for s in range(len(plan.slots)):
                prm = _constrain_params(xs[s], slot_shardings[s])
                hh, a_s = blocks[s](prm, hh)
                hh = constrain(hh)
                ax += a_s
            return (hh, ax), None

        if remat:
            body = jax.checkpoint(body)
        (h, aux), _ = jax.lax.scan(body, (h, aux), tuple(params['body']))
    for i in range(plan.n_tail):
        h, a = run_block(params['tail'][i], h, plan.slots[i],
                         plan.use_moe[-1])
        h = constrain(h)
        aux += a
    return h, aux


def lm_apply(params, tokens: jax.Array, cfg: ModelConfig, *,
             positions: Optional[jax.Array] = None, rules=None,
             remat: bool = False, precomputed=None,
             return_hidden: bool = False) -> Tuple[jax.Array, jax.Array]:
    """tokens (B,S) -> (logits (B,S,V), aux_loss).

    ``precomputed``: a repro.core.PrecomputedTable — the paper's feature. When
    given, the embedding lookup AND all of layer 0's position-independent
    computation are replaced by a single gather of the expanded table.
    """
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    if precomputed is not None:
        pre0 = precomputed.gather(tokens)
        h = pre0['s'] if 's' in pre0 else pre0['x']
    else:
        pre0 = None
        h = embed_tokens(params, tokens, cfg, positions)
    if cfg.num_meta_tokens:
        meta = jnp.broadcast_to(
            params['meta'].astype(h.dtype)[None],
            (B, cfg.num_meta_tokens, cfg.d_model))
        h = jnp.concatenate([meta, h], axis=1)
        mpos = jnp.broadcast_to(
            jnp.arange(cfg.num_meta_tokens, dtype=jnp.int32)[None],
            (B, cfg.num_meta_tokens))
        positions = jnp.concatenate([mpos, positions + cfg.num_meta_tokens],
                                    axis=1)
        if pre0 is not None:   # meta tokens are not vocabulary entries:
            # compute their layer-0 projections on the fly and prepend
            from repro.models.blocks import block_preproj
            plan = layer_plan(cfg)
            mpre = block_preproj(params['backbone']['layer0'], h[:, :cfg.num_meta_tokens],
                                 cfg, plan.kinds[0], plan.use_moe[0])
            pre0 = {k: jnp.concatenate([mpre[k], pre0[k]], axis=1)
                    for k in pre0}
            h = pre0['s'] if 's' in pre0 else pre0['x']
    h, aux = backbone_apply(params['backbone'], h, positions, cfg,
                            rules=rules, remat=remat, pre0=pre0)
    h = L.norm_apply(params['final_norm'], h, cfg.norm)
    if cfg.num_meta_tokens:
        h = h[:, cfg.num_meta_tokens:]
    if return_hidden:
        return h, aux
    return lm_head(params, h, cfg), aux


# ==================================================================== decode
def backbone_make_states(cfg: ModelConfig, batch: int, seq_len: int,
                         dtype=jnp.bfloat16, quant: bool = False,
                         chunk: int = 1, num_pages: int = 0,
                         page_size: int = 0) -> Dict:
    plan = layer_plan(cfg)
    mk = lambda kind: block_make_state(cfg, kind, batch, seq_len, dtype,
                                       quant, chunk, num_pages, page_size)
    st: Dict[str, Any] = {'layer0': mk(plan.kinds[0])}
    if plan.n_head:
        st['head'] = [mk(plan.kinds[1 + i]) for i in range(plan.n_head)]
    if plan.reps:
        st['body'] = [
            jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (plan.reps,) + x.shape)
                .copy() if hasattr(x, 'shape') else x, mk(k))
            for k in plan.slots]
    if plan.n_tail:
        st['tail'] = [mk(plan.slots[i]) for i in range(plan.n_tail)]
    return st


def backbone_paged_mask(cfg: ModelConfig, quant: bool = False) -> Dict:
    """Bool tree matching :func:`backbone_make_states` (paged mode): True on
    page-pool leaves, False on per-slot state — drives the engine's
    slot-reset / snapshot / restore tree walks."""
    plan = layer_plan(cfg)
    st: Dict[str, Any] = {
        'layer0': block_paged_mask(cfg, plan.kinds[0], quant)}
    if plan.n_head:
        st['head'] = [block_paged_mask(cfg, plan.kinds[1 + i], quant)
                      for i in range(plan.n_head)]
    if plan.reps:
        st['body'] = [block_paged_mask(cfg, k, quant) for k in plan.slots]
    if plan.n_tail:
        st['tail'] = [block_paged_mask(cfg, plan.slots[i], quant)
                      for i in range(plan.n_tail)]
    return st


def backbone_states_abstract(cfg: ModelConfig, batch: int, seq_len: int,
                             rules, dtype=jnp.bfloat16,
                             quant: bool = False, chunk: int = 1) -> Dict:
    plan = layer_plan(cfg)

    def stack_sds(sds_tree, n):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                (n,) + s.shape, s.dtype,
                sharding=_prepend_none(s.sharding)), sds_tree)

    def _prepend_none(sh):
        if sh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(sh.mesh, P(*((None,) + tuple(sh.spec))))

    st: Dict[str, Any] = {
        'layer0': block_state_abstract(cfg, plan.kinds[0], batch, seq_len,
                                       rules, dtype, quant, chunk)}
    if plan.n_head:
        st['head'] = [block_state_abstract(cfg, plan.kinds[1 + i], batch,
                                           seq_len, rules, dtype, quant,
                                           chunk)
                      for i in range(plan.n_head)]
    if plan.reps:
        st['body'] = [stack_sds(block_state_abstract(cfg, k, batch, seq_len,
                                                     rules, dtype, quant,
                                                     chunk),
                                plan.reps)
                      for k in plan.slots]
    if plan.n_tail:
        st['tail'] = [block_state_abstract(cfg, plan.slots[i], batch, seq_len,
                                           rules, dtype, quant, chunk)
                      for i in range(plan.n_tail)]
    return st


def backbone_decode(params, h: jax.Array, states: Dict, pos: jax.Array,
                    cfg: ModelConfig, *, pre0: Optional[Dict] = None,
                    rules=None, n_valid: Optional[jax.Array] = None,
                    rope_applied: bool = False, paged=None,
                    lane_valid: Optional[jax.Array] = None,
                    attn_backend=None, packed=None
                    ) -> Tuple[jax.Array, Dict, jax.Array]:
    """``n_valid is None``: classic one-token step (h is (B,1,d)).
    With ``n_valid`` (B,): chunked step — h is (B,T,d); attention layers
    (incl. MLA) write their chunk of K/V (or latents) in one call, recurrent
    layers scan the chunk with masked state commits. Every kind supports it.
    ``paged`` (a PageTables) switches attention caches to page-pool
    addressing; ``lane_valid`` masks dead slots out of MoE routing in the
    one-token step; ``attn_backend`` (an ``attn_backend.AttnBackend``;
    None = reference) picks the attend implementation for every attention
    layer in the stack; ``packed`` (an ``attention.PackedLayout``) runs
    the segment-packed chunk layout — ``h`` is bin-packed (R, T, d) while
    ``pos`` / ``n_valid`` / ``states`` stay slot-major (see
    ``blocks.block_decode``). Returns (h, states,
    moe_dropped_token_slots).
    """
    plan = layer_plan(cfg)
    kw = dict(n_valid=n_valid, paged=paged, lane_valid=lane_valid,
              backend=attn_backend, packed=packed)
    drops = jnp.zeros((), jnp.int32)
    new_states: Dict[str, Any] = {}
    h, st, d0 = block_decode(params['layer0'], h, states['layer0'], pos, cfg,
                             plan.kinds[0], plan.use_moe[0], pre=pre0,
                             rope_applied=rope_applied, **kw)
    new_states['layer0'] = st
    drops += d0
    if plan.n_head:
        new_states['head'] = []
        for i in range(plan.n_head):
            h, st, d = block_decode(params['head'][i], h, states['head'][i],
                                    pos, cfg, plan.kinds[1 + i],
                                    plan.use_moe[1 + i], **kw)
            new_states['head'].append(st)
            drops += d
    if plan.reps:
        body_moe = plan.use_moe[1 + plan.n_head]
        slot_shardings = _slot_shardings(cfg, plan, body_moe, rules)

        def body(carry, xs):
            hh, dr = carry
            prm, sts = xs
            outs = []
            for s, kind in enumerate(plan.slots):
                prm_s = _constrain_params(prm[s], slot_shardings[s])
                hh, st_s, d_s = block_decode(prm_s, hh, sts[s], pos, cfg,
                                             kind, body_moe, **kw)
                outs.append(st_s)
                dr += d_s
            return (hh, dr), tuple(outs)

        (h, drops), body_states = jax.lax.scan(
            body, (h, drops), (tuple(params['body']), tuple(states['body'])))
        new_states['body'] = list(body_states)
    if plan.n_tail:
        new_states['tail'] = []
        for i in range(plan.n_tail):
            h, st, d = block_decode(params['tail'][i], h, states['tail'][i],
                                    pos, cfg, plan.slots[i],
                                    plan.use_moe[-1], **kw)
            new_states['tail'].append(st)
            drops += d
    return h, new_states, drops


def prime_meta_states(params, states: Dict, cfg: ModelConfig,
                      batch: int) -> Dict:
    """Feed the learnable meta tokens (Hymba) through the decode path so the
    caches/recurrent states start as if the meta prefix had been prefilled.
    Token positions must then start at ``cfg.num_meta_tokens``.
    """
    for i in range(cfg.num_meta_tokens):
        h = jnp.broadcast_to(
            params['meta'][i].astype(jnp.dtype(cfg.dtype))[None, None],
            (batch, 1, cfg.d_model))
        _, states, _ = backbone_decode(params['backbone'], h, states,
                                       jnp.full((batch,), i, jnp.int32), cfg)
    return states


def lm_decode_step(params, tokens: jax.Array, states: Dict, pos: jax.Array,
                   cfg: ModelConfig, *, precomputed=None, rules=None,
                   n_valid: Optional[jax.Array] = None,
                   return_hidden: bool = False,
                   fused_gather_rope: bool = False, paged=None,
                   lane_valid: Optional[jax.Array] = None,
                   return_stats: bool = False,
                   attn_backend=None, packed=None) -> Tuple[jax.Array, Dict]:
    """tokens (B,T), pos (B,) -> (logits (B,T,V), new states).

    ``n_valid is None`` is the classic one-token step (T == 1). With
    ``n_valid`` (B,) the whole T-token chunk advances in one call — for
    EVERY architecture kind (attention, MLA, mLSTM/sLSTM, hybrid): slot b's
    tokens sit at positions ``pos[b] .. pos[b] + n_valid[b] - 1``; lanes
    beyond ``n_valid`` are padding (computed but never committed to caches
    or recurrent states, their logits are garbage).

    With ``precomputed``, the embedding read + layer-0 projections collapse to
    one row gather — the paper's decode-time win, amortised over the chunk.
    ``fused_gather_rope`` additionally folds layer-0 RoPE into that gather via
    the Pallas kernel (kernels/gather_rope.py); it requires a q/k layout
    (dense non-MLA) and rope positions.

    ``return_hidden`` skips final-norm + lm_head and returns the raw hidden
    states — the serving engine selects each slot's last valid lane first and
    runs the head on (B,1,d) instead of (B,T,V).

    ``paged`` (an ``attention.PageTables``) switches the attention caches to
    page-pool addressing — shared-prefix serving. ``lane_valid`` (B,) masks
    dead slots out of MoE routing in the one-token step. ``return_stats``
    appends a stats dict (``moe_drops``) to the return tuple.
    ``attn_backend`` selects the attend implementation (see
    ``repro.models.attn_backend``; None = the bit-identical reference).

    ``packed`` (an ``attention.PackedLayout``; chunked path only) runs the
    segment-packed prefill layout: ``tokens`` is the bin-packed (R, T)
    grid, per-lane positions come from ``packed.lane_pos``, and the
    returned hidden/logit grid is packed — select per-slot rows through
    ``packed.seg_row`` / ``packed.seg_off``. ``pos`` / ``n_valid`` /
    ``states`` stay slot-major (S,).
    """
    rope_applied = False
    if packed is not None:
        assert n_valid is not None, 'packed prefill runs the chunked path'
    if n_valid is None:
        if precomputed is not None:
            pre0 = precomputed.gather(tokens)
            h = pre0['s'] if 's' in pre0 else pre0['x']
        else:
            pre0 = None
            h = embed_tokens(params, tokens, cfg,
                             positions=pos[:, None] if cfg.pos == 'learned'
                             else None)
    else:
        T = tokens.shape[1]
        if packed is not None:
            pos_t = packed.lane_pos
        else:
            pos_t = pos[:, None].astype(jnp.int32) \
                + jnp.arange(T, dtype=jnp.int32)
        if precomputed is not None:
            if fused_gather_rope and fused_rope_eligible(precomputed, cfg):
                pre0 = _fused_gather_rope_pre0(precomputed, tokens, pos_t, cfg)
                rope_applied = True
            else:
                pre0 = precomputed.gather(tokens)
            h = pre0['s'] if 's' in pre0 else pre0['x']
        else:
            pre0 = None
            h = embed_tokens(params, tokens, cfg,
                             positions=pos_t if cfg.pos == 'learned' else None)
    h, states, drops = backbone_decode(params['backbone'], h, states, pos,
                                       cfg, pre0=pre0, rules=rules,
                                       n_valid=n_valid,
                                       rope_applied=rope_applied,
                                       paged=paged, lane_valid=lane_valid,
                                       attn_backend=attn_backend,
                                       packed=packed)
    out = h if return_hidden else lm_logits(params, h, cfg)
    if return_stats:
        return out, states, {'moe_drops': drops}
    return out, states


def fused_rope_eligible(precomputed, cfg: ModelConfig) -> bool:
    """Can layer 0's row gather fold RoPE in-kernel for this config?

    True for rope-positional attention-first stacks whose precomputed row
    carries either the flat q/k layout (dense GQA) or the MLA latent layout
    (per-head ``[qk_nope | qk_rope]`` q slices plus the shared ``k_pe``
    slice). Ineligible configs (hybrid/recurrent layer 0, learned
    positions) fall back to the unfused gather — callers need no
    special-casing.
    """
    from repro.models.blocks import ATTN_KINDS
    if precomputed is None or cfg.pos != 'rope':
        return False
    if layer_plan(cfg).kinds[0] not in ATTN_KINDS:
        return False
    names = [nm for nm, _ in precomputed.layout]
    if cfg.mla is not None:
        return 'q' in names and 'ckv' in names and 'kpe' in names
    return 'q' in names and 'k' in names


def _fused_gather_rope_pre0(precomputed, tokens: jax.Array, pos_t: jax.Array,
                            cfg: ModelConfig) -> Dict[str, jax.Array]:
    """Layer-0 rows via the fused gather→RoPE kernel: one table read per
    token with the rotary slices already rotated for their positions —
    q/k for the dense layout, per-head ``qk_rope`` plus ``k_pe`` for MLA."""
    from repro.kernels import ops
    from repro.models.blocks import kind_theta
    plan = layer_plan(cfg)
    assert fused_rope_eligible(precomputed, cfg)
    offs, off = {}, 0
    for nm, w in precomputed.layout:
        offs[nm] = off
        off += w
    theta = kind_theta(cfg, plan.kinds[0])
    if cfg.mla is not None:
        m = cfg.mla
        dn, dr = m.qk_nope_dim, m.qk_rope_dim
        segs = tuple((offs['q'] + h * (dn + dr) + dn, 1, dr)
                     for h in range(cfg.num_heads))
        segs += ((offs['kpe'], 1, dr),)
        rows = ops.gather_rope_rows_segs(precomputed.table, tokens, pos_t,
                                         segs=segs, theta=theta)
    else:
        rows = ops.gather_rope_rows(
            precomputed.table, tokens, pos_t,
            q_off=offs['q'], num_heads=cfg.num_heads,
            k_off=offs['k'], num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, theta=theta)
    return precomputed.split(rows)


def pad_table_for_fused(precomputed):
    """Pad the precomputed table to the gather kernel's tiling ONCE — row
    width to 128 lanes, vocab to a whole sublane tile — so ``ops`` wrappers
    don't re-pad (copy) the whole table inside every jit'd chunk dispatch.
    ``split()`` reads only the layout's widths and token ids never reach the
    pad rows, so the padding is inert."""
    import dataclasses
    from repro.kernels.gather_rope import tile_rows
    V, W = precomputed.table.shape
    pad_v = (-V) % tile_rows(precomputed.table.dtype)
    pad_w = (-W) % 128
    if pad_v or pad_w:
        precomputed = dataclasses.replace(
            precomputed,
            table=jnp.pad(precomputed.table, ((0, pad_v), (0, pad_w))))
    return precomputed
