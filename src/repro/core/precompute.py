"""THE PAPER: offline precomputation of the first transformer layer.

For every entry of the vocabulary, run the *position-independent* part of
layer 0 (first LayerNorm, Q/K/V projections, and — for parallel blocks — the
full FFN with the skip connection folded in) and store the results as an
expanded embedding table:

    serial   row = [x, q, k, v]                width d + q_size + 2e
    parallel row = [s = x + FFN(LN2(x)), q, k, v]   (same width)
    MLA      row = [x, q, c_kv, k_pe]
    mLSTM    row = [x, u1, u2, v, ifg]         (beyond-paper, see DESIGN.md)
    sLSTM    row = [x, z_in, o_in]
    hybrid   row = [x, q, k, v, x_in, gate]

At inference, the embedding lookup *and* those projections collapse into one
row gather (`PrecomputedTable.gather`). RoPE and attention stay at runtime —
that is the enabling condition (RoPE is applied after the projections).

This is done once, offline (`build_precomputed_table`), and the table is
stored with the parameters — exactly the paper's §1 procedure.

Serving-time note: during chunked prefill the per-token row gather becomes a
multi-row gather per chunk, and ``kernels/gather_rope.py`` provides a fused
Pallas kernel that applies layer-0 RoPE to the q/k slices inside the same
VMEM pass as the gather (opt-in via ``ServingEngine(fused_gather_rope=True)``)
— the rows go gather→RoPE→attention without an HBM round-trip.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.blocks import block_preproj, preproj_layout
from repro.models.transformer import layer_plan


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=['table'], meta_fields=['layout', 'name'])
@dataclasses.dataclass
class PrecomputedTable:
    """Expanded embedding table + row layout.

    ``table``: (vocab, row_width). ``layout``: ((name, width), ...) in storage
    order. ``gather`` returns the named pieces for a batch of token ids —
    the paper's "one memory read per token". A pytree (``table`` is its one
    leaf), so jitted programs take it as an argument.
    """
    table: jax.Array
    layout: Tuple[Tuple[str, int], ...]
    name: str = ''

    @property
    def row_width(self) -> int:
        return int(self.table.shape[1])

    @property
    def vocab_size(self) -> int:
        return int(self.table.shape[0])

    def split(self, rows: jax.Array) -> Dict[str, jax.Array]:
        out, off = {}, 0
        for nm, w in self.layout:
            out[nm] = rows[..., off:off + w]
            off += w
        return out

    def gather(self, tokens: jax.Array) -> Dict[str, jax.Array]:
        rows = jnp.take(self.table, tokens, axis=0)
        return self.split(rows)

    def abstract(self, rules) -> 'PrecomputedTable':
        """ShapeDtypeStruct stand-in (vocab-sharded) for the dry-run."""
        from repro.sharding import logical_sds
        sds = logical_sds(self.table.shape, self.table.dtype,
                          ('vocab', 'table_row'), rules)
        return PrecomputedTable(sds, self.layout, self.name)


VOCAB_PAD = 256   # pad the table's vocab dim so it shards on any mesh axis


def padded_vocab(vocab_size: int) -> int:
    return -(-vocab_size // VOCAB_PAD) * VOCAB_PAD


def table_abstract(cfg: ModelConfig, rules, dtype=jnp.bfloat16
                   ) -> PrecomputedTable:
    """Abstract table straight from a config (no params needed) — dry-run.

    The vocab dim is padded to a multiple of 256: odd vocabularies
    (151655, 32001, 51865 in the assigned pool) would otherwise fall back to
    a REPLICATED table on a 16-way model axis — 16x the HBM footprint.
    """
    from repro.sharding import logical_sds
    plan = layer_plan(cfg)
    layout = preproj_layout(cfg, plan.kinds[0], plan.use_moe[0])
    width = sum(w for _, w in layout)
    sds = logical_sds((padded_vocab(cfg.vocab_size), width), dtype,
                      ('vocab', 'table_row'), rules)
    return PrecomputedTable(sds, layout, cfg.name)


def build_precomputed_table(params, cfg: ModelConfig, *, chunk: int = 8192,
                            pad_vocab: bool = False) -> PrecomputedTable:
    """Offline pass: run the whole vocabulary through layer 0's
    position-independent computation. Chunked so huge vocabs don't blow memory.
    """
    assert cfg.precompute_supported, (
        f'{cfg.name}: position encoding "{cfg.pos}" is applied before the '
        'projections — the paper\'s precondition does not hold')
    plan = layer_plan(cfg)
    kind0, moe0 = plan.kinds[0], plan.use_moe[0]
    layout = preproj_layout(cfg, kind0, moe0)
    embed = params['embed']['table']
    V = embed.shape[0]

    @jax.jit
    def one_chunk(x):
        x = x.astype(jnp.dtype(cfg.dtype))
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        pieces = block_preproj(params['backbone']['layer0'], x[None], cfg,
                               kind0, moe0)
        return jnp.concatenate([pieces[nm].astype(jnp.dtype(cfg.dtype))
                                for nm, _ in layout], axis=-1)[0]

    rows = []
    for s in range(0, V, chunk):
        rows.append(one_chunk(embed[s:s + chunk]))
    table = jnp.concatenate(rows, axis=0)
    if pad_vocab:   # mesh-friendly padding (ids never reach the pad rows)
        table = jnp.pad(table, ((0, padded_vocab(V) - V), (0, 0)))
    return PrecomputedTable(table, layout, cfg.name)


def hybrid_vlm_pre0(params, cfg: ModelConfig, table: PrecomputedTable,
                    tokens: jax.Array, vision_h: jax.Array,
                    n_prefix: int) -> Dict[str, jax.Array]:
    """VLM 'hybrid' precompute: gather rows for text tokens, compute layer-0
    projections on the fly for (continuous) vision embeddings, and splice the
    sequences:   [text_prefix | vision tokens | text_suffix].
    """
    plan = layer_plan(cfg)
    pre_txt = table.gather(tokens)
    vpre = block_preproj(params['backbone']['layer0'], vision_h, cfg,
                         plan.kinds[0], plan.use_moe[0])
    out = {}
    for nm, _ in table.layout:
        t = pre_txt[nm]
        out[nm] = jnp.concatenate(
            [t[:, :n_prefix], vpre[nm].astype(t.dtype), t[:, n_prefix:]],
            axis=1)
    return out
