"""Work the algorithm needs, from shapes: FLOPs and bytes.

Counts are of useful work only. A token's model FLOPs are its matmuls
(2 per weight) plus attention over the keys it really sees (its position + 1),
not over the pages a kernel walks. Layer 0's projections that the
precomputed table replaces (Q, K, V) are not counted: the served algorithm
gathers them. The output head is counted once per sampled token, where the
program applies it.

The weight accounting is that of ``repro.core.analysis`` (the paper's
tables), written out again here so that no change to the program can change
the yardstick; ``bench/tests/test_work.py`` holds the two equal.
"""
from __future__ import annotations

from bench.weights import Dims

BYTES = {'bfloat16': 2, 'float16': 2, 'float32': 4}


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
    """Model FLOPs of one step's work. ``lanes`` holds, per slot, the
    absolute positions of the tokens the slot processed in the step; each
    slot also samples one token through the head."""
    n = 0
    for positions in lanes:
        n += len(positions) * matmul_flops_per_token(d) + head_flops(d)
        n += sum(attention_flops(d, p + 1) for p in positions)
    return n


def paged_attention_work(d: Dims, lanes) -> tuple:
    """(FLOPs, bytes) that the paged-attention kernel needs for one step,
    all layers: each slot's queries meet its valid keys (position + 1 of
    each query), and its K/V are read once for the step up to its last
    position. Pages walked past the valid context are not counted."""
    b = BYTES[d.dtype]
    flops = byts = 0
    for positions in lanes:
        if not positions:
            continue
        flops += sum(attention_flops(d, p + 1) for p in positions)
        keys = max(positions) + 1
        kv = 2 * keys * kv_size(d) * b                 # K and V rows
        qo = 2 * len(positions) * q_size(d) * b        # queries and output
        byts += d.layers * (kv + qo + keys * 4)        # + stored positions
    return flops, byts
