"""Work the algorithm needs, from shapes: FLOPs and bytes.

Counts are of useful work only. A token's model FLOPs are its matmuls
(2 per weight) plus attention over the keys it really sees (its position + 1),
not over the pages a kernel walks. The output head is counted once per
sampled token, where the program applies it. An architecture
(``bench/arch/``) gives its own counts per token, per key and per query;
this module holds the loop over one step's lanes that every family shares.
"""
from __future__ import annotations

from typing import Callable

BYTES = {'bfloat16': 2, 'float16': 2, 'float32': 4}


def model_flops(lanes, *, token: int, head: int,
                attention: Callable[[int], int]) -> int:
    """Model FLOPs of one step's work. ``lanes`` holds, per slot, the
    absolute positions of the tokens the slot processed in the step; each
    slot also samples one token through the head. ``token``: matmul FLOPs
    of one processed token; ``head``: FLOPs of one sampled token's head;
    ``attention(keys)``: FLOPs of one query over ``keys`` keys."""
    n = 0
    for positions in lanes:
        n += len(positions) * token + head
        n += sum(attention(p + 1) for p in positions)
    return n


def paged_attention_work(lanes, *, attention: Callable[[int], int],
                         key_bytes: int, query_bytes: int) -> tuple:
    """(FLOPs, bytes) that the paged-attention kernel needs for one step:
    each slot's queries meet its valid keys (position + 1 of each query),
    and its cache is read once for the step up to its last position. Pages
    walked past the valid context are not counted. ``key_bytes``: what one
    cached key costs to read, all layers (its cached rows and stored
    position); ``query_bytes``: one query's input and output, all
    layers."""
    flops = byts = 0
    for positions in lanes:
        if not positions:
            continue
        flops += sum(attention(p + 1) for p in positions)
        byts += (max(positions) + 1) * key_bytes \
            + len(positions) * query_bytes
    return flops, byts
