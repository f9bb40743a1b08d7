"""Seeded request streams: the one generator every traffic mix uses.

A mix is a data file, ``bench/traffic/<mix>.json``; the generator reads its
parameters and yields request specs forever, block by block. Each block of
``block`` requests holds the same multiset of sizes, taken from stratified
quantiles of the mix's distributions; the seed chooses the token ids and,
unless the mix fixes it, the order of the sizes. So two seeds give the same
work, and a window that covers whole blocks sees the same lengths whatever
the seed; one that covers a few tens of requests sees the same ones only
where the mix fixes the order.

Mix keys:

- ``arrival``: ``{"kind": "backlog"}``, the one kind there is: the client
  keeps the engine's queue full, and requests have no due times.
- ``prompt``, ``output``: length distributions of the prompt and of
  ``max_new_tokens``, each ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}``; every prompt token is drawn fresh.
- ``order_seed`` (optional): the order of the sizes in each block is drawn
  from this number instead of the run's seed, so every run sends the same
  lengths in the same order and only the tokens differ.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np

FIRST_TOKEN_ID = 3          # ids below are left to special tokens


@dataclasses.dataclass
class Spec:
    """One request as the client sends it."""
    idx: int
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def stratified_lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles ((i + 0.5) / n) of a length distribution,
    rounded and clipped to [min, max]."""
    if dist.get('dist', 'lognormal') != 'lognormal':
        raise ValueError(f'unknown length distribution {dist["dist"]!r}')
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(math.log(dist['median']) + dist['sigma'] * z)
    return np.clip(np.rint(vals), dist['min'], dist['max']).astype(np.int64)


def seed_words(seed: int) -> list:
    """A whole-number seed of any size as non-negative 32-bit words."""
    seed = int(seed) % (1 << 64)
    return [seed & 0xffffffff, seed >> 32]


class Stream:
    """An endless, seeded stream of request specs for one mix."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        if mix['arrival']['kind'] != 'backlog':
            raise ValueError(f'unknown arrival kind '
                             f'{mix["arrival"]["kind"]!r}')
        self.vocab, self.seed = vocab, seed
        self.order_seed = mix.get('order_seed', seed)
        self.block = int(mix.get('block', 128))
        self._out = stratified_lengths(mix['output'], self.block)
        self._prompt = stratified_lengths(mix['prompt'], self.block)

    def __iter__(self) -> Iterator[Spec]:
        idx, blk = 0, 0
        while True:
            order = np.random.default_rng(seed_words(self.order_seed)
                                          + [0, blk])
            out = order.permutation(self._out)
            plen = order.permutation(self._prompt)
            rng = np.random.default_rng(seed_words(self.seed) + [1, blk])
            for i in range(self.block):
                prompt = rng.integers(FIRST_TOKEN_ID, self.vocab,
                                      int(plen[i]), dtype=np.int32)
                yield Spec(idx=idx, prompt=prompt,
                           max_new_tokens=int(out[i]))
                idx += 1
            blk += 1
