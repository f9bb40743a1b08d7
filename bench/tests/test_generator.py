"""The traffic generator: seeded, deterministic, and true to its mix."""
import itertools
import json
import os

import numpy as np
import pytest

from bench import generator as G

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'traffic')


def mix(name):
    with open(os.path.join(TRAFFIC, f'{name}.json')) as f:
        return json.load(f)


def take(m, seed, n, vocab=50432):
    return list(itertools.islice(G.Stream(m, vocab, seed), n))


MIXES = sorted(f[:-len('.json')] for f in os.listdir(TRAFFIC))


@pytest.mark.parametrize('name', MIXES)
def test_same_seed_same_requests(name):
    a, b = take(mix(name), 2**31 + 11, 300), take(mix(name), 2**31 + 11, 300)
    for x, y in zip(a, b):
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize('name', MIXES)
def test_seeds_reorder_the_same_work(name):
    """Every block holds the same lengths whatever the seed; the seed
    changes the tokens, and their order unless the mix fixes it."""
    m = mix(name)
    a, b = take(m, 5, m['block'] * 2), take(m, 2**40 + 6, m['block'] * 2)
    for blk in range(2):
        sl = slice(blk * m['block'], (blk + 1) * m['block'])
        assert [r.max_new_tokens for r in a[sl]] != \
            sorted(r.max_new_tokens for r in a[sl])
        for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            assert sorted(map(f, a[sl])) == sorted(map(f, b[sl]))
    same_order = [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert same_order == ('order_seed' in m)
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


@pytest.mark.parametrize('order_seed', [None, 2**35 + 3])
def test_order_seed_fixes_the_order(order_seed):
    """With ``order_seed`` every seed sends the same lengths in the same
    order, blocks differ from each other, and the tokens still follow the
    run's seed; without it the run's seed orders the lengths."""
    m = {k: v for k, v in mix('offline-decode').items() if k != 'order_seed'}
    if order_seed is not None:
        m['order_seed'] = order_seed
    n = m['block'] * 2
    a, b = take(m, 2**31 + 5, n), take(m, 9, n)
    order = lambda rs: [(len(r.prompt), r.max_new_tokens) for r in rs]
    assert (order(a) == order(b)) == (order_seed is not None)
    assert order(a[:m['block']]) != order(a[m['block']:])
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_length_statistics_match_the_mix():
    m = mix('offline-decode')
    rs = take(m, 3, m['block'] * 4)
    plen = np.array([len(r.prompt) for r in rs])
    out = np.array([r.max_new_tokens for r in rs])
    for got, d in ((plen, m['prompt']), (out, m['output'])):
        assert got.min() >= d['min'] and got.max() <= d['max']
        assert abs(np.median(got) - d['median']) <= 0.05 * d['median']
        sigma = np.std(np.log(got[(got > d['min']) & (got < d['max'])]))
        assert 0.7 * d['sigma'] < sigma < 1.05 * d['sigma']
    assert all(r.prompt.min() >= G.FIRST_TOKEN_ID and r.prompt.max() < 50432
               for r in rs)


def test_unknown_arrival_kind_is_refused():
    m = dict(mix('offline-decode'), arrival={'kind': 'poisson', 'rate': 4})
    with pytest.raises(ValueError, match='poisson'):
        G.Stream(m, 50432, 1)
