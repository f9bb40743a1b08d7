"""An architecture is found by its configuration's ``model_type``.

The Mistral family, moved into ``bench/arch/mistral.py``, gives bitwise the
weights and reference rows that the code before the move gave; a family
that exists only as new files in a benchmark tree runs the whole harness
through its own functions; and a ``model_type`` with no module is refused
before any weight is drawn.
"""
import hashlib
import json
import os
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench import manifest as M
from bench import weights as W
from bench.tests import test_faults
from bench.tests import tiny

SEED = 2**33 + 12345
# sha256 over the leaves' bytes, in tree order, at tiny.small_config's size,
# as computed by bench/model.py, bench/weights.py and bench/reference.py
# before the move (on the CPU, XLA with more than one thread: one thread
# sums some reference rows in another order)
PINS = {
    'params': '20e2aa6035ca250f45d7e47bdb51ef1f'
              'c4fff69f25856be66c3be80b1b1a3f25',
    'top': 'c27136fbc3acdd3c11b4bfb358544a36'
           '45968a543e70d8c6a89a7630a9248d97',
    'rows': '01effac11cdbe2a240e869757f31368a'
            '3b0d0dfbc1d997d19be2977e49791345',
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def pinned(what: str):
    from repro.models.model import Model
    spec = tiny.small_config('mistral-7b-v0.3')
    A = M.arch(spec)
    dims = A.dims_of(spec)
    if what == 'params':
        model = Model(A.model_config('tiny', spec))
        return A.program_params(model, dims, SEED)
    if what == 'top':
        return W.top_weights(W.base_key(SEED), dims, A.TOP_TENSORS)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(4, 256, n, dtype=np.int32)
            for n in (40, 77, 300)]
    return [[r[k] for k in ('best', 'next', 'control')]
            for r in A.score(spec, SEED, seqs, control=True)]


@pytest.mark.parametrize('what', sorted(PINS))
def test_mistral_family_is_bitwise_as_before_the_move(what):
    assert digest(pinned(what)) == PINS[what]


FAMILY = '''"""Mistral's equations under a model_type of their own,
recording which of its functions the harness calls."""
from bench import manifest as M

base = M.arch({'model_type': 'mistral'})
calls = set()


def _as_mistral(spec):
    return {**spec, 'model_type': 'mistral'}


def dims_of(spec):
    calls.add('dims_of')
    return base.dims_of(spec)


def model_config(name, spec):
    calls.add('model_config')
    return base.model_config(name, _as_mistral(spec))


def program_params(model, dims, seed):
    calls.add('program_params')
    return base.program_params(model, dims, seed)


def score(spec, seed, seqs, *, control=False):
    calls.add('score')
    return base.score(spec, seed, seqs, control=control)


def model_flops(dims, lanes):
    calls.add('model_flops')
    return base.model_flops(dims, lanes)


def paged_attention_work(dims, lanes):
    calls.add('paged_attention_work')
    return base.paged_attention_work(dims, lanes)


def small(spec):
    return base.small(spec)
'''
CELL = 'tiny-family.offline'


@pytest.fixture
def family_tree(tmp_path, monkeypatch):
    """A tiny tree whose one cell's configuration states a model_type that
    no file in the repository serves; -> its configuration file."""
    tiny.tree(str(tmp_path), monkeypatch, cells={
        CELL: ('tiny-family', 'mistral-7b-v0.3', 'offline-decode')})
    path = os.path.join(M.HERE, 'configs', 'tiny-family.json')
    with open(path) as f:
        spec = json.load(f)
    spec['model_type'] = 'tiny_family'
    with open(path, 'w') as f:
        json.dump(spec, f)
    os.makedirs(os.path.join(M.HERE, 'limits'))
    with open(os.path.join(M.HERE, 'limits', f'{CELL}.json'), 'w') as f:
        json.dump({'mean_gap': test_faults.LIMIT}, f)
    return path


def test_new_architecture_drops_in(family_tree, monkeypatch):
    with open(os.path.join(M.HERE, 'arch', 'tiny_family.py'), 'w') as f:
        f.write(FAMILY)
    monkeypatch.setattr(harness, 'reduce_trace', tiny.device_trace())
    out = harness.run(CELL, 2**32 + 9, 1.0, True, t_start=time.monotonic())
    assert out['correct'], out['checks']
    for name in ('mfu.offline', 'paged_attention_roofline.offline'):
        assert 0 < out['metrics'][name]['value'] < float('inf')
    family = M.arch({'model_type': 'tiny_family'})
    assert family.__file__.startswith(str(M.ROOT))
    assert family.calls == {'dims_of', 'model_config', 'program_params',
                            'score', 'model_flops', 'paged_attention_work'}


def test_unknown_architecture_is_refused_before_weights(family_tree,
                                                        monkeypatch):
    def drawn(*_):
        raise AssertionError('a weight was drawn')
    monkeypatch.setattr(W, 'base_key', drawn)
    with pytest.raises(M.ManifestError, match='bench/arch/tiny_family.py'):
        harness.run(CELL, 7, 1.0, False, t_start=time.monotonic())
