"""The check that decides ``correct`` fails what it must.

A tiny CPU run of the whole path (the chip check left out), with the timed
path broken underneath, comes out not correct: once for a token altered
where it is produced, once for a step that returns its state unchanged.
The sound run comes out correct, and the int8 control reads above the
limit: the same comparison that the cells' limits were set from on the
chip, at a size a test run holds. (A served model has no batch mean and a
one-chip cell no exchange between chips: those faults do not apply.)
"""
import json
import os
import time

import pytest

from bench import harness
from bench import manifest as M
from bench.tests import tiny

CELL = 'tiny-mistral.offline'
SEED = 2**31 + 5
# Readings of this tiny cell's mean gap on the CPU, over 48 fixed requests
# (327 served tokens) on 8 seeds: the bf16 program 0.000079-0.000254, the
# int8 control 0.00117-0.00328; over window samples (51-70 tokens) on 10
# seeds the program reads up to 0.00049. (The widest gap does not separate
# here: program up to 0.036, control from 0.084.)
LIMIT = 0.0008
REQUESTS = 48


@pytest.fixture
def tiny_tree(tmp_path, monkeypatch):
    tiny.tree(str(tmp_path), monkeypatch)
    os.makedirs(os.path.join(M.HERE, 'limits'))
    with open(os.path.join(M.HERE, 'limits', f'{CELL}.json'), 'w') as f:
        json.dump({'mean_gap': LIMIT}, f)


def run():
    return harness.run(CELL, SEED, 1.0, False, t_start=time.monotonic())


def test_sound_run_is_correct(tiny_tree):
    out = run()
    assert out['correct'], out['checks']


def test_control_reads_above_the_limit(tiny_tree):
    """The int8 control over a fixed set of served requests (no window, so
    the sample does not depend on the machine's speed) reads above the
    limit, the program below it."""
    import itertools

    import numpy as np

    from bench import generator as G
    from bench import model as BM
    from repro.serving import Request
    bench = M.load()
    cell = M.workload(bench, CELL)
    spec = M.config(bench, cell['config'])
    eng, cfg = BM.build(M.arch(spec), cell['config'], spec, SEED)
    reqs = [Request(uid=s.idx, prompt=s.prompt,
                    max_new_tokens=s.max_new_tokens)
            for s in itertools.islice(G.Stream(M.mix(cell['traffic']),
                                               cfg.vocab_size, SEED),
                                      REQUESTS)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    out = harness.compare(spec, SEED, [(r.prompt, np.asarray(
        r.generated, np.int32)) for r in reqs], control=True)
    assert out['mean_gap'] <= LIMIT < out['control_mean_gap'], out


def test_token_altered_where_produced(tiny_tree, monkeypatch):
    from repro.serving import engine as E
    real = E.sample_tokens

    def off_by_one(logits, key, temps):
        return (real(logits, key, temps) + 1) % logits.shape[-1]
    monkeypatch.setattr(E, 'sample_tokens', off_by_one)
    out = run()
    assert not out['correct']
    assert out['checks']['mean_gap']['value'] > LIMIT


def test_step_returns_its_state_unchanged(tiny_tree, monkeypatch):
    from repro.models.model import Model
    real = Model.decode_step

    def forgetful(self, params, tokens, states, pos, **kw):
        out = real(self, params, tokens, states, pos, **kw)
        return (out[0], states) + tuple(out[2:])
    monkeypatch.setattr(Model, 'decode_step', forgetful)
    out = run()
    assert not out['correct']
