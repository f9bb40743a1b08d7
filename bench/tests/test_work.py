"""bench/work.py counts the same weights as the paper's accounting in
repro.core.analysis, for every configuration, and its FLOPs and bytes
follow from shapes."""
import os

import pytest

from bench import model as BM
from bench import weights as W
from bench import work


CONFIGS = sorted(f[:-len('.json')] for f in os.listdir(
    os.path.join(W.HERE, 'configs')))


@pytest.mark.parametrize('name', CONFIGS)
def test_weight_counts_match_the_paper_accounting(name):
    from repro.core import analysis
    spec = W.load_spec(name)
    cfg = BM.model_config(name, spec)
    dims = W.dims_of(spec)
    wc = analysis.weight_counts(cfg)
    lw = work.layer_weights(dims)
    assert (lw['q_p'], lw['k_v'], lw['ffn']) == \
        (wc.q_p_per_layer, wc.k_v_per_layer, wc.ffn_per_layer)
    assert work.embed_weights(dims) == wc.embed
    assert dims.layers * sum(lw.values()) + work.embed_weights(dims) == \
        wc.total
    assert work.eliminated_weights(dims) == analysis.eliminated_weights(cfg)
    assert work.row_width(dims) == analysis.analyze(cfg).row_width


def test_published_sizes():
    m = W.dims_of(W.load_spec('mistral-7b-v0.3'))
    assert sum(work.layer_weights(m).values()) == 218_103_808
    assert work.row_width(m) == 10240


def test_flops_and_bytes_count_valid_context_only():
    d = W.dims_of(W.load_spec('mistral-7b-v0.3'))
    one = work.model_flops(d, [[9]])
    assert one == work.matmul_flops_per_token(d) + work.head_flops(d) \
        + d.layers * 4 * d.heads * d.head_dim * 10
    f, b = work.paged_attention_work(d, [[0, 1, 2, 3], []])
    assert f == sum(d.layers * 4 * 32 * 128 * k for k in (1, 2, 3, 4))
    assert b == d.layers * (2 * 4 * 8 * 128 * 2 + 2 * 4 * 32 * 128 * 2
                            + 4 * 4)
    # a slot's K/V are read once per step, however many queries it has
    _, b1 = work.paged_attention_work(d, [[99]])
    _, b64 = work.paged_attention_work(d, [list(range(36, 100))])
    kv = d.layers * 2 * 100 * 8 * 128 * 2
    assert b64 - kv == 64 * (b1 - kv) - 63 * d.layers * 100 * 4
