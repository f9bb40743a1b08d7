"""The architectures' work counts: each family that keeps an accounting of
its own (``paper_accounting``) counts the same weights as the paper's
accounting in repro.core.analysis, for every configuration of that family,
and the FLOPs and bytes follow from shapes."""
import os

import pytest

from bench import manifest as M

SPECS = {f[:-len('.json')]: M.data('configs', f)
         for f in sorted(os.listdir(os.path.join(M.HERE, 'configs')))}
ACCOUNTED = [name for name, spec in SPECS.items()
             if hasattr(M.arch(spec), 'paper_accounting')]


def mistral():
    spec = SPECS['mistral-7b-v0.3']
    A = M.arch(spec)
    return A, A.dims_of(spec)


@pytest.mark.parametrize('name', ACCOUNTED)
def test_weight_counts_match_the_paper_accounting(name):
    pairs = M.arch(SPECS[name]).paper_accounting(name, SPECS[name])
    assert {k: a for k, (a, _) in pairs.items()} == \
        {k: b for k, (_, b) in pairs.items()}


def test_published_sizes():
    A, m = mistral()
    assert sum(A.layer_weights(m).values()) == 218_103_808
    assert A.row_width(m) == 10240


def test_flops_and_bytes_count_valid_context_only():
    A, d = mistral()
    one = A.model_flops(d, [[9]])
    assert one == A.matmul_flops_per_token(d) + A.head_flops(d) \
        + d.layers * 4 * d.heads * d.head_dim * 10
    f, b = A.paged_attention_work(d, [[0, 1, 2, 3], []])
    assert f == sum(d.layers * 4 * 32 * 128 * k for k in (1, 2, 3, 4))
    assert b == d.layers * (2 * 4 * 8 * 128 * 2 + 2 * 4 * 32 * 128 * 2
                            + 4 * 4)
    # a slot's K/V are read once per step, however many queries it has
    _, b1 = A.paged_attention_work(d, [[99]])
    _, b64 = A.paged_attention_work(d, [list(range(36, 100))])
    kv = d.layers * 2 * 100 * 8 * 128 * 2
    assert b64 - kv == 64 * (b1 - kv) - 63 * d.layers * 100 * 4


def test_counts_are_as_before_the_move():
    """One mixed step's counts at the published widths, as bench/work.py
    gave them before the Mistral family moved into bench/arch/."""
    A, d = mistral()
    lanes = [list(range(192, 256)), [1023], [31], list(range(0, 17))]
    assert A.model_flops(d, lanes) == 288_579_780_608
    assert A.paged_attention_work(d, lanes) == (2_041_708_544, 54_470_176)
