"""The reduction from trace events to busy time, kernel time and labelled
idle gaps."""
from bench import tracing

P = '/device:TPU:0'


def synthetic():
    ops = [['XLA Ops', 'fusion.1', 100, 50],        # 100-150
           ['XLA Ops', 'paged_kernel', 140, 40],    # overlaps: 100-180
           ['XLA Ops', 'fusion.1', 300, 100],       # 300-400
           ['XLA Ops', 'paged_kernel', 900, 60],    # 900-960
           ['XLA Modules', 'jit_chunk_step', 100, 300]]
    host = [['bench.window', 0, 1000], ['bench.step_once', 90, 330],
            ['bench.observe', 420, 100], ['bench.generator_wait', 600, 280]]
    return {'device': {P: ops, '/device:TPU:0 SparseCore': []},
            'host': host}


def test_union_and_busy_time():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]
    tr = synthetic()
    assert tracing.device_planes(tr) == [P]
    assert tracing.busy_ns(tr, P, 0, 1000) == 80 + 100 + 60
    assert tracing.busy_ns(tr, P, 120, 350) == 60 + 50


def test_kernel_time_by_name_and_line():
    tr = synthetic()
    assert tracing.time_by_name(tr, P, 'paged_kernel') == 100e-9
    assert tracing.time_by_name(tr, P, 'chunk_step',
                                line=tracing.MODULES_LINE) == 300e-9
    assert tracing.top_ops(tr, P, 1) == [['fusion.1', 150e-9]]
    assert tracing.top_ops(tr, P, 1, line=tracing.MODULES_LINE) == \
        [['jit_chunk_step', 300e-9]]


def test_idle_gaps_are_labelled_by_the_open_host_span():
    gaps = tracing.idle_gaps(synthetic(), P, 0, 1000)
    assert gaps[0] == ['generator_wait', 500e-9]      # 400-900
    assert gaps[1] == ['step_once', 120e-9]           # 180-300
    assert gaps[2] == ['window', 100e-9]              # 0-100 (no inner span)
    assert gaps[3] == ['window', 40e-9]               # 960-1000


def test_trim_keeps_what_overlaps():
    t = tracing.trim(synthetic(), 350, 950)
    assert [e[2] for e in t['device'][P]] == [300, 900, 100]
    assert [h[0] for h in t['host']] == ['bench.window', 'bench.step_once',
                                         'bench.observe',
                                         'bench.generator_wait']


def test_reduction_of_a_chip_trace():
    """A quarter second of a traced offline-decode window, recorded on a
    TPU v5e (``bench/run.py --trace 1 --keep-trace``): the names the
    readers look for are there, and ops are named without their HLO."""
    import json
    import os

    from bench.readers import PAGED_ATTENTION, STEP_PROGRAM
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'testdata', 'offline-decode-trace.json')
    with open(path) as f:
        tr = json.load(f)
    assert tracing.device_planes(tr) == [P]
    win = [h for h in tr['host'] if h[0] == 'bench.window'][0]
    lo, hi = win[1], win[1] + 250_000_000
    busy = tracing.busy_ns(tr, P, lo, hi)
    assert 0.5 * (hi - lo) < busy <= hi - lo
    kernel = tracing.time_by_name(tr, P, PAGED_ATTENTION)
    assert 0 < kernel < (hi - lo) / 1e9
    assert tracing.time_by_name(tr, P, STEP_PROGRAM,
                                line=tracing.MODULES_LINE) > 0
    top = tracing.top_ops(tr, P)
    assert top and all(' ' not in name for name, _ in top)
    labels = {g[0] for g in tracing.idle_gaps(tr, P, lo, hi)}
    assert labels <= {'window', 'step_once', 'observe', 'submit'}
