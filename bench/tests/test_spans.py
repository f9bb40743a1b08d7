"""Step time by kind and host-bound idle time, from the engine's phase
spans in a trace."""
import json
import os

import pytest

from bench import spans, tracing

P = '/device:TPU:0'
TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'testdata')


def synthetic():
    """Three steps launched from ``engine.dispatch`` spans: decode (device
    100-300), mixed (410-800), decode (905-1000) in a window 0-1090. Idle:
    0-100, 300-410, 800-905, 1000-1090. Run ids 11-13 link each program
    to its enqueue."""
    ops, run = [], spans.RUN_LINE
    for rid, name, s, e, enq in [('11', 'jit_chunk_step(7)', 100, 300, 95),
                                 ('99', 'jit_reset(3)', 360, 365, 355),
                                 ('12', 'jit_chunk_step(8)', 410, 800, 405),
                                 ('13', 'jit_chunk_step(7)', 905, 1000, 905)]:
        if 'reset' not in name:
            ops.append(['XLA Ops', 'fusion.1', s, e - s])
        ops += [['XLA Modules', name, s, e - s], [run, rid, s, e - s],
                [spans.ENQUEUE_LINE, rid, enq, 1]]
    host = [['bench.window', 0, 1090], ['bench.step_once', 0, 330],
            ['bench.observe', 330, 10], ['bench.step_once', 340, 500],
            ['bench.observe', 840, 10], ['bench.step_once', 850, 220],
            ['engine.radix_lookup', 20, 10, {}]]
    for step, kind, (t0, sched, pack, disp, wait, end) in [
            (0, 'decode', (0, 60, 80, 100, 310, 325)),
            (1, 'mixed', (340, 370, 390, 410, 805, 838)),
            (2, 'decode', (850, 880, 890, 905, 1060, 1065))]:
        host += [
            ['engine.step', t0, end - t0, {'step': step}],
            ['engine.host_schedule', t0, sched - t0, {}],
            ['engine.pack_layout', sched, pack - sched, {}],
            ['engine.dispatch', pack, disp - pack,
             {'step': step, 'kind': kind, 'T': 64, 'nb': 64}],
            ['engine.sample_commit', disp, end - disp, {'step': step}],
            ['engine.device_wait', disp, wait - disp, {'step': step}]]
    return {'device': {P: ops}, 'host': host}


def test_steps_are_paired_with_their_dispatch_in_launch_order():
    tr = synthetic()
    assert spans.step_ms_by_kind(tr, P, 0, 1090) == {
        'decode': [200e-6, 95e-6], 'mixed': [390e-6]}
    got = spans.readings(tr, P, 0, 1090, steps=3)
    assert got['decode_step_ms'] == pytest.approx(147.5e-6)
    assert got['prefill_step_ms'] == pytest.approx(390e-6)
    # the run ids are a cross-check only: without them, the same pairing
    tr['device'][P] = [e for e in tr['device'][P]
                       if e[0] != spans.ENQUEUE_LINE]
    assert spans.readings(tr, P, 0, 1090, steps=3) == got


@pytest.mark.parametrize('fault', ['count', 'run_id'])
def test_a_pairing_that_does_not_hold_reads_nothing(fault):
    tr = synthetic()
    if fault == 'count':        # a dispatch the trace lost
        tr['host'] = [h for h in tr['host'] if not (
            h[0] == 'engine.dispatch' and h[3]['step'] == 2)]
    else:                       # program 12 enqueued after the next dispatch
        for e in tr['device'][P]:
            if e[0] == spans.ENQUEUE_LINE and e[1] in ('12', '13'):
                e[1] = '13' if e[1] == '12' else '12'
    assert spans.step_ms_by_kind(tr, P, 0, 1090) is None
    got = spans.readings(tr, P, 0, 1090, steps=3)
    assert got['decode_step_ms'] is None and got['prefill_step_ms'] is None


def test_idle_gaps_are_named_by_the_innermost_span_holding_most():
    assert spans.idle_gaps(synthetic(), P, 0, 1090) == [
        ['engine.host_schedule', 110e-9],   # 300-410: 30 of it
        ['engine.sample_commit', 105e-9],   # 800-905: 33 (self time)
        ['engine.host_schedule', 100e-9],   # 0-100: 50 (radix out)
        ['engine.device_wait', 90e-9]]      # 1000-1090: 60


def test_host_idle_is_engine_time_outside_the_device_wait():
    # 0-100 all; 300-410: commit 15, schedule 30, pack 20, dispatch 20;
    # 800-905: commit 33, schedule 30, pack 10, dispatch 15; 1000-1090:
    # commit 5. The rest is device_wait, bench.observe, step_once, window.
    tr = synthetic()
    want = 100 + 85 + 88 + 5
    assert spans.host_idle_ns(tr, P, 0, 1090) == want
    assert spans.readings(tr, P, 0, 1090, steps=3)[
        'host_idle_ms_per_step'] == pytest.approx(want / 1e6 / 3)
    assert sum(ns for _, ns, _ in spans.idle_by_span(tr, P, 0, 1090)) == \
        1090 - tracing.busy_ns(tr, P, 0, 1090)


def test_device_events_are_aligned_to_their_launches():
    """Device events that sit 7 ns early against the host's clock (a
    program starting before its enqueue) are moved back by the run ids."""
    tr, early = synthetic(), synthetic()
    early['device'][P] = [e if e[0] == spans.ENQUEUE_LINE
                          else [e[0], e[1], e[2] - 7, e[3]]
                          for e in early['device'][P]]
    assert spans.clock_skew_ns(tr, P) == 0
    assert spans.clock_skew_ns(early, P) == 7
    assert spans.idle(early, P, 0, 1090) == spans.idle(tr, P, 0, 1090)
    assert spans.readings(early, P, 0, 1090, 3) == \
        spans.readings(tr, P, 0, 1090, 3)


def test_a_trace_without_engine_spans_reads_nothing_new():
    """The older chip trace has only the benchmark's spans: nothing is
    read, and the gaps and labels are ``tracing.idle_gaps``'s."""
    with open(os.path.join(TESTDATA, 'offline-decode-trace.json')) as f:
        tr = json.load(f)
    win = [h for h in tr['host'] if h[0] == 'bench.window'][0]
    lo, hi = win[1], win[1] + 250_000_000
    assert spans.idle_gaps(tr, P, lo, hi) == tracing.idle_gaps(tr, P, lo, hi)
    assert spans.readings(tr, P, lo, hi, steps=1) == {
        'decode_step_ms': None, 'prefill_step_ms': None,
        'host_idle_ms_per_step': 0.0}


def test_a_chip_trace_reads_by_kind():
    """Two whole steps of a traced offline-decode window on a TPU v5e, one
    that carries a prefill chunk and one decode step, with the engine's
    spans and run ids (``spans.load``, then ``tracing.trim`` to the two
    ``engine.step`` spans; op names cut to ``tracing.op_name``)."""
    with open(os.path.join(TESTDATA, 'offline-decode-engine-trace.json')) as f:
        tr = json.load(f)
    steps = sorted(h for h in tr['host'] if h[0] == 'engine.step')
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    assert len(steps) == 2 and tracing.device_planes(tr) == [P]
    assert len(tracing.ops(tr, P, spans.RUN_LINE)) >= 2
    by = spans.step_ms_by_kind(tr, P, lo, hi)
    assert sorted(by) == ['decode', 'mixed']
    got = spans.readings(tr, P, lo, hi, steps=2)
    assert 140 < got['decode_step_ms'] < 150
    assert 505 < got['prefill_step_ms'] < 520
    idle_ms = (hi - lo - tracing.busy_ns(tr, P, lo, hi)) / 1e6 / 2
    assert 0 < got['host_idle_ms_per_step'] < idle_ms
    labels = {g[0] for g in spans.idle_gaps(tr, P, lo, hi)}
    assert labels and all(x.startswith(spans.ENGINE_PREFIX) for x in labels)
