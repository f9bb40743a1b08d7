"""Serving telemetry: registry correctness, trace completeness, zero-cost
disabled mode.

- **Histogram percentiles vs numpy** on random samples: the fixed
  geometric buckets (ratio sqrt(2)) must land every interpolated
  percentile within one bucket width of ``np.percentile``, exactly for
  single-valued data, ``None`` when empty.
- **Trace completeness under chaos** (``pytest -m chaos``): with scripted
  page steals forcing preemption, every preempted request's span must read
  ``SUBMIT .. PREEMPT -> RESUME .. FINISH``, fault injections must appear
  on the engine-global stream, and the Chrome-trace export must round-trip
  (dump -> parse -> same lifecycle assertions on the parsed events alone).
- **Disabled mode is zero-cost**: ``telemetry=False`` engines share the
  ``NULL_TELEMETRY`` singleton (no-op recorder identity), a spy recorder
  with ``enabled=False`` proves the engine makes *zero* recorder calls,
  and tokens are bitwise identical telemetry-on vs telemetry-off across
  dense / paged / packed engines (greedy and sampled).
- **Single-source metric names**: ``kvpool.stats()`` keys are the
  ``KV_*`` constants and the registry gauges mirror them after
  ``bind_telemetry``.
- **Profiler spans**: a telemetry-on engine run under ``jax.profiler``
  leaves every ``engine.*`` phase span, nested and with its attributes,
  one ``engine.dispatch`` per step, and span durations that add up to
  the ``engine.step.phase_s`` totals. Its ``walk`` attribute and the
  ``engine.step.walk`` histogram match a hand count of reachable pages.
"""
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.models.model import Model
from repro.serving import (NULL_TELEMETRY, Histogram, Request,
                           ScriptedFaults, ServingEngine, Telemetry)
from repro.serving import telemetry as TM
from repro.serving.engine import RequestStatus
from repro.serving.kvpool import PrefixCache

PS = 8
MAX_SEQ = 64

_BUILT = {}


def _build():
    if 'm' not in _BUILT:
        cfg = ModelConfig(name='tel-gqa', arch_class='dense', num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=211,
                          max_seq_len=256, dtype='float32')
        model = Model(cfg)
        _BUILT['m'] = (model, model.init(jax.random.PRNGKey(0)))
    return _BUILT['m']


def _reqs(n=5, new_tokens=8, temp=0.0):
    rng = np.random.default_rng(7)
    base = rng.integers(3, 200, size=24).astype(np.int32)
    return [Request(uid=i,
                    prompt=np.concatenate([base[:16],
                                           base[:5] * 0 + 3 + i]),
                    max_new_tokens=new_tokens, temperature=temp)
            for i in range(n)]


def _engine(telemetry, *, paged=True, pack=False, faults=None,
            num_pages=24, async_loop=False):
    model, params = _build()
    kw = dict(max_slots=4, max_seq=MAX_SEQ, chunk_size=4,
              fault_injector=faults, telemetry=telemetry,
              pack_prefill=pack, async_loop=async_loop)
    if paged:
        kw.update(prefix_cache=True, page_size=PS, num_pages=num_pages)
    return ServingEngine(model, params, **kw)


# --------------------------------------------------------------- histogram
def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    for scale in (1e-4, 1e-2, 1.0):
        vals = rng.uniform(0.2 * scale, 9.0 * scale, size=500)
        h = Histogram.of(vals)
        for q in (50, 90, 99):
            est = h.percentile(q)
            ref = float(np.percentile(vals, q))
            # geometric buckets, ratio sqrt(2): the interpolated estimate
            # must sit within one bucket width of the true percentile
            assert ref / 2 ** 0.5 - 1e-12 <= est <= ref * 2 ** 0.5 + 1e-12, \
                (scale, q, est, ref)


def test_histogram_single_value_exact_and_clamped():
    h = Histogram.of([0.37] * 10)
    assert h.percentile(50) == pytest.approx(0.37)
    assert h.percentile(99) == pytest.approx(0.37)
    assert h.percentile(1) == pytest.approx(0.37)   # clamped to min == max
    assert h.count == 10 and h.mean == pytest.approx(0.37)


def test_histogram_empty_returns_none():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.count == 0
    snap = h.snapshot()
    assert snap['count'] == 0 and 'p50' not in snap


def test_latency_summary_omits_empty():
    assert TM.latency_summary('ttft_s', []) == {}
    out = TM.latency_summary('ttft_s', [0.25, 0.5, 1.0])
    assert set(out) == {'mean_ttft_s', 'p50_ttft_s', 'p99_ttft_s'}
    assert out['mean_ttft_s'] == pytest.approx(np.mean([0.25, 0.5, 1.0]))


def test_registry_series_and_prometheus_text():
    tel = Telemetry()
    tel.registry.counter('widgets').inc(3)
    tel.registry.histogram(TM.STEP_PHASE, phase='dispatch',
                           backend='reference', kind='decode').observe(1e-3)
    tel.registry.gauge('pool.depth', fn=lambda: 7)
    snap = tel.snapshot()['metrics']
    assert snap['counters']['widgets'] == 3
    assert snap['gauges']['pool.depth'] == 7.0
    key = ('engine.step.phase_s{backend=reference,kind=decode,'
           'phase=dispatch}')
    assert snap['histograms'][key]['count'] == 1
    text = tel.prometheus_text()
    assert '# TYPE widgets counter' in text
    assert 'pool_depth 7' in text
    assert ('engine_step_phase_s_count{backend="reference",kind="decode",'
            'phase="dispatch"} 1') in text


# ------------------------------------------------------- engine instruments
def test_phase_histograms_cover_every_dispatch():
    eng = _engine(True)
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    eng.run()
    series = eng.telemetry.registry.find(TM.STEP_PHASE)
    per_phase = {ph: 0 for ph in TM.PHASES}
    for labels, hist in series.items():
        lb = dict(labels)
        assert lb['backend'] == eng.attn_backend.name
        assert lb['kind'] in TM.STEP_KINDS and lb['phase'] in TM.PHASES
        per_phase[lb['phase']] += hist.count
    # every dispatched step observed exactly one histogram per phase
    assert all(n == eng.steps for n in per_phase.values()), \
        (per_phase, eng.steps)


def test_request_span_lifecycle_and_stats_percentiles():
    eng = _engine(True)
    reqs = _reqs(n=3)
    for r in reqs:
        eng.submit(r)
    report = eng.run()
    for r in reqs:
        names = eng.telemetry.tracer.names(r.uid)
        assert names[0] == TM.EV_SUBMIT and names[1] == TM.EV_ADMIT
        assert names[-1] == TM.EV_FINISH
        assert TM.EV_FIRST_TOKEN in names
        assert names.count(TM.EV_DECODE_STEP) == len(r.generated) - 1
    for k in ('p50_latency_s', 'p99_latency_s', 'p50_ttft_s', 'p99_ttft_s'):
        assert k in report and report[k] > 0
    st = eng.stats(reqs)
    assert st['p99_latency_s'] >= st['p50_latency_s'] > 0
    assert st['mean_ttft_s'] > 0


def test_stats_omits_latency_keys_when_no_samples():
    eng = _engine(True)
    bad = Request(uid=1, prompt=np.array([], np.int32), max_new_tokens=4)
    eng.submit(bad)
    assert bad.status is RequestStatus.FAILED
    st = eng.stats([bad])
    for k in ('mean_latency_s', 'mean_ttft_s', 'p50_latency_s',
              'p99_latency_s', 'mean_ttft_on_hit_s'):
        assert k not in st, k
    # the failed submit still leaves a complete span
    assert eng.telemetry.tracer.names(1) == [TM.EV_SUBMIT, TM.EV_FAIL]


def test_kvpool_stats_keys_single_source():
    kv = PrefixCache(8, PS)
    expected = {TM.KV_PREFIX_HITS, TM.KV_PREFIX_MISSES,
                TM.KV_PREFIX_HIT_RATE, TM.KV_PREFIX_HIT_TOKENS,
                TM.KV_PAGES_IN_USE, TM.KV_PAGES_FREE,
                TM.KV_PAGES_RECLAIMABLE, TM.KV_EVICTIONS}
    assert set(kv.stats()) == expected
    tel = Telemetry()
    kv.bind_telemetry(tel)
    pages = kv.alloc(3)
    assert pages is not None
    gauges = tel.snapshot()['metrics']['gauges']
    st = kv.stats()
    for key in (TM.KV_PAGES_IN_USE, TM.KV_PAGES_FREE,
                TM.KV_PAGES_RECLAIMABLE, TM.KV_EVICTIONS):
        assert gauges[key] == st[key], key


# ------------------------------------------------------------ profiler spans
def _profiled_run(log_dir, async_loop):
    """A telemetry-on engine run under the JAX profiler; -> (engine, its
    ``engine.*`` host spans as (name, start_ns, end_ns, attrs))."""
    eng = _engine(True, async_loop=async_loop)
    for r in _reqs():
        eng.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(log_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    spans = [(ev.name, int(ev.start_ns),
              int(ev.start_ns) + int(ev.duration_ns), dict(ev.stats))
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith('/host:')
             for line in plane.lines for ev in line.events
             if ev.name.startswith('engine.')]
    return eng, spans


@pytest.mark.parametrize('async_loop', [False, True],
                         ids=['sync', 'async'])
def test_phase_spans_in_profiler_trace(tmp_path, async_loop):
    eng, spans = _profiled_run(str(tmp_path), async_loop)
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert set(by) == {TM.SPAN_STEP, TM.SPAN_DEVICE_WAIT,
                       *TM.SPAN_PHASE.values()}

    def parent(sp, name):
        """The span named ``name`` that holds ``sp`` (exactly one)."""
        out = [p for p in by[name] if p[1] <= sp[1] and sp[2] <= p[2]]
        assert len(out) == 1, (sp, name, out)
        return out[0]

    disp = by[TM.SPAN_PHASE['dispatch']]
    assert [d[3]['step'] for d in disp] == list(range(eng.steps))
    for d in disp:
        assert d[3]['kind'] in TM.STEP_KINDS
        assert d[3]['T'] in (1, eng.chunk_size) and d[3]['nb'] >= 1
        assert 0 < d[3]['walk'] <= 1
        assert parent(d, TM.SPAN_STEP)[3]['step'] == d[3]['step']
    for ph in ('host_schedule', 'pack_layout'):
        for sp in by[TM.SPAN_PHASE[ph]]:
            parent(sp, TM.SPAN_STEP)
    for sp in by[TM.SPAN_PHASE['radix_lookup']]:
        parent(sp, TM.SPAN_PHASE['host_schedule'])
    lag = 1 if async_loop else 0
    commits = by[TM.SPAN_PHASE['sample_commit']]
    assert sorted(c[3]['step'] for c in commits) == list(range(eng.steps))
    for c in commits:
        held = [p for p in by[TM.SPAN_STEP] if p[1] <= c[1] and c[2] <= p[2]]
        if held:        # the async loop's last commit drains after a step
            assert held[0][3]['step'] == c[3]['step'] + lag
    for w in by[TM.SPAN_DEVICE_WAIT]:
        assert parent(w, TM.SPAN_PHASE['sample_commit'])[3]['step'] == \
            w[3]['step']

    # the spans measure what the phase histograms measure; host_schedule
    # by its self time (radix_lookup taken out), in steps that dispatched
    dur = lambda sps: sum(e - s for _, s, e, _ in sps) / 1e9
    steps = [p for p in by[TM.SPAN_STEP]
             if any(p[1] <= d[1] < p[2] for d in disp)]
    inside = lambda name: [sp for sp in by[TM.SPAN_PHASE[name]]
                           if any(p[1] <= sp[1] < p[2] for p in steps)]
    got = {ph: dur(inside(ph)) for ph in ('radix_lookup', 'pack_layout',
                                          'dispatch')}
    got['host_schedule'] = dur(inside('host_schedule')) - got['radix_lookup']
    got['sample_commit'] = dur(commits)
    want = {ph: 0.0 for ph in TM.PHASES}
    for labels, h in eng.telemetry.registry.find(TM.STEP_PHASE).items():
        want[dict(labels)['phase']] += h.total
    for ph in TM.PHASES:
        assert abs(got[ph] - want[ph]) <= 0.05 * want[ph] + 1e-3, \
            (ph, got[ph], want[ph])


def test_dispatch_walk_counts_reachable_pages():
    """``walk`` on ``engine.dispatch`` and the ``engine.step.walk``
    histogram: per step, the pages up to each slot's last reachable block,
    counted by hand from the positions the step program was given, over
    ``nb * P``."""
    eng = _engine(True)
    P = MAX_SEQ // PS
    assert eng._pt.shape[1] == P
    hand = []                                   # (T, walk) per dispatch
    step = eng._chunk_step

    def counted(*args, **kw):
        tokens, pos = args[2], np.asarray(args[3])
        T = tokens.shape[1]
        reach = [min((int(p) + T - 1) // PS, P - 1) + 1 for p in pos]
        hand.append((T, sum(reach) / (len(pos) * P)))
        return step(*args, **kw)
    eng._chunk_step = counted
    attrs = []
    span = eng.telemetry.span

    def recording(name, **kw):
        if name == TM.SPAN_PHASE['dispatch']:
            attrs.append(kw)
        return span(name, **kw)
    eng.telemetry.span = recording
    for r in _reqs():
        eng.submit(r)
    eng.run()
    assert [a['T'] for a in attrs] == [T for T, _ in hand]
    assert {T for T, _ in hand} == {1, eng.chunk_size}   # decode and chunk
    assert [a['walk'] for a in attrs] == pytest.approx([w for _, w in hand])
    assert max(w for _, w in hand) > 1 / P     # some slot reaches page 2+
    for kind in TM.STEP_KINDS:
        h = eng.telemetry.registry.histogram(
            TM.STEP_WALK, backend=eng.attn_backend.name, kind=kind)
        mine = [w for a, (_, w) in zip(attrs, hand) if a['kind'] == kind]
        assert h.count == len(mine)
        assert h.total == pytest.approx(sum(mine))


# ------------------------------------------------------------- chaos traces
@pytest.mark.chaos
def test_preempted_span_sequence_and_fault_events():
    faults = ScriptedFaults(steal_pages={3: 14}, restore_pages_at=[9])
    eng = _engine(True, faults=faults)
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    report = eng.run(400)
    assert report['preemptions'] >= 1
    preempted = [r for r in reqs if r.preemptions > 0]
    assert preempted, 'chaos script forced no preemption'
    for r in reqs:
        assert r.status is RequestStatus.FINISHED
        names = eng.telemetry.tracer.names(r.uid)
        assert names[0] == TM.EV_SUBMIT and names[-1] == TM.EV_FINISH
    for r in preempted:
        names = eng.telemetry.tracer.names(r.uid)
        i = names.index(TM.EV_PREEMPT)
        assert TM.EV_RESUME in names[i:], names
        assert names.index(TM.EV_FIRST_TOKEN) > i or \
            TM.EV_DECODE_STEP in names[i:]
    engine_stream = eng.telemetry.tracer.names(None)
    assert TM.EV_FAULT_STEAL in engine_stream
    assert TM.EV_FAULT_RESTORE in engine_stream


@pytest.mark.chaos
def test_chrome_trace_roundtrip():
    faults = ScriptedFaults(steal_pages={3: 14}, restore_pages_at=[9],
                            cancel_uids={6: [4]})
    eng = _engine(True, faults=faults)
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    eng.run(400)
    # export -> serialize -> parse: lifecycle must be reconstructible from
    # the parsed JSON alone
    trace = json.loads(json.dumps(eng.telemetry.chrome_trace()))
    evs = trace['traceEvents']
    assert trace['displayTimeUnit'] == 'ms'
    by_uid, slices = {}, {}
    for ev in evs:
        if ev['ph'] == 'i' and ev['args'].get('uid') is not None:
            by_uid.setdefault(ev['args']['uid'], []).append(ev)
        elif ev['ph'] == 'X':
            slices.setdefault(ev['args']['uid'], []).append(ev)
    for r in reqs:
        names = [e['name'] for e in by_uid[r.uid]]
        ts = [e['ts'] for e in by_uid[r.uid]]
        assert ts == sorted(ts), 'trace timestamps out of order'
        assert names[0] == TM.EV_SUBMIT
        assert names[-1] in (TM.EV_FINISH, TM.EV_CANCEL)
        if r.preemptions:
            i = names.index(TM.EV_PREEMPT)
            assert TM.EV_RESUME in names[i:]
        # synthesized queued/running slices are well-formed
        assert slices[r.uid], 'no span slices synthesized'
        assert all(s['dur'] >= 0 for s in slices[r.uid])
        assert {s['name'] for s in slices[r.uid]} <= {'queued', 'running'}
    # thread metadata: one named track per request + the engine track
    threads = {e['tid']: e['args']['name'] for e in evs
               if e['ph'] == 'M' and e['name'] == 'thread_name'}
    assert threads[0] == 'engine'
    assert sum(v.startswith('request ') for v in threads.values()) \
        == len(reqs)
    # fault injections ride the engine-global track (uid None)
    fault_names = [e['name'] for e in evs
                   if e['ph'] == 'i' and e['args'].get('uid') is None]
    assert TM.EV_FAULT_STEAL in fault_names
    assert TM.EV_FAULT_CANCEL in fault_names


# -------------------------------------------------------- disabled == free
class _SpyRecorder:
    """enabled=False recorder that screams if the engine calls anything."""
    enabled = False

    def __getattr__(self, name):
        raise AssertionError(
            f'engine called {name}() on a disabled telemetry recorder')


def test_disabled_engine_makes_zero_recorder_calls():
    eng = _engine(_SpyRecorder())
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    eng.run()          # any recorder call raises inside the spy
    assert all(r.status is RequestStatus.FINISHED for r in reqs)


def test_disabled_engines_share_null_singleton():
    a = _engine(False)
    b = _engine(None, paged=False)
    assert a.telemetry is NULL_TELEMETRY and b.telemetry is NULL_TELEMETRY
    assert a.metrics() == {'enabled': False}
    assert NULL_TELEMETRY.prometheus_text() == ''
    assert NULL_TELEMETRY.chrome_trace()['traceEvents'] == []


@pytest.mark.parametrize('mode', ['dense', 'paged', 'packed'])
@pytest.mark.parametrize('temp', [0.0, 0.8])
def test_tokens_bitwise_identical_telemetry_on_off(mode, temp):
    out = {}
    for tel in (False, True):
        eng = _engine(tel, paged=mode != 'dense', pack=mode == 'packed')
        reqs = _reqs(new_tokens=6, temp=temp)
        for r in reqs:
            eng.submit(r)
        eng.run()
        out[tel] = [list(r.generated) for r in reqs]
    assert out[True] == out[False], \
        f'{mode} temp={temp}: telemetry changed the tokens'


@pytest.mark.chaos
def test_tokens_bitwise_identical_under_chaos_telemetry_on_off():
    out = {}
    for tel in (False, True):
        faults = ScriptedFaults(steal_pages={3: 14}, restore_pages_at=[9])
        eng = _engine(tel, faults=faults)
        reqs = _reqs(new_tokens=6)
        for r in reqs:
            eng.submit(r)
        eng.run(400)
        out[tel] = [(r.status.value, list(r.generated)) for r in reqs]
    assert out[True] == out[False]
