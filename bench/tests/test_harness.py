"""The harness finds a configuration, a traffic mix and a metric by name:
dropped in as new files plus manifest entries, they run with no edit to
any file that is there. The run itself is a tiny CPU run of the whole
path, the chip check left out."""
import json
import math
import os
import time

import pytest

from bench import harness
from bench import manifest as M
from bench.tests import tiny

READER = '''"""Generated tokens per engine step in the window."""


def read(rec):
    return rec.tokens / rec.steps if rec.steps else None
'''


@pytest.fixture
def dropped_in(tmp_path, monkeypatch):
    bench = tiny.tree(str(tmp_path), monkeypatch)
    here = M.HERE
    spec = tiny.small_config('mistral-7b-v0.3')
    spec.update(vocab_size=320, num_key_value_heads=4)
    with open(os.path.join(here, 'configs', 'tiny-new.json'), 'w') as f:
        json.dump(spec, f)
    mix = tiny.small_mix('offline-decode')
    mix['output'].update(median=4, min=2, max=8)
    with open(os.path.join(here, 'traffic', 'tiny-new-mix.json'), 'w') as f:
        json.dump(mix, f)
    with open(os.path.join(here, 'metrics', 'tokens_per_step.new.py'),
              'w') as f:
        f.write(READER)
    bench['configs'].append({'name': 'tiny-new', 'source': 'tiny',
                             'file': 'bench/configs/tiny-new.json',
                             'reduced': [], 'why': 'tiny'})
    bench['workloads'].append({'name': 'tiny-new.cell', 'config': 'tiny-new',
                               'traffic': 'tiny-new-mix', 'chips': 1,
                               'why': 'tiny'})
    bench['per_layer'].append({'name': 'tokens_per_step.new', 'unit': 'tok',
                               'better': 'higher', 'source': 'host_clock',
                               'layer': 'scheduler', 'moves': 'itl_p95_ms',
                               'workloads': ['tiny-new.cell']})
    with open(os.path.join(M.ROOT, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    return bench


def test_new_files_are_found_by_name(dropped_in, monkeypatch):
    monkeypatch.setattr(harness, 'reduce_trace', tiny.device_trace())
    out = harness.run('tiny-new.cell', 2**31 + 77, 1.0, True,
                      t_start=time.monotonic())
    assert set(out['metrics']) == {'tokens_per_step.new'}
    assert out['metrics']['tokens_per_step.new']['value'] > 0
    assert out['device']['platform'] == 'cpu'
    assert out['attempted'] > 0 and out['failed'] == 0
    assert list(out)[-1] == 'checks'
    assert out['checks']['mean_gap']['limit'] == 0.0    # no limits file


def test_cells_report_their_metrics():
    bench = M.load()
    names = {w['name']: {g: {m['name'] for m in M.metrics_for(bench, w, g)}
                         for g in ('end_to_end', 'per_layer')}
             for w in bench['workloads']}
    for cell, groups in names.items():
        assert 'setup_s' in groups['end_to_end']
        assert len(groups['end_to_end']) >= 2 and groups['per_layer']
        for m in bench['per_layer']:
            if m['name'] in groups['per_layer']:
                assert m['moves'] in groups['end_to_end']
        for name in groups['end_to_end'] | groups['per_layer']:
            assert os.path.exists(os.path.join(M.HERE, 'metrics',
                                               f'{name}.py'))
    for c in bench['configs']:
        assert os.path.exists(os.path.join(M.ROOT, c['file']))
    for w in bench['workloads']:
        M.mix(w['traffic'])
        if os.path.exists(os.path.join(M.HERE, 'limits',
                                       f'{w["name"]}.json')):
            assert all(v > 0 for v in M.limits(w['name']).values())


@pytest.mark.parametrize('kernel', ['paged_kernel', 'renamed_kernel'])
def test_listed_metrics_read_or_fail_the_run(tmp_path, monkeypatch, kernel):
    """A traced run reports every per-layer metric its cell lists; where a
    reader finds nothing, such as a kernel name missing from the trace,
    the run fails and names the metric."""
    tiny.tree(str(tmp_path), monkeypatch)
    monkeypatch.setattr(harness, 'reduce_trace', tiny.device_trace(kernel))
    run = lambda: harness.run('tiny-mistral.offline', 2**33 + 1, 1.0, True,
                              t_start=time.monotonic())
    if kernel == 'renamed_kernel':
        with pytest.raises(harness.MissingMetric,
                           match='paged_attention_roofline.offline'):
            run()
        return
    bench = M.load()
    cell = M.workload(bench, 'tiny-mistral.offline')
    out = run()
    assert set(out['metrics']) == {
        m['name'] for m in M.metrics_for(bench, cell, 'per_layer')}
    assert 0 < out['metrics']['paged_attention_roofline.offline']['value']


def test_infinite_tail_fails_the_run():
    rec = harness.Record(cell='c', dims=None, arch=None, peaks={},
                         setup_s=1.0, window_s=1.0, tokens=1,
                         itl_s=[0.1, math.inf], steps=1)
    cell = {'name': 'c'}
    bench = {'end_to_end': [{'name': 'itl_p95_ms', 'unit': 'ms'}],
             'per_layer': []}
    with pytest.raises(harness.MissingMetric, match='itl_p95_ms'):
        harness.read_metrics(bench, cell, 'end_to_end', rec)


def test_no_device_no_result(monkeypatch):
    """On the CPU the run refuses to start."""
    with pytest.raises(harness.NoDevice):
        harness.find_device(1)
