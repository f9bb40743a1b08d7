"""A benchmark tree at a tiny size, for running the harness on the CPU.

``tree(tmp, monkeypatch, cells)`` writes BENCHMARK.json and the data files
of tiny cells under ``tmp``, derived from the real configuration and mix
files with small widths (each architecture's ``small``) and lengths, and
points ``bench.manifest`` at them.
"""
import json
import os
import shutil

from bench import manifest as M

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = {'max_slots': 8, 'max_seq': 128, 'chunk_size': 16,
          'page_size': 16, 'num_pages': 96}
LENGTHS = {'prompt': (24, 8, 40), 'output': (6, 3, 12)}
PEAKS = {'bf16_flops': 1e12, 'hbm_bytes_per_s': 1e11}   # made up
PLANE = '/device:TPU:0'
# tiny cell -> (its configuration, the real configuration, the real mix)
CELLS = {'tiny-mistral.offline': ('tiny-mistral', 'mistral-7b-v0.3',
                                  'offline-decode')}


def on_cpu(chips: int):
    """In place of ``harness.find_device``: the CPU, with made-up peaks."""
    import jax
    return jax.devices()[:chips], PEAKS


def device_trace(kernel: str = 'paged_kernel'):
    """In place of ``harness.reduce_trace``, since a CPU trace has no
    device plane: a step program and a kernel named ``kernel`` over half a
    microsecond window."""
    ops = [['XLA Ops', 'fusion.1', 0, 200], ['XLA Ops', kernel, 200, 300],
           ['XLA Modules', 'jit_chunk_step', 0, 500]]
    return lambda log_dir, keep: {
        'events': {'device': {PLANE: ops}, 'host': []}, 'planes': [PLANE],
        'lo': 0, 'hi': 1000, 'busy_s': 5e-7, 'window_s': 1e-6}


def small_config(name: str) -> dict:
    with open(os.path.join(BENCH, 'configs', f'{name}.json')) as f:
        spec = json.load(f)
    return dict(M.arch(spec).small(spec), engine=dict(ENGINE))


def small_mix(name: str, **over) -> dict:
    with open(os.path.join(BENCH, 'traffic', f'{name}.json')) as f:
        mix = json.load(f)
    for key, (median, lo, hi) in LENGTHS.items():
        mix[key].update(median=median, min=lo, max=hi)
    mix['block'] = 16
    mix.update(over)
    return mix


def tree(tmp, monkeypatch, cells=CELLS) -> dict:
    """Write the tiny tree and point the manifest at it; -> the manifest.
    A tiny cell reports the metrics of the real cell of its real
    configuration and mix."""
    here = os.path.join(tmp, 'bench')
    for sub in ('metrics', 'arch'):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(here, sub),
                        ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(BENCH, 'peaks.json'), here)
    os.makedirs(os.path.join(here, 'configs'))
    os.makedirs(os.path.join(here, 'traffic'))
    with open(os.path.join(M.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    real = {(w['config'], w['traffic']): w['name']
            for w in bench['workloads']}
    rename = {}
    bench['configs'], bench['workloads'] = [], []
    for cell, (cfg, real_cfg, real_mix) in cells.items():
        rename.setdefault(real.get((real_cfg, real_mix)), []).append(cell)
        with open(os.path.join(here, 'configs', f'{cfg}.json'), 'w') as f:
            json.dump(small_config(real_cfg), f)
        mix = small_mix(real_mix)
        with open(os.path.join(here, 'traffic', f'tiny-{real_mix}.json'),
                  'w') as f:
            json.dump(mix, f)
        bench['configs'].append({'name': cfg, 'source': 'tiny',
                                 'file': f'bench/configs/{cfg}.json',
                                 'reduced': [], 'why': 'tiny'})
        bench['workloads'].append({'name': cell, 'config': cfg,
                                   'traffic': f'tiny-{real_mix}', 'chips': 1,
                                   'why': 'tiny'})
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'workloads' in m:
            m['workloads'] = [c for w in m['workloads']
                              for c in rename.get(w, [])]
    with open(os.path.join(tmp, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    monkeypatch.setattr(M, 'ROOT', str(tmp))
    monkeypatch.setattr(M, 'HERE', here)
    # the process's JAX settings stay as they are: no persistent cache
    from bench import harness
    monkeypatch.setattr(harness, 'use_cache', lambda: 'off')
    monkeypatch.setattr(harness, 'find_device', on_cpu)
    return bench
