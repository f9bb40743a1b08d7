"""A benchmark tree at a tiny size, for running the harness on the CPU.

``tree(tmp)`` writes BENCHMARK.json and the data files of a tiny cell under
``tmp``, derived from the real configuration and mix files with small widths
and lengths, and points ``bench.manifest`` at them.
"""
import json
import os
import shutil

from bench import manifest as M

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = {'hidden_size': 64, 'intermediate_size': 128,
         'num_attention_heads': 4, 'num_hidden_layers': 2,
         'vocab_size': 256, 'max_position_embeddings': 256}
ENGINE = {'max_slots': 8, 'max_seq': 128, 'chunk_size': 16,
          'page_size': 16, 'num_pages': 96}
LENGTHS = {'prompt': (24, 8, 40), 'output': (6, 3, 12)}
PEAKS = {'bf16_flops': 1e12, 'hbm_bytes_per_s': 1e11}   # made up
PLANE = '/device:TPU:0'


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
    spec.update(SMALL, engine=dict(ENGINE), num_key_value_heads=2,
                head_dim=16)
    return spec


def small_mix(name: str, **over) -> dict:
    with open(os.path.join(BENCH, 'traffic', f'{name}.json')) as f:
        mix = json.load(f)
    for key, (median, lo, hi) in LENGTHS.items():
        mix[key].update(median=median, min=lo, max=hi)
    mix['block'] = 16
    mix.update(over)
    return mix


def tree(tmp, monkeypatch) -> dict:
    """Write the tiny tree and point the manifest at it; -> the manifest."""
    here = os.path.join(tmp, 'bench')
    shutil.copytree(os.path.join(BENCH, 'metrics'),
                    os.path.join(here, 'metrics'))
    shutil.copy(os.path.join(BENCH, 'peaks.json'), here)
    os.makedirs(os.path.join(here, 'configs'))
    os.makedirs(os.path.join(here, 'traffic'))
    cells = {'tiny-mistral.offline': ('tiny-mistral', 'mistral-7b-v0.3',
                                      'offline-decode')}
    with open(os.path.join(M.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    bench['configs'], bench['workloads'] = [], []
    for cell, (cfg, real_cfg, real_mix) in cells.items():
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
    rename = {'mistral-7b-v0.3.offline-decode': 'tiny-mistral.offline'}
    for m in bench['end_to_end'] + bench['per_layer']:
        if 'workloads' in m:
            m['workloads'] = [rename[w] for w in m['workloads']
                              if w in rename]
    with open(os.path.join(tmp, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    monkeypatch.setattr(M, 'ROOT', str(tmp))
    monkeypatch.setattr(M, 'HERE', here)
    # the process's JAX settings stay as they are: no persistent cache
    from bench import harness
    monkeypatch.setattr(harness, 'use_cache', lambda: 'off')
    monkeypatch.setattr(harness, 'find_device', on_cpu)
    return bench
