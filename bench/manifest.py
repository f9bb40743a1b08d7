"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, a metric's reader
``bench/metrics/<metric>.py`` and a cell's correctness limit
``bench/limits/<cell>.json``. Adding one is adding its file and its entry;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(LookupError):
    """A name in BENCHMARK.json has no entry or no file."""


def load() -> dict:
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def data(*parts) -> dict:
    """A JSON file under ``bench/``."""
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        raise ManifestError(f'missing {os.path.relpath(path, ROOT)}')
    with open(path) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench['workloads']:
        if w['name'] == name:
            return w
    raise ManifestError(f'no workload {name!r} in BENCHMARK.json')


def config(bench: dict, name: str) -> dict:
    for c in bench['configs']:
        if c['name'] == name:
            return data(os.path.relpath(os.path.join(ROOT, c['file']), HERE))
    raise ManifestError(f'no config {name!r} in BENCHMARK.json')


def mix(name: str) -> dict:
    return data('traffic', f'{name}.json')


def limits(cell: str) -> dict:
    """The numbers the cell's ``correct`` compares, each with its limit.
    Until a limit is set from readings, the mean gap is held to 0."""
    path = os.path.join(HERE, 'limits', f'{cell}.json')
    if not os.path.exists(path):
        return {'mean_gap': 0.0}
    with open(path) as f:
        return json.load(f)


def metrics_for(bench: dict, cell: dict, group: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    whose ``workloads`` list it, and those without a list (a per-layer one
    then where the cell reports the end-to-end metric it moves)."""
    lists = lambda m: cell['name'] in m.get('workloads', [cell['name']])
    e2e = [m for m in bench['end_to_end'] if lists(m)]
    if group == 'end_to_end':
        return e2e
    names = {m['name'] for m in e2e}
    return [m for m in bench['per_layer']
            if (lists(m) if 'workloads' in m else m['moves'] in names)]


def reader(name: str) -> Callable:
    """``read(rec) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = os.path.join(HERE, 'metrics', f'{name}.py')
    if not os.path.exists(path):
        raise ManifestError(f'no reader bench/metrics/{name}.py')
    spec = importlib.util.spec_from_file_location(
        'bench_metric_' + name.replace('.', '_').replace('-', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
