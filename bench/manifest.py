"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, a metric's reader
``bench/metrics/<metric>.py`` and a cell's correctness limit
``bench/limits/<cell>.json``, and a configuration's architecture
``bench/arch/<model_type>.py``, by the ``model_type`` its file states.
Adding one is adding its file and its entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
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


def _module(path: str, name: str, what: str):
    """The module in file ``path``, loaded once under ``name``."""
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, '__file__', None) == path:
        return mod
    if not os.path.exists(path):
        raise ManifestError(f'no {what} {os.path.relpath(path, ROOT)}')
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up here
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def reader(name: str) -> Callable:
    """``read(rec) -> float | None`` from ``bench/metrics/<name>.py``."""
    return _module(os.path.join(HERE, 'metrics', f'{name}.py'),
                   'bench_metric_' + re.sub(r'\W', '_', name),
                   'reader').read


def arch(spec: dict):
    """The architecture module of a configuration (``bench/arch/``'s
    interface), ``bench/arch/<model_type>.py``, found by the
    ``model_type`` that the configuration file states."""
    kind = spec.get('model_type')
    if not isinstance(kind, str) or \
            not re.fullmatch(r'[A-Za-z0-9_][A-Za-z0-9_-]*', kind):
        raise ManifestError(f'model_type {kind!r} cannot name a file '
                            'bench/arch/<model_type>.py')
    return _module(os.path.join(HERE, 'arch', f'{kind}.py'),
                   'bench_arch_' + re.sub(r'\W', '_', kind), 'architecture')
