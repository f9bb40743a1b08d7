"""Reduction of a profiler trace to device busy time, kernel time and
labelled idle gaps.

A trace is read once into plain events, ``{"device": [[line, name, start_ns,
dur_ns], ...], "host": [[name, start_ns, dur_ns], ...]}``: the device
planes' events, and the host spans the benchmark opened around its own calls
(``jax.profiler.TraceAnnotation`` names starting with ``bench.``). Every
function below works on that form, so ``bench/tests/test_tracing.py``
checks the reduction without a chip, and ``bench/run.py --keep-trace``
writes a trimmed trace in it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = 'bench.'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'


def load(log_dir: str) -> dict:
    """Plain events of the newest ``.xplane.pb`` under ``log_dir``. Device
    planes are averaged later by the caller; with one chip there is one."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, 'plugins', 'profile',
                                          '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError(f'no xplane.pb under {log_dir}')
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith('/device:'):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                for ev in line.events:
                    evs.append([line.name, ev.name, int(ev.start_ns),
                                int(ev.duration_ns)])
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {'device': device, 'host': host}


def device_planes(trace: dict, kind: str = '/device:TPU:') -> List[str]:
    """Names of the accelerator planes that carry operations."""
    return sorted(p for p, evs in trace['device'].items()
                  if p.startswith(kind)
                  and any(e[0] == OPS_LINE for e in evs))


def op_name(name: str) -> str:
    """An event's operation name: the profiler names XLA ops by their whole
    HLO text (``%paged_attention.1 = bf16[...] custom-call(...)``), whose
    operands name other ops."""
    return name.split(' = ', 1)[0].lstrip('%')


def ops(trace: dict, plane: str, line: str = OPS_LINE) -> List[list]:
    return [e for e in trace['device'][plane] if e[0] == line]


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(trace: dict, plane: str, lo: int, hi: int) -> int:
    """Nanoseconds in [lo, hi) in which some operation ran on the plane."""
    ivs = [(max(s, lo), min(s + d, hi)) for _, _, s, d in ops(trace, plane)]
    return sum(e - s for s, e in union([iv for iv in ivs if iv[1] > iv[0]]))


def time_by_name(trace: dict, plane: str, pattern: str,
                 line: str = OPS_LINE) -> float:
    """Seconds of the events on ``line`` whose operation name matches
    ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return sum(d for _, n, _, d in ops(trace, plane, line)
               if rx.search(op_name(n))) / 1e9


def top_ops(trace: dict, plane: str, n: int = 10,
            line: str = OPS_LINE) -> List[list]:
    """The n operation names on ``line`` that took most device time,
    [name, s]."""
    tot: Dict[str, int] = {}
    for _, name, _, d in ops(trace, plane, line):
        tot[op_name(name)] = tot.get(op_name(name), 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def host_label(trace: dict, t: int) -> str:
    """The innermost benchmark span open at time t ('none' if none)."""
    best: Optional[list] = None
    for name, s, d in trace['host']:
        if s <= t < s + d and (best is None or d < best[2]):
            best = [name, s, d]
    return best[0][len(HOST_PREFIX):] if best else 'none'


def idle_gaps(trace: dict, plane: str, lo: int, hi: int,
              n: int = 10) -> List[list]:
    """The n longest gaps in [lo, hi) with no operation on the plane, each
    labelled by the benchmark span open at its middle: [label, s]."""
    busy = union([(s, s + d) for _, _, s, d in ops(trace, plane)
                  if s + d > lo and s < hi])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_label(trace, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:n]]


def trim(trace: dict, lo: int, hi: int) -> dict:
    """The events that overlap [lo, hi): a small trace to keep."""
    keep = lambda s, d: s + d > lo and s < hi
    return {'device': {p: [e for e in evs if keep(e[2], e[3])]
                       for p, evs in trace['device'].items()},
            'host': [e for e in trace['host'] if keep(e[1], e[2])]}
