"""The engine's step phases in a profiler trace: step time by kind and
device idle time pinned to host phases.

A telemetry-on engine writes its phases as ``jax.profiler`` annotations
(``engine.step``, ``engine.host_schedule`` > ``engine.radix_lookup``,
``engine.pack_layout``, ``engine.dispatch`` with ``step``/``kind``/``T``/
``nb``, ``engine.sample_commit`` > ``engine.device_wait``). Everything
here works on the plain events of ``bench/tracing.py``, which
:func:`load` extends:

- ``host`` also holds the engine's spans, ``[name, start_ns, dur_ns,
  attrs]``, beside the benchmark's ``[name, start_ns, dur_ns]``;
- a device plane's events also hold two lines that tie each program
  execution to its launch by the runtime's ``run_id``: ``RUN_LINE``
  mirrors the ``XLA Modules`` events, named by their run id, and
  ``ENQUEUE_LINE`` holds the host's enqueue of each run id.

``tracing.trim`` keeps all of it, and every ``tracing`` function reads
such a trace as before.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import tracing
from bench.readers import STEP_PROGRAM

ENGINE_PREFIX = 'engine.'
DISPATCH = 'engine.dispatch'
DEVICE_WAIT = 'engine.device_wait'
PREFILL_KINDS = ('prefill', 'mixed')    # steps that carry a prefill chunk
RUN_LINE = 'XLA Modules run_id'
ENQUEUE_LINE = 'Host enqueue run_id'
ENQUEUE_EVENT = 'DoEnqueueProgram'      # the TPU runtime's launch, on the host

Intervals = List[Tuple[int, int]]


def load(log_dir: str) -> dict:
    """``tracing.load``'s events of the newest capture under ``log_dir``,
    with the engine's spans and the run-id lines added."""
    from jax.profiler import ProfileData
    trace = tracing.load(log_dir)
    path = sorted(glob.glob(os.path.join(log_dir, 'plugins', 'profile',
                                         '*', '*.xplane.pb')))[-1]
    enqueues = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if plane.name in trace['device']:
                    if line.name == tracing.MODULES_LINE:
                        rid = dict(ev.stats).get('run_id')
                        if rid is not None:
                            trace['device'][plane.name].append(
                                [RUN_LINE, str(rid), s, d])
                elif not plane.name.startswith('/host:'):
                    continue
                elif ev.name.startswith(ENGINE_PREFIX):
                    trace['host'].append([ev.name, s, d, dict(ev.stats)])
                elif ev.name == ENQUEUE_EVENT:
                    rid = dict(ev.stats).get('run_id')
                    if rid is not None:
                        enqueues.append([ENQUEUE_LINE, str(rid), s, d])
    for evs in trace['device'].values():
        evs.extend(enqueues)
    return trace


def clock_skew_ns(trace: dict, plane: str) -> int:
    """How far the plane's events sit early against the host's: the most
    that a program, linked to its enqueue by run id, starts before that
    enqueue (0 where none does). The profiler aligns the two clocks only
    roughly; a program cannot start before it is enqueued."""
    rid = {s: n for _, n, s, _ in tracing.ops(trace, plane, RUN_LINE)}
    enq = {n: s for _, n, s, _ in tracing.ops(trace, plane, ENQUEUE_LINE)}
    return max([enq[n] - s for s, n in rid.items() if n in enq] + [0])


def idle(trace: dict, plane: str, lo: int, hi: int) -> Intervals:
    """The intervals of [lo, hi), on the host's clock, in which no
    operation ran on the plane (its events moved by the clock skew)."""
    k = clock_skew_ns(trace, plane)
    busy = tracing.union([(s + k, s + k + d) for _, _, s, d in
                          tracing.ops(trace, plane)
                          if s + k + d > lo and s + k < hi])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _innermost(trace: dict, lo: int, hi: int) -> List[tuple]:
    """[lo, hi) cut at every host span's ends: (start, end, name of the
    innermost span open there, or 'none')."""
    spans = sorted((h for h in trace['host']
                    if h[1] < hi and h[1] + h[2] > lo), key=lambda h: h[1])
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for h in spans
                              for t in (h[1], h[1] + h[2])})
    out, open_, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][1] <= a:
            open_.append(spans[k])
            k += 1
        open_ = [h for h in open_ if h[1] + h[2] > a]
        out.append((a, b, min(open_, key=lambda h: h[2])[0] if open_
                    else 'none'))
    return out


def idle_by_span(trace: dict, plane: str, lo: int,
                 hi: int) -> List[Tuple[int, int, str]]:
    """Each idle interval of [lo, hi), cut where the innermost host span
    changes: (index of the idle gap, nanoseconds, innermost span)."""
    gaps, segs = idle(trace, plane, lo, hi), _innermost(trace, lo, hi)
    out, i, j = [], 0, 0
    while i < len(gaps) and j < len(segs):
        s, e = max(gaps[i][0], segs[j][0]), min(gaps[i][1], segs[j][1])
        if s < e:
            out.append((i, e - s, segs[j][2]))
        if gaps[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_gaps(trace: dict, plane: str, lo: int, hi: int,
              n: int = 10) -> List[list]:
    """The n longest idle gaps in [lo, hi), each named by the innermost
    span, engine or benchmark, that holds most of it: [label, s]. An
    engine span keeps its whole name; a benchmark span loses ``bench.``."""
    gaps = idle(trace, plane, lo, hi)
    held: Dict[int, Dict[str, int]] = {}
    for i, ns, name in idle_by_span(trace, plane, lo, hi):
        held.setdefault(i, {}).setdefault(name, 0)
        held[i][name] += ns
    pre = tracing.HOST_PREFIX
    out = []
    for i in sorted(range(len(gaps)), key=lambda i: gaps[i][0] - gaps[i][1]):
        if len(out) == n:
            break
        name = max(held[i], key=held[i].get)
        out.append([name[len(pre):] if name.startswith(pre) else name,
                    (gaps[i][1] - gaps[i][0]) / 1e9])
    return out


def host_idle_ns(trace: dict, plane: str, lo: int, hi: int) -> int:
    """Device-idle nanoseconds of [lo, hi) whose innermost span is an
    engine phase other than ``engine.device_wait``: the time the device
    waits on the host's own work."""
    return sum(ns for _, ns, name in idle_by_span(trace, plane, lo, hi)
               if name.startswith(ENGINE_PREFIX) and name != DEVICE_WAIT)


def step_ms_by_kind(trace: dict, plane: str, lo: int,
                    hi: int) -> Optional[Dict[str, List[float]]]:
    """Device ms of each step program that started in [lo, hi), by the
    ``kind`` of the ``engine.dispatch`` that launched it. Programs on the
    ``XLA Modules`` line and dispatches in the window are paired in launch
    order, as one device runs its programs in the order they were
    enqueued. Where the trace links a program to its enqueue by run id,
    the enqueue must fall between its dispatch's start and the next
    one's. None where the counts differ or a run id disagrees: no
    guessing."""
    rx = re.compile(STEP_PROGRAM)
    runs = sorted((s, d) for _, n, s, d in
                  tracing.ops(trace, plane, tracing.MODULES_LINE)
                  if lo <= s < hi and rx.search(tracing.op_name(n)))
    disp = sorted((h[1], h[3]['kind']) for h in trace['host']
                  if h[0] == DISPATCH and lo <= h[1] < hi)
    if not runs or len(runs) != len(disp):
        return None
    rid = {s: n for _, n, s, _ in tracing.ops(trace, plane, RUN_LINE)}
    enq = {n: s for _, n, s, _ in tracing.ops(trace, plane, ENQUEUE_LINE)}
    ends = [t for t, _ in disp[1:]] + [hi]
    out: Dict[str, List[float]] = {}
    for (s, d), (t, kind), end in zip(runs, disp, ends):
        launched = enq.get(rid.get(s))
        if launched is not None and not t <= launched < end:
            return None
        out.setdefault(kind, []).append(d / 1e6)
    return out


def readings(trace: dict, plane: str, lo: int, hi: int,
             steps: int) -> Dict[str, Optional[float]]:
    """The step program's mean device ms over decode steps
    (``decode_step_ms``) and over steps that carry a prefill chunk
    (``prefill_step_ms``), and the host-bound idle ms per step
    (``host_idle_ms_per_step``); None where a reading has nothing to
    read."""
    by = step_ms_by_kind(trace, plane, lo, hi) or {}
    mean = lambda v: sum(v) / len(v) if v else None
    return {'decode_step_ms': mean(by.get('decode')),
            'prefill_step_ms': mean([ms for k in PREFILL_KINDS
                                     for ms in by.get(k, ())]),
            'host_idle_ms_per_step': host_idle_ns(trace, plane, lo, hi)
            / 1e6 / steps if steps else None}
