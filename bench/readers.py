"""Arithmetic the metric readers under ``bench/metrics/`` share."""
from __future__ import annotations

import math
from typing import Optional

from bench import tracing

PAGED_ATTENTION = r'paged_kernel|paged_attention'   # kernel name in a trace
STEP_PROGRAM = r'chunk_step|(^|_)step\b'            # engine step programs


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank q-th percentile (0..100); None without samples."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def window_lanes(rec):
    """Per window step, the positions each request processed there."""
    if rec.telemetry is None:
        return None
    return [list(by_uid.values())
            for by_uid in rec.telemetry['lanes'].values()]


def model_flops(rec) -> Optional[int]:
    steps = window_lanes(rec)
    if steps is None:
        return None
    return sum(rec.arch.model_flops(rec.dims, lanes) for lanes in steps)


def mfu(rec, seconds: float) -> Optional[float]:
    """Model FLOPs of the window's work over ``seconds`` at the chip's
    peak, in %."""
    flops = model_flops(rec)
    if not flops or seconds <= 0:
        return None
    return 100.0 * flops / seconds / rec.peaks['bf16_flops']


def kernel_roofline(rec, pattern: str, work_fn) -> Optional[float]:
    """Least time the chip needs for the kernel's useful work
    (``work_fn(dims, lanes) -> (flops, bytes)`` per step) over its device
    time in the trace, in %. None where the trace has no such
    kernel or the window did no such work."""
    steps = window_lanes(rec)
    if rec.trace is None or not rec.trace['planes'] or steps is None:
        return None
    plane = rec.trace['planes'][0]
    t = tracing.time_by_name(rec.trace['events'], plane, pattern)
    flops = byts = 0
    for lanes in steps:
        f, b = work_fn(rec.dims, lanes)
        flops, byts = flops + f, byts + b
    if t <= 0 or not (flops or byts):
        return None
    least = max(flops / rec.peaks['bf16_flops'],
                byts / rec.peaks['hbm_bytes_per_s'])
    return 100.0 * least / t
