"""One run of one benchmark cell, in one process.

Set-up builds the seeded weights, the precomputed table and the engine,
compiles (or loads from JAX's persistent cache) every program shape the
cell's traffic uses, and drives the cell's own traffic until it is steady.
The window then drives ``ServingEngine.submit`` and ``step_once`` for
``seconds``, with the engine's queue never holding fewer than ``max_slots``
requests, and stamps every token on the client's side as it arrives.
Afterwards the engine is freed and the plain reference checks a seeded
sample of the finished requests. The configuration's architecture
(``bench/arch/<model_type>.py``) brings the parameter tree, the reference
and the work counts; the deployment path is held the same for all
(``bench/model.py``).

With ``trace`` the window runs with the engine's telemetry on and under the
JAX profiler, and the cell's per-layer metrics are read from both; without
it, its end-to-end metrics. Each metric is read by its own file under
``bench/metrics/``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import generator as G
from bench import manifest as M
from bench import tracing

WARM_UID = 10 ** 12             # warm-up requests' uids start here
SAMPLE_MIN, SAMPLE_MAX, SAMPLE_TOKENS = 3, 8, 512
KEEP_TRACE_S = 0.25             # --keep-trace: seconds of window kept


class NoDevice(RuntimeError):
    """No accelerator of a known kind, or fewer chips than the cell asks."""


class MissingMetric(RuntimeError):
    """A metric the cell lists read nothing, or an infinite tail: a name
    its reader looks for is not in the trace, or a request never got its
    token."""


class CompileClock:
    """Counts XLA backend compiles (seconds, programs), persistent-cache
    hits and traces; a trace inside the window is a shape the warm-up
    missed."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        self.traces = 0

        def on_duration(event, duration, **_):
            if event == '/jax/core/compile/backend_compile_duration':
                self.seconds += duration
                self.programs += 1
            elif event == '/jax/core/compile/jaxpr_trace_duration':
                self.traces += 1

        def on_event(event, **_):
            if event == '/jax/compilation_cache/cache_hits':
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.programs, self.seconds, self.cache_hits


@dataclasses.dataclass
class Tracked:
    """A request as the client sees it."""
    spec: G.Spec
    req: object
    seen: int = 0
    last_t: float = 0.0
    finish_t: Optional[float] = None


@dataclasses.dataclass
class Record:
    """What one run measured; each metric's reader takes what it needs."""
    cell: str
    dims: object                      # the architecture's sizes
    arch: object                      # its module (bench/arch/)
    peaks: dict
    setup_s: float
    window_s: float
    tokens: int                       # generated tokens received in window
    itl_s: List[float]                # gaps between tokens, ending in window
    steps: int                        # engine steps dispatched in window
    telemetry: Optional[dict] = None  # see Client.telemetry_record
    trace: Optional[dict] = None      # see reduce_trace


def find_device(chips: int):
    """-> (devices, peaks). Refuses a platform or kind not in the peaks
    table, and fewer devices than the cell asks for."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    table = M.data('peaks.json')['devices']
    if devices[0].platform != 'tpu' or kind not in table:
        raise NoDevice(f'JAX found {devices[0].platform!r} ({kind}); the '
                       f'benchmark runs on {sorted(table)}')
    if len(devices) < chips:
        raise NoDevice(f'the cell needs {chips} chips, JAX found '
                       f'{len(devices)}')
    return devices[:chips], table[kind]


def use_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.serve.use_compile_cache``): the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache`` at the root of
    the checkout, a fixed path. Every program is kept, however fast it
    compiled, so that only a checkout's first run compiles."""
    import jax
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR') or \
        os.path.join(M.ROOT, '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return path


class Client:
    """The client: submits a mix's requests to the engine, steps it and
    watches every request's tokens arrive."""

    def __init__(self, eng, stream: G.Stream, trace: bool,
                 clock: CompileClock):
        import jax
        self.eng, self.clock = eng, clock
        self.specs = iter(stream)
        self.next_spec = next(self.specs)
        self.inflight: List[Tracked] = []
        self.done: List[Tracked] = []
        self.submitted: List[Tracked] = []
        self.ann = jax.profiler.TraceAnnotation if trace \
            else (lambda name: contextlib.nullcontext())
        self.t0 = self.t1 = math.inf
        self.tokens = 0
        self.itl: List[float] = []
        self.steps = 0

    # ---------------------------------------------------------- the client
    def submit(self) -> Tracked:
        from repro.serving import Request
        spec = self.next_spec
        self.next_spec = next(self.specs)
        req = Request(uid=spec.idx, prompt=spec.prompt,
                      max_new_tokens=spec.max_new_tokens)
        tr = Tracked(spec, req)
        with self.ann('bench.submit'):
            self.eng.submit(req)
        self.submitted.append(tr)
        (self.done if req.terminal else self.inflight).append(tr)
        return tr

    def step(self) -> None:
        """One engine step, then the tokens it delivered."""
        t = time.monotonic()
        before = self.eng.steps
        with self.ann('bench.step_once'):
            self.eng.step_once()
        now = time.monotonic()
        in_window = self.t0 <= t < self.t1
        if in_window:
            self.steps += self.eng.steps - before
        with self.ann('bench.observe'):
            for tr in list(self.inflight):
                n = len(tr.req.generated)
                if n > tr.seen:
                    gaps = n - tr.seen - (tr.seen == 0)
                    if in_window:
                        self.tokens += n - tr.seen
                        if gaps > 0:
                            first = now - tr.last_t if tr.seen else 0.0
                            self.itl += [first] + [0.0] * (gaps - 1)
                    tr.seen, tr.last_t = n, now
                if tr.req.terminal:
                    tr.finish_t = now
                    self.inflight.remove(tr)
                    self.done.append(tr)

    def waiting(self) -> int:
        from repro.serving import RequestStatus
        return sum(tr.req.status in (RequestStatus.QUEUED,
                                     RequestStatus.PREEMPTED)
                   for tr in self.inflight)

    # -------------------------------------------------------- telemetry
    def phase_sums(self) -> Dict[str, float]:
        from repro.serving import telemetry as TM
        tel = self.eng.telemetry
        out: Dict[str, float] = {}
        if not tel.enabled:
            return out
        for labels, h in tel.registry.find(TM.STEP_PHASE).items():
            ph = dict(labels)['phase']
            out[ph] = out.get(ph, 0.0) + h.total
        return out

    def telemetry_record(self, steps0: int, steps1: int, phase0: dict,
                         phase1: dict) -> Optional[dict]:
        """From the engine's spans: per window step the positions each
        request processed, and the host phases' seconds summed over the
        window."""
        from repro.serving import telemetry as TM
        tel = self.eng.telemetry
        if not tel.enabled:
            return None
        plen = {tr.spec.idx: len(tr.spec.prompt) for tr in self.submitted}
        lanes: Dict[int, Dict[int, List[int]]] = {}
        for uid, evs in tel.tracer.spans.items():
            if uid not in plen:
                continue
            k = 0
            for t, name, at in evs:
                at = at or {}
                if name == TM.EV_PREFILL_CHUNK:
                    s = int(at['step'])
                    if steps0 <= s < steps1:
                        lanes.setdefault(s, {}).setdefault(uid, []).extend(
                            range(int(at['pos']), int(at['pos'] + at['n'])))
                elif name in (TM.EV_FIRST_TOKEN, TM.EV_DECODE_STEP):
                    s = int(at['step'])
                    if k and steps0 <= s < steps1:
                        lanes.setdefault(s, {}).setdefault(uid, []).append(
                            plen[uid] + k - 1)
                    k += 1
        return {'lanes': lanes,
                'phase_s': {p: phase1.get(p, 0.0) - phase0.get(p, 0.0)
                            for p in phase1}}


def warm_compile(eng, vocab: int, seed: int) -> None:
    """Run every program shape the cell's traffic can reach: the chunk and
    the single-token step at each slot bucket, slot reset, the deferred
    page clear, and copy-on-write of a cached partial page."""
    from repro.serving import Request
    rng = np.random.default_rng(G.seed_words(seed) + [99])
    chunk, ps = eng.chunk_size, eng.page_size
    head = rng.integers(G.FIRST_TOKEN_ID, vocab, 2 * ps, dtype=np.int32)
    buckets, b = [], 8
    while b < eng.max_slots:
        buckets.append(b)
        b *= 2
    buckets.append(eng.max_slots)
    uid = WARM_UID
    for i, nb in enumerate(buckets):
        reqs = []
        for _ in range(nb):
            tail = rng.integers(G.FIRST_TOKEN_ID, vocab, chunk + 5,
                                dtype=np.int32)
            reqs.append(Request(uid=uid, prompt=np.concatenate([head, tail]),
                                max_new_tokens=2))
            uid += 1
        if i:   # stops inside a cached page: copy-on-write
            reqs.append(Request(uid=uid, prompt=head[:ps + ps // 2].copy(),
                                max_new_tokens=2))
            uid += 1
        for r in reqs:
            eng.submit(r)
        eng.run()


def reduce_trace(log_dir: str, keep: Optional[str]) -> dict:
    """Device busy time, window and breakdown of the traced window."""
    tr = tracing.load(log_dir)
    win = [h for h in tr['host'] if h[0] == 'bench.window']
    if not win:
        raise RuntimeError('the trace has no bench.window span')
    lo, hi = win[0][1], win[0][1] + win[0][2]
    planes = tracing.device_planes(tr)
    if keep:
        import json
        with open(keep, 'w') as f:
            json.dump(tracing.trim(tr, lo, lo + int(KEEP_TRACE_S * 1e9)), f)
    return {'events': tr, 'planes': planes, 'lo': lo, 'hi': hi,
            'busy_s': sum(tracing.busy_ns(tr, p, lo, hi)
                          for p in planes) / max(1, len(planes)) / 1e9,
            'window_s': (hi - lo) / 1e9}


def sample_finished(done: List[Tracked], t0: float, seed: int) -> List:
    """Requests finished from the window on: the longest, then a seeded
    draw until some hundreds of served tokens are in the sample."""
    from repro.serving import RequestStatus
    fin = [tr for tr in done if tr.req.status is RequestStatus.FINISHED
           and tr.finish_t is not None and tr.finish_t >= t0]
    if not fin:
        return []
    fin.sort(key=lambda tr: (-(len(tr.spec.prompt) + len(tr.req.generated)),
                             tr.spec.idx))
    rng = np.random.default_rng(G.seed_words(seed) + [7])
    pick = [fin[0]] + [fin[1:][i] for i in rng.permutation(len(fin) - 1)]
    out, served = [], 0
    for tr in pick:
        if len(out) >= SAMPLE_MAX or (len(out) >= SAMPLE_MIN
                                      and served >= SAMPLE_TOKENS):
            break
        out.append((np.asarray(tr.spec.prompt, np.int32),
                    np.asarray(tr.req.generated, np.int32)))
        served += len(tr.req.generated)
    return out


def compare(spec: dict, seed: int, sample, control: bool = False) -> dict:
    """By how much each served token's logit lies below the reference's
    best at its position, over the sample: the widest gap (``logit_gap``)
    and the mean (``mean_gap``). With ``control``, the same of the token
    the int8 control puts first (``control_gap``, ``control_mean_gap``)."""
    seqs = [np.concatenate([p, g]) for p, g in sample]
    rows = M.arch(spec).score(spec, seed, seqs, control=control)
    gaps, ctl = [], []
    for (p, g), row in zip(sample, rows):
        sl = slice(len(p) - 1, len(p) - 1 + len(g))
        gaps.append(row['best'][sl] - row['next'][sl])
        if control:
            ctl.append(row['best'][sl] - row['control'][sl])
    gaps = np.concatenate(gaps)
    out = {'logit_gap': float(gaps.max()), 'mean_gap': float(gaps.mean()),
           'requests': len(sample), 'served_tokens': int(gaps.size)}
    if control:
        ctl = np.concatenate(ctl)
        out.update(control_gap=float(ctl.max()),
                   control_mean_gap=float(ctl.mean()))
    return out


def read_metrics(bench: dict, cell: dict, group: str, rec: Record) -> dict:
    """The cell's ``end_to_end`` or ``per_layer`` metrics, each by its
    reader. A metric the cell lists (every end-to-end one, and a per-layer
    one whose ``workloads`` name the cell) has to read a finite number;
    one that applies by what it moves is left out where it reads none."""
    out = {}
    for m in M.metrics_for(bench, cell, group):
        value = M.reader(m['name'])(rec)
        if value is None or not math.isfinite(value):
            if group == 'end_to_end' or cell['name'] in m.get('workloads',
                                                               ()):
                raise MissingMetric(f'{m["name"]} read {value!r} in '
                                    f'{cell["name"]}, which lists it')
            continue
        out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, control: bool = False,
        keep_trace: Optional[str] = None, log=print) -> dict:
    """Run one cell once; -> the result object (the last stdout line).
    ``control`` also reads the int8 control (``bench/control.py``)."""
    bench = M.load()
    cell = M.workload(bench, cell_name)
    spec = M.config(bench, cell['config'])
    arch = M.arch(spec)
    mix = M.mix(cell['traffic'])
    devices, peaks = find_device(cell['chips'])
    cache = use_cache()
    clock = CompileClock()
    log(f'device {devices[0].device_kind} x{len(devices)}; cache {cache}')

    from bench import model as BM
    eng, _ = BM.build(arch, cell['config'], spec, seed, telemetry=trace)
    log(f'engine built at {time.monotonic() - t_start:.1f}s')
    dims = arch.dims_of(spec)
    warm_compile(eng, dims.vocab, seed)
    log(f'shapes warmed at {time.monotonic() - t_start:.1f}s; compile '
        '(programs, s, cache hits) %s' % (clock.snapshot(),))

    cl = Client(eng, G.Stream(mix, dims.vocab, seed), trace, clock)
    log_dir = tempfile.mkdtemp(prefix='bench-trace-') if trace else None
    try:
        timing = _drive_backlog(cl, mix, seconds, log_dir)
        mem = max(int((d.memory_stats() or {}).get('peak_bytes_in_use', 0))
                  for d in devices)
        rec = Record(
            cell=cell_name, dims=dims, arch=arch, peaks=peaks,
            setup_s=timing['t0'] - t_start, window_s=timing['window_s'],
            tokens=cl.tokens, itl_s=cl.itl, steps=cl.steps,
            telemetry=cl.telemetry_record(*timing['telemetry']))
        compiles = timing['compiles']
        if trace:
            rec.trace = reduce_trace(log_dir, keep_trace)
            if not rec.trace['planes']:
                planes = list(rec.trace['events']['device'])
                raise RuntimeError(f'no device plane with operations in '
                                   f'the trace: {planes}')
            for line in (tracing.OPS_LINE, tracing.MODULES_LINE):
                log(f'trace, most time on {line!r}: %s' % tracing.top_ops(
                    rec.trace['events'], rec.trace['planes'][0], line=line))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    attempted = timing['attempted']
    failed = sum(tr.req.status.value == 'failed' for tr in attempted)
    sample = sample_finished(cl.done, cl.t0, seed)
    log(f'window {rec.window_s:.2f}s: {rec.steps} steps, {rec.tokens} '
        f'tokens, {len(attempted)} requests attempted, {failed} failed, '
        f'{len(cl.done)} done in all; compiles in window {compiles}; '
        f'peak memory {mem} B')
    del cl, eng
    gc.collect()

    metrics = read_metrics(bench, cell, 'per_layer' if trace
                           else 'end_to_end', rec)

    t_ref = time.monotonic()
    got = compare(spec, seed, sample, control) if sample else \
        {'requests': 0, 'served_tokens': 0}
    log(f'reference over {got["requests"]} requests, {got["served_tokens"]} '
        f'served tokens in {time.monotonic() - t_ref:.1f}s; widest gap '
        f'{got.get("logit_gap")}, mean gap {got.get("mean_gap")}')
    checks = {name: {'value': got.get(name), 'limit': limit}
              for name, limit in M.limits(cell_name).items()}
    checks['failed_requests'] = {'value': failed, 'limit': 0}
    correct = all(c['value'] is not None and c['value'] <= c['limit']
                  for c in checks.values())
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices),
              'memory_peak_bytes': mem}
    out = {'correct': bool(correct), 'attempted': len(attempted),
           'failed': failed, 'metrics': metrics, 'device': device}
    if trace:
        t = rec.trace
        device.update(busy_s=t['busy_s'], window_s=t['window_s'])
        if t['planes']:
            p = t['planes'][0]
            out['breakdown'] = {
                'device_ops': tracing.top_ops(t['events'], p),
                'idle_gaps': tracing.idle_gaps(t['events'], p, t['lo'],
                                               t['hi'])}
    if control:
        out['readings'] = {k: got.get(k) for k in (
            'logit_gap', 'mean_gap', 'control_gap', 'control_mean_gap',
            'served_tokens')}
    out['checks'] = checks
    return out


def _window_start(cl: Client, log_dir: Optional[str]) -> None:
    import jax
    if log_dir:
        # the benchmark's spans and the device, without Python's calls
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    cl.steps0, cl.phase0 = cl.eng.steps, cl.phase_sums()
    cl.win_ann = cl.ann('bench.window')
    cl.win_ann.__enter__()
    cl.t0 = time.monotonic()


def _window_end(cl: Client, log_dir: Optional[str]) -> None:
    import jax
    cl.steps1, cl.phase1 = cl.eng.steps, cl.phase_sums()
    jax.block_until_ready(cl.eng.states)
    cl.win_ann.__exit__(None, None, None)
    if log_dir:
        jax.profiler.stop_trace()


def _drive_backlog(cl: Client, mix: dict, seconds: float,
                   log_dir: Optional[str]) -> dict:
    """Offline batch: the queue never holds fewer than max_slots requests.
    Warm-up runs until ``finished_slots`` x max_slots requests finished."""
    eng = cl.eng
    target = int(round(mix['warmup']['finished_slots'] * eng.max_slots))
    clock_mark = None
    while True:
        while cl.waiting() < eng.max_slots:
            cl.submit()
        if cl.t0 == math.inf and len(cl.done) >= target:
            _window_start(cl, log_dir)
            cl.t1 = cl.t0 + seconds
            first = len(cl.submitted)
            clock_mark = cl.clock.traces
            continue
        if time.monotonic() >= cl.t1:
            break
        cl.step()
    t_end = time.monotonic()
    compiles = cl.clock.traces - clock_mark
    _window_end(cl, log_dir)
    return {'t0': cl.t0, 'window_s': t_end - cl.t0,
            'attempted': cl.submitted[first:], 'compiles': compiles,
            'telemetry': (cl.steps0, cl.steps1, cl.phase0, cl.phase1)}

