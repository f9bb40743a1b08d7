"""Batched serving engine: continuous batching with chunked prefill and an
optional paged KV pool with shared-prefix caching.

The engine schedules **mixed steps** over a fixed set of slots. Decoding
slots consume one (sampled) token per step; prefilling slots consume up to
``chunk_size`` prompt tokens at once through the chunked decode path
(``Model.decode_step`` with ``n_valid``), which writes a whole chunk of K/V
(or MLA latents) per layer in a single call and scans recurrent states with
masked commits. A 512-token prompt therefore costs
``ceil(512 / chunk_size)`` jit'd dispatches instead of 512 — the
time-to-first-token win measured by ``benchmarks/serving_throughput.py``.
When every occupied slot is decoding, the engine falls back to the
single-token step (a separately compiled, narrower program). Chunking works
for EVERY architecture kind — dense/GQA, MoE, MLA, mLSTM/sLSTM, hybrid,
VLM-text — with bit-identical-to-token-by-token semantics (audio enc-dec
decode is driven by its own API and stays one token per step).

Finished slots are freed and refilled from the queue — no head-of-line
blocking. Slot reuse runs a pre-jitted per-slot indexed reset (one
``dynamic_update_slice`` per state leaf) instead of rebuilding the state
tree host-side.

**Paged KV + prefix caching** (``prefix_cache=True``): per-slot contiguous
caches are replaced by a global pool of ``page_size``-token pages (one
``(num_pages, page_size, ...)`` array per attention layer) addressed
through per-slot page tables, and admission looks the prompt up in a
token-prefix radix index (``repro.serving.kvpool``). A request whose
prompt shares a cached prefix attaches the prefix's pages read-only and
skips that part of its chunked prefill entirely — the shared-system-prompt
TTFT win. Under the ``'reference'`` backend decode attends over a gathered
dense-shaped *view* of the slot's pages, so token outputs stay
bit-identical to the dense engine.

**Attention backend** (``attn_backend='auto' | 'reference' | 'pallas'``):
every attend in the stack routes through ``repro.models.attn_backend``.
Selection policy — ``'auto'`` (the default) resolves per platform: TPU,
where the kernels compile (``tests/test_tpu_compile.py`` compiles them for
a v5e; ``chip_smoke.py`` serves through them on one), gets ``'pallas'``;
CPU/GPU, where they would run interpreted (orders of magnitude slower, for
validation only), get ``'reference'``. Passing a concrete name pins the
backend regardless of platform. The parity contract per backend: ``'reference'`` is the
bit-identity oracle (lane-at-a-time rounding, dense-gathered paged
views) — tokens/logits bit-identical to the dense engine across chunking,
paging, packing and preempt/resume. ``'pallas'`` runs
``kernels/paged_attention.py`` — KV pages are read **in place** through
the page table (the per-layer dense gather disappears) and all chunk
query lanes are batched into one kernel dispatch; attend outputs match
the reference within ``attn_backend.PALLAS_TOL`` (fp32 running-softmax
reassociation, not bitwise), while cache *contents* stay bitwise. The
pallas backend also declares ``fused_maintenance``: the per-layer paged
cache writes move in-kernel (``kernels/paged_maintenance.py``) — the
chunk K/V scatter becomes a per-page job-list kernel, clear-on-alloc is
deferred (``PageTables.pending``) and folded into first-write masking in
that same pass, and copy-on-write runs as a page-to-page DMA kernel — so
a paged decode step touches each pool page once, with no standalone
clear/COW XLA dispatch and no dense (B,S,H,d) gather anywhere on the hot
path.
Sliding-window layers get private ring pages; architectures with ring or
recurrent state additionally store a per-boundary state *snapshot* on the
radix node and restore it on a hit. A request that stops short inside a
cached page copies the shared rows into a private page (copy-on-write).
MoE routing masks padding and free-slot lanes (they can never displace a
real token from expert capacity) and ``stats()`` reports the drop counter.

**Segment-packed prefill** (``pack_prefill=True``, prepacking — arXiv
2404.09529): without packing, a mixed step dispatches the full
``(max_slots, chunk_size)`` grid and every decode lane or short prefill
tail wastes most of its row on masked-out lanes. With packing, the
scheduler bin-packs this step's per-slot segments — each active slot
contributes one contiguous run of ``n_valid[s]`` lanes, decode singletons
included — into a compact ``(R, T)`` grid (first-fit decreasing; R rounds
up to a power of two for bounded retraces and is capped at ``max_slots``).
Token-wise compute (embedding/table gather, norms, FFN, residuals,
lm_head) runs on the packed grid; each *mixer* (attention / MLA / mLSTM /
sLSTM / hybrid) gathers its inputs back to the slot-major ``(S, T)``
layout and runs the unchanged unpacked code against the unchanged
per-slot caches and states (``attention.PackedLayout``). Cross-segment
attention is therefore *structurally impossible* — a slot's queries only
ever meet that slot's own cache — rather than relying on a per-lane
segment-id mask, and packed tokens are **bitwise identical** to the
unpacked chunked path (tests/test_packed_prefill.py). The scheduler's
saving shows up in ``stats()`` as ``prefill_lane_utilization``
(= lane_tokens / lanes_dispatched) and as the TTFT win in
``benchmarks/serving_throughput.py --workload bursty``. MoE configs
pack too: expert capacity is a function of the dispatch grid's token
count, so the packed dispatch pins it to the slot-major count
(``moe_apply(capacity_tokens=S*T)``) and breaks dispatch-sort ties by a
canonical slot-major lane index (``lane_order``) — routing decisions,
capacity drops and combine accumulation order are then identical
between the packed (R, T) and unpacked (S, T) grids, preserving
bit-identity. Composes with paged KV, prefix caching, precomputed
tables and fused gather→RoPE (per-lane positions ride in
``PackedLayout.lane_pos``).

**Sharded many-slot serving** (``mesh='PxH'`` string, ``(P, H)`` tuple
or a ``('pool', 'heads')`` ``jax.sharding.Mesh``): KV storage is laid
out 2D over the two axes the paged-attention grid already iterates —
every pool leaf's leading ``num_pages`` axis (and per-slot dense
state's batch axis) shards over ``'pool'``, and K/V storage's
``kv_heads`` axis over ``'heads'`` (``repro.sharding.serving_rules``;
non-divisible dims fall back to replication per leaf). The layout is
**shard storage, replicate compute**: states live sharded *at rest*
(the HBM-capacity story — a pool P× too big for one device still fits
the mesh), every jitted step gathers them to replicated at entry, runs
the exact single-device math (identical reduction geometry, so tokens
stay bitwise identical to the unsharded engine — a GSPMD-partitioned
o_proj contraction would reassociate the fp32 reduction and break
that), and re-constrains outputs to the sharded layout before
returning (donation-safe). The one genuinely partitioned compute is
the Pallas paged-attention kernel: its kv heads are independent, so
``kernels.paged_attention.sharded_paged_attention`` shard_maps it over
``'heads'`` with the page-table / ``pos0`` scalar-prefetch operands
kept device-local (replicated) — the sharded kernel's output is
bitwise equal to the unsharded kernel's. The compiler cannot partition
a Mosaic kernel, so the step programs are traced under the mesh and
every other kernel call runs whole on each device inside a
``shard_map`` (``kernels.ops.on_every_device``). Fused in-kernel page
maintenance is disabled under a mesh (the job-list kernels assume one
unpartitioned pool pass); maintenance falls back to the exact XLA
scatter path. ``max_slots`` scales to the hundreds: host args and
per-slot state leaves are sliced to a power-of-two slot bucket
(floor 8, capped at ``max_slots``) derived from the highest active
slot, so jit retraces stay bounded at ~``log2(max_slots)`` shapes and
an engine with 3 live slots never pays a 256-wide dispatch.

**Async double-buffered host loop** (``async_loop=True``): the
scheduling work for step N+1 — admission, radix lookups, deadline
checks, segment bin-packing — overlaps the device compute of step N.
:meth:`step_once` splits into a schedule/dispatch half and a commit
half, pipelined one step deep: step N's sampled tokens are committed
(``np.asarray``, the only device wait) *after* step N+1 has been
dispatched, and the dispatched program splices each decoding slot's
previous sampled token in on device (``prev_nxt``/``use_prev``
arguments), so scheduling never blocks on a transfer. **One-step
sampling lag is the documented contract**: host-visible request state
(``generated``, terminations, prompt logits, radix publishes) trails
the device by exactly one dispatch, and :meth:`run` drains the
pipeline before returning. Greedy (temperature 0) tokens are bitwise
identical to the synchronous loop: deterministic terminations
(``max_new_tokens`` / ``max_seq``) are predicted at schedule time so
the doomed slot is simply not scheduled, EOS and watchdog terminations
dispatch one speculative lane whose commit record is then discarded
(guarded by slot identity + admission sequence number), and a pending
lane landing exactly on a ring/recurrent snapshot boundary forces a
pipeline flush before that slot's next chunk so the captured state
matches the synchronous capture. Temperature > 0 streams are *not*
bitwise across the two modes (the PRNG split schedule differs);
greedy decoding is the parity contract
(``tests/test_sharded_serving.py``).

Logits-on-demand (prompt scoring): a request submitted with
``return_logits=True`` gets ``prompt_logits`` filled with the all-position
logits of its prompt — row ``i`` is the next-token distribution after
consuming ``prompt[i]`` — reusing the same chunk path with the lm_head run
on every valid lane instead of the last one. :meth:`ServingEngine.score`
wraps this for a batch of prompts. Scoring requests always prefill cold
(their logits must cover every prompt position).

THE PAPER lives here: constructing the engine with ``precomputed=`` makes
every step's embedding-read + layer-0 projections a single row gather per
token — during chunked prefill that is one contiguous *multi-row* gather per
chunk. ``fused_gather_rope=True`` additionally folds layer-0 RoPE into that
gather via the Pallas kernel (``kernels/gather_rope.py``), so rows go
gather→RoPE→attention without an HBM round-trip (compiled TPU path; on CPU
the kernel runs in interpret mode and is for validation only). This covers
dense q/k layouts AND MLA layouts (each head's rotary ``q_pe`` slice plus
the shared ``k_pe`` row rotate in-gather; the attend is told via
``rope_applied``); eligibility is decided by
``transformer.fused_rope_eligible`` and ineligible configs (non-rope
position encodings, hybrid layer-0) silently fall back to the unfused
gather — no special-casing here.

**Failure semantics** (fault-tolerant serving): every request carries a
``RequestStatus`` lifecycle (``QUEUED → PREFILLING → DECODING → FINISHED``,
with ``FAILED / CANCELLED / PREEMPTED`` branches) and every failure mode is
a *per-request outcome* — the engine itself never dies on load:

- **Validation at submit**: empty prompts, prompts that cannot fit
  ``max_seq``, and non-positive ``max_new_tokens`` are marked
  ``FAILED`` immediately (``error`` says why); the engine keeps stepping.
  Duplicate *live* uids are rejected with ``ValueError``.
- **Preemption instead of pool-exhaustion crashes**: when the paged KV
  pool runs dry (and eviction finds nothing cold), the engine preempts a
  victim slot — fewest decoded tokens, LIFO on ties; the oldest in-flight
  request is protected so some request always runs to completion (no
  mutual-preemption livelock, which would otherwise be fatal for
  ring/recurrent archs whose mid-page progress can't be published) —
  publishes the victim's fully-written pages into the radix prefix index,
  releases its pages, and requeues it. Resume is a prefix hit: only the
  uncached tail recomputes, and greedy tokens across preempt/resume are
  **bitwise identical** to an uninterrupted run (the chunked-prefill
  identity contract extended to the failure path). A request that cannot
  be scheduled even after bounded retries and preemption fails with
  ``error='unschedulable'`` instead of wedging the queue.
- **Cancellation and deadlines**: :meth:`ServingEngine.cancel` removes a
  request wherever it is (queued or mid-flight, prefill or decode);
  ``Request(deadline_s=...)`` is an elapsed-time budget from submit time,
  enforced at the top of every :meth:`step_once` on the **monotonic**
  clock (``time.monotonic()`` — a wall-clock step from NTP/DST can
  neither spuriously expire a request nor immortalize one; all request
  timestamps are monotonic stamps, meaningful only as differences).
- **NaN/Inf watchdog**: every dispatch returns a per-lane finiteness flag
  on the sampled logits; a non-finite lane fails *only that request*
  (``error='nonfinite_logits'``) — the batch keeps decoding.
- **No silent drops**: :meth:`run` returns a report, and if its iteration
  budget expires with work still queued, that work is marked
  ``FAILED('stalled')`` instead of being dropped on the floor.
- **Chaos hooks**: ``ServingEngine(fault_injector=...)`` takes a
  :class:`repro.serving.faults.FaultInjector` whose ``before_step`` /
  ``poison_lanes`` hooks deterministically force pool exhaustion, lane
  NaNs, and mid-flight cancels — the harness behind ``pytest -m chaos``
  and ``benchmarks/serving_throughput.py --workload overload``.

Observability
-------------
``ServingEngine(telemetry=True)`` attaches a
:class:`repro.serving.telemetry.Telemetry` recorder (or pass an existing
instance to share one registry across engines). Everything below is
host-side only — no jit'd code is touched, no device syncs are added, and
every bit-identity contract holds with telemetry on or off (tested).

**Phase taxonomy.** Each :meth:`step_once` dispatch is split into five
named phases, observed into the ``engine.step.phase_s`` histogram family
keyed by ``phase`` × ``backend`` (``reference``/``pallas``) × ``kind``
(``prefill``/``decode``/``mixed`` — a step is *mixed* when some active
lanes consume prompt tokens while others decode):

- ``host_schedule`` — fault hooks, deadline sweep, admission, lane
  building and preemption handling (radix time subtracted out);
- ``radix_lookup`` — time inside ``PrefixCache.match`` during this step's
  admissions (steps that admit but dispatch nothing drop their lookup
  time — there is no kind to charge it to);
- ``pack_layout`` — temps/pos/token staging and segment bin-packing;
- ``dispatch`` — the jit call itself. XLA dispatch is asynchronous, so
  this is *host enqueue cost*, not device compute;
- ``sample_commit`` — the ``np.asarray`` host transfer (this is where the
  device wait lands, keeping the kernel pipeline unsynced), token commit,
  radix publish, terminations. Under ``async_loop=True`` this phase
  belongs to the *previous* dispatch (one-step pipeline), and is still
  charged to that dispatch's ``kind``.

**Profiler spans.** The same intervals are also ``jax.profiler``
annotations (:meth:`repro.serving.telemetry.Telemetry.span`), so a
profiler capture of a telemetry-on engine shows each phase beside the
device ops it waits on or enqueues: ``engine.step`` (attr ``step``)
around :meth:`step_once`, holding ``engine.host_schedule`` (with one
``engine.radix_lookup`` per lookup nested in it), ``engine.pack_layout``,
``engine.dispatch`` (``step``, ``kind``, ``T``, ``nb``, and on a paged
engine ``walk``: the share of the linear page table the step's attention
walk visits, also the ``engine.step.walk`` histogram) and
``engine.sample_commit`` (``step`` of the dispatch it commits). Inside
``sample_commit``, ``engine.device_wait`` covers only the ``np.asarray``
transfers, so ``sample_commit`` minus ``device_wait`` is the host's
commit work. A telemetry-off engine opens no annotation.

Telemetry-enabled engines also register ``engine.queue.depth`` (a
callback gauge: requests waiting for a slot at scrape time), and async
engines the ``engine.step.overlap_s`` histogram (keyed by ``backend``):
the host scheduling time (host_schedule + radix_lookup + pack_layout)
of step N+1 spent while step N's dispatch was still uncommitted. The
sustained-workload benchmark reports
``sum(overlap_s) / sum(host_schedule + radix_lookup + pack_layout)``
as its overlap fraction.

**Metric names** live in exactly one place — constants in
:mod:`repro.serving.telemetry`: ``engine.step.phase_s``,
``request.latency_s``, ``request.ttft_s``, and the KV pool series
(``pages_in_use``, ``pages_free``, ``pages_reclaimable`` gauges;
``prefix_hits``/``misses``/``hit_tokens``, ``evictions``, ``cow_copies``
counters) that also name the ``kvpool.stats()`` keys. Future PRs add
metrics by defining a constant there first.

**Request spans.** The tracer records one event stream per uid: SUBMIT →
ADMIT (with ``prefix_hit_tokens``) → PREFILL_CHUNK per dispatch →
FIRST_TOKEN → DECODE_STEP per token → FINISH/FAIL/CANCEL, with PREEMPT →
RESUME pairs, COW/EVICT page events, and ``FAULT_*`` injections (uid
``None``) interleaved — a chaos run is replayable from the trace alone.

**Exports.** :meth:`metrics` returns the structured snapshot;
``Telemetry.prometheus_text()`` / ``write_json`` dump the registry
(``serve.py --metrics-out``); ``Telemetry.chrome_trace()`` emits
Chrome-trace JSON (``chrome://tracing`` / Perfetto) with one track per
request (``serve.py --trace-out``). Disabled mode is zero-cost: the
shared ``NULL_TELEMETRY`` no-op recorder guards every site behind a
single ``enabled`` bool — no clock reads, no per-step allocation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import paged_maintenance as PM
from repro.models import attention as A
from repro.models.model import Model
from repro.models.transformer import lm_logits
from repro.serving import telemetry as TM
from repro.serving.faults import FaultInjector
from repro.serving.kvpool import PrefixCache
from repro.serving.sampler import sample_tokens

_NO_SPAN = contextlib.nullcontext()     # device_wait's stand-in, telemetry off


class RequestStatus(str, enum.Enum):
    """Per-request lifecycle. ``FINISHED`` / ``FAILED`` / ``CANCELLED`` are
    terminal; ``PREEMPTED`` requests sit in the queue and resume as a
    prefix-cache hit."""
    QUEUED = 'queued'
    PREFILLING = 'prefilling'
    DECODING = 'decoding'
    FINISHED = 'finished'
    FAILED = 'failed'
    CANCELLED = 'cancelled'
    PREEMPTED = 'preempted'


TERMINAL_STATUSES = frozenset({RequestStatus.FINISHED, RequestStatus.FAILED,
                               RequestStatus.CANCELLED})


class ScoringError(RuntimeError):
    """Raised by :meth:`ServingEngine.score` when any scoring request
    terminates without its prompt logits (stall, deadline, non-finite
    watchdog, cancellation). ``errors[i]`` is ``None`` for prompts that
    scored fine and the failure reason string otherwise; ``logits[i]``
    holds whatever completed (``None`` for the failed prompts) so partial
    results are recoverable. Callers used to get silent ``None`` entries
    and crash later indexing into them."""

    def __init__(self, errors, logits):
        self.errors = errors
        self.logits = logits
        bad = [f'prompt {i}: {e}' for i, e in enumerate(errors)
               if e is not None]
        n = sum(e is not None for e in errors)
        super().__init__(f'scoring failed for {n}/{len(errors)} prompts '
                         f'({"; ".join(bad)})')

# internal (engine-allocated) uids start far below any plausible caller uid
_INTERNAL_UID_BASE = -(10 ** 12)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                    # (P,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    return_logits: bool = False           # collect all-position prompt logits
    deadline_s: Optional[float] = None    # wall-clock budget from submit time
    # filled by the engine:
    status: RequestStatus = RequestStatus.QUEUED
    error: Optional[str] = None           # why status == FAILED
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # monotonic-clock stamps (time.monotonic()): only differences are
    # meaningful (latency = finish_t - submit_t); never compare to wall time
    submit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    prompt_logits: Optional[np.ndarray] = None    # (P, V) if return_logits
    prefix_hit_tokens: int = 0            # prompt tokens served from cache
    preemptions: int = 0                  # times this request was preempted
    _logit_chunks: List[np.ndarray] = dataclasses.field(default_factory=list,
                                                        repr=False)
    _admit_fails: int = dataclasses.field(default=0, repr=False)
    _stuck_pos: int = dataclasses.field(default=-1, repr=False)
    _stuck: int = dataclasses.field(default=0, repr=False)
    _hold_until: int = dataclasses.field(default=0, repr=False)

    @property
    def terminal(self) -> bool:
        return self.status in TERMINAL_STATUSES


def _is_body(path) -> bool:
    return "'body'" in jax.tree_util.keystr(path)


def _is_pos_leaf(path) -> bool:
    return jax.tree_util.keystr(path).endswith("['pos']")


def _leaf_name(path) -> str:
    """Innermost string key of a tree path ('k', 'v', 'k_scale', ...)."""
    for entry in reversed(path):
        k = getattr(entry, 'key', None)
        if isinstance(k, str):
            return k
    return ''


@dataclasses.dataclass
class _Lane:
    """Commit record for one dispatched lane (async pipeline): everything
    the deferred commit needs, captured at dispatch time so later host
    mutations (preemption, re-admission) cannot skew it. ``admit_seq``
    plus request identity guards against the slot having been vacated and
    re-admitted (even by the same request) while the dispatch was in
    flight — a stale lane's commit record is silently discarded."""
    slot: int
    req: Request
    admit_seq: int
    consumed: int
    p_before: int           # stream progress before this dispatch
    p_after: int            # ... and after
    pos_after: int          # absolute slot position after this dispatch
    gen: bool               # commit will append a sampled token


@dataclasses.dataclass
class _PendingStep:
    """One in-flight dispatch awaiting commit (the one-step-deep async
    pipeline). ``nxt``/``finite``/``drops``/``logits`` are device arrays —
    no host transfer happens until :meth:`ServingEngine._commit`."""
    nxt: jax.Array
    finite: jax.Array
    drops: jax.Array
    logits: Optional[jax.Array]
    lanes: List[_Lane]
    pk_row: Optional[np.ndarray]    # packed-grid logit locations (scoring)
    pk_off: Optional[np.ndarray]
    nb: int                         # slot bucket this dispatch ran at
    step_idx: int
    kind: Optional[str]             # telemetry kind (None with tel. off)
    times: Optional[tuple]          # (host_schedule, radix, pack, dispatch)
    needs_sync: bool                # commit captures device state: flush
                                    # before the slot's next dispatch


class ServingEngine:
    def __init__(self, model: Model, params, *, max_slots: int = 8,
                 max_seq: int = 512, precomputed=None, seed: int = 0,
                 dtype=jnp.float32, kv_quant: bool = False,
                 chunk_size: int = 1, fused_gather_rope: bool = False,
                 prefix_cache: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 attn_backend: str = 'auto',
                 fault_injector: Optional[FaultInjector] = None,
                 admit_retry_steps: int = 8,
                 pack_prefill: bool = False,
                 telemetry=False,
                 mesh=None,
                 async_loop: bool = False):
        from repro.launch.mesh import make_serving_mesh
        from repro.models.attn_backend import get_backend
        from repro.sharding import serving_rules
        self.model, self.params = model, params
        self.max_slots, self.max_seq = max_slots, max_seq
        self.precomputed = precomputed
        # ------------------------------------------------- mesh / async loop
        # mesh: None | 'PxH' | (P, H) | a ('pool','heads') Mesh — resolved
        # (and ValueError'd on impossible shapes) by make_serving_mesh.
        self.mesh = make_serving_mesh(mesh)
        self._rules = serving_rules(self.mesh)
        self.async_loop = bool(async_loop)
        self._pending = None            # in-flight dispatch (async pipeline)
        self.attn_backend = get_backend(attn_backend)
        if self.mesh is not None and self.attn_backend.name == 'pallas':
            # partition the kernel for real: shard_map over 'heads' (the
            # kernel's embarrassingly-parallel grid axis — bitwise equal
            # to the unsharded kernel). Fused maintenance is off under a
            # mesh; ShardedPallasBackend declares that.
            from repro.models.attn_backend import ShardedPallasBackend
            self.attn_backend = ShardedPallasBackend(self.mesh)
        # ------------------------------------------------------ telemetry
        # False/None -> the shared no-op singleton (zero-cost: every hot
        # instrumentation site is guarded by `if tel.enabled`), True -> a
        # fresh recorder, or pass an existing Telemetry to share one
        # registry across engines.
        self.telemetry = TM.coerce(telemetry)
        tel = self.telemetry
        # Engine-lifetime request latency/TTFT histograms back run()'s
        # p50/p99 even with telemetry off (one observe per request
        # lifetime, not a per-step cost); with telemetry on they are
        # registry series and ride every export.
        if tel.enabled:
            self._lat_hist = tel.registry.histogram(TM.REQUEST_LATENCY)
            self._ttft_hist = tel.registry.histogram(TM.REQUEST_TTFT)
            self._cow_counter = tel.registry.counter(TM.KV_COW_COPIES)
            self._phase_h = {
                kind: {ph: tel.registry.histogram(
                    TM.STEP_PHASE, phase=ph,
                    backend=self.attn_backend.name, kind=kind)
                    for ph in TM.PHASES}
                for kind in TM.STEP_KINDS}
            self._walk_h = {
                kind: tel.registry.histogram(
                    TM.STEP_WALK, backend=self.attn_backend.name, kind=kind)
                for kind in TM.STEP_KINDS}
            tel.registry.gauge(TM.QUEUE_DEPTH, fn=lambda: len(self.queue))
            self._overlap_h = tel.registry.histogram(
                TM.STEP_OVERLAP, backend=self.attn_backend.name) \
                if self.async_loop else None
        else:
            self._lat_hist = TM.Histogram()
            self._ttft_hist = TM.Histogram()
            self._cow_counter = None
            self._phase_h = None
            self._walk_h = None
            self._overlap_h = None
        self._t_radix = 0.0     # radix-lookup seconds within current step
        self._phase_span = None  # open profiler span of the current phase
        if model.cfg.arch_class == 'audio':
            chunk_size = 1   # enc-dec decode is one token per step by API
            if prefix_cache:
                raise ValueError('paged prefix caching is not supported for '
                                 'audio enc-dec decode')
            if self.attn_backend.name != 'reference':
                raise ValueError('audio enc-dec decode supports only the '
                                 'reference attention backend')
        from repro.models.blocks import kind_window
        from repro.models.transformer import (fused_rope_eligible, layer_plan,
                                              pad_table_for_fused)
        plan = layer_plan(model.cfg)
        # fused gather→RoPE eligibility lives with the model code now
        # (transformer.fused_rope_eligible — q/k AND MLA-latent layouts);
        # ineligible configs silently fall back to the unfused gather.
        if fused_gather_rope and (chunk_size == 1 and not prefix_cache):
            fused_gather_rope = False   # one-token path never fuses
        fused_gather_rope = fused_gather_rope \
            and fused_rope_eligible(precomputed, model.cfg)
        if fused_gather_rope:
            self.precomputed = precomputed = pad_table_for_fused(precomputed)
        self.chunk_size = chunk_size
        self.fused_gather_rope = fused_gather_rope
        self._meta = getattr(model.cfg, 'num_meta_tokens', 0)
        self.paged = bool(prefix_cache)
        self.page_size = page_size
        # Segment-packed prefill (see the docstring section): needs a real
        # chunk grid to pack into. MoE configs pack too — the dispatch pins
        # expert capacity to the slot-major token count and canonicalises
        # the dispatch-sort tie order (blocks.block_decode passes
        # capacity_tokens / lane_order), so shrinking the grid from (S, T)
        # to (R, T) cannot change routing. Audio never chunks.
        self.pack_prefill = bool(pack_prefill) and chunk_size > 1 \
            and model.cfg.arch_class != 'audio'
        # chunk-grid utilization counters (packed-prefill win metric):
        # lanes dispatched vs lanes that actually carried a token
        self.lanes_dispatched = 0
        self.lane_tokens = 0

        # --------------------------------------------------- paged geometry
        if self.paged:
            if self._meta:
                raise ValueError('paged prefix caching does not support '
                                 'meta-token architectures yet (the primed '
                                 'meta prefix would need template pages)')
            if max_seq % page_size:
                raise ValueError(f'max_seq ({max_seq}) must be a multiple of '
                                 f'page_size ({page_size}) so the paged '
                                 'virtual cache matches the dense cache '
                                 'length exactly (bit-identity)')
            windowed = any(kind_window(model.cfg, k) for k in plan.kinds)
            self._sc_ring = A.cache_len(model.cfg.window, max_seq,
                                        chunk_size) if windowed else 0
            self._pages_lin = max_seq // page_size
            self._pages_ring = -(-self._sc_ring // page_size)
            # snapshot archs: any layer whose decode state is rewritten in
            # place (ring caches, recurrent/conv state) — prefix resume
            # needs the radix node's state snapshot, not just shared pages
            self._needs_snapshot = any(k != 'global' for k in plan.kinds)
            if num_pages is None:
                num_pages = 1 + max_slots * (self._pages_lin
                                             + self._pages_ring) \
                    + 8 * self._pages_lin
            # a single admission needs ring pages + a COW page, and the
            # first dispatch one linear page; below this floor admission
            # can never succeed and run() would stall silently
            floor = 1 + self._pages_ring + 2
            if num_pages < floor:
                raise ValueError(f'num_pages ({num_pages}) cannot host even '
                                 f'one request: need >= {floor} '
                                 f'(null page + {self._pages_ring} ring '
                                 'pages + COW/linear headroom)')
            self.kv = PrefixCache(num_pages, page_size)
            if tel.enabled:
                self.kv.bind_telemetry(tel)
            self.num_pages = num_pages
        else:
            self._sc_ring = 0
            self.kv = None
            self.num_pages = 0

        self.states = model.make_states(
            max_slots, max_seq, dtype, kv_quant=kv_quant, chunk=chunk_size,
            num_pages=self.num_pages if self.paged else 0,
            page_size=page_size if self.paged else 0)
        if self._meta:
            # prime hymba-style learnable meta tokens into every slot's state
            from repro.models.transformer import prime_meta_states
            self.states = prime_meta_states(params, self.states, model.cfg,
                                            max_slots)
        self._paged_mask = model.paged_state_mask(kv_quant) if self.paged \
            else None
        # template for clean slot reuse (covers caches AND recurrent states).
        # A real copy: the step/reset jits donate their states argument, so
        # the template must not alias the live buffers. Page-pool leaves are
        # never slot-reset (pages are cleared on allocation instead) — their
        # template entry is a dummy.
        if self.paged:
            self._fresh = jax.tree_util.tree_map(
                lambda x, m: jnp.zeros(()) if m else jnp.array(x),
                self.states, self._paged_mask)
        else:
            self._fresh = jax.tree_util.tree_map(jnp.array, self.states)
        if self.mesh is not None:
            # Shard storage at rest: pool leaves over ('pool', heads over
            # 'heads'), per-slot leaves over 'pool' on batch. Params, the
            # precomputed table and the reset template are jit arguments
            # -> replicate them explicitly.
            rep = jax.sharding.NamedSharding(self.mesh,
                                             jax.sharding.PartitionSpec())
            self.params = jax.device_put(self.params, rep)
            self.precomputed = jax.device_put(self.precomputed, rep)
            self._fresh = jax.device_put(self._fresh, rep)
            self.states = self._place_states(self.states)
        # params and the precomputed table ride into every step program as
        # its first argument: a closed-over array would be embedded in the
        # program as a constant — a second copy of a vocab-sized table per
        # compiled program, and a slow compile
        self._weights = (self.params, self.precomputed)
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.slot_pos = np.zeros(max_slots, np.int64)       # next position
        self.slot_next_tok = np.zeros(max_slots, np.int32)  # token to feed
        # the token stream a slot serves: prompt, or prompt + generated-so-far
        # for a resumed (previously preempted) request
        self.slot_stream: List[Optional[np.ndarray]] = [None] * max_slots
        self.slot_admit_seq = np.zeros(max_slots, np.int64)  # LIFO victim tie
        self.queue: List[Request] = []
        self.key = jax.random.PRNGKey(seed)
        self.steps = 0
        self.ticks = 0      # step_once entries; unlike steps, never freezes
        self.moe_token_drops = 0
        # ------------------------------------------------ fault tolerance
        self.fault_injector = fault_injector
        self._admit_retry_steps = max(1, admit_retry_steps)
        self._live_uids: set = set()
        self._internal_uid = _INTERNAL_UID_BASE
        self._admit_seq = 0
        self.preemptions = 0
        self.n_failed = 0
        self.n_cancelled = 0
        self.n_deadline = 0
        self.n_stalled = 0

        # ------------------------------------------------ per-slot paging
        # Deferred clear-on-alloc: with a fused_maintenance backend, freshly
        # allocated pages are queued here instead of being zeroed by a
        # standalone XLA dispatch; the queue rides into the next step as
        # PageTables.pending and every paged layer folds the clears into
        # its fused chunk write (kernels/paged_maintenance). Overflow past
        # _pending_cap (a fixed jit shape) flushes eagerly.
        self._fused_maint = self.paged \
            and getattr(self.attn_backend, 'fused_maintenance', False) \
            and self.mesh is None
        self._pending_clear: List[int] = []
        self._pending_cap = 64
        if self.paged:
            self._pt = np.zeros((max_slots, self._pages_lin), np.int32)
            self._rt = np.zeros((max_slots, max(self._pages_ring, 1)),
                                np.int32)
            self.slot_node = [None] * max_slots       # attached radix node
            self.slot_nblocks = np.zeros(max_slots, np.int32)
            self.slot_priv: List[List[int]] = [[] for _ in range(max_slots)]
            self.slot_ring: List[List[int]] = [[] for _ in range(max_slots)]
            self.slot_insert_at = np.full(max_slots, -1, np.int64)

        self._build_programs()
        if self.paged:
            self._build_page_ops()

    # ---------------------------------------------------- mesh state layout
    def _leaf_axes(self, path, leaf, pooled: bool) -> List[Optional[str]]:
        """Logical axes for one state leaf under serving_rules: the lead
        axis (after a 'body' scan axis) is 'pages' for pool leaves /
        'batch' for per-slot leaves, K/V storage's kv_heads axis maps by
        leaf name. Non-divisible dims drop to replication downstream
        (Rules.spec_for_shape)."""
        lead = 1 if _is_body(path) else 0
        axes: List[Optional[str]] = [None] * leaf.ndim
        if leaf.ndim > lead:
            axes[lead] = 'pages' if pooled else 'batch'
        name = _leaf_name(path)
        if name in ('k', 'v') and leaf.ndim - lead >= 3:
            axes[-2] = 'kv_heads'           # (..., seq/page_tok, KV, hd)
        elif name in ('k_scale', 'v_scale') and leaf.ndim - lead >= 2:
            axes[-1] = 'kv_heads'           # (..., seq/page_tok, KV)
        return axes

    def _state_sharding(self, path, leaf, pooled: bool):
        return self._rules.sharding_for_shape(
            leaf.shape, self._leaf_axes(path, leaf, pooled))

    def _map_states(self, states, fn):
        """tree_map_with_path over states with the pool mask riding along
        (pooled=False everywhere for dense engines)."""
        mask = self._paged_mask
        if mask is None:
            return jax.tree_util.tree_map_with_path(
                lambda p, x: fn(p, x, False), states)
        return jax.tree_util.tree_map_with_path(
            lambda p, x, m: fn(p, x, bool(m)), states, mask)

    def _place_states(self, states):
        """device_put every leaf to its at-rest sharded layout."""
        return self._map_states(
            states, lambda p, x, m: jax.device_put(
                x, self._state_sharding(p, x, m)))

    def _rep_in(self, states):
        """Inside jit: gather sharded storage to replicated at program
        entry — the 'replicate compute' half of the layout contract (the
        replicated program runs the exact single-device math, keeping
        tokens bitwise)."""
        if self.mesh is None:
            return states
        rep = jax.sharding.NamedSharding(self.mesh,
                                         jax.sharding.PartitionSpec())
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), states)

    def _shard_out(self, states):
        """Inside jit: re-constrain program outputs to the at-rest sharded
        layout (keeps donation aliasing clean and storage sharded)."""
        if self.mesh is None:
            return states
        return self._map_states(
            states, lambda p, x, m: jax.lax.with_sharding_constraint(
                x, self._state_sharding(p, x, m)))

    # -------------------------------------------------------- slot buckets
    def _bucket(self, active: List[int]) -> int:
        """Power-of-two slot-count bucket covering the highest active slot
        (floor 8, capped at max_slots). Engines with max_slots <= 8 always
        run the full width — their HLO is untouched by bucketing."""
        S = self.max_slots
        if S <= 8 or not active:
            return S
        nb = 8
        hi = max(active) + 1
        while nb < hi:
            nb *= 2
        return min(nb, S)

    def _slice_states(self, states, nb: int):
        """Inside jit: per-slot leaves sliced to the slot bucket (pool
        leaves pass whole — pages are slot-agnostic). nb == max_slots is
        the identity, so default-bucket programs trace exactly the
        historical HLO."""
        if nb == self.max_slots:
            return states

        def one(path, leaf, pooled):
            if pooled:
                return leaf
            return leaf[:, :nb] if _is_body(path) else leaf[:nb]
        return self._map_states(states, one)

    def _merge_states(self, full, part, nb: int):
        """Inside jit: write the bucket's updated per-slot rows back into
        the full-width (donated) buffers."""
        if nb == self.max_slots:
            return part
        mask = self._paged_mask

        def one(path, f, p, *m):
            if m and m[0]:
                return p
            return f.at[:, :nb].set(p) if _is_body(path) else f.at[:nb].set(p)
        if mask is None:
            return jax.tree_util.tree_map_with_path(one, full, part)
        return jax.tree_util.tree_map_with_path(one, full, part, mask)

    # ----------------------------------------------------------- programs
    def _jit(self, fn, **kw):
        """``jax.jit`` of one step program, traced under the serving mesh
        (if any): the Pallas kernels inside then know to run replicated on
        every device, which the compiler cannot partition for them
        (``kernels.ops.on_every_device``)."""
        if self.mesh is None:
            return jax.jit(fn, **kw)
        abstract = self.mesh.abstract_mesh

        @functools.wraps(fn)
        def on_mesh(*args, **kwargs):
            with jax.sharding.use_abstract_mesh(abstract):
                return fn(*args, **kwargs)
        return jax.jit(on_mesh, **kw)

    def _build_programs(self) -> None:
        model = self.model
        sc_ring = self._sc_ring
        backend = self.attn_backend

        def paged_tables(pt, rt, pending=None):
            if pt is None:
                return None
            return A.PageTables(pt, rt, sc_ring, pending)

        def feed_prev(tokens, prev_nxt, use_prev, packed=None):
            # async pipeline: splice the previous dispatch's sampled token
            # into each decoding lane on device (the host value is one
            # step stale by contract). prev_nxt may come from a different
            # slot bucket — pad/slice to this dispatch's width; slots past
            # the old width can't have a pending token (use_prev False).
            # None (sync mode / empty pipeline) traces to the exact
            # historical program.
            if prev_nxt is None:
                return tokens
            nb = use_prev.shape[0]
            pn = prev_nxt.astype(jnp.int32)
            if pn.shape[0] < nb:
                pn = jnp.pad(pn, (0, nb - pn.shape[0]))
            elif pn.shape[0] > nb:
                pn = pn[:nb]
            if packed is None:
                return tokens.at[:, 0].set(
                    jnp.where(use_prev, pn, tokens[:, 0]))
            # packed grid: slot s's decode singleton sits at lane
            # (seg_row[s], seg_off[s]); non-pending slots scatter out of
            # bounds (idx == R*T) so they can never collide with lane 0
            R, T = tokens.shape
            idx = jnp.where(use_prev,
                            packed.seg_row * T + packed.seg_off,
                            jnp.int32(R * T))
            flat = tokens.reshape(R * T)
            flat = flat.at[idx].set(jnp.where(use_prev, pn, 0), mode='drop')
            return flat.reshape(R, T)

        def step(weights, states, tokens, pos, key, temps, lane_valid,
                 prev_nxt=None, use_prev=None):
            params, precomputed = weights
            states = self._rep_in(states)
            sub = self._slice_states(states, lane_valid.shape[0])
            tokens = feed_prev(tokens, prev_nxt, use_prev)
            logits, sub, stats = model.decode_step(
                params, tokens, sub, pos, precomputed=precomputed,
                lane_valid=lane_valid, return_stats=True,
                attn_backend=backend)
            nxt = sample_tokens(logits[:, 0], key, temps)
            # NaN/Inf watchdog: per-lane finiteness of the sampled logits
            finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            states = self._merge_states(states, sub, lane_valid.shape[0])
            return self._shard_out(states), nxt, stats['moe_drops'], finite

        self._step = self._jit(step, donate_argnums=1)

        def step_logits(weights, states, tokens, pos, key, temps, lane_valid,
                        prev_nxt=None, use_prev=None):
            params, precomputed = weights
            states = self._rep_in(states)
            sub = self._slice_states(states, lane_valid.shape[0])
            tokens = feed_prev(tokens, prev_nxt, use_prev)
            logits, sub, stats = model.decode_step(
                params, tokens, sub, pos, precomputed=precomputed,
                lane_valid=lane_valid, return_stats=True,
                attn_backend=backend)
            nxt = sample_tokens(logits[:, 0], key, temps)
            finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            states = self._merge_states(states, sub, lane_valid.shape[0])
            return self._shard_out(states), nxt, stats['moe_drops'], \
                finite, logits                                   # (B,1,V)

        self._step_logits = self._jit(step_logits, donate_argnums=1)

        def chunk_hidden(weights, states, tokens, pos, n_valid, key, temps,
                         pt, rt, pending, prev_nxt, use_prev):
            params, precomputed = weights
            states = self._rep_in(states)
            sub = self._slice_states(states, n_valid.shape[0])
            tokens = feed_prev(tokens, prev_nxt, use_prev)
            h, sub, stats = model.decode_step(
                params, tokens, sub, pos, precomputed=precomputed,
                n_valid=n_valid, return_hidden=True,
                fused_gather_rope=self.fused_gather_rope,
                paged=paged_tables(pt, rt, pending), return_stats=True,
                attn_backend=backend)
            # head only on each slot's last valid lane, not all T lanes
            idx = jnp.maximum(n_valid - 1, 0)[:, None, None]
            h_last = jnp.take_along_axis(h, idx, axis=1)          # (B,1,d)
            logits = lm_logits(params, h_last, model.cfg)
            nxt = sample_tokens(logits[:, 0], key, temps)
            finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            states = self._merge_states(states, sub, n_valid.shape[0])
            return h, self._shard_out(states), nxt, \
                stats['moe_drops'], finite

        def chunk_step(weights, states, tokens, pos, n_valid, key, temps,
                       pt=None, rt=None, pending=None, prev_nxt=None,
                       use_prev=None):
            _, states, nxt, drops, finite = chunk_hidden(
                weights, states, tokens, pos, n_valid, key, temps, pt, rt,
                pending, prev_nxt, use_prev)
            return states, nxt, drops, finite

        def chunk_step_logits(weights, states, tokens, pos, n_valid, key,
                              temps, pt=None, rt=None, pending=None,
                              prev_nxt=None, use_prev=None):
            # logits-on-demand: same sampled-token program as chunk_step
            # (last-valid-lane head), plus the lm_head on EVERY lane for
            # prompt scoring — padding lanes (t >= n_valid) are garbage and
            # dropped host-side.
            h, states, nxt, drops, finite = chunk_hidden(
                weights, states, tokens, pos, n_valid, key, temps, pt, rt,
                pending, prev_nxt, use_prev)
            return states, nxt, drops, finite, \
                lm_logits(weights[0], h, model.cfg)

        # paged mode always runs the chunk-shaped program (its T == 1 case
        # is bit-identical to the single-token step), so a paged engine
        # needs the chunk jits even at chunk_size == 1
        want_chunk = self.chunk_size > 1 or self.paged
        self._chunk_step = self._jit(chunk_step, donate_argnums=1) \
            if want_chunk else None
        self._chunk_step_logits = \
            self._jit(chunk_step_logits, donate_argnums=1) \
            if want_chunk else None

        def packed_hidden(weights, states, tokens, pos, n_valid, packed, key,
                          temps, pt, rt, pending, prev_nxt, use_prev):
            # segment-packed prefill: tokens is the bin-packed (R, T) grid,
            # pos/n_valid/states stay slot-major (S,). Each slot's last
            # valid hidden lives at lane (seg_row, seg_off + n_valid - 1).
            params, precomputed = weights
            states = self._rep_in(states)
            sub = self._slice_states(states, n_valid.shape[0])
            tokens = feed_prev(tokens, prev_nxt, use_prev, packed)
            h, sub, stats = model.decode_step(
                params, tokens, sub, pos, precomputed=precomputed,
                n_valid=n_valid, return_hidden=True,
                fused_gather_rope=self.fused_gather_rope,
                paged=paged_tables(pt, rt, pending), packed=packed,
                return_stats=True, attn_backend=backend)
            R, T = tokens.shape
            flat = h.reshape((R * T,) + h.shape[2:])
            idx = packed.seg_row * T + packed.seg_off \
                + jnp.maximum(n_valid - 1, 0)
            h_last = flat[idx][:, None]                           # (S,1,d)
            logits = lm_logits(params, h_last, model.cfg)
            nxt = sample_tokens(logits[:, 0], key, temps)
            finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            states = self._merge_states(states, sub, n_valid.shape[0])
            return h, self._shard_out(states), nxt, \
                stats['moe_drops'], finite

        def packed_step(weights, states, tokens, pos, n_valid, packed, key,
                        temps, pt=None, rt=None, pending=None,
                        prev_nxt=None, use_prev=None):
            _, states, nxt, drops, finite = packed_hidden(
                weights, states, tokens, pos, n_valid, packed, key, temps,
                pt, rt, pending, prev_nxt, use_prev)
            return states, nxt, drops, finite

        def packed_step_logits(weights, states, tokens, pos, n_valid, packed,
                               key, temps, pt=None, rt=None, pending=None,
                               prev_nxt=None, use_prev=None):
            # packed scoring: the lm_head on every packed lane — slot s's
            # prompt logits live at row seg_row[s], cols seg_off[s]..+n_valid
            h, states, nxt, drops, finite = packed_hidden(
                weights, states, tokens, pos, n_valid, packed, key, temps,
                pt, rt, pending, prev_nxt, use_prev)
            return states, nxt, drops, finite, \
                lm_logits(weights[0], h, model.cfg)

        self._packed_step = self._jit(packed_step, donate_argnums=1) \
            if self.pack_prefill else None
        self._packed_step_logits = \
            self._jit(packed_step_logits, donate_argnums=1) \
            if self.pack_prefill else None

        mask = self._paged_mask

        def reset(states, fresh, slot):
            # stacked ('body') states carry the scan axis first -> batch is 1
            def one(path, leaf, fr, *m):
                if m and m[0]:
                    return leaf                    # page-pool leaf: shared
                axis = 1 if _is_body(path) else 0
                row = jax.lax.dynamic_index_in_dim(fr, slot, axis=axis,
                                                   keepdims=True)
                return jax.lax.dynamic_update_slice_in_dim(leaf, row, slot,
                                                           axis=axis)
            if mask is None:
                return self._shard_out(
                    jax.tree_util.tree_map_with_path(one, states, fresh))
            return self._shard_out(
                jax.tree_util.tree_map_with_path(one, states, fresh, mask))

        self._reset = jax.jit(reset, donate_argnums=0)

    def _build_page_ops(self) -> None:
        """Jitted page maintenance: clear-on-alloc, copy-on-write, and the
        per-boundary snapshot capture/restore for ring/recurrent state."""
        mask = self._paged_mask

        def clear(states, pages):
            # pages (K,) physical ids; OOB entries (== num_pages) dropped.
            # Restores freshly-allocated pages to the null state (zeros,
            # pos == -1) so stale contents from a previous owner can never
            # alias into a new slot's validity mask.
            def one(path, leaf, m):
                if not m:
                    return leaf
                val = -1 if _is_pos_leaf(path) else 0
                if _is_body(path):
                    return leaf.at[:, pages].set(val, mode='drop')
                return leaf.at[pages].set(val, mode='drop')
            return self._shard_out(
                jax.tree_util.tree_map_with_path(one, states, mask))

        self._clear_pages = jax.jit(clear, donate_argnums=0)

        def cow(states, src, dst, rem):
            # copy rows [0, rem) of page src into page dst; remaining rows
            # of dst get the null state — bitwise what a cold prefill of
            # those rem tokens would have left in a fresh page
            def one(path, leaf, m):
                if not m:
                    return leaf
                body = _is_body(path)
                axis = 1 if body else 0
                row = jax.lax.dynamic_index_in_dim(leaf, src, axis=axis,
                                                   keepdims=False)
                ps = row.shape[1 if body else 0]
                keep = jnp.arange(ps, dtype=jnp.int32) < rem
                keep = keep.reshape((1, ps) + (1,) * (row.ndim - 2)) if body \
                    else keep.reshape((ps,) + (1,) * (row.ndim - 1))
                fresh = -1 if _is_pos_leaf(path) else 0
                row = jnp.where(keep, row, jnp.asarray(fresh, row.dtype))
                if body:
                    return leaf.at[:, dst].set(row)
                return leaf.at[dst].set(row)
            return self._shard_out(
                jax.tree_util.tree_map_with_path(one, states, mask))

        def cow_pallas(states, src, dst, rem):
            # same contract as `cow`, as a page-to-page DMA kernel: each
            # pool leaf is one cow_page_copy dispatch (scan-stacked 'body'
            # leaves flatten their (reps, NP) leading axes and issue one
            # job per scan rep) instead of a gather + masked scatter pair
            def one(path, leaf, m):
                if not m:
                    return leaf
                fill = -1 if _is_pos_leaf(path) else 0
                if _is_body(path):
                    R, NP = leaf.shape[:2]
                    offs = jnp.arange(R, dtype=jnp.int32) * NP
                    sdr = jnp.stack(
                        [src + offs, dst + offs,
                         jnp.full((R,), rem, jnp.int32)], axis=1)
                    flat = leaf.reshape((R * NP,) + leaf.shape[2:])
                    return PM.cow_page_copy(flat, sdr,
                                            fill=fill).reshape(leaf.shape)
                sdr = jnp.stack([src, dst, rem]).astype(jnp.int32)[None]
                return PM.cow_page_copy(leaf, sdr, fill=fill)
            return jax.tree_util.tree_map_with_path(one, states, mask)

        self._cow_copy = jax.jit(cow_pallas if self._fused_maint else cow,
                                 donate_argnums=0)

        def capture(states, slot, ring_pages):
            # snapshot of everything a shared-page attach cannot restore:
            # per-slot state rows (recurrent / conv) + ring page contents
            def one(path, leaf, m):
                if m:
                    if _is_body(path):
                        return jnp.take(leaf, ring_pages, axis=1)
                    return jnp.take(leaf, ring_pages, axis=0)
                axis = 1 if _is_body(path) else 0
                return jax.lax.dynamic_index_in_dim(leaf, slot, axis=axis,
                                                    keepdims=False)
            return jax.tree_util.tree_map_with_path(one, states, mask)

        self._capture = jax.jit(capture)     # read-only: no donation

        def restore(states, snap, slot, ring_pages):
            def one(path, leaf, sn, m):
                if m:
                    if _is_body(path):
                        return leaf.at[:, ring_pages].set(sn, mode='drop')
                    return leaf.at[ring_pages].set(sn, mode='drop')
                axis = 1 if _is_body(path) else 0
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, jnp.expand_dims(sn, axis), slot, axis=axis)
            return self._shard_out(
                jax.tree_util.tree_map_with_path(one, states, snap, mask))

        self._restore = jax.jit(restore, donate_argnums=0)

    # ------------------------------------------------------------- plumbing
    def _validate(self, req: Request) -> Optional[str]:
        prompt = np.atleast_1d(np.asarray(req.prompt))
        if prompt.size == 0:
            return 'empty_prompt'
        if prompt.size + self._meta >= self.max_seq:
            return 'prompt_too_long'
        if req.max_new_tokens <= 0:
            return 'max_new_tokens_not_positive'
        return None

    def submit(self, req: Request) -> None:
        """Validate and enqueue one request.

        Malformed requests (empty prompt, prompt that cannot fit
        ``max_seq``, non-positive ``max_new_tokens``) are marked ``FAILED``
        immediately with ``error`` set — the engine keeps serving everything
        else. A uid that is already live (queued or in flight) raises
        ``ValueError``: uids are the cancel/dedup handle and must be unique
        among concurrent requests.
        """
        req.submit_t = time.monotonic()
        tel = self.telemetry
        err = self._validate(req)
        if err is not None:
            req.status = RequestStatus.FAILED
            req.error = err
            req.finish_t = req.submit_t
            self.n_failed += 1
            if tel.enabled:
                tel.event(req.uid, TM.EV_SUBMIT, t=req.submit_t,
                          prompt_len=len(req.prompt))
                tel.event(req.uid, TM.EV_FAIL, t=req.finish_t, error=err)
            return
        if req.uid in self._live_uids:
            raise ValueError(f'uid {req.uid} is already live in this engine '
                             '(queued or in flight); pick a fresh uid')
        self._live_uids.add(req.uid)
        req.status = RequestStatus.QUEUED
        self.queue.append(req)
        if tel.enabled:
            tel.event(req.uid, TM.EV_SUBMIT, t=req.submit_t,
                      prompt_len=len(req.prompt))

    def _next_internal_uid(self) -> int:
        """Engine-private uid for internally synthesized requests (scoring):
        drawn from a counter far below any plausible caller range, skipping
        anything currently live."""
        while True:
            self._internal_uid -= 1
            if self._internal_uid not in self._live_uids:
                return self._internal_uid

    def _terminate(self, req: Request, status: RequestStatus,
                   error: Optional[str] = None) -> None:
        """Move a request to a terminal status and update counters."""
        req.status = status
        req.error = error
        req.finish_t = time.monotonic()
        if status is RequestStatus.FINISHED:
            req.done = True
            # Engine-lifetime histograms back run()'s p50/p99 regardless of
            # telemetry mode — one observe per request lifetime.
            self._lat_hist.observe(req.finish_t - req.submit_t)
            if req.first_token_t is not None:
                self._ttft_hist.observe(req.first_token_t - req.submit_t)
        elif status is RequestStatus.FAILED:
            self.n_failed += 1
        elif status is RequestStatus.CANCELLED:
            self.n_cancelled += 1
        self._live_uids.discard(req.uid)
        tel = self.telemetry
        if tel.enabled:
            ev = {RequestStatus.FINISHED: TM.EV_FINISH,
                  RequestStatus.FAILED: TM.EV_FAIL,
                  RequestStatus.CANCELLED: TM.EV_CANCEL}[status]
            attrs = {'generated': len(req.generated)}
            if error is not None:
                attrs['error'] = error
            tel.event(req.uid, ev, t=req.finish_t, **attrs)

    def _vacate(self, slot: int) -> None:
        """Free one slot's scheduling state (and pages, in paged mode)."""
        self.slot_req[slot] = None
        self.slot_stream[slot] = None
        if self.paged:
            self._release_slot_pages(slot)

    def cancel(self, uid: int) -> bool:
        """Cancel a live request by uid, wherever it is — still queued, or
        in flight mid-prefill / mid-decode. Returns False if no live
        request has that uid (already terminal, or never submitted)."""
        for i, req in enumerate(self.queue):
            if req.uid == uid:
                self.queue.pop(i)
                self._terminate(req, RequestStatus.CANCELLED)
                return True
        for s in range(self.max_slots):
            req = self.slot_req[s]
            if req is not None and req.uid == uid:
                self._vacate(s)
                self._terminate(req, RequestStatus.CANCELLED)
                return True
        return False

    def _check_deadlines(self) -> None:
        """Fail any live request whose time budget has expired. Uses the
        monotonic clock: a wall-clock (``time.time``) step — NTP slew,
        manual reset, DST — must never spuriously expire (or immortalize)
        an in-flight request."""
        now = time.monotonic()

        def expired(req: Request) -> bool:
            return req.deadline_s is not None \
                and now - req.submit_t > req.deadline_s

        for s in range(self.max_slots):
            req = self.slot_req[s]
            if req is not None and expired(req):
                self._vacate(s)
                self.n_deadline += 1
                self._terminate(req, RequestStatus.FAILED,
                                'deadline_exceeded')
        if any(expired(r) for r in self.queue):
            keep = []
            for req in self.queue:
                if expired(req):
                    self.n_deadline += 1
                    self._terminate(req, RequestStatus.FAILED,
                                    'deadline_exceeded')
                else:
                    keep.append(req)
            self.queue = keep

    def _reset_slot(self, slot: int) -> None:
        """Restore one slot's state (KV cache validity, recurrent/conv state,
        primed meta prefix) from the fresh template — no cross-request
        leakage on slot reuse. One jit'd indexed copy per leaf; O(slot) work
        instead of flattening/rebuilding the whole state tree host-side.
        In paged mode only per-slot leaves reset; pages are cleared on
        allocation instead.
        """
        self.states = self._reset(self.states, self._fresh,
                                  jnp.int32(slot))

    # ------------------------------------------------------------ paged ops
    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        if n == 0:
            return []
        pages = self.kv.alloc(n)
        if pages is None:
            return None
        if self._fused_maint:
            # clear-on-alloc is deferred: the ids ride into the next fused
            # dispatch as PageTables.pending, where the maintenance kernel
            # folds the clear into first-write masking (or a mode-2 clear
            # job) — no standalone XLA clear dispatch on the hot path
            self._pending_clear.extend(pages)
            if len(self._pending_clear) > self._pending_cap:
                self._flush_pending()       # overflow: rare, eager is fine
        else:
            ids = jnp.asarray(np.asarray(pages, np.int32))
            self.states = self._clear_pages(self.states, ids)
        return pages

    def _flush_pending(self) -> None:
        """Eagerly clear deferred pages. Needed whenever raw page contents
        are read outside the fused kernels (snapshot capture) or the
        pending list outgrows the fixed-width array the kernels take."""
        if not self._pending_clear:
            return
        ids = jnp.asarray(np.asarray(self._pending_clear, np.int32))
        self.states = self._clear_pages(self.states, ids)
        self._pending_clear = []

    def _pending_array(self) -> Optional[jax.Array]:
        """Deferred-clear page ids as the fixed-width (cap,) int32 array the
        fused maintenance kernels consume (zero-padded; page 0 is the null
        page, so padding entries decay to idempotent null-page rewrites).
        None when maintenance is not fused — the jitted programs then build
        PageTables without a pending leaf and nothing defers."""
        if not self._fused_maint:
            return None
        arr = np.zeros(self._pending_cap, np.int32)
        ids = self._pending_clear[:self._pending_cap]
        arr[:len(ids)] = ids
        return jnp.asarray(arr)

    def _release_slot_pages(self, slot: int) -> None:
        if self.slot_node[slot] is not None:
            self.kv.release(self.slot_node[slot])
            self.slot_node[slot] = None
        if self.slot_priv[slot]:
            self.kv.free(self.slot_priv[slot])
            self.slot_priv[slot] = []
        if self.slot_ring[slot]:
            self.kv.free(self.slot_ring[slot])
            self.slot_ring[slot] = []
        self._pt[slot] = 0
        self._rt[slot] = 0
        self.slot_nblocks[slot] = 0
        self.slot_insert_at[slot] = -1

    def _admit_paged(self, slot: int, req: Request,
                     stream: np.ndarray) -> bool:
        """Prefix lookup + page attach for one admission. ``stream`` is the
        token stream to serve — the prompt, or prompt + generated-so-far
        for a resumed (preempted) request, whose published pages make the
        resume a prefix hit. Returns False if the pool cannot currently
        host the request (it goes back to the queue)."""
        ps = self.page_size
        prompt = stream
        P = len(prompt)
        node, nblocks, pages = None, 0, []
        if not req.return_logits and P > 1:
            if self.telemetry.enabled:
                with self.telemetry.span(TM.SPAN_PHASE['radix_lookup']):
                    _r0 = self.telemetry.now()
                    res = self.kv.match(prompt, max_tokens=P - 1,
                                        need_snapshot=self._needs_snapshot)
                    self._t_radix += self.telemetry.now() - _r0
            else:
                res = self.kv.match(prompt, max_tokens=P - 1,
                                    need_snapshot=self._needs_snapshot)
            node, nblocks, pages = res.node, res.n_blocks, res.pages
        # pin the match before any allocation can trigger eviction
        self.kv.attach(node)
        ring = self._alloc_pages(self._pages_ring)
        if ring is None:
            self.kv.release(node)
            return False
        eff = nblocks * ps
        cow_page = None
        if not self._needs_snapshot and not req.return_logits:
            # copy-on-write: reuse the head of a cached block this prompt
            # stops short inside (or diverges from past its shared rows)
            tail_len = min(P - 1 - eff, ps - 1)
            if tail_len > 0:
                alloc = self._alloc_pages(1)
                if alloc is None:
                    self.kv.release(node)
                    self.kv.free(ring)
                    return False
                src = self.kv.find_extension(node, prompt[eff:eff + tail_len])
                if src >= 0:
                    self.states = self._cow_copy(
                        self.states, jnp.int32(src), jnp.int32(alloc[0]),
                        jnp.int32(tail_len))
                    cow_page = alloc[0]
                    if self.telemetry.enabled:
                        self._cow_counter.inc()
                        self.telemetry.event(
                            req.uid, TM.EV_COW, src_page=int(src),
                            dst_page=int(alloc[0]), rows=int(tail_len))
                    eff += tail_len
                    if self._fused_maint and alloc[0] in self._pending_clear:
                        # the COW kernel just wrote dst in full (copied
                        # head + null tail); a later deferred clear would
                        # destroy it
                        self._pending_clear.remove(alloc[0])
                else:
                    self.kv.free(alloc)
        self._reset_slot(slot)
        self.slot_ring[slot] = ring
        self._rt[slot, :len(ring)] = ring
        row = list(pages) + ([cow_page] if cow_page is not None else [])
        self._pt[slot, :len(row)] = row
        self.slot_nblocks[slot] = len(row)
        self.slot_node[slot] = node
        self.slot_priv[slot] = [cow_page] if cow_page is not None else []
        if eff:
            self.kv.hits += 1
            self.kv.hit_tokens += eff
            req.prefix_hit_tokens = eff
        elif not req.return_logits:
            self.kv.misses += 1
        if self._needs_snapshot and node is not None:
            ring_ids = jnp.asarray(np.asarray(
                ring if ring else [self.num_pages], np.int32))
            self.states = self._restore(self.states, node.snapshot,
                                        jnp.int32(slot), ring_ids)
            if self._fused_maint:
                # restored ring pages carry live snapshot content now —
                # drop their deferred clears
                keep = set(ring)
                self._pending_clear = [p for p in self._pending_clear
                                       if p not in keep]
        # where to publish this prompt's prefix
        if req.return_logits:
            self.slot_insert_at[slot] = -1
        elif self._needs_snapshot:
            target = ((P - 1) // ps) * ps
            self.slot_insert_at[slot] = target if target > eff else -1
        else:
            self.slot_insert_at[slot] = P if P // ps > nblocks else -1
        self.slot_pos[slot] = eff
        self.slot_next_tok[slot] = int(prompt[eff])
        return True

    # ---------------------------------------------------------- preemption
    def _pick_victim(self, exclude=(),
                     protect_oldest: bool = True) -> Optional[int]:
        """Preemption victim policy: fewest decoded tokens first (cheapest
        work to redo), ties broken LIFO (most recently admitted). Scoring
        slots are never victims — their host-side logit chunks could not
        survive a requeue-and-resume.

        With ``protect_oldest`` (the default) the longest-admitted in-flight
        request is also immune. That guarantees global forward progress: two
        requests that cannot coexist in the pool would otherwise preempt
        each other forever — fatal for snapshot archs (ring/recurrent),
        whose mid-page progress cannot be published and is lost on every
        preemption. Admission escalation may drop the protection as a last
        resort (a lone never-terminating decoder must stay preemptible)."""
        protected = None
        if protect_oldest:
            live = [(int(self.slot_admit_seq[s]), s)
                    for s in range(self.max_slots)
                    if self.slot_req[s] is not None]
            if live:
                protected = min(live)[1]
        best = None
        for s in range(self.max_slots):
            req = self.slot_req[s]
            if req is None or s in exclude or s == protected \
                    or req.return_logits:
                continue
            key = (len(req.generated), -int(self.slot_admit_seq[s]))
            if best is None or key < best[0]:
                best = (key, s)
        return None if best is None else best[1]

    def _publish_preempted(self, slot: int) -> None:
        """Publish a preempted slot's fully-written pages into the radix
        index, so its resume is a prefix hit that recomputes only the
        uncached tail. Blocks may cover generated tokens too — radix keys
        are token values, and identical tokens at identical positions give
        bitwise-identical pages."""
        req = self.slot_req[slot]
        if req.return_logits:
            return                          # scoring resumes cold anyway
        ps = self.page_size
        pos = int(self.slot_pos[slot])
        n_blocks = pos // ps
        if n_blocks <= 0:
            return
        # the admit-time stream does not grow during decode — rebuild the
        # full written token stream (prompt + everything generated)
        stream = np.atleast_1d(np.asarray(req.prompt))
        if req.generated:
            stream = np.concatenate(
                [stream, np.asarray(req.generated, stream.dtype)])
        snap = None
        if self._needs_snapshot:
            # ring/recurrent state can only resume from a snapshot taken
            # exactly at a block boundary; mid-page positions can't publish
            if pos != n_blocks * ps:
                return
            ring_ids = jnp.asarray(np.asarray(
                self.slot_ring[slot] if self.slot_ring[slot]
                else [self.num_pages], np.int32))
            if self._fused_maint:
                self._flush_pending()   # capture reads raw page contents
            snap = self._capture(self.states, jnp.int32(slot), ring_ids)
        node, transferred = self.kv.insert(
            stream, n_blocks, list(self._pt[slot, :n_blocks]), snapshot=snap)
        moved = set(transferred)
        self.slot_priv[slot] = [p for p in self.slot_priv[slot]
                                if p not in moved]
        self.kv.attach(node)
        self.kv.release(self.slot_node[slot])
        self.slot_node[slot] = node

    def _preempt_slot(self, slot: int, hold: bool = False) -> None:
        """Evict one in-flight request from its slot and requeue it at the
        front. In paged mode its finished pages are published first, so the
        resume attaches them (prefix hit) and recomputes only the tail —
        greedy tokens across preempt/resume stay bitwise identical to an
        uninterrupted run (chunked prefill == token-by-token contract).

        ``hold`` delays re-admission by ``admit_retry_steps`` dispatches —
        used when a slot yields to pool contention, so the surviving
        (protected) request gets room to run instead of thrashing."""
        req = self.slot_req[slot]
        if self.telemetry.enabled:
            self.telemetry.event(
                req.uid, TM.EV_PREEMPT, slot=slot,
                pos=int(self.slot_pos[slot]),
                generated=len(req.generated), hold=bool(hold))
        if self.paged:
            self._publish_preempted(slot)
        self._vacate(slot)
        req.status = RequestStatus.PREEMPTED
        req.preemptions += 1
        self.preemptions += 1
        req._logit_chunks = []              # scoring resumes from position 0
        if hold:
            req._hold_until = self.ticks + self._admit_retry_steps
        self.queue.insert(0, req)

    def _ensure_blocks(self, slot: int, end_pos: int) -> bool:
        """On-demand linear-page allocation up to position ``end_pos``.

        Pool exhaustion (nothing evictable) is no longer an engine crash:
        it preempts a victim slot to free pages, falls back to preempting
        ``slot`` itself, and — if repeated self-preemption makes no forward
        progress — fails the request as ``unschedulable``. Returns False
        iff ``slot`` no longer holds its request (preempted or failed)."""
        need = -(-end_pos // self.page_size)
        while self.slot_nblocks[slot] < need:
            alloc = self._alloc_pages(1)
            if alloc is not None:
                nb = int(self.slot_nblocks[slot])
                self._pt[slot, nb] = alloc[0]
                self.slot_priv[slot].append(alloc[0])
                self.slot_nblocks[slot] = nb + 1
                continue
            victim = self._pick_victim(exclude=(slot,))
            if victim is not None:
                self._preempt_slot(victim)
                continue
            if any(self.slot_req[s] is not None
                   for s in range(self.max_slots) if s != slot):
                # others are in flight but untouchable (protected oldest /
                # scoring): yield to them with an admission hold — they will
                # free pages by finishing; this is contention, not a dead
                # pool, so it never counts toward the stuck escalation
                self._preempt_slot(slot, hold=True)
                return False
            # alone in the engine: preempt ourselves unless we're making no
            # progress between self-preemptions (pool truly cannot host us)
            req = self.slot_req[slot]
            pos = int(self.slot_pos[slot])
            if pos <= req._stuck_pos:
                req._stuck += 1
            else:
                req._stuck_pos, req._stuck = pos, 0
            if req._stuck >= 2:
                self._vacate(slot)
                self._terminate(req, RequestStatus.FAILED, 'unschedulable')
            else:
                self._preempt_slot(slot)
            return False
        return True

    def _maybe_insert(self, slot: int, p_before: int, p_after: int) -> None:
        """Publish a prefilled prompt's full pages into the radix index."""
        target = int(self.slot_insert_at[slot])
        if target < 0:
            return
        ps = self.page_size
        prompt = self.slot_stream[slot]
        P = len(prompt)
        if self._needs_snapshot:
            if p_after != target:
                return
            n_blocks = target // ps
            ring_ids = jnp.asarray(np.asarray(
                self.slot_ring[slot] if self.slot_ring[slot]
                else [self.num_pages], np.int32))
            if self._fused_maint:
                self._flush_pending()   # capture reads raw page contents
            snap = self._capture(self.states, jnp.int32(slot), ring_ids)
        else:
            if not (p_before < P <= p_after):
                return
            n_blocks = P // ps
            snap = None
        node, transferred = self.kv.insert(prompt, n_blocks,
                                           list(self._pt[slot, :n_blocks]),
                                           snapshot=snap)
        moved = set(transferred)
        self.slot_priv[slot] = [p for p in self.slot_priv[slot]
                                if p not in moved]
        self.kv.attach(node)
        self.kv.release(self.slot_node[slot])
        self.slot_node[slot] = node
        self.slot_insert_at[slot] = -1

    def _admit(self) -> None:
        for slot in range(self.max_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue[0]
                if req._hold_until > self.ticks and any(
                        r is not None for r in self.slot_req):
                    return      # yielding to in-flight work; retry later
                req = self.queue.pop(0)
                req._hold_until = 0
                stream = np.atleast_1d(np.asarray(req.prompt))
                if req.generated:       # resuming a preempted request
                    stream = np.concatenate(
                        [stream, np.asarray(req.generated, stream.dtype)])
                if self.paged:
                    if not self._admit_with_retry(slot, req, stream):
                        return          # queue head parked (or failed)
                else:
                    self.slot_req[slot] = req
                    self.slot_pos[slot] = self._meta  # tokens follow meta
                    self.slot_next_tok[slot] = int(stream[0])
                    self._reset_slot(slot)
                if self.slot_req[slot] is not req:
                    continue            # admission failed terminally
                self.slot_stream[slot] = stream
                self.slot_admit_seq[slot] = self._admit_seq
                self._admit_seq += 1
                req.status = RequestStatus.PREFILLING
                req._admit_fails = 0
                if self.telemetry.enabled:
                    self.telemetry.event(
                        req.uid,
                        TM.EV_RESUME if req.preemptions else TM.EV_ADMIT,
                        slot=slot,
                        prefix_hit_tokens=int(req.prefix_hit_tokens))

    def _admit_with_retry(self, slot: int, req: Request,
                          stream: np.ndarray) -> bool:
        """Paged admission with the bounded-retry → preempt →
        FAILED('unschedulable') escalation (replaces the old heuristic that
        only detected permanent starvation when *zero* slots were in
        flight). Returns False when admission should stop for this step —
        the queue head is parked for retry, or was failed terminally (in
        which case ``slot_req[slot]`` stays None and the caller skips it).
        """
        while True:
            if self._admit_paged(slot, req, stream):
                self.slot_req[slot] = req
                return True
            req._admit_fails += 1
            if req._admit_fails <= self._admit_retry_steps:
                self.queue.insert(0, req)     # pool full: retry next step
                return False
            # bounded retries exhausted: preempt a victim to make room —
            # last resort drops oldest-protection, else a lone
            # never-terminating decoder starves the queue forever
            victim = self._pick_victim()
            if victim is None:
                victim = self._pick_victim(protect_oldest=False)
            if victim is not None:
                self._preempt_slot(victim)
                req._admit_fails = 0
                continue
            if any(r is not None for r in self.slot_req):
                self.queue.insert(0, req)     # only scoring slots in flight
                return False
            # nothing in flight will ever free pages, eviction already ran
            # dry inside alloc, and the bounded retries gave any external
            # page squeeze time to lift: unschedulable, per-request
            self._terminate(req, RequestStatus.FAILED, 'unschedulable')
            return False

    # ----------------------------------------------------------------- run
    def _progress(self, slot: int) -> int:
        """Index of the next prompt token this slot will consume."""
        return int(self.slot_pos[slot]) - self._meta

    def _pack_layout(self, tokens: np.ndarray, n_valid: np.ndarray):
        """Bin-pack this step's per-slot segments into a compact (R, T)
        grid: first-fit decreasing over the active slots (each contributes
        ONE contiguous segment of ``n_valid[s]`` lanes, never split across
        rows). R is rounded up to the next power of two (bounded jit
        retraces: at most log2(max_slots)+1 packed grid shapes) and capped
        at ``max_slots`` — the worst case packs exactly like the unpacked
        grid. Returns ``(ptoks, layout, seg_row, seg_off)``; the numpy
        ``seg_row``/``seg_off`` locate slot ``s``'s scoring logits at
        ``logits[seg_row[s], seg_off[s] : seg_off[s] + n_valid[s]]``.
        """
        S, T = tokens.shape
        order = sorted((s for s in range(S) if n_valid[s] > 0),
                       key=lambda s: (-int(n_valid[s]), s))
        seg_row = np.zeros(S, np.int32)
        seg_off = np.zeros(S, np.int32)
        space: List[int] = []              # free lanes per packed row
        for s in order:
            ln = int(n_valid[s])
            for r, free in enumerate(space):
                if free >= ln:
                    seg_row[s], seg_off[s] = r, T - free
                    space[r] = free - ln
                    break
            else:
                seg_row[s], seg_off[s] = len(space), 0
                space.append(T - ln)
        R = 1
        while R < max(1, len(space)):
            R *= 2
        R = min(R, S)
        ptoks = np.zeros((R, T), np.int32)
        lane_slot = np.zeros((R, T), np.int32)
        lane_local = np.zeros((R, T), np.int32)
        lane_pos = np.zeros((R, T), np.int32)
        lane_valid = np.zeros((R, T), bool)
        for s in order:
            ln = int(n_valid[s])
            r, o = int(seg_row[s]), int(seg_off[s])
            ptoks[r, o:o + ln] = tokens[s, :ln]
            lane_slot[r, o:o + ln] = s
            lane_local[r, o:o + ln] = np.arange(ln)
            lane_pos[r, o:o + ln] = int(self.slot_pos[s]) + np.arange(ln)
            lane_valid[r, o:o + ln] = True
        layout = A.PackedLayout(
            seg_row=jnp.asarray(seg_row), seg_off=jnp.asarray(seg_off),
            lane_slot=jnp.asarray(lane_slot),
            lane_local=jnp.asarray(lane_local),
            lane_pos=jnp.asarray(lane_pos),
            lane_valid=jnp.asarray(lane_valid))
        return ptoks, layout, seg_row, seg_off

    def step_once(self) -> None:
        """One engine tick. Synchronous mode dispatches and commits in the
        same tick (the historical behavior, value-identical). Async mode
        (``async_loop=True``) dispatches tick N's work, then commits tick
        N-1's pending dispatch — the one-step-deep pipeline documented in
        the module docstring."""
        self.ticks += 1
        tel = self.telemetry
        obs = tel.enabled
        if obs:
            self._t_radix = 0.0
            step_span = tel.span(TM.SPAN_STEP, step=self.steps)
            step_span.__enter__()
            self._phase_to('host_schedule')
        _t0 = tel.now() if obs else 0.0
        if self.fault_injector is not None:
            self.fault_injector.before_step(self)
        self._check_deadlines()
        if self._pending is not None and self._pending.needs_sync:
            # a pending lane landed exactly on a snapshot boundary: its
            # commit captures device state, so it must land before the
            # slot's next chunk is dispatched
            self._flush_async()
        self._admit()
        rec = self._schedule_dispatch(_t0, obs)
        if obs:
            self._phase_to(None)    # host_schedule, if nothing was dispatched
        if self.async_loop:
            prev, self._pending = self._pending, rec
            if prev is not None:
                self._commit(prev)
        elif rec is not None:
            self._commit(rec)
        if obs:
            step_span.__exit__(None, None, None)

    def _phase_to(self, phase: Optional[str], **attrs) -> None:
        """Close the open phase's profiler span; open ``phase``'s."""
        if self._phase_span is not None:
            self._phase_span.__exit__(None, None, None)
            self._phase_span = None
        if phase is not None:
            self._phase_span = self.telemetry.span(TM.SPAN_PHASE[phase],
                                                   **attrs)
            self._phase_span.__enter__()

    def _walk_attr(self, kind: str, T: int, nb: int) -> Dict[str, float]:
        """Paged engines: the share of the linear page table that this
        step's ``paged_attention`` walk visits — each slot's blocks up to
        the last one its T lanes from ``slot_pos`` can attend, over
        ``nb * P`` — observed into the ``engine.step.walk`` histogram of
        ``kind`` and returned as the dispatch span's ``walk`` attribute."""
        if not self.paged:
            return {}
        P = self._pt.shape[1]
        last = np.minimum((self.slot_pos[:nb] + T - 1) // self.page_size,
                          P - 1)
        walk = float((last + 1).sum()) / (nb * P)
        self._walk_h[kind].observe(walk)
        return {'walk': walk}

    def _flush_async(self) -> None:
        """Drain the async pipeline: commit the pending dispatch (if any).
        Re-entrancy-safe — the pending slot is cleared before committing."""
        rec, self._pending = self._pending, None
        if rec is not None:
            self._commit(rec)

    def _schedule_dispatch(self, _t0: float,
                           obs: bool) -> Optional[_PendingStep]:
        """Build, stage and dispatch one step's lanes; advance host-side
        scheduling state (slot positions); return the commit record.
        Returns None when nothing was dispatched. Does NOT transfer any
        device value to the host."""
        tel = self.telemetry
        active = [s for s in range(self.max_slots)
                  if self.slot_req[s] is not None]
        pend = self._pending
        use_prev = None
        if pend is not None:
            # One dispatch is in flight. Decoding slots whose pending lane
            # samples a token get it spliced in on device (use_prev);
            # deterministic terminations (max_new_tokens / max_seq) are
            # predictable one step ahead, so the doomed slot is simply not
            # scheduled — EOS / watchdog terminations dispatch one
            # speculative lane whose commit record is later discarded.
            use_prev = np.zeros(self.max_slots, bool)
            skip = set()
            for ln in pend.lanes:
                s = ln.slot
                if self.slot_req[s] is not ln.req \
                        or int(self.slot_admit_seq[s]) != ln.admit_seq \
                        or not ln.gen:
                    continue
                use_prev[s] = True
                if len(ln.req.generated) + 1 >= ln.req.max_new_tokens \
                        or int(self.slot_pos[s]) + 1 >= self.max_seq:
                    skip.add(s)
            if skip:
                active = [s for s in active if s not in skip]
                for s in skip:
                    use_prev[s] = False
        if not active:
            return None
        step_idx = self.steps
        prefilling = self.chunk_size > 1 and any(
            len(self.slot_stream[s]) - self._progress(s) > 1
            for s in active)
        # logits-on-demand: any scoring request still consuming its prompt
        # switches this step to the (separately compiled) logits-returning
        # program; steps without scoring work keep the narrow fast path.
        want_logits = any(
            self.slot_req[s].return_logits
            and self._progress(s) < len(self.slot_stream[s])
            for s in active)
        self.key, sub = jax.random.split(self.key)

        logits = None
        pk_row = pk_off = None
        if prefilling or self.paged:
            # paged mode always runs the chunk-shaped program: its T == 1
            # case is bit-identical to the single-token step, and the page
            # scatter/gather needs the n_valid lane masking anyway
            T = self.chunk_size if prefilling else 1
            tokens = np.zeros((self.max_slots, T), np.int32)
            n_valid = np.zeros(self.max_slots, np.int32)
            for s in active:
                req = self.slot_req[s]
                if req is None:
                    continue      # preempted by an earlier slot's _ensure
                stream = self.slot_stream[s]
                p = self._progress(s)
                if p < len(stream):                  # prefilling slot
                    take = min(T, len(stream) - p)
                    if self.paged and self._needs_snapshot \
                            and p < self.slot_insert_at[s]:
                        # land exactly on the snapshot boundary so the
                        # captured state is the state after `target` tokens
                        take = min(take, int(self.slot_insert_at[s]) - p)
                else:                                # decoding slot: 1 token
                    take = 1
                if self.paged and not self._ensure_blocks(
                        s, int(self.slot_pos[s]) + take):
                    continue      # slot preempted/failed: lane stays empty
                if p < len(stream):
                    tokens[s, :take] = stream[p:p + take]
                else:
                    tokens[s, 0] = self.slot_next_tok[s]
                n_valid[s] = take
            # a preemption above may have vacated an already-scheduled lane
            for s in range(self.max_slots):
                if self.slot_req[s] is None and n_valid[s]:
                    tokens[s] = 0
                    n_valid[s] = 0
            if use_prev is not None:
                # preemptions above may have vacated pending-token slots
                for s in range(self.max_slots):
                    if self.slot_req[s] is None:
                        use_prev[s] = False
            active = [s for s in active
                      if self.slot_req[s] is not None and n_valid[s] > 0]
            if not active:
                return None       # everything was preempted this step
            # _ensure_blocks may have preempted the slots that justified the
            # expensive program choices above — recompute from the surviving
            # lanes: a step whose only scoring slot was preempted must NOT
            # run the logits-returning program, and a step whose prefill
            # slots were all preempted narrows back to the T == 1 grid
            # (bit-identical: the chunk path's T == 1 case IS the decode
            # step, and every surviving lane has n_valid == 1).
            want_logits = any(
                self.slot_req[s].return_logits
                and self._progress(s) < len(self.slot_stream[s])
                for s in active)
            if prefilling and max(int(n_valid[s]) for s in active) <= 1:
                prefilling = False
                tokens = tokens[:, :1]
            if obs:
                n_pre = 0
                for s in active:
                    p = self._progress(s)
                    if p < len(self.slot_stream[s]):
                        n_pre += 1
                        tel.event(self.slot_req[s].uid, TM.EV_PREFILL_CHUNK,
                                  step=step_idx, pos=p, n=int(n_valid[s]))
                kind = ('mixed' if 0 < n_pre < len(active)
                        else ('prefill' if n_pre else 'decode'))
                _t1 = tel.now()
                self._phase_to('pack_layout')
            nb = self._bucket(active)
            tokens, n_valid = tokens[:nb], n_valid[:nb]
            temps = jnp.asarray([
                (self.slot_req[s].temperature if self.slot_req[s] else 0.0)
                for s in range(nb)], jnp.float32)
            pos = jnp.asarray(self.slot_pos[:nb].astype(np.int32))
            kw = {}
            if pend is not None:
                kw = dict(prev_nxt=pend.nxt,
                          use_prev=jnp.asarray(use_prev[:nb]))
            if self.pack_prefill and prefilling:
                ptoks, playout, pk_row, pk_off = \
                    self._pack_layout(tokens, n_valid)
                self.lanes_dispatched += int(ptoks.size)
                self.lane_tokens += int(n_valid.sum())
                args = [self._weights, self.states, jnp.asarray(ptoks), pos,
                        jnp.asarray(n_valid), playout, sub, temps]
                if self.paged:
                    args += [jnp.asarray(self._pt[:nb]),
                             jnp.asarray(self._rt[:nb]),
                             self._pending_array()]
                if obs:
                    T_ = int(ptoks.shape[1])
                    walk = self._walk_attr(kind, T_, nb)
                    _t2 = tel.now()
                    self._phase_to('dispatch', step=step_idx, kind=kind,
                                   T=T_, nb=nb, **walk)
                if want_logits:
                    self.states, nxt, drops, finite, logits = \
                        self._packed_step_logits(*args, **kw)
                else:
                    self.states, nxt, drops, finite = \
                        self._packed_step(*args, **kw)
                self._pending_clear = []
            else:
                self.lanes_dispatched += int(tokens.size)
                self.lane_tokens += int(n_valid.sum())
                args = [self._weights, self.states, jnp.asarray(tokens), pos,
                        jnp.asarray(n_valid), sub, temps]
                if self.paged:
                    args += [jnp.asarray(self._pt[:nb]),
                             jnp.asarray(self._rt[:nb]),
                             self._pending_array()]
                if obs:
                    T_ = int(tokens.shape[1])
                    walk = self._walk_attr(kind, T_, nb)
                    _t2 = tel.now()
                    self._phase_to('dispatch', step=step_idx, kind=kind,
                                   T=T_, nb=nb, **walk)
                if want_logits:
                    self.states, nxt, drops, finite, logits = \
                        self._chunk_step_logits(*args, **kw)
                else:
                    self.states, nxt, drops, finite = \
                        self._chunk_step(*args, **kw)
                self._pending_clear = []
            consumed = n_valid
        else:
            if obs:
                n_pre = 0
                for s in active:
                    p = self._progress(s)
                    if p < len(self.slot_stream[s]):
                        n_pre += 1
                        tel.event(self.slot_req[s].uid, TM.EV_PREFILL_CHUNK,
                                  step=step_idx, pos=p, n=1)
                kind = ('mixed' if 0 < n_pre < len(active)
                        else ('prefill' if n_pre else 'decode'))
                _t1 = tel.now()
                self._phase_to('pack_layout')
            nb = self._bucket(active)
            temps = jnp.asarray([
                (self.slot_req[s].temperature if self.slot_req[s] else 0.0)
                for s in range(nb)], jnp.float32)
            pos = jnp.asarray(self.slot_pos[:nb].astype(np.int32))
            tokens = jnp.asarray(self.slot_next_tok[:nb, None])
            lv = np.zeros(nb, bool)
            lv[active] = True
            lane_valid = jnp.asarray(lv)
            args = (self._weights, self.states, tokens, pos, sub, temps,
                    lane_valid)
            kw = {}
            if pend is not None:
                kw = dict(prev_nxt=pend.nxt,
                          use_prev=jnp.asarray(use_prev[:nb]))
            if obs:
                _t2 = tel.now()
                self._phase_to('dispatch', step=step_idx, kind=kind, T=1,
                               nb=nb)
            if want_logits:
                self.states, nxt, drops, finite, logits = \
                    self._step_logits(*args, **kw)
            else:
                self.states, nxt, drops, finite = self._step(*args, **kw)
            n_valid = None
            consumed = np.ones(self.max_slots, np.int32)

        if obs:
            _t3 = tel.now()
            self._phase_to(None)
        # advance host scheduling state NOW (dispatch time): async mode
        # schedules the next step from these positions before the commit
        # lands. Everything the deferred commit needs is recorded per lane.
        lanes: List[_Lane] = []
        needs_sync = False
        for s in active:
            req = self.slot_req[s]
            c = int(consumed[s])
            stream = self.slot_stream[s]
            p_before = self._progress(s)
            self.slot_pos[s] += c
            p_after = self._progress(s)
            lanes.append(_Lane(
                slot=s, req=req, admit_seq=int(self.slot_admit_seq[s]),
                consumed=c, p_before=p_before, p_after=p_after,
                pos_after=int(self.slot_pos[s]),
                gen=p_after >= len(stream)))
            if self.paged and self._needs_snapshot \
                    and int(self.slot_insert_at[s]) >= 0 \
                    and p_after == int(self.slot_insert_at[s]):
                needs_sync = True
        self.steps += 1
        times = None
        if obs:
            times = (max(0.0, _t1 - _t0 - self._t_radix), self._t_radix,
                     _t2 - _t1, _t3 - _t2)
            if self._overlap_h is not None and pend is not None:
                # host scheduling work performed while the previous
                # dispatch was still uncommitted — the double-buffering win
                self._overlap_h.observe(max(0.0, _t2 - _t0))
        return _PendingStep(
            nxt=nxt, finite=finite, drops=drops, logits=logits,
            lanes=lanes, pk_row=pk_row, pk_off=pk_off, nb=nb,
            step_idx=step_idx, kind=kind if obs else None, times=times,
            needs_sync=needs_sync)

    def _commit(self, rec: _PendingStep) -> None:
        """Commit one dispatched step: the ``np.asarray`` device wait,
        per-lane token/logit commit, radix publishes and terminations.
        Stale lanes — the slot was vacated (cancel, deadline, preemption,
        EOS misprediction) or re-admitted while the dispatch was in
        flight — are discarded by the (request identity, admit_seq)
        guard; their device work is wasted but harmless (masked lanes /
        pages freed after the in-order device writes)."""
        tel = self.telemetry
        obs = tel.enabled and rec.kind is not None
        if obs:
            commit_span = tel.span(TM.SPAN_PHASE['sample_commit'],
                                   step=rec.step_idx)
            commit_span.__enter__()
        _t3 = tel.now() if obs else 0.0
        with (tel.span(TM.SPAN_DEVICE_WAIT, step=rec.step_idx) if obs
              else _NO_SPAN):
            nxt = np.asarray(rec.nxt)
            bad = ~np.asarray(rec.finite)
            drops = int(rec.drops)
            logits = None if rec.logits is None else np.asarray(rec.logits)
        if self.fault_injector is not None:
            for s in self.fault_injector.poison_lanes(self, rec.step_idx):
                if 0 <= s < len(bad):
                    bad[s] = True
        self.moe_token_drops += drops
        for ln in rec.lanes:
            s, req = ln.slot, ln.req
            if self.slot_req[s] is not req \
                    or int(self.slot_admit_seq[s]) != ln.admit_seq:
                continue                 # stale speculative lane: discard
            if bad[s]:
                # NaN/Inf watchdog: fail only the offending lane — its
                # cache rows are garbage, but they free with the slot
                self._vacate(s)
                self._terminate(req, RequestStatus.FAILED,
                                'nonfinite_logits')
                continue
            stream = self.slot_stream[s]
            if self.paged:
                self._maybe_insert(s, ln.p_before, ln.p_after)
            if req.return_logits and ln.p_before < len(stream):
                # lanes 0..consumed-1 hold logits for
                # stream[p_before..p_after-1]; copy so the slice doesn't
                # pin the whole step's (B,T,V) array in memory for the
                # rest of the prefill. In a packed dispatch the slot's
                # lanes sit at (pk_row[s], pk_off[s]..).
                if rec.pk_row is not None:
                    row, off = int(rec.pk_row[s]), int(rec.pk_off[s])
                    req._logit_chunks.append(
                        logits[row, off:off + ln.consumed].copy())
                else:
                    req._logit_chunks.append(
                        logits[s, :ln.consumed].copy())
                if ln.p_after >= len(stream):
                    req.prompt_logits = np.concatenate(req._logit_chunks, 0)
                    req._logit_chunks = []
            if ln.p_after < len(stream):             # still prefilling
                self.slot_next_tok[s] = int(stream[ln.p_after])
                continue
            req.status = RequestStatus.DECODING
            tok = int(nxt[s])
            if not req.generated:
                req.first_token_t = time.monotonic()
                if obs:
                    tel.event(req.uid, TM.EV_FIRST_TOKEN,
                              t=req.first_token_t, step=rec.step_idx,
                              token=tok)
            elif obs:
                tel.event(req.uid, TM.EV_DECODE_STEP,
                          step=rec.step_idx, token=tok)
            req.generated.append(tok)
            self.slot_next_tok[s] = tok
            hit_eos = req.eos_id is not None and tok == req.eos_id
            if hit_eos or len(req.generated) >= req.max_new_tokens \
                    or ln.pos_after + 1 >= self.max_seq:
                self._vacate(s)
                self._terminate(req, RequestStatus.FINISHED)
        if obs:
            # Phase accounting for this dispatch (see the Observability
            # section of the module docstring for the taxonomy). The device
            # wait lands in sample_commit via the np.asarray(nxt) transfer;
            # no sync points are added. In async mode the schedule-side
            # phases were measured at dispatch time (rec.times) and
            # sample_commit is measured here, one step later.
            _t4 = tel.now()
            ph = self._phase_h[rec.kind]
            hs, rx, pk, dp = rec.times
            ph['host_schedule'].observe(hs)
            ph['radix_lookup'].observe(rx)
            ph['pack_layout'].observe(pk)
            ph['dispatch'].observe(dp)
            ph['sample_commit'].observe(_t4 - _t3)
            commit_span.__exit__(None, None, None)

    def run(self, max_iters: int = 100_000) -> Dict[str, int]:
        """Drive the engine until all submitted work reaches a terminal
        status, or ``max_iters`` engine steps elapse.

        Never returns silently with half-finished work: if the iteration
        budget expires, still-queued requests are marked
        ``FAILED('stalled')`` and the returned report says how much work
        was abandoned (requests still occupying slots keep their state and
        resume on the next ``run()`` call)."""
        it = 0
        while (self.queue or any(r is not None for r in self.slot_req)
               or self._pending is not None) and it < max_iters:
            self.step_once()
            it += 1
        stalled = 0
        if it >= max_iters and self.queue:
            for req in self.queue:
                self._terminate(req, RequestStatus.FAILED, 'stalled')
                stalled += 1
            self.queue = []
            self.n_stalled += stalled
        out = {
            'iters': it,
            'stalled': stalled,
            'in_flight': sum(r is not None for r in self.slot_req),
            'preemptions': self.preemptions,
            'failed': self.n_failed,
            'cancelled': self.n_cancelled,
            'deadline_exceeded': self.n_deadline,
        }
        # Histogram-backed request percentiles over the engine lifetime
        # (keys omitted until at least one request finished — a missing key
        # is "no samples", never a fake 0.0).
        if self._lat_hist.count:
            out['p50_latency_s'] = self._lat_hist.percentile(50)
            out['p99_latency_s'] = self._lat_hist.percentile(99)
        if self._ttft_hist.count:
            out['p50_ttft_s'] = self._ttft_hist.percentile(50)
            out['p99_ttft_s'] = self._ttft_hist.percentile(99)
        return out

    def score(self, prompts: List[np.ndarray]) -> List[np.ndarray]:
        """Logits-on-demand for prompt-scoring workloads: run each prompt
        through the (chunked) prefill path and return its all-position
        logits — ``out[i][t]`` is the next-token distribution after
        consuming ``prompts[i][t]``, so
        ``log_softmax(out[i][t - 1])[prompts[i][t]]`` scores token ``t``.
        Shares slots/steps with any concurrently queued generation work.
        Scoring prompts always prefill cold (every position's logits are
        required), even in a prefix-cached engine. Internal uids come from
        a private counter so they can never collide with caller-chosen uids
        live in the same engine.

        A prompt whose request terminates without logits (stall, deadline,
        NaN/Inf watchdog, ...) raises :class:`ScoringError` — per-prompt
        reasons in ``.errors``, partial results in ``.logits`` — instead of
        silently returning ``None`` entries for callers to trip over.
        """
        reqs = [Request(uid=self._next_internal_uid(),
                        prompt=np.asarray(p, np.int32),
                        max_new_tokens=1, return_logits=True)
                for p in prompts]
        for r in reqs:
            self.submit(r)
        self.run()
        if any(r.status is not RequestStatus.FINISHED
               or r.prompt_logits is None for r in reqs):
            errors = [None if (r.status is RequestStatus.FINISHED
                               and r.prompt_logits is not None)
                      else (r.error or r.status.value) for r in reqs]
            raise ScoringError(errors, [r.prompt_logits for r in reqs])
        return [r.prompt_logits for r in reqs]

    # ------------------------------------------------------------- metrics
    def metrics(self) -> Dict:
        """Structured snapshot of the telemetry registry: counters, gauges,
        and histogram summaries (count/sum/mean/min/max/p50/p90/p99 +
        nonzero buckets). ``{'enabled': False}`` when telemetry is off."""
        return self.telemetry.snapshot()

    def stats(self, requests: List[Request]) -> Dict[str, float]:
        """Aggregate serving statistics over ``requests`` plus engine
        lifetime counters. Latency/TTFT summary keys
        (``mean_/p50_/p99_{latency,ttft}_s`` and ``..._ttft_on_hit_s``) are
        OMITTED when their sample set is empty — a missing key means "no
        samples", never a fake 0.0 (consumers print n/a)."""
        done = [r for r in requests if r.done]
        toks = sum(len(r.generated) for r in done)
        lat = [r.finish_t - r.submit_t for r in done]
        ttft = [r.first_token_t - r.submit_t for r in done
                if r.first_token_t]
        hit_ttft = [r.first_token_t - r.submit_t for r in done
                    if r.first_token_t and r.prefix_hit_tokens]
        out = {
            'completed': len(done), 'tokens': toks,
            'engine_steps': self.steps,
            'moe_token_drops': self.moe_token_drops,
            # chunk-grid utilization (segment-packed prefill win metric)
            'lanes_dispatched': self.lanes_dispatched,
            'lane_tokens': self.lane_tokens,
            'prefill_lane_utilization':
                self.lane_tokens / self.lanes_dispatched
                if self.lanes_dispatched else 0.0,
            # failure-semantics counters (engine lifetime totals)
            'preemptions': self.preemptions,
            'failed': self.n_failed,
            'cancelled': self.n_cancelled,
            'deadline_exceeded': self.n_deadline,
            'stalled': self.n_stalled,
        }
        out.update(TM.latency_summary('latency_s', lat))
        out.update(TM.latency_summary('ttft_s', ttft))
        if self.kv is not None:
            out.update(self.kv.stats())
            out.update(TM.latency_summary('ttft_on_hit_s', hit_ttft))
        return out
