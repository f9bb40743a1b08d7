"""End-to-end serving driver: batched requests through the continuous-batching
engine, with the paper's precomputed first layer ON by default.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
        --requests 8 --no-precompute   # baseline comparison

    # paged serving with the in-place Pallas attention kernel
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
        --prefix-cache --shared-prefix 64 --attn-backend pallas

    # the published widths (seeded random weights) in the config's dtype
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --published

JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
points, else in ``.jax_cache`` at the root of the checkout.

Failure semantics: every failure is a per-request outcome, never an engine
crash. Requests move through QUEUED -> PREFILLING -> DECODING -> FINISHED,
with FAILED / CANCELLED / PREEMPTED branches: malformed submissions fail at
submit time with ``error`` set; KV-pool exhaustion preempts a victim slot
(fewest decoded tokens, LIFO tie-break, oldest in flight protected) whose
finished pages are published to the prefix cache so its resume recomputes
only the uncached tail — tokens across preempt/resume stay bit-identical
to an uninterrupted run; ``--deadline`` bounds each request's wall clock;
non-finite logits fail only the offending lane. ``run()`` reports
preemptions / failed / cancelled / deadline_exceeded, printed below.

Observability: ``--telemetry`` turns on the engine's metrics registry and
per-request span tracer (host-side only, tokens stay bit-identical), and
the engine's step phases as ``jax.profiler`` annotations (``engine.*``),
which a profiler capture shows beside the device ops;
``--metrics-out FILE`` dumps the registry as Prometheus text (``.prom``/
``.txt``) or structured JSON, and ``--trace-out FILE`` writes a
Chrome-trace-format span export (load in ``chrome://tracing`` or Perfetto).
Both imply ``--telemetry``.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models.model import Model
from repro.serving import Request, ServingEngine
from repro.serving import telemetry as TM

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed directory, so a
    later process finds what an earlier one compiled: where
    ``JAX_COMPILATION_CACHE_DIR`` points (JAX reads it itself), else
    ``.jax_cache`` at the root of the checkout. Returns the directory."""
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not path:
        path = str(REPO_ROOT / '.jax_cache')
        jax.config.update('jax_compilation_cache_dir', path)
    return path


def load_model(arch: str, *, published: bool, seed: int):
    """-> (model, params): the published widths (``get_config``) or the
    tiny smoke config, with seeded random weights in the config's dtype."""
    cfg = get_config(arch) if published else get_smoke_config(arch)
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def build_engine(model: Model, params, table, **opts) -> ServingEngine:
    """The serving engine as every entry point builds it: KV state in the
    config's dtype, layer 0 from the precomputed ``table`` (None = off).
    ``opts`` are ``ServingEngine`` keyword options."""
    return ServingEngine(model, params, precomputed=table,
                         dtype=jnp.dtype(model.cfg.dtype), **opts)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--arch', default='gemma3-1b')
    ap.add_argument('--published', action='store_true',
                    help='serve the published widths (get_config) instead '
                         'of the tiny smoke config; weights stay seeded '
                         'random')
    ap.add_argument('--requests', type=int, default=8)
    ap.add_argument('--slots', type=int, default=4)
    ap.add_argument('--max-slots', type=int, default=0,
                    help='override the engine slot count (0 = --slots). '
                         'Large values are cheap to hold: dispatches are '
                         'sliced to the smallest power-of-two bucket '
                         'covering the active slots, so a 256-slot engine '
                         'serving 3 requests traces/pays an 8-wide step')
    ap.add_argument('--mesh', default='',
                    help='serving device mesh "PxH" (pool x heads), e.g. '
                         '"2x2". Shards KV pool storage over P devices and '
                         'the pallas attend over H kv-head groups; tokens '
                         'stay bitwise identical to the single-device '
                         'engine. Empty / "1x1" = no mesh. On CPU the '
                         'devices are emulated (host-platform device count '
                         'is set automatically when possible). Impossible '
                         'shapes raise ValueError, never assert')
    ap.add_argument('--async-loop', action='store_true',
                    help='double-buffered host loop: schedule step N+1 '
                         'while the device runs step N; sampling commits '
                         'one step late (greedy tokens stay bitwise '
                         'identical to the synchronous loop)')
    ap.add_argument('--new-tokens', type=int, default=24)
    ap.add_argument('--max-seq', type=int, default=256)
    ap.add_argument('--temperature', type=float, default=0.0)
    ap.add_argument('--no-precompute', action='store_true')
    ap.add_argument('--chunk-size', type=int, default=16,
                    help='prompt tokens per prefill dispatch (1 = token-by-'
                         'token; chunking works for every architecture — '
                         'dense, MoE, MLA, SSM, hybrid, VLM-text)')
    ap.add_argument('--fused-gather-rope', action='store_true',
                    help='fold layer-0 RoPE into the precomputed-row gather '
                         '(Pallas kernel; needs precompute + chunking + a '
                         'flat q/k layer-0 row layout)')
    ap.add_argument('--score', action='store_true',
                    help='logits-on-demand demo: score each prompt (mean '
                         'token logprob over all positions) instead of '
                         'generating')
    ap.add_argument('--prefix-cache', action='store_true',
                    help='paged KV pool + shared-prefix radix cache: '
                         'requests sharing a cached prompt prefix attach '
                         'its pages and skip that prefill work (token '
                         'outputs stay bit-identical to the dense engine)')
    ap.add_argument('--page-size', type=int, default=16,
                    help='tokens per KV page (prefix-cache mode; must '
                         'divide --max-seq)')
    ap.add_argument('--num-pages', type=int, default=0,
                    help='KV pool size in pages (0 = auto: slots + cache '
                         'headroom)')
    ap.add_argument('--shared-prefix', type=int, default=0,
                    help='prepend a common system prompt of this many '
                         'tokens to every request (demonstrates the '
                         'prefix-cache hit rate)')
    ap.add_argument('--attn-backend', default='auto',
                    choices=['auto', 'reference', 'pallas'],
                    help='attention backend for every decode attend: '
                         '"reference" keeps the lane-at-a-time bit-identity '
                         'oracle (paged mode gathers a dense view per '
                         'layer); "pallas" runs the in-place paged/chunked '
                         'attention kernel — pages are read straight from '
                         'the pool through the page table and all chunk '
                         'query lanes are batched into one dispatch '
                         '(compiled on TPU, interpret mode on CPU; outputs '
                         'match reference to fp32 tolerance, not bitwise); '
                         '"auto" (default) picks pallas on TPU and '
                         'reference elsewhere')
    ap.add_argument('--deadline', type=float, default=0.0,
                    help='per-request wall-clock budget in seconds, '
                         'enforced every engine step; an expired request '
                         'is FAILED("deadline_exceeded") and its slot '
                         'freed, the rest keep serving (0 = no deadline)')
    ap.add_argument('--telemetry', action='store_true',
                    help='enable the metrics registry + per-request span '
                         'tracer, and the step phases as profiler '
                         'annotations (host-side; tokens stay '
                         'bit-identical). Implied by --metrics-out / '
                         '--trace-out')
    ap.add_argument('--metrics-out', default='',
                    help='write the metrics registry to this file: '
                         'Prometheus exposition text for .prom/.txt, '
                         'structured JSON otherwise')
    ap.add_argument('--trace-out', default='',
                    help='write request spans as Chrome trace-event JSON '
                         '(chrome://tracing / Perfetto)')
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args()
    want_telemetry = bool(args.telemetry or args.metrics_out
                          or args.trace_out)
    if args.mesh:
        _ensure_mesh_devices(args.mesh)
    use_compile_cache()

    model, params = load_model(args.arch, published=args.published,
                               seed=args.seed)
    cfg = model.cfg
    if cfg.arch_class in ('audio',):
        raise SystemExit('use examples/whisper_transcribe.py for audio')
    table = None
    if not args.no_precompute and cfg.precompute_supported:
        t0 = time.time()
        table = model.build_table(params)
        print(f'precomputed table: {table.table.shape} '
              f'({table.table.size * table.table.dtype.itemsize / 2**20:.1f} '
              f'MiB) built in {time.time() - t0:.2f}s')
    eng = build_engine(model, params, table,
                       max_slots=args.max_slots or args.slots,
                       max_seq=args.max_seq,
                       seed=args.seed, chunk_size=args.chunk_size,
                       fused_gather_rope=args.fused_gather_rope,
                       prefix_cache=args.prefix_cache,
                       page_size=args.page_size,
                       num_pages=args.num_pages or None,
                       attn_backend=args.attn_backend,
                       telemetry=want_telemetry,
                       mesh=args.mesh or None,
                       async_loop=args.async_loop)
    if eng.mesh is not None:
        sizes = dict(zip(eng.mesh.axis_names, eng.mesh.devices.shape))
        print(f'serving mesh: {sizes["pool"]}x{sizes["heads"]} '
              f'(pool x heads) over {eng.mesh.devices.size} devices')
    if eng.async_loop:
        print('async double-buffered host loop (one-step sampling lag)')
    if eng.chunk_size > 1:
        print(f'chunked prefill: {eng.chunk_size} tokens/dispatch'
              + (' + fused gather→RoPE' if eng.fused_gather_rope else ''))
    if eng.paged:
        print(f'paged KV: {eng.num_pages} pages x {eng.page_size} tokens '
              f'+ shared-prefix radix cache')
    if eng.attn_backend.name != 'reference':
        print(f'attention backend: {eng.attn_backend.name} '
              '(in-place paged/chunked kernel)')
    rng = np.random.default_rng(args.seed)
    sys_prompt = rng.integers(3, cfg.vocab_size, size=args.shared_prefix) \
        if args.shared_prefix else None
    if args.score:
        prompts = [rng.integers(3, cfg.vocab_size,
                                size=int(rng.integers(4, 12)))
                   for _ in range(args.requests)]
        t0 = time.time()
        all_logits = eng.score(prompts)
        dt = time.time() - t0
        for i, (p, lg) in enumerate(zip(prompts, all_logits)):
            m = lg.max(-1, keepdims=True)
            logp = lg - m - np.log(np.exp(lg - m).sum(-1, keepdims=True))
            mean_lp = float(np.mean([logp[t - 1, p[t]]
                                     for t in range(1, len(p))]))
            print(f'prompt {i}: len={len(p)} logits={lg.shape} '
                  f'mean token logprob={mean_lp:.3f}')
        toks = sum(len(p) for p in prompts)
        print(f'scored {len(prompts)} prompts ({toks} tokens) in {dt:.2f}s')
        _write_exports(eng, args)
        return
    def mkprompt():
        p = rng.integers(3, cfg.vocab_size, size=int(rng.integers(4, 12)))
        return p if sys_prompt is None else np.concatenate([sys_prompt, p])

    reqs = [Request(uid=i, prompt=mkprompt(),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature,
                    deadline_s=args.deadline or None)
            for i in range(args.requests)]
    t0 = time.time()
    for r in reqs:
        eng.submit(r)
    report = eng.run()
    dt = time.time() - t0
    stats = eng.stats(reqs)
    total_toks = stats['tokens']
    def fmt(key: str) -> str:
        # latency keys are OMITTED from stats() when no request produced a
        # sample — print n/a, never a fake 0.000s
        return f'{stats[key]:.3f}s' if key in stats else 'n/a'

    print(f'{stats["completed"]} requests, {total_toks} new tokens in '
          f'{dt:.2f}s -> {total_toks / dt:.1f} tok/s '
          f'(mode={"precompute" if table is not None else "baseline"})')
    print(f'mean latency {fmt("mean_latency_s")} '
          f'(p50 {fmt("p50_latency_s")} / p99 {fmt("p99_latency_s")}), '
          f'mean TTFT {fmt("mean_ttft_s")} '
          f'(p50 {fmt("p50_ttft_s")} / p99 {fmt("p99_ttft_s")}), '
          f'engine steps {stats["engine_steps"]}, '
          f'MoE token drops {stats["moe_token_drops"]}')
    print(f'fault tolerance: {stats["preemptions"]} preemptions, '
          f'{stats["failed"]} failed, {stats["cancelled"]} cancelled, '
          f'{stats["deadline_exceeded"]} deadline-exceeded, '
          f'{report["stalled"]} stalled')
    if eng.paged:
        print(f'prefix cache: hit rate {stats[TM.KV_PREFIX_HIT_RATE]:.2f} '
              f'({stats[TM.KV_PREFIX_HITS]} hits / '
              f'{stats[TM.KV_PREFIX_MISSES]} misses, '
              f'{stats[TM.KV_PREFIX_HIT_TOKENS]} tokens served from '
              f'cache), TTFT on hit {fmt("mean_ttft_on_hit_s")}, '
              f'{stats[TM.KV_PAGES_IN_USE]} pages in use, '
              f'{stats[TM.KV_EVICTIONS]} evictions')
    _write_exports(eng, args)


def _ensure_mesh_devices(spec: str) -> None:
    """Emulated CPU meshes need ``--xla_force_host_platform_device_count``
    in XLA_FLAGS before jax initialises its backend. argparse runs before
    any device access, so a well-formed ``--mesh`` can set it here; a
    malformed spec is left for ``make_serving_mesh`` to reject with its
    proper ValueError."""
    import os
    parts = spec.lower().replace('×', 'x').split('x')
    try:
        need = 1
        for p in parts:
            need *= int(p)
    except ValueError:
        return
    flags = os.environ.get('XLA_FLAGS', '')
    if need > 1 and 'xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + f' --xla_force_host_platform_device_count={need}'
        ).strip()


def _write_exports(eng: ServingEngine, args) -> None:
    """Dump the telemetry registry / trace where --metrics-out / --trace-out
    point. Prometheus text for .prom/.txt metric paths, JSON otherwise."""
    if args.metrics_out:
        if args.metrics_out.endswith(('.prom', '.txt')):
            eng.telemetry.write_prometheus(args.metrics_out)
        else:
            eng.telemetry.write_json(args.metrics_out)
        print(f'metrics -> {args.metrics_out}')
    if args.trace_out:
        eng.telemetry.write_chrome_trace(args.trace_out)
        print(f'trace -> {args.trace_out}')


if __name__ == '__main__':
    main()
