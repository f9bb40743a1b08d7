#!/usr/bin/env python3
"""Chip smoke test: serve gemma3-1b at its published width on one TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --mesh 4x1   # only the four-chip mesh phase

Builds the serving engine the way ``repro.launch.serve`` builds it, at the
full published gemma3-1b widths (26 layers, d=1152, vocab 262144, 5:1
local/global, bf16) with seeded random weights, on the path a deployment
takes: the paper's precomputed layer-0 table with the fused gather->RoPE
kernel, the paged KV pool with the radix prefix cache, chunked prefill, the
Pallas paged-attention backend (named, not ``'auto'``) and fused in-kernel
page maintenance.

One-chip run:
  1. serve a system-prompt request, then requests sharing its prefix whose
     prompts outrun the 512-token local window (the ring caches wrap);
  2. check every request FINISHED with all its tokens, and that prefix hits
     happened;
  3. score a few prompts on that engine, on a reference-backend engine and
     on an engine without the precomputed table (same params), and check
     the logits agree within ``LOGIT_TOL`` — the paper's equivalence,
     precompute on vs off, at full width on the chip.

``--mesh PxH`` runs only the sharded engine on a ('pool', 'heads') mesh of
TPU devices, and the same requests and scores on one chip of the same
process, and compares them.

Exits non-zero on any failure, and before any work when JAX finds no TPU or
the script sits outside a checkout of the repository. Informational lines
come first (none of the timings is a benchmark metric); the last line of
stdout is ``{"ok": true, "device": {...}}``. JAX's persistent compilation
cache lives where ``JAX_COMPILATION_CACHE_DIR`` points, else in
``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = 'gemma3-1b'

# Worst prompt position's relative RMS logit error, ||a - b|| / ||b|| over
# the vocabulary. The engines under comparison round differently in bf16;
# on a TPU v5e each of them sits 1.4e-2 to 1.8e-2 from an fp32 reference at
# every position, and from each other. The bound is well under what broken
# cache metadata gives: stored positions rounded to bf16 in the page-write
# kernel moved single positions by 3.3e-2 and 7.2e-2; unrelated logits of
# equal norm sit near sqrt(2).
LOGIT_TOL = 3e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Engine geometry and traffic of one smoke run."""
    slots: int = 8
    max_seq: int = 1024
    chunk: int = 64
    page: int = 16
    prefix: int = 256              # shared system prompt (page multiple)
    prompt_lens: tuple = (600, 900)  # whole prompts, prefix included
    requests: int = 8
    new_tokens: int = 32
    score_lens: tuple = (130, 300, 700)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums XLA backend compile seconds and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == '/jax/core/compile/backend_compile_duration':
                self.seconds += duration
                self.programs += 1

        def on_event(event, **_):
            if event == '/jax/compilation_cache/cache_hits':
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f'{self.programs} programs, {self.seconds:.1f}s backend '
                f'compile, {self.cache_hits} persistent-cache hits')


def check_compiled_kernels() -> None:
    """Every Pallas entry point resolves interpret-vs-compiled through
    ``kernels.ops._interpret``; on the chip it must say compiled."""
    from repro.kernels import ops
    check(not ops._interpret(), 'Pallas kernels would run interpreted')


def check_tpu(devices) -> None:
    """The mesh is built from TPU devices, not emulated host devices."""
    kinds = {d.platform for d in devices}
    check(kinds == {'tpu'}, f'mesh devices are {sorted(kinds)}, not tpu')


def engine_options(sizes: Sizes, **over) -> dict:
    opts = dict(max_slots=sizes.slots, max_seq=sizes.max_seq,
                chunk_size=sizes.chunk, prefix_cache=True,
                page_size=sizes.page, attn_backend='pallas',
                fused_gather_rope=True)
    opts.update(over)
    return opts


def check_fast_path(eng, *, meshed: bool = False) -> None:
    """The engine really runs the kernels it was asked for — nothing was
    dropped silently (fused gather->RoPE turns itself off for ineligible
    configs, fused maintenance under a mesh)."""
    check(eng.attn_backend.name == 'pallas',
          f'attention backend is {eng.attn_backend.name!r}, not pallas')
    check(eng.fused_gather_rope, 'fused gather->RoPE was turned off')
    check(eng.paged and eng.chunk_size > 1, 'paged chunked path is off')
    if not meshed:
        check(eng._fused_maint, 'fused page maintenance is off')
    check_compiled_kernels()


def make_requests(vocab: int, sizes: Sizes, seed: int):
    """-> (warm-up request, requests). The warm-up request is the system
    prompt alone; it publishes the prefix (with the ring-state snapshot a
    sliding-window model resumes from) that the others then hit."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, vocab, sizes.prefix)
    warm = Request(uid=0, max_new_tokens=1, prompt=np.concatenate(
        [prefix, rng.integers(3, vocab, 1)]))
    lo, hi = sizes.prompt_lens
    reqs = [Request(uid=1 + i, max_new_tokens=sizes.new_tokens,
                    prompt=np.concatenate(
                        [prefix, rng.integers(3, vocab, n - sizes.prefix)]))
            for i, n in enumerate(rng.integers(lo, hi + 1, sizes.requests))]
    return warm, reqs


def serve(eng, vocab: int, sizes: Sizes, seed: int):
    """Serve the warm-up request, then the shared-prefix requests; check
    they all finished. -> (requests, stats, seconds)."""
    import jax
    from repro.serving import RequestStatus
    warm, reqs = make_requests(vocab, sizes, seed)
    t0 = time.monotonic()
    eng.submit(warm)
    eng.run()
    for r in reqs:
        eng.submit(r)
    eng.run()
    jax.block_until_ready(eng.states)
    dt = time.monotonic() - t0
    for r in [warm] + reqs:
        check(r.status is RequestStatus.FINISHED
              and len(r.generated) == r.max_new_tokens,
              f'request {r.uid}: {r.status.value} ({r.error}), '
              f'{len(r.generated)}/{r.max_new_tokens} tokens')
    stats = eng.stats(reqs)
    check(stats['prefix_hits'] > 0, 'no prefix-cache hits')
    return reqs, stats, dt


def score_prompts(vocab: int, sizes: Sizes, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(3, vocab, n) for n in sizes.score_lens]


def worst_rel_err(got, want) -> float:
    """Largest per-position ||got - want|| / ||want|| over the vocab."""
    import numpy as np
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        err = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
        worst = max(worst, float(err.max()))
    return worst


def compare_logits(name: str, got, want) -> float:
    err = worst_rel_err(got, want)
    print(f'{name}: worst position relative logit error {err:.3e} '
          f'(tolerance {LOGIT_TOL:.0e})', flush=True)
    check(err <= LOGIT_TOL, f'{name}: logits differ by {err:.3e}')
    return err


def single_chip(model, params, table, sizes: Sizes, seed: int,
                clock: CompileClock | None = None) -> None:
    """The one-chip phase: serve, then score on three engines."""
    from repro.launch.serve import build_engine
    vocab = model.cfg.vocab_size
    note = (lambda: f' [{clock.line()}]') if clock else (lambda: '')

    eng = build_engine(model, params, table, seed=seed,
                       **engine_options(sizes))
    check_fast_path(eng)
    reqs, stats, dt = serve(eng, vocab, sizes, seed)
    toks = sum(len(r.generated) for r in reqs)
    print(f'served {len(reqs)} requests + 1 warm-up: {toks} new tokens, '
          f'prompts {min(len(r.prompt) for r in reqs)}-'
          f'{max(len(r.prompt) for r in reqs)} tokens, '
          f'{stats["prefix_hits"]} prefix hits '
          f'({stats["prefix_hit_tokens"]} tokens from cache), '
          f'{stats["engine_steps"]} steps, {dt:.2f}s wall{note()}',
          flush=True)

    prompts = score_prompts(vocab, sizes, seed)
    t0 = time.monotonic()
    fast = eng.score(prompts)
    print(f'scored {len(prompts)} prompts {list(sizes.score_lens)} on the '
          f'pallas engine in {time.monotonic() - t0:.2f}s{note()}',
          flush=True)
    del eng
    ref = build_engine(model, params, table, seed=seed,
                       **engine_options(sizes, attn_backend='reference',
                                        fused_gather_rope=False))
    compare_logits('pallas vs reference backend', fast, ref.score(prompts))
    del ref
    plain = build_engine(model, params, None, seed=seed,
                         **engine_options(sizes, fused_gather_rope=False))
    compare_logits('precompute on vs off', fast, plain.score(prompts))
    del plain


def mesh_phase(model, params, table, sizes: Sizes, seed: int, spec: str,
               clock: CompileClock | None = None) -> None:
    """Serve and score the same requests on a ('pool','heads') mesh and on
    one chip, in this process, and compare."""
    from repro.launch.serve import build_engine
    vocab = model.cfg.vocab_size
    meshed = build_engine(model, params, table, seed=seed, mesh=spec,
                          **engine_options(sizes))
    check(meshed.mesh is not None, f'mesh {spec!r} resolved to one device')
    check_tpu(meshed.mesh.devices.flat)
    check_fast_path(meshed, meshed=True)
    sizes_str = 'x'.join(str(n) for n in meshed.mesh.devices.shape)
    out = {}
    for name, eng in (('mesh', meshed), ('one chip', None)):
        if eng is None:
            eng = build_engine(model, params, table, seed=seed,
                               **engine_options(sizes))
            check_fast_path(eng)
        reqs, stats, dt = serve(eng, vocab, sizes, seed)
        t0 = time.monotonic()
        logits = eng.score(score_prompts(vocab, sizes, seed))
        out[name] = ([r.generated for r in reqs], logits)
        note = f' [{clock.line()}]' if clock else ''
        print(f'{name} ({sizes_str if eng is meshed else "1"} devices): '
              f'{sum(len(g) for g in out[name][0])} new tokens, '
              f'{stats["prefix_hits"]} prefix hits, serve {dt:.2f}s, '
              f'score {time.monotonic() - t0:.2f}s{note}', flush=True)
        del eng
    same = sum(a == b for a, b in zip(out['mesh'][0], out['one chip'][0]))
    print(f'greedy token streams identical on {same}/{len(out["mesh"][0])} '
          'requests', flush=True)
    compare_logits(f'mesh {spec} vs one chip', out['mesh'][1],
                   out['one chip'][1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument('--mesh', default='',
                    help='run only the sharded phase on a PxH (pool x '
                         'heads) mesh of TPU devices, e.g. 4x1')
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, 'src')
    if not os.path.isdir(os.path.join(src, 'repro')):
        print(f'chip_smoke.py: no src/repro next to {HERE}; run it from a '
              'checkout of the repository', file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    from repro.launch.serve import load_model, use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != 'tpu':
        print(f'chip_smoke.py: JAX found platform {dev.platform!r} '
              f'({dev.device_kind}); this smoke test needs a TPU',
              file=sys.stderr)
        return 1
    clock = CompileClock()
    print(f'device: {dev.device_kind} ({dev.platform}), '
          f'{len(devices)} visible; compile cache {cache_dir}', flush=True)

    try:
        t0 = time.monotonic()
        model, params = load_model(ARCH, published=True, seed=args.seed)
        table = model.build_table(params)
        jax.block_until_ready((params, table.table))
        cfg = model.cfg
        print(f'model: {cfg.name} {cfg.num_layers}L d={cfg.d_model} '
              f'{cfg.num_heads}H/{cfg.num_kv_heads}KV x{cfg.head_dim} '
              f'vocab={cfg.vocab_size} window={cfg.window} {cfg.dtype}, '
              f'table {table.table.shape} {table.table.dtype}; set-up '
              f'{time.monotonic() - t0:.1f}s [{clock.line()}]', flush=True)
        t0 = time.monotonic()
        if args.mesh:
            mesh_phase(model, params, table, Sizes(), args.seed, args.mesh,
                       clock)
        else:
            single_chip(model, params, table, Sizes(), args.seed, clock)
        print(f'phases {time.monotonic() - t0:.1f}s wall; compile: '
              f'{clock.line()}', flush=True)
    except SmokeFailure as e:
        print(f'chip_smoke.py: FAILED: {e}', file=sys.stderr)
        return 1
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': len(devices)}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
