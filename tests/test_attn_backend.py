"""Unified attention-backend layer: pallas-vs-reference parity matrix.

The reference backend is the bit-identity oracle (lane-at-a-time rounding,
dense-gathered paged views — pinned by test_chunked_all_archs.py and
test_paged_prefix.py, which run it by default). The Pallas backend
(kernels/paged_attention.py, interpret mode on CPU) must match it within
the documented ``attn_backend.PALLAS_TOL`` bound across the whole matrix:
page sizes {8, 16}, unaligned final pages, ring wraparound, sliding-window
layers, GQA fp32/int8, and MLA — at kernel, model-step and engine level.
Plus a hypothesis property: attention is invariant under any permutation of
the physical page pool (with the page tables remapped to match).

The fused paged-maintenance kernels (kernels/paged_maintenance.py — chunk
scatter + deferred clear-on-alloc + COW DMA) hold a STRICTER contract:
cache contents bitwise equal to eager clear + XLA scatter, including
non-page-multiple ring lengths whose last page is partial.

Tests marked ``compiled`` resolve ``interpret`` by platform
(``kernels.ops._interpret``): on CPU they run the kernels interpreted; run
on a TPU, the same assertions would hold the compiled kernels (not done
yet). Whether the kernels compile for a TPU at all is checked by
``tests/test_tpu_compile.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.config import MLAConfig, ModelConfig, MoEConfig
from repro.kernels import paged_maintenance as PM
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.models.attention import paged_scatter
from repro.models.attn_backend import (BACKENDS, PALLAS, PALLAS_TOL,
                                       REFERENCE, auto_backend, get_backend)
from repro.models.model import Model
from repro.serving import Request, ServingEngine

TOL = PALLAS_TOL        # the documented pallas-vs-reference attend bound


# ========================================================== kernel vs oracle
def _pool(seed, NP, ps, KV, d, quant=False):
    kk = jax.random.PRNGKey(seed)
    mk = lambda i, shape: jax.random.normal(jax.random.fold_in(kk, i), shape)
    if quant:
        k = jax.random.randint(jax.random.fold_in(kk, 0), (NP, ps, KV, d),
                               -127, 127).astype(jnp.int8)
        v = jax.random.randint(jax.random.fold_in(kk, 1), (NP, ps, KV, d),
                               -127, 127).astype(jnp.int8)
        ks = jnp.abs(mk(2, (NP, ps, KV))) * 0.05 + 1e-3
        vs = jnp.abs(mk(3, (NP, ps, KV))) * 0.05 + 1e-3
        return k, v, ks.astype(jnp.bfloat16), vs.astype(jnp.bfloat16)
    return mk(0, (NP, ps, KV, d)), mk(1, (NP, ps, KV, d)), None, None


def _fill_positions(NP, ps, table, lengths, Sc):
    """Stored positions for each slot's pages: slot b holds positions
    [0, lengths[b]) at virtual index pos % Sc — ring layers wrap, linear
    layers have Sc >= length. Unallocated entries stay -1 (null page 0)."""
    cpos = np.full((NP, ps), -1, np.int32)
    B, P = table.shape
    for b in range(B):
        n = int(lengths[b])
        for pos in range(max(0, n - Sc), n):      # live ring window
            idx = pos % Sc
            pg = int(table[b, idx // ps])
            if pg:
                cpos[pg, idx % ps] = pos
    return jnp.asarray(cpos)


@pytest.mark.slow
@pytest.mark.parametrize('ps', [8, 16])
@pytest.mark.parametrize('window', [0, 5])
@pytest.mark.parametrize('quant', [False, True])
@pytest.mark.parametrize('T', [1, 5])
def test_kernel_matches_gather_oracle(ps, window, quant, T):
    """In-place page reads == gather-then-attend, including null-page table
    entries, an unaligned final page and ring wraparound (Sc=11 < length)."""
    B, KV, G, d = 2, 2, 2, 16
    Sc = 11 if window else 24             # ring: not a page multiple
    P = -(-Sc // ps)
    NP = 1 + B * P
    table = np.zeros((B, P), np.int32)
    nxt = 1
    for b in range(B):
        for j in range(P):
            table[b, j] = nxt
            nxt += 1
    table[1, -1] = 0                      # slot 1: trailing null-page entry
    lengths = [Sc + 7, ps - 3]            # wraps ring / ends mid-first-page
    k, v, ks, vs = _pool(0, NP, ps, KV, d, quant)
    cpos = _fill_positions(NP, ps, table, lengths, Sc)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, T, KV, G, d))
    pos0 = jnp.asarray([le - 1 for le in lengths], jnp.int32)
    args = (q, k, v, cpos, jnp.asarray(table), pos0)
    kw = dict(scale=d ** -0.5, window=window, k_scale_pages=ks,
              v_scale_pages=vs)
    got = paged_attention(*args, **kw, interpret=True)
    want = ref.paged_attention_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize('ps', [8, 16])
def test_kernel_mla_matches_gather_oracle(ps):
    B, H, r, dr = 2, 3, 12, 6
    Sc, T = 24, 4
    P = -(-Sc // ps)
    NP = 1 + B * P
    table = np.arange(B * P).reshape(B, P).astype(np.int32) + 1
    lengths = [Sc - 2, 5]
    kk = jax.random.PRNGKey(3)
    ckv = jax.random.normal(kk, (NP, ps, 1, r))
    kpe = jax.random.normal(jax.random.fold_in(kk, 1), (NP, ps, 1, dr))
    cpos = _fill_positions(NP, ps, table, lengths, Sc)
    q = jax.random.normal(jax.random.fold_in(kk, 2), (B, T, 1, H, r + dr))
    pos0 = jnp.asarray([le - 1 for le in lengths], jnp.int32)
    kw = dict(scale=(r + dr) ** -0.5, k2_pages=kpe, mla_split=r)
    got = paged_attention(q, ckv, None, cpos, jnp.asarray(table), pos0,
                          **kw, interpret=True)
    want = ref.paged_attention_ref(q, ckv, None, cpos, jnp.asarray(table),
                                   pos0, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize('pool', ['bf16', 'int8', 'mla'])
@pytest.mark.parametrize('T', [1, 16])
def test_kernel_walk_ends_at_last_reachable_page(pool, T):
    """On a linear table (window 0) the kernel attends no page past
    ``(pos0 + T - 1) // ps``: table entries there pointing at empty pages
    full of NaN (NaN scales for the int8 pool) give the output of null-page
    entries bit for bit. A full walk would poison it through ``0 * NaN`` in
    the value mix. Slots: an idle lane (pos0 0, no pages), and chunks whose
    last query sits at the first and at the last offset of a page."""
    B, KV, G, d, ps, P = 3, 2, 2, 16, 8, 6
    r, dr = 12, 6                                  # MLA latent widths
    pos0 = np.array([0, 2 * ps - T + 1, 3 * ps - T], np.int32)
    last = (pos0 + T - 1) // ps
    n_live = [0, int(pos0[1]) + T, int(pos0[2]) + T]   # written positions
    NP = 1 + B * P                     # null page, then one page per entry
    table = np.zeros((B, P), np.int32)
    poisoned = np.zeros((B, P), np.int32)
    cpos = np.full((NP, ps), -1, np.int32)
    live = np.zeros(NP, bool)
    live[0] = True                     # the null page: clean, never written
    nxt = 1
    for b in range(B):
        for j in range(P):
            if j * ps < n_live[b]:
                table[b, j] = nxt
                hi = min(n_live[b], (j + 1) * ps)
                cpos[nxt, :hi - j * ps] = np.arange(j * ps, hi)
                live[nxt] = True
            poisoned[b, j] = table[b, j] if j <= last[b] else nxt
            nxt += 1
    live = jnp.asarray(live)[:, None, None, None]
    poison = lambda x: jnp.where(live, x, jnp.nan).astype(x.dtype)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, T, KV, G, d))
    kw = {}
    if pool == 'mla':
        kk = jax.random.PRNGKey(6)
        q = jax.random.normal(kk, (B, T, 1, 2, r + dr))
        k = jax.random.normal(jax.random.fold_in(kk, 1), (NP, ps, 1, r))
        k2 = jax.random.normal(jax.random.fold_in(kk, 2), (NP, ps, 1, dr))
        kw = dict(scale=(r + dr) ** -0.5, mla_split=r)
        clean = (k, None, dict(k2_pages=k2))
        dirty = (poison(k), None, dict(k2_pages=poison(k2)))
    else:
        k, v, ks, vs = _pool(4, NP, ps, KV, d, quant=pool == 'int8')
        kw = dict(scale=d ** -0.5)
        if pool == 'int8':
            sc = lambda x: poison(x[..., None])[..., 0]
            clean = (k, v, dict(k_scale_pages=ks, v_scale_pages=vs))
            dirty = (k, v, dict(k_scale_pages=sc(ks), v_scale_pages=sc(vs)))
        else:
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
            clean = (k, v, {})
            dirty = (poison(k), poison(v), {})
    pos0 = jnp.asarray(pos0)
    cpos = jnp.asarray(cpos)

    def run(pages, tab):
        kp, vp, extra = pages
        return np.asarray(paged_attention(q, kp, vp, cpos, jnp.asarray(tab),
                                          pos0, **kw, **extra,
                                          interpret=True))
    want = run(clean, table)
    got = run(dirty, poisoned)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got, want)
    oracle = ref.paged_attention_ref(q, clean[0], clean[1], cpos,
                                     jnp.asarray(table), pos0, **kw,
                                     **clean[2])
    np.testing.assert_allclose(want, np.asarray(oracle), **TOL)


@pytest.mark.parametrize('T', [1, 16])
def test_kernel_window_walks_every_ring_page(T):
    """Sliding-window layers keep the full walk: ring tables map blocks to
    no fixed positions. A ring that has wrapped (positions past its
    length) and one that has not, against the gather oracle."""
    B, KV, G, d, ps, window = 2, 2, 2, 16, 8, 12
    Sc = 24 if T == 1 else 40            # ring length >= window + T - 1
    P = Sc // ps
    table = np.arange(1, 1 + B * P, dtype=np.int32).reshape(B, P)[:, ::-1]
    lengths = [Sc + 13, T + 3]
    k, v, _, _ = _pool(8, 1 + B * P, ps, KV, d)
    cpos = _fill_positions(1 + B * P, ps, table, lengths, Sc)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, T, KV, G, d))
    pos0 = jnp.asarray([le - T for le in lengths], jnp.int32)
    args = (q, k, v, cpos, jnp.asarray(table.copy()), pos0)
    got = paged_attention(*args, scale=d ** -0.5, window=window,
                          interpret=True)
    want = ref.paged_attention_ref(*args, scale=d ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ================================================== model-step parity (dense)
def _cfg(kind):
    base = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                head_dim=16, d_ff=128, vocab_size=211, max_seq_len=256,
                dtype='float32')
    if kind == 'gqa':
        return ModelConfig(name='ab-gqa', arch_class='dense', **base)
    if kind == 'local':
        return ModelConfig(name='ab-local', arch_class='dense',
                           pattern=('global', 'local'), window=8, **base)
    if kind == 'mla':
        return ModelConfig(
            name='ab-mla', arch_class='moe', num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
            vocab_size=211, max_seq_len=256, dtype='float32',
            tie_embeddings=False,
            mla=MLAConfig(kv_lora_rank=16, q_lora_rank=0, qk_nope_dim=16,
                          qk_rope_dim=8, v_head_dim=16),
            moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                          num_shared=1, first_dense_layers=1,
                          capacity_factor=2.0))
    raise ValueError(kind)


def _build(kind):
    cfg = _cfg(kind)
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize('kind', ['gqa', 'local', 'mla'])
@pytest.mark.parametrize('quant', [False, True])
def test_model_chunked_decode_parity_dense(kind, quant):
    """Whole-prompt chunked decode over dense caches: pallas logits match
    the reference backend at every position (incl. ring wraparound for the
    sliding-window layer: prompt 20 > ring 8 + slack)."""
    if quant and kind == 'mla':
        pytest.skip('MLA latent cache is not int8-quantised')
    cfg, model, params = _build(kind)
    B, P, T = 2, 20, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 3, 200)
    outs = {}
    for backend in ('reference', 'pallas'):
        states = model.make_states(B, 32, jnp.float32, kv_quant=quant,
                                   chunk=T)
        logits, p = [], 0
        while p < P:
            n = min(T, P - p)
            block = jnp.zeros((B, T), jnp.int32).at[:, :n].set(
                toks[:, p:p + n])
            lg, states = model.decode_step(
                params, block, states, jnp.full((B,), p, jnp.int32),
                n_valid=jnp.full((B,), n, jnp.int32), attn_backend=backend)
            logits.append(lg[:, :n])
            p += n
        outs[backend] = np.asarray(jnp.concatenate(logits, 1))
    tol = dict(TOL)
    if quant:
        # An fp32-ulp difference in one layer's output can flip the rounding
        # of a cached int8 value in the next layer, moving it by one
        # quantisation step (1/127 of its row's max-abs) — and the logits by
        # up to about one step of their own scale.
        tol['atol'] += np.abs(outs['reference']).std() / 127
    np.testing.assert_allclose(outs['pallas'], outs['reference'], **tol)


# ============================================================= engine parity
@pytest.mark.compiled
@pytest.mark.parametrize('kind,quant,ps', [
    ('gqa', False, 8), ('gqa', True, 16), ('local', False, 8),
    ('mla', False, 16),
])
def test_engine_paged_pallas_matches_reference(kind, quant, ps):
    """Paged serving with the pallas backend: greedy tokens equal the
    reference engine's across cold prefill AND prefix-cache hits (second
    wave), with no dense per-layer gather on the attend path."""
    cfg, model, params = _build(kind)
    prefix = np.random.default_rng(99).integers(3, 200, size=20)

    def run(backend):
        eng = ServingEngine(model, params, max_slots=2, max_seq=64,
                            chunk_size=4, kv_quant=quant, prefix_cache=True,
                            page_size=ps, attn_backend=backend)
        waves = []
        for seeds in ([7, 8, 9], [50, 51]):         # wave 2 hits the radix
            reqs = [Request(uid=s, prompt=np.concatenate([
                prefix, np.random.default_rng(s).integers(3, 200, size=4)]),
                max_new_tokens=5) for s in seeds]
            for r in reqs:
                eng.submit(r)
            eng.run()
            waves += reqs
        assert eng.stats(waves)['prefix_hits'] >= 2
        return [r.generated for r in waves]

    assert run('pallas') == run('reference')


def test_engine_pallas_score_logits_close():
    """Prompt-scoring logits through the pallas backend stay within fp32
    tolerance of the reference engine's at every position."""
    cfg, model, params = _build('gqa')
    prompt = np.random.default_rng(5).integers(3, 200, size=10)
    want = ServingEngine(model, params, max_slots=2, max_seq=64,
                         chunk_size=4).score([prompt])[0]
    got = ServingEngine(model, params, max_slots=2, max_seq=64, chunk_size=4,
                        attn_backend='pallas').score([prompt])[0]
    np.testing.assert_allclose(got, want, **TOL)


# ===================================================== page-table permutation
@settings(max_examples=15, deadline=None)
@given(ps=st.sampled_from([4, 8]), seed=st.integers(0, 2 ** 16),
       window=st.sampled_from([0, 6]), data=st.data())
def test_page_table_permutation_invariance(ps, seed, window, data):
    """Attention output is BITWISE invariant under any permutation of the
    physical page pool when the tables are remapped to match — physical
    page identity carries no information (the allocator may hand out any
    free page)."""
    B, KV, G, d, T = 2, 2, 1, 8, 3
    Sc = 16
    P = Sc // ps
    NP = 1 + B * P + 2                    # a couple of free pages too
    table = np.arange(B * P).reshape(B, P).astype(np.int32) + 1
    lengths = [Sc + 3 if window else Sc - 2, 5]
    k, v, _, _ = _pool(seed, NP, ps, KV, d)
    cpos = _fill_positions(NP, ps, table, lengths, Sc)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, KV, G, d))
    pos0 = jnp.asarray([le - 1 for le in lengths], jnp.int32)
    kw = dict(scale=d ** -0.5, window=window, interpret=True)
    base = paged_attention(q, k, v, cpos, jnp.asarray(table), pos0, **kw)

    # permute physical pages 1..NP-1 (page 0 stays the null page)
    perm = np.asarray(
        data.draw(st.permutations(list(range(1, NP))), label='perm'))
    perm = np.concatenate([[0], perm])
    inv = np.argsort(perm)                # new position of old page i
    k2 = jnp.asarray(np.asarray(k)[perm])
    v2 = jnp.asarray(np.asarray(v)[perm])
    cpos2 = jnp.asarray(np.asarray(cpos)[perm])
    table2 = jnp.asarray(inv[table].astype(np.int32))
    got = paged_attention(q, k2, v2, cpos2, table2, pos0, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# ===================================================== fused paged maintenance
def _maint_pool(seed, NP, ps, quant):
    """Random pool dict with page 0 already the null page (fill values)."""
    rng = np.random.default_rng(seed)
    if quant:
        cache = {
            'k': rng.integers(-127, 128, (NP, ps, 2, 8)).astype(np.int8),
            'v': rng.integers(-127, 128, (NP, ps, 2, 8)).astype(np.int8),
            'k_scale': rng.random((NP, ps, 2), np.float32),
            'v_scale': rng.random((NP, ps, 2), np.float32),
        }
        cache['k_scale'] = cache['k_scale'].astype(jnp.bfloat16)
        cache['v_scale'] = cache['v_scale'].astype(jnp.bfloat16)
    else:
        cache = {'k': rng.standard_normal((NP, ps, 2, 8), np.float32),
                 'v': rng.standard_normal((NP, ps, 2, 8), np.float32)}
    cache['pos'] = rng.integers(0, 99, (NP, ps)).astype(np.int32)
    cache = {nm: jnp.asarray(v) for nm, v in cache.items()}
    return {nm: v.at[0].set(PM.leaf_fill(nm)) for nm, v in cache.items()}


def _eager_clear(cache, pages):
    return {nm: v.at[np.asarray(pages)].set(PM.leaf_fill(nm))
            for nm, v in cache.items()}


@pytest.mark.parametrize('quant', [False, True])
@pytest.mark.parametrize('ps,Sc', [
    (8, 32),     # page-aligned linear table
    (8, 11),     # ring shorter than 2 pages: partial last page + wraparound
    (4, 10),     # partial last page, no wrap in this chunk
])
def test_fused_chunk_scatter_bitwise(ps, Sc, quant):
    """fused_chunk_scatter == eager _clear_pages + XLA paged_scatter, bit
    for bit on every leaf — covering ring wraparound, a non-page-multiple
    ring's partial last page (its tail rows back no virtual index and must
    never be written), an inactive slot, a fresh page written this chunk
    (clear folded into first-write masking) and a pending page not written
    at all (whole-page clear job)."""
    B, T = 3, 4
    P = -(-Sc // ps)
    NP = 1 + B * P + 3
    table = np.arange(B * P).reshape(B, P).astype(np.int32) + 1
    rng = np.random.default_rng(5)
    cache = _maint_pool(7, NP, ps, quant)
    # slot 0 wraps the ring, slot 1 starts cold, slot 2 is inactive
    pos0 = jnp.asarray([Sc - 2, 0, 0], jnp.int32)
    n_valid = jnp.asarray([T, T - 1, 0], jnp.int32)
    # slot 1's first page is a fresh alloc; two more pending pages are not
    # written this chunk; rest of the K-wide array is 0-padding
    pending = np.zeros(8, np.int32)
    pending[:3] = [table[1, 0], NP - 1, NP - 2]
    upd = {'k': rng.standard_normal((B, T, 2, 8), np.float32),
           'v': rng.standard_normal((B, T, 2, 8), np.float32)}
    if quant:
        upd = {'k': rng.integers(-127, 128, (B, T, 2, 8)).astype(np.int8),
               'v': rng.integers(-127, 128, (B, T, 2, 8)).astype(np.int8),
               'k_scale': jnp.asarray(
                   rng.random((B, T, 2), np.float32)).astype(jnp.bfloat16),
               'v_scale': jnp.asarray(
                   rng.random((B, T, 2), np.float32)).astype(jnp.bfloat16)}
    upd = {nm: jnp.asarray(v) for nm, v in upd.items()}
    tbl = jnp.asarray(table)

    got = PM.fused_chunk_scatter(cache, upd, pos0, n_valid, tbl, Sc,
                                 jnp.asarray(pending))
    want = paged_scatter(_eager_clear(cache, pending[:3]), upd, pos0,
                         n_valid, tbl, Sc)
    assert set(got) == set(want)
    for nm in want:
        np.testing.assert_array_equal(np.asarray(got[nm]),
                                      np.asarray(want[nm]), err_msg=nm)


def test_cow_page_copy_bitwise():
    """cow_page_copy == gather + masked pad, bit for bit — including rem=0
    (pure clear), rem=ps (pure copy) and the pos leaf's -1 fill."""
    NP, ps = 6, 8
    rng = np.random.default_rng(3)
    pool = {'k': jnp.asarray(rng.standard_normal((NP, ps, 2, 4),
                                                 np.float32)),
            'pos': jnp.asarray(rng.integers(0, 50, (NP, ps)).astype(
                np.int32))}
    sdr = jnp.asarray([[1, 2, 3], [4, 5, 0], [3, 1, ps]], jnp.int32)
    for nm, leaf in pool.items():
        fill = PM.leaf_fill(nm)
        got = PM.cow_page_copy(leaf, sdr, fill=fill)
        want = np.array(leaf)
        for src, dst, rem in np.asarray(sdr):
            row = want[src].copy()
            row[rem:] = fill
            want[dst] = row
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=nm)


# ============================================== compiled-mode parity (-m compiled)
@pytest.mark.compiled
@pytest.mark.parametrize('quant', [False, True])
def test_compiled_attend_matches_oracle_within_bound(quant):
    """Platform-default mode (interpret=None -> ops._interpret): the paged
    attend holds the documented PALLAS_TOL bound vs the gather oracle.
    Interpreted on CPU; on a TPU it would be the compiled kernel the
    engine's 'auto' backend serves with."""
    B, KV, G, d, T, ps, window = 2, 2, 2, 16, 4, 8, 5
    Sc = 11
    P = -(-Sc // ps)
    NP = 1 + B * P
    table = np.arange(B * P).reshape(B, P).astype(np.int32) + 1
    lengths = [Sc + 3, ps - 2]
    k, v, ks, vs = _pool(1, NP, ps, KV, d, quant)
    cpos = _fill_positions(NP, ps, table, lengths, Sc)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, T, KV, G, d))
    pos0 = jnp.asarray([le - 1 for le in lengths], jnp.int32)
    args = (q, k, v, cpos, jnp.asarray(table), pos0)
    kw = dict(scale=d ** -0.5, window=window, k_scale_pages=ks,
              v_scale_pages=vs)
    got = paged_attention(*args, **kw)            # interpret by platform
    want = ref.paged_attention_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **PALLAS_TOL)


@pytest.mark.compiled
def test_compiled_maintenance_stays_bitwise():
    """Platform-default compile of the maintenance kernels: the bitwise
    contract (no tolerance at all) must survive compilation — the one-hot
    matmul scatter and the COW DMA round int8/int32/f32 exactly."""
    B, T, ps, Sc = 2, 4, 8, 16
    P = Sc // ps
    NP = 1 + B * P + 1
    table = np.arange(B * P).reshape(B, P).astype(np.int32) + 1
    cache = _maint_pool(11, NP, ps, quant=False)
    rng = np.random.default_rng(12)
    upd = {'k': jnp.asarray(rng.standard_normal((B, T, 2, 8), np.float32)),
           'v': jnp.asarray(rng.standard_normal((B, T, 2, 8), np.float32))}
    pos0 = jnp.asarray([Sc - 1, 2], jnp.int32)
    n_valid = jnp.asarray([T, T], jnp.int32)
    pending = np.zeros(4, np.int32)
    pending[0] = NP - 1
    got = PM.fused_chunk_scatter(cache, upd, pos0, n_valid,
                                 jnp.asarray(table), Sc,
                                 jnp.asarray(pending))
    want = paged_scatter(_eager_clear(cache, pending[:1]), upd, pos0,
                         n_valid, jnp.asarray(table), Sc)
    for nm in want:
        np.testing.assert_array_equal(np.asarray(got[nm]),
                                      np.asarray(want[nm]), err_msg=nm)


# ================================================================ resolution
def test_get_backend_resolution():
    assert get_backend(None) is REFERENCE
    assert get_backend('reference') is REFERENCE
    assert get_backend('pallas') is PALLAS
    assert get_backend(PALLAS) is PALLAS
    assert get_backend('auto') is auto_backend()
    # 'auto' is the platform pick: pallas where the kernels compile (TPU),
    # reference where they would run interpreted
    from repro.kernels.ops import _interpret
    assert auto_backend() is (REFERENCE if _interpret() else PALLAS)
    assert set(BACKENDS) == {'reference', 'pallas'}
    with pytest.raises(ValueError):
        get_backend('nope')
