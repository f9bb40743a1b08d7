"""Chunked-prefill equivalence: the multi-token fast path must match
token-by-token prefill — logits at every prompt position AND final KV-cache
contents — within ``FP32_TOL`` for chunk sizes {1, 4, 32}, with and without
the precomputed first-layer table, across serial/parallel blocks and
sliding-window layers. Plus engine-level invariants: identical greedy tokens
and the ~chunk_size× step reduction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.models import attention as A
from repro.models.model import Model
from repro.serving import Request, ServingEngine

CHUNKS = (1, 4, 32)
PROMPT_LEN = 13          # not a multiple of any chunk size -> ragged tail
# A 32-row chunk runs the layer matmuls at another shape than a 1-row step,
# and XLA's CPU backend may block (reassociate) their fp32 sums differently:
# observed up to 4.3e-7 on logits of magnitude ~0.6 and 2.9e-6 on cached
# K/V. The bound leaves headroom of a few fp32 ulps per reassociated sum.
FP32_TOL = dict(atol=1e-5, rtol=1e-5)


def mkmodel(block_type='serial', pattern=('global',), window=8):
    cfg = ModelConfig(name='t-chunk', arch_class='dense', num_layers=3,
                      d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=97, max_seq_len=64,
                      dtype='float32', block_type=block_type, pattern=pattern,
                      window=window, glu=(block_type == 'serial'))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def token_by_token(model, params, toks, seq, pre, chunk):
    """Reference prefill: T=1 decode steps on identically-sized states
    (windowed rings get the same chunk slack, so cache trees compare equal).
    """
    B = toks.shape[0]
    states = model.make_states(B, seq, jnp.float32, chunk=chunk)
    logits = []
    for t in range(toks.shape[1]):
        lg, states = model.decode_step(params, toks[:, t:t + 1], states,
                                       jnp.full((B,), t, jnp.int32),
                                       precomputed=pre)
        logits.append(lg[:, 0])
    return jnp.stack(logits, 1), states


def chunked(model, params, toks, seq, pre, chunk):
    B, P = toks.shape
    states = model.make_states(B, seq, jnp.float32, chunk=chunk)
    logits, p = [], 0
    while p < P:
        n = min(chunk, P - p)
        block = jnp.zeros((B, chunk), jnp.int32).at[:, :n].set(
            toks[:, p:p + n])
        lg, states = model.decode_step(
            params, block, states, jnp.full((B,), p, jnp.int32),
            n_valid=jnp.full((B,), n, jnp.int32), precomputed=pre)
        logits.append(lg[:, :n])
        p += n
    return jnp.concatenate(logits, 1), states


@pytest.mark.parametrize('use_table', [False, True],
                         ids=['baseline', 'precomputed'])
@pytest.mark.parametrize('block_type,pattern',
                         [('serial', ('global',)),
                          ('parallel', ('global',)),
                          ('serial', ('local', 'global'))],
                         ids=['serial', 'parallel', 'windowed'])
def test_chunked_prefill_bit_identical(block_type, pattern, use_table):
    cfg, model, params = mkmodel(block_type, pattern)
    pre = model.build_table(params) if use_table else None
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, PROMPT_LEN), 3, 90)
    for chunk in CHUNKS:
        want_lg, want_st = token_by_token(model, params, toks, 64, pre, chunk)
        got_lg, got_st = chunked(model, params, toks, 64, pre, chunk)
        np.testing.assert_allclose(np.asarray(got_lg), np.asarray(want_lg),
                                   **FP32_TOL, err_msg=f'logits chunk={chunk}')
        for g, w in zip(jax.tree_util.tree_leaves(got_st),
                        jax.tree_util.tree_leaves(want_st)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       **FP32_TOL,
                                       err_msg=f'cache chunk={chunk}')


def test_chunked_prefill_int8_cache_bit_identical():
    """The quantised cache path quantises chunk writes identically."""
    cfg, model, params = mkmodel()
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, PROMPT_LEN), 3, 90)
    B = 2
    for chunk in (4, 32):
        ref_st = model.make_states(B, 64, jnp.float32, kv_quant=True,
                                   chunk=chunk)
        for t in range(PROMPT_LEN):
            _, ref_st = model.decode_step(params, toks[:, t:t + 1], ref_st,
                                          jnp.full((B,), t, jnp.int32))
        st = model.make_states(B, 64, jnp.float32, kv_quant=True, chunk=chunk)
        p = 0
        while p < PROMPT_LEN:
            n = min(chunk, PROMPT_LEN - p)
            block = jnp.zeros((B, chunk), jnp.int32).at[:, :n].set(
                toks[:, p:p + n])
            _, st = model.decode_step(params, block, st,
                                      jnp.full((B,), p, jnp.int32),
                                      n_valid=jnp.full((B,), n, jnp.int32))
            p += n
        for g, w in zip(jax.tree_util.tree_leaves(st),
                        jax.tree_util.tree_leaves(ref_st)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_cache_update_chunk_matches_sequential_ring_wrap():
    """Chunk writes that lap the ring resolve to the final write per slot."""
    cfg, model, params = mkmodel()
    B, T, Sc = 2, 16, 8        # chunk twice as long as the ring
    cache = A.make_cache(cfg, B, Sc, window=Sc, dtype=jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(0), (B, T, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, T, 2, 16))
    pos0 = jnp.array([0, 5], jnp.int32)
    n_valid = jnp.array([16, 11], jnp.int32)
    seq = jax.tree_util.tree_map(lambda x: x, cache)
    for t in range(T):
        upd = A.cache_update(seq, k[:, t:t + 1], v[:, t:t + 1], pos0 + t)
        keep = (t < n_valid)
        seq = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                keep.reshape((B,) + (1,) * (new.ndim - 1)), new, old),
            upd, seq)
    got = A.cache_update_chunk(cache, k, v, pos0, n_valid)
    for nm in got:
        np.testing.assert_array_equal(np.asarray(got[nm]),
                                      np.asarray(seq[nm]), err_msg=nm)


def test_recurrent_arch_chunks_no_fallback():
    """Chunked prefill is universal: the engine keeps chunk_size for a
    recurrent (xLSTM) stack — the per-architecture fallback (the old
    ``Model.supports_chunked_decode`` gate) is gone. Full cross-arch
    bit-identity coverage lives in test_chunked_all_archs.py."""
    from repro.config import SSMConfig
    cfg = ModelConfig(name='t-xlstm', arch_class='ssm', num_layers=2,
                      d_model=32, num_heads=4, num_kv_heads=4, head_dim=8,
                      d_ff=0, vocab_size=64, max_seq_len=64,
                      pattern=('mlstm', 'slstm'), pos='none',
                      tie_embeddings=True, dtype='float32',
                      ssm=SSMConfig(conv_kernel=4, expand=2,
                                    num_ssm_heads=4))
    model = Model(cfg)
    assert not hasattr(model, 'supports_chunked_decode')
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_slots=1, max_seq=32, chunk_size=8)
    assert eng.chunk_size == 8            # chunking sticks for SSM stacks
    r = Request(uid=0, prompt=np.arange(4) + 3, max_new_tokens=3)
    eng.submit(r)
    eng.run()
    assert len(r.generated) == 3


# ------------------------------------------------------------------ engine
def mkreq(uid, seed, n=8, plen=23):
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                           (plen,), 3, 90))
    return Request(uid=uid, prompt=prompt, max_new_tokens=n)


@pytest.mark.parametrize('use_table', [False, True],
                         ids=['baseline', 'precomputed'])
def test_chunked_engine_matches_token_engine(use_table):
    cfg, model, params = mkmodel()
    pre = model.build_table(params) if use_table else None
    for chunk in (4, 32):
        e1 = ServingEngine(model, params, max_slots=2, max_seq=64,
                           precomputed=pre)
        e2 = ServingEngine(model, params, max_slots=2, max_seq=64,
                           precomputed=pre, chunk_size=chunk)
        r1 = [mkreq(i, 20 + i) for i in range(5)]
        r2 = [mkreq(i, 20 + i) for i in range(5)]
        for r in r1:
            e1.submit(r)
        for r in r2:
            e2.submit(r)
        e1.run()
        e2.run()
        for a, b in zip(r1, r2):
            assert a.generated == b.generated
        assert e2.steps < e1.steps      # prefill actually got chunked


def test_fused_gather_rope_engine_matches():
    """gather→RoPE→attention via the Pallas kernel: same greedy tokens."""
    cfg, model, params = mkmodel()
    table = model.build_table(params)
    base = ServingEngine(model, params, max_slots=2, max_seq=64,
                         precomputed=table, chunk_size=8)
    fused = ServingEngine(model, params, max_slots=2, max_seq=64,
                          precomputed=table, chunk_size=8,
                          fused_gather_rope=True)
    assert fused.fused_gather_rope
    rb = [mkreq(i, 50 + i) for i in range(4)]
    rf = [mkreq(i, 50 + i) for i in range(4)]
    for r in rb:
        base.submit(r)
    for r in rf:
        fused.submit(r)
    base.run()
    fused.run()
    for a, b in zip(rb, rf):
        assert a.generated == b.generated


def test_mixed_prefill_decode_scheduling():
    """A long-prompt request admitted while another slot is mid-decode:
    both finish, and the decoding slot's tokens are unaffected by its
    neighbour's chunked prefill."""
    cfg, model, params = mkmodel()
    solo = ServingEngine(model, params, max_slots=1, max_seq=64, chunk_size=8)
    a_solo = mkreq(0, 7, n=12, plen=5)
    solo.submit(a_solo)
    solo.run()

    eng = ServingEngine(model, params, max_slots=2, max_seq=64, chunk_size=8)
    a = mkreq(0, 7, n=12, plen=5)
    eng.submit(a)
    # let request a finish its prefill and start decoding, then admit b
    for _ in range(6):
        eng.step_once()
    assert a.generated, 'request a should be decoding by now'
    b = mkreq(1, 8, n=4, plen=30)
    eng.submit(b)
    eng.run()
    assert a.done and b.done
    assert a.generated == a_solo.generated
