"""Compile the serving path's Pallas kernels for a TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, too much VMEM, a kernel the compiler
would have to partition. Here each kernel of the serving path is lowered
and compiled at real model widths for one chip of a described ``v5e:2x2``
topology — nothing runs, so these tests check compilation only.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_maintenance as PM
from repro.kernels.gather_rope import gather_rope
from repro.kernels.paged_attention import paged_attention

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
NP, B = 577, 8              # pool pages, slots


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert 'tpu_custom_call' in compiled.as_text()
        return compiled
    return run


# (T, KV, G, d, page_size, pages per slot, dtype, window, int8 pool,
#  slots, pool pages)
ATTN = {
    'gemma3-1b-decode': (1, 1, 4, 256, 16, 64, BF16, 512, False, B, NP),
    'gemma3-1b-chunk64': (64, 1, 4, 256, 16, 64, BF16, 512, False, B, NP),
    'mistral-7b-chunk16': (16, 8, 4, 128, 16, 64, BF16, 0, False, B, NP),
    'mistral-7b-int8': (16, 8, 4, 128, 32, 32, BF16, 0, True, B, NP),
    # the benchmark cell's grid: 64 slots x 256 pages, the bounded walk
    'mistral-7b-cell-decode': (1, 8, 4, 128, 16, 256, BF16, 0, False, 64,
                               10000),
    'mistral-7b-cell-chunk64': (64, 8, 4, 128, 16, 256, BF16, 0, False, 64,
                                10000),
}


@pytest.mark.parametrize('case', sorted(ATTN))
def test_paged_attention_compiles(case, compile_for_chip):
    T, KV, G, d, ps, P, dt, window, quant, B, NP = ATTN[case]

    def f(q, k, v, cpos, table, pos0, *scales):
        ks, vs = scales if quant else (None, None)
        return paged_attention(q, k, v, cpos, table, pos0, scale=d ** -0.5,
                               window=window, k_scale_pages=ks,
                               v_scale_pages=vs, interpret=False)
    pool = ((NP, ps, KV, d), I8 if quant else dt)
    shapes = [((B, T, KV, G, d), dt), pool, pool, ((NP, ps), I32),
              ((B, P), I32), ((B,), I32)]
    if quant:
        shapes += [((NP, ps, KV), dt)] * 2
    compile_for_chip(f, *shapes)


@pytest.mark.parametrize('T', [1, 16])
def test_mla_paged_attention_compiles(T, compile_for_chip):
    """deepseek-v2-lite latent attention: r=512, dr=64, 16 heads."""
    H, r, dr, ps, P = 16, 512, 64, 16, 64

    def f(q, ckv, kpe, cpos, table, pos0):
        return paged_attention(q, ckv, None, cpos, table, pos0,
                               scale=(128 + dr) ** -0.5, k2_pages=kpe,
                               mla_split=r, interpret=False)
    compile_for_chip(f, ((B, T, 1, H, r + dr), F32), ((NP, 16, 1, r), BF16),
                     ((NP, ps, 1, dr), BF16), ((NP, ps), I32), ((B, P), I32),
                     ((B,), I32))


# leaf name -> (trailing dims, dtype) at gemma3-1b / mistral-7b widths
LEAVES = {
    'gemma3-1b-k': ((1, 256), BF16),
    'mistral-7b-k': ((8, 128), BF16),
    'mistral-7b-int8-k': ((8, 128), I8),
    'mistral-7b-int8-k_scale': ((8,), BF16),
    'pos': ((), I32),
}


@pytest.mark.parametrize('leaf', sorted(LEAVES))
def test_fused_page_write_compiles(leaf, compile_for_chip):
    tail, dt = LEAVES[leaf]
    ps = 32 if dt == I8 else 16
    fill = PM.leaf_fill(leaf.rsplit('-', 1)[-1])

    def f(pool, vals, jobs):
        return PM.fused_page_write(pool, vals, jobs, Sc=1024, fill=fill,
                                   interpret=False)
    compile_for_chip(f, ((NP, ps) + tail, dt), ((B, 64) + tail, dt),
                     ((136, 6), I32))


@pytest.mark.parametrize('leaf', sorted(LEAVES))
def test_cow_page_copy_compiles(leaf, compile_for_chip):
    tail, dt = LEAVES[leaf]
    ps = 32 if dt == I8 else 16
    fill = PM.leaf_fill(leaf.rsplit('-', 1)[-1])

    def f(pool, sdr):
        return PM.cow_page_copy(pool, sdr, fill=fill, interpret=False)
    compile_for_chip(f, ((NP, ps) + tail, dt), ((4, 3), I32))


@pytest.mark.parametrize('N', [8, 512])
def test_gather_rope_compiles(N, compile_for_chip):
    """gemma3-1b's precomputed table: 262144 rows of [x | q | k | v], 2688
    wide; q (4 heads) and k (1 head) of 256 rotate."""
    segs = ((1152, 4, 256), (2176, 1, 256))

    def f(table, ids, pos):
        return gather_rope(table, ids, pos, segs=segs, theta=10_000.0,
                           interpret=False)
    compiled = compile_for_chip(f, ((262144, 2688), BF16), ((N,), I32),
                                ((N,), I32))
    # the table is read in place: no relayout copy of it
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
