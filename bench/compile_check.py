#!/usr/bin/env python3
"""Compile a configuration's serving step for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --config mistral-7b-v0.3

Lowers and compiles, at the configuration's real widths and engine geometry
(``bench/configs/<config>.json``), the program the engine's paged chunk step
runs: layer 0 from the precomputed table through the fused gather->RoPE
kernel, the Pallas paged attention and fused page maintenance, then the
head on each slot's last lane. Shapes only: nothing is allocated. Prints
the compiler's memory analysis for one chip (weights, table and KV pool are
the arguments), so a geometry that does not fit is refused here and not on
the chip. It is a rehearsal, never a measurement.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--config', required=True)
    ap.add_argument('--chunk', type=int, nargs='*', default=None,
                    help='query lanes per slot to compile (default: 1 and '
                         'the chunk size)')
    ap.add_argument('--num-pages', type=int, default=0,
                    help='KV pool pages to try (default: the file\'s)')
    args = ap.parse_args(argv)
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    sys.path[:0] = [os.path.join(ROOT, 'src'), ROOT]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import manifest as M
    from repro.kernels import ops
    from repro.models import attention as A
    from repro.models.attn_backend import get_backend
    from repro.models.model import Model
    from repro.models.transformer import lm_logits, pad_table_for_fused

    jax.config.update('jax_enable_compilation_cache', False)
    ops._interpret = lambda: False            # compile the kernels for Mosaic
    spec = M.data('configs', f'{args.config}.json')
    geo = dict(spec['engine'])
    geo['num_pages'] = args.num_pages or geo['num_pages']
    cfg = M.arch(spec).model_config(args.config, spec)
    model = Model(cfg)
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    table = jax.eval_shape(lambda p: pad_table_for_fused(
        model.build_table(p)), params)
    B, ps = geo['max_slots'], geo['page_size']
    states = jax.eval_shape(lambda: model.make_states(
        B, geo['max_seq'], jnp.dtype(cfg.dtype), chunk=geo['chunk_size'],
        num_pages=geo['num_pages'], page_size=ps))
    backend = get_backend('pallas')

    def chunk_step(weights, states, tokens, pos, n_valid, pt, rt, pending):
        params, table = weights
        h, states, _ = model.decode_step(
            params, tokens, states, pos, precomputed=table, n_valid=n_valid,
            return_hidden=True, fused_gather_rope=True,
            paged=A.PageTables(pt, rt, 0, pending), return_stats=True,
            attn_backend=backend)
        idx = jnp.maximum(n_valid - 1, 0)[:, None, None]
        logits = lm_logits(params, jnp.take_along_axis(h, idx, 1), cfg)
        return states, jnp.argmax(logits[:, 0], -1)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    for T in args.chunk or [1, geo['chunk_size']]:
        compiled = jax.jit(chunk_step, donate_argnums=1).lower(
            place((params, table)), place(states), i32(B, T), i32(B),
            i32(B), i32(B, geo['max_seq'] // ps), i32(B, 1),
            i32(64)).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        gib = lambda n: f'{n / 2**30:.3f} GiB'
        print(f'{args.config} T={T} pages={geo["num_pages"]}: arguments '
              f'{gib(mem.argument_size_in_bytes)}, temporaries '
              f'{gib(mem.temp_size_in_bytes)}, outputs '
              f'{gib(mem.output_size_in_bytes)} (aliased '
              f'{gib(mem.alias_size_in_bytes)}); Mosaic kernels: '
              f'{text.count("tpu_custom_call")}', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
