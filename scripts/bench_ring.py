"""Long-context attention throughput: ring vs Ulysses vs dense replicated.

Benchmark for the sequence-parallel subsystem (no reference counterpart —
SURVEY.md §5.7; this is the framework's beyond-parity capability): tokens/s
of one fwd+bwd attention call at a given global sequence length, sequence
sharded over the available mesh, plus the dense replicated baseline while
it still fits.

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/bench_ring.py \
      [--seq-lens 4096 16384] [--heads 8] [--d-head 64] [--impl ring ulysses]
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from scripts.utils import timeit

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu.parallel.ring_attention import (
    ring_attention, ulysses_attention)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--seq-lens', nargs='+', type=int,
                    default=[4096, 16384])
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--d-head', type=int, default=64)
    ap.add_argument('--impl', nargs='+',
                    default=['ring', 'ulysses', 'dense'])
    args = ap.parse_args()

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ('seq',))
    spec = P(None, None, 'seq', None)
    print(f'{n} devices ({devices[0].platform}); B={args.batch} '
          f'H={args.heads} D={args.d_head}; fwd+bwd causal attention')

    impls = {
        'ring': functools.partial(ring_attention, axis_name='seq',
                                  causal=True),
        'ulysses': functools.partial(ulysses_attention, axis_name='seq',
                                     causal=True),
        'dense': functools.partial(ring_attention, axis_name=None,
                                   causal=True),
    }

    for L in args.seq_lens:
        rng = np.random.RandomState(0)
        shape = (args.batch, args.heads, L, args.d_head)
        q = jnp.asarray(rng.randn(*shape), jnp.float32)
        k = jnp.asarray(rng.randn(*shape), jnp.float32)
        v = jnp.asarray(rng.randn(*shape), jnp.float32)
        for name in args.impl:
            fn = impls[name]
            if name == 'dense':
                def run(q, k, v, fn=fn):
                    return (fn(q, k, v) ** 2).sum()
                g = jax.jit(jax.grad(run, argnums=(0, 1, 2)))
                qs, ks, vs = q, k, v
            else:
                if name == 'ulysses' and args.heads % n:
                    print(f'  L={L:>7} {name:>8}: skip (heads % devices)')
                    continue
                def local(q, k, v, fn=fn):
                    loss = (fn(q, k, v).astype(jnp.float32) ** 2).sum()
                    return jax.lax.psum(loss, 'seq')
                sharded = jax.shard_map(
                    lambda q, k, v: jax.grad(local, argnums=(0, 1, 2))(
                        q, k, v),
                    mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
                g = jax.jit(sharded)
                sh = NamedSharding(mesh, spec)
                qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
            try:
                t = timeit(g, qs, ks, vs, warmup=1, iters=3,
                           vary=lambda i: (qs * (1 + 1e-4 * i),
                                           ks, vs))
            except Exception as e:  # OOM for dense at long L
                print(f'  L={L:>7} {name:>8}: failed ({type(e).__name__})')
                continue
            toks = args.batch * L / t
            print(f'  L={L:>7} {name:>8}: {t * 1e3:>9.1f} ms '
                  f'({toks / 1e3:>8.1f}K tok/s)')


if __name__ == '__main__':
    main()
