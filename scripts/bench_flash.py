"""Single-chip attention kernel A/B: Pallas flash block vs plain XLA.

Times one fwd+bwd causal attention call at growing sequence length with
both block implementations (`parallel/ring_attention.py` dispatch). The
XLA path materializes the [L, L] score block in HBM; the Pallas kernel
streams K/V tiles through VMEM — the gap grows with L until the XLA path
OOMs, which is the kernel's reason to exist.

Usage: python scripts/bench_flash.py [--seq-lens 1024 4096 16384]
       [--heads 8] [--d-head 64] [--batch 1]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from scripts.utils import timeit

import jax
import jax.numpy as jnp
import numpy as np

from kfac_pytorch_tpu.parallel.ring_attention import ring_attention


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--seq-lens', nargs='+', type=int,
                    default=[1024, 4096, 8192, 16384])
    ap.add_argument('--batch', type=int, default=1)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--d-head', type=int, default=64)
    ap.add_argument('--impls', nargs='+', default=None,
                    help="default: xla + (pallas on tpu | "
                         "pallas_interpret elsewhere)")
    ap.add_argument('--bwd-impls', nargs='+', default=None,
                    choices=['pallas', 'recompute'],
                    help='A/B the pallas-path backward: each entry times '
                         'the pallas block impl with this backward '
                         '(KFAC_ATTN_BWD_IMPL is set before tracing)')
    args = ap.parse_args()

    on_tpu = jax.default_backend() == 'tpu'
    if args.impls and args.bwd_impls:
        raise SystemExit('--impls and --bwd-impls are mutually exclusive '
                         '(bwd mode pins the pallas forward)')
    impls = args.impls or ['xla', 'pallas' if on_tpu else
                           'pallas_interpret']
    tile_env = [k for k in ('KFAC_FLASH_TQ', 'KFAC_FLASH_TK')
                if k in os.environ]
    print(f'device: {jax.devices()[0]}; B={args.batch} H={args.heads} '
          f'D={args.d_head}; fwd+bwd causal attention')
    if tile_env:
        # report the EFFECTIVE tile per length — _fwd_tile clamps/rounds
        # the request (e.g. 480->128), so echoing the raw env would
        # misattribute sweep rows
        from kfac_pytorch_tpu.ops.pallas_attention import _fwd_tile
        for L in args.seq_lens:
            eff = {k: _fwd_tile(k, 128, L) for k in tile_env}
            print(f'  L={L:>7} effective tiles: {eff}')

    for L in args.seq_lens:
        rng = np.random.RandomState(0)
        shape = (args.batch, args.heads, L, args.d_head)
        q = jnp.asarray(rng.randn(*shape), jnp.float32)
        k = jnp.asarray(rng.randn(*shape), jnp.float32)
        v = jnp.asarray(rng.randn(*shape), jnp.float32)
        outs = {}
        pallas_impl = 'pallas' if on_tpu else 'pallas_interpret'
        runs = ([(i, None) for i in impls] if not args.bwd_impls else
                [(pallas_impl, b) for b in args.bwd_impls])
        baseline_missing = False  # bwd mode: did the first impl fail?
        for run_idx, (impl, bwd) in enumerate(runs):
            if bwd is not None:
                os.environ['KFAC_ATTN_BWD_IMPL'] = bwd
            tag = impl if bwd is None else f'{impl}/bwd={bwd}'

            def loss(q, k, v, impl=impl):
                out = ring_attention(q, k, v, axis_name=None, causal=True,
                                     block_impl=impl)
                return (out.astype(jnp.float32) ** 2).sum()

            fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            try:
                val, grads = fn(q, k, v)  # warms the jit cache
                if bwd is None:
                    # impl mode: forward losses are the agreement basis
                    outs[tag] = float(val)
                elif run_idx == 0:
                    # bwd mode: hold the FIRST impl's grads only; the
                    # second run compares and frees immediately (keeping
                    # both backends' dq/dk/dv would hold 6 full-length
                    # tensors on the host at large L)
                    outs[tag] = [np.asarray(g) for g in grads]
                elif baseline_missing:
                    print(f'  L={L:>7} grad agreement SKIPPED '
                          '(baseline impl failed — timings below are '
                          'unverified)')
                else:
                    prev = next(iter(outs.values()))
                    rels = [float(np.linalg.norm(np.asarray(gb) - ga)
                                  / max(np.linalg.norm(ga), 1e-9))
                            for ga, gb in zip(prev, grads)]
                    print(f'  L={L:>7} grad agreement (dq/dk/dv rel): '
                          + ' '.join(f'{r:.2e}' for r in rels))
                    outs.clear()
                del grads
                # vary q per iteration: identical (program, input)
                # repeats can be served from remote execution caches
                t = timeit(fn, q, k, v, warmup=1, iters=3,
                           vary=lambda i: (q * (1 + 1e-4 * i), k, v))
                print(f'  L={L:>7} {tag:>22}: {t * 1e3:>9.2f} ms '
                      f'({args.batch * L / t / 1e3:>8.1f}K tok/s)')
            except Exception as e:
                if bwd is not None and run_idx == 0 and tag not in outs:
                    # only when the baseline GRADS were never stored — a
                    # later timeit failure still leaves a usable baseline
                    baseline_missing = True
                print(f'  L={L:>7} {tag:>22}: failed '
                      f'({type(e).__name__}: {str(e)[:80]})')
        if not args.bwd_impls and len(outs) == 2:
            a, b = list(outs.values())
            rel = abs(a - b) / max(abs(a), 1e-9)
            print(f'  L={L:>7} loss agreement: rel diff {rel:.2e}')


if __name__ == '__main__':
    main()
