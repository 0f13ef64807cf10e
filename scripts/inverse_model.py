"""Decomposition cost-model data: eigh vs Cholesky-inverse time over factor dims.

Capability parity with the reference's eig-cost probe
(reference: scripts/inverse_model.py:1-42 — `torch.symeig` timing over dims
64..8192 including the real ResNet-50 A/G factor dims) re-designed for the
TPU ops layer: measures both decomposition paths this framework uses
(`ops.sym_eig` for the eigen variants, `ops.psd_inverse` for the inverse
variants) and fits the alpha + beta * d^3 cost model consumed by the
balanced-assignment scheduler (`kfac_pytorch_tpu/parallel/partition.py`).

Usage: python scripts/inverse_model.py [--max-dim 8192] [--csv out.csv]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from scripts.utils import fit_linear, timeit

import jax
import jax.numpy as jnp
import numpy as np

from kfac_pytorch_tpu import ops

# Real ResNet-50 per-layer factor dims (reference: scripts/inverse_model.py:19-20)
RESNET50_A_DIMS = [147, 64, 256, 576, 512, 1024, 1152, 2048, 2304, 4608, 2049]
RESNET50_G_DIMS = [64, 128, 256, 512, 1024, 2048, 1000]


def _spd(rng, dim):
    a = rng.randn(dim, dim).astype(np.float32) / np.sqrt(dim)
    return jnp.asarray(a @ a.T + np.eye(dim, dtype=np.float32))


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--max-dim', type=int, default=8192)
    p.add_argument('--csv', default=None)
    args = p.parse_args()

    dims = [d for d in (64, 128, 256, 512, 1024, 2048, 4096, 8192)
            if d <= args.max_dim]
    dims = sorted(set(dims + [d for d in RESNET50_A_DIMS + RESNET50_G_DIMS
                              if d <= args.max_dim]))
    rng = np.random.RandomState(0)
    eig_fn = jax.jit(ops.sym_eig)
    inv_fn = jax.jit(ops.psd_inverse)

    rows = []
    print(f'{"dim":>6} {"eigh (ms)":>12} {"chol-inv (ms)":>14} {"ratio":>7}')
    for d in dims:
        x = _spd(rng, d)
        te = timeit(eig_fn, x, iters=5)
        ti = timeit(inv_fn, x, iters=5)
        rows.append((d, te, ti))
        print(f'{d:>6} {te * 1e3:>12.3f} {ti * 1e3:>14.3f} {te / ti:>7.2f}')

    # Fit t = alpha + beta * d^3 (least squares) for each path — the cost
    # model the scheduler's `balanced` assignment uses for layer weights.
    d3 = [r[0] ** 3 for r in rows]
    for name, col in (('eigh', 1), ('chol-inv', 2)):
        alpha, beta = fit_linear(d3, [r[col] for r in rows])
        print(f'{name}: t(d) ~= {alpha * 1e3:.3f} ms + {beta * 1e12:.3f} ps * d^3')

    if args.csv:
        with open(args.csv, 'w') as f:
            f.write('dim,eigh_s,cholinv_s\n')
            for d, te, ti in rows:
                f.write(f'{d},{te:.6f},{ti:.6f}\n')
        print('wrote', args.csv)


if __name__ == '__main__':
    main()
