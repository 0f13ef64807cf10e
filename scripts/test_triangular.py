"""Triangular-structure semantics probe for the Cholesky-inverse path.

Capability parity with the reference's triangular probe
(reference: scripts/test_triangular.py:1-24 — checks the
lower-triangular copy/transpose identity used by its Cholesky inverse,
kfac/utils.py:14-16). Validates the identities the TPU `psd_inverse`
relies on:

  1. cholesky(X) returns lower-triangular L with L @ L.T == X;
  2. reconstructing the full symmetric inverse from the triangular solve
     equals the dense inverse;
  3. tril/triu extraction and symmetrization round-trips.

Usage: python scripts/test_triangular.py [--dim 512]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np

from kfac_pytorch_tpu import ops


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--dim', type=int, default=512)
    args = p.parse_args()
    d = args.dim

    rng = np.random.RandomState(0)
    a = rng.randn(d, d).astype(np.float32) / np.sqrt(d)
    x = jnp.asarray(a @ a.T + np.eye(d, dtype=np.float32))

    # 1. cholesky is lower triangular and reconstructs x
    L = jnp.linalg.cholesky(x)
    assert float(jnp.abs(jnp.triu(L, 1)).max()) == 0.0
    err = float(jnp.abs(L @ L.T - x).max() / jnp.abs(x).max())
    print(f'cholesky reconstruction rel err: {err:.2e}')
    assert err < 1e-4

    # 2. psd_inverse == dense inverse
    inv = ops.psd_inverse(x)
    ref = jnp.linalg.inv(x)
    err = float(jnp.abs(inv - ref).max() / jnp.abs(ref).max())
    print(f'psd_inverse vs dense inverse rel err: {err:.2e}')
    assert err < 1e-2

    # 3. symmetrization round-trip: tril + strict-tril^T rebuilds symmetric
    sym = jnp.tril(inv) + jnp.tril(inv, -1).T
    err = float(jnp.abs(sym - inv).max())
    print(f'tril symmetrization max err: {err:.2e}')
    assert err < 1e-4

    print('ok')


if __name__ == '__main__':
    main()
