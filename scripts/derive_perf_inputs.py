"""Derive the analytic perf model's inputs: per-program XLA cost
analysis of the compiled train-step variants (VERDICT r4 #1).

Compiles — on the CPU backend, where compilation needs no chip — the
same cond-free step programs bench.py times on hardware (each
(update_factors, update_inverse, update_basis) combination is its own
jitted program, training.build_train_step), and records XLA's
post-optimization ``cost_analysis()`` flops / bytes-accessed totals.
Dot/conv flop counts are backend-independent; LAPACK custom calls
(eigh / Cholesky / triangular solve on CPU) carry NO flop count, which
is exactly why kfac_pytorch_tpu/perfmodel.py reconstructs the two
decomposition phases from fenced chip measurements (eigh) and analytic
counts (Cholesky) instead of from these totals.

Writes kfac_pytorch_tpu/data/perf_inputs_resnet50_bs32.json (committed;
the perf model and bench.py's `predicted` block read it — regenerate
only when the engine's per-step math changes).

Usage:
  JAX_PLATFORMS=cpu python scripts/derive_perf_inputs.py          # official
  DERIVE_MODEL=resnet20 DERIVE_IMG=32 DERIVE_BATCH=8 ... --out X  # smoke
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import models, training

MODEL = os.environ.get('DERIVE_MODEL', 'resnet50')
BATCH = int(os.environ.get('DERIVE_BATCH', 32))
IMG = int(os.environ.get('DERIVE_IMG', 224))
OFFICIAL = (MODEL, BATCH, IMG) == ('resnet50', 32, 224)
DEFAULT_OUT = os.path.join(os.path.dirname(__file__), '..',
                           'kfac_pytorch_tpu', 'data',
                           'perf_inputs_resnet50_bs32.json')


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _cost(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {'flops': float(ca.get('flops', 0.0)),
            'bytes': float(ca.get('bytes accessed', 0.0))}


def analyze(variant, combos):
    """Compile each (uf, ui, ub) combo of one variant's step and return
    {tag: {flops, bytes}} plus the factor plan's bucket table."""
    rng = np.random.RandomState(0)
    n_classes = 1000 if IMG >= 64 else 10
    batch = {'input': jnp.asarray(rng.randn(BATCH, IMG, IMG, 3),
                                  jnp.bfloat16),
             'label': jnp.asarray(rng.randint(0, n_classes, BATCH))}
    model = models.get_model(MODEL, num_classes=n_classes,
                             dtype=jnp.bfloat16)
    tx = training.sgd(0.0125, momentum=0.9, weight_decay=5e-5)
    precond = None
    if variant is not None:
        precond = kfac.KFAC(variant=variant, lr=0.0125, damping=0.002,
                            fac_update_freq=1, kfac_update_freq=1,
                            num_devices=1, axis_name=None,
                            assignment='balanced')
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0),
                                      batch['input'])
    step = training.build_train_step(model, tx, precond, _ce,
                                     extra_mutable=('batch_stats',))
    hyper = training.KFACHyperParams(lr=jnp.float32(0.0125),
                                     damping=jnp.float32(0.002))
    out = {}
    for tag, (uf, ui, ub) in combos.items():
        t0 = time.time()
        if variant is None:
            prog = step.make_variant(False, False)
        else:
            prog = step.make_variant(uf, ui, ub)
        out[tag] = _cost(prog.lower(state, batch, hyper).compile())
        print(f'{tag:>22}: flops={out[tag]["flops"]:.4g} '
              f'bytes={out[tag]["bytes"]:.4g} '
              f'({time.time() - t0:.0f}s compile)', flush=True)
    buckets = None
    if precond is not None:
        buckets = [[int(b.n_rows), int(dim)]
                   for dim, b in sorted(precond.plan.buckets.items())]
    return out, buckets


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--out', default=DEFAULT_OUT)
    args = p.parse_args()
    if not OFFICIAL and os.path.abspath(args.out) == os.path.abspath(
            DEFAULT_OUT):
        p.error('smoke config (DERIVE_* overrides set) would overwrite '
                'the committed official inputs file — pass --out')

    programs = {}
    sgd, _ = analyze(None, {'sgd': (False, False, True)})
    programs.update(sgd)
    inv, buckets = analyze('inverse_dp', {
        'inverse_dp_base': (False, False, True),
        'inverse_dp_factor': (True, False, True),
        'inverse_dp_full': (True, True, True),
    })
    programs.update(inv)
    eig, _ = analyze('eigen_dp', {
        'eigen_dp_base': (False, False, True),
        'eigen_dp_factor': (True, False, True),
        'eigen_dp_full': (True, True, True),
        'eigen_dp_refresh': (True, True, False),
    })
    programs.update(eig)
    ek, _ = analyze('ekfac', {'ekfac_factor': (True, False, True)})
    programs.update(ek)

    doc = {
        'meta': {
            'model': MODEL, 'batch': BATCH, 'img': IMG,
            'official': OFFICIAL,
            'backend': jax.default_backend(),
            'jax_version': jax.__version__,
            'derived_by': 'scripts/derive_perf_inputs.py',
            'note': ('post-optimization compiled cost_analysis totals; '
                     'LAPACK custom calls (eigh/cholesky/trsm) count 0 '
                     'flops on this backend — perfmodel.py reconstructs '
                     'those phases from fenced chip constants and '
                     'analytic counts'),
        },
        'programs': programs,
        'buckets': buckets,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, 'w') as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print('wrote', args.out)


if __name__ == '__main__':
    main()
