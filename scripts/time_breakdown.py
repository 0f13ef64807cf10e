"""Per-phase K-FAC step time breakdown via the exclude-parts subtraction method.

Capability parity with the reference's breakdown analysis
(reference: scripts/time_breakdown.py:1-83 — stacked phase times for SGD vs
K-FAC; fed by --exclude-parts ablation runs, kfac_preconditioner_base.py:96-99).

On TPU the step is one fused XLA program, so phases cannot be wall-clocked
inside it; this script measures them the way the reference's method does —
by differencing ablated variants (each `exclude_parts` setting compiles a
program *without* that phase):

  FactorComp   = t(full) - t(exclude ComputeFactor... everything downstream)
  InverseComp  = ...

Run it directly; it builds the CIFAR ResNet flagship config and prints the
stacked breakdown. Use --model/--batch for other shapes.

Usage: python scripts/time_breakdown.py [--model resnet32] [--batch 128]
       [--variant eigen_dp] [--num-devices 1]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from scripts.utils import build_vision_model

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import training
from kfac_pytorch_tpu.utils import profiling


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--model', default='resnet32')
    ap.add_argument('--batch', type=int, default=128)
    ap.add_argument('--variant', default='eigen_dp')
    ap.add_argument('--num-devices', type=int, default=1)
    ap.add_argument('--iters', type=int, default=10)
    args = ap.parse_args()

    rng = np.random.RandomState(0)
    model, img, ncls = build_vision_model(args.model)
    batch = {'input': jnp.asarray(rng.randn(args.batch, img, img, 3),
                                  jnp.float32),
             'label': jnp.asarray(rng.randint(0, ncls, args.batch))}
    tx = training.sgd(0.1, momentum=0.9, weight_decay=5e-4)

    def ce(outputs, b):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, b['label']).mean()

    def make_step(exclude_parts):
        precond = kfac.KFAC(variant=args.variant, lr=0.1, damping=0.003,
                            fac_update_freq=1, kfac_update_freq=1,
                            num_devices=args.num_devices, axis_name=None,
                            exclude_parts=exclude_parts)
        state = training.init_train_state(model, tx, precond,
                                          jax.random.PRNGKey(0),
                                          batch['input'])
        step = training.build_train_step(model, tx, precond, ce,
                                         extra_mutable=('batch_stats',))
        return step, state

    breakdown = profiling.exclude_parts_breakdown(
        make_step, batch, iters=args.iters, lr=0.1, damping=0.003)

    # SGD reference (no preconditioner at all)
    state = training.init_train_state(model, tx, None, jax.random.PRNGKey(0),
                                      batch['input'])
    sgd = training.build_train_step(model, tx, None, ce,
                                    extra_mutable=('batch_stats',))
    sgd_t, _, _ = profiling.time_steps(sgd, state, batch, iters=args.iters,
                                       warmup=3)

    total = breakdown['Total']
    print(f'\n{args.model} bs{args.batch} {args.variant} '
          f'nd{args.num_devices} — iter {total * 1e3:.2f} ms '
          f'(SGD {sgd_t * 1e3:.2f} ms, overhead {total / sgd_t:.2f}x)')
    order = ['ComputeFactor', 'CommunicateFactor', 'ComputeInverse',
             'CommunicateInverse']
    rows = ([('FF&BP+update (sgd)', sgd_t),
             ('capture+glue', max(breakdown['Rest'] - sgd_t, 0.0))]
            + [(p, breakdown[p]) for p in reversed(order)])
    for name, t in rows:
        bar = '#' * int(60 * t / total)
        print(f'  {name:<20} {t * 1e3:>8.2f} ms  {bar}')


if __name__ == '__main__':
    main()
