#!/usr/bin/env python3
"""What the plain reference's host arithmetic costs, before a chip call:
``kfac_plain``'s ``_inverse_of`` of both damped factors and the two solves
``A^-1 (G^-1 dW)'``, in float64, for a list of layers.

    python3 benchmarks/tools/reference_cost.py [--steps N] <list> [<list> ...]
    python3 benchmarks/tools/reference_cost.py --steps 2 bert-base

A list is a name from ``LISTS`` below or ``d_in,d_out,count[;...]``. One
layer of each shape is timed (the best of ``REPEATS``) and multiplied by
its count: factorisations happen on the reference's steps that update the
decomposition (one of a cell's first ``--steps`` at cadence 10), solves on
every step. Host only: it touches no device, so it can run on the chip
machine's host beside nothing else (``chiprun -- python3 ...``), where the
reference runs. The verdict holds the sum against what a traced run's
other parts leave of the driver's 360 s."""

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import files  # noqa: E402

REPEATS = 2
RUN_LIMIT_S = 360.0
#: a warm traced run's parts other than the reference (BERT-base, PR 28's
#: table in benchmarks/README.md: set-up 55.5, window 30.2, traced 27.0,
#: plan 0.1, first-order 10.3, reduce 10.0)
OTHER_PARTS_S = 133.1

# (d_in, d_out, layers): d_in counts the bias's homogeneous coordinate
LISTS = {
    # 12 x (query, key, value, attention output | intermediate | ffn
    # output) + the span head
    'bert-base': [(769, 768, 48), (769, 3072, 12), (3073, 768, 12),
                  (769, 2, 1)],
    # PERF.md section 7, "K-FAC state at language-model widths": 1 dense +
    # 4 expert blocks at hidden 2,048 with latent attention on 4 heads (q
    # 2,048 -> 768, kv_a 2,048 -> 576, kv_b 512 -> 1,024, o 512 -> 2,048),
    # a dense block's gate / up / down at 6,144, an expert block's shared
    # expert at 1,536 and 8 routed experts at 768, no biases
    'planned-sparse': [(2048, 768, 5), (2048, 576, 5), (512, 1024, 5),
                       (512, 2048, 5),
                       (2048, 6144, 2), (6144, 2048, 1),
                       (2048, 1536, 8), (1536, 2048, 4),
                       (2048, 768, 64), (768, 2048, 32)],
}


def kfac_shaped(dim, rank, rng):
    """A damped running average as K-FAC has it after a step: 5 % identity,
    95 % a covariance of ``rank`` rows, plus the damping."""
    rows = rng.standard_normal((min(rank, dim), dim))
    out = 0.95 * (rows.T @ rows) / len(rows)
    out.flat[::dim + 1] += 0.05 + 0.05
    return out


def time_layer(kfac_plain, d_in, d_out, rng):
    """-> (seconds to factor both, seconds for the two solves)."""
    grad = rng.standard_normal((d_out, d_in))
    best_f = best_s = float('inf')
    for _ in range(REPEATS):
        fa, fg = kfac_shaped(d_in, 64, rng), kfac_shaped(d_out, 64, rng)
        t0 = time.perf_counter()
        inv_a = kfac_plain._inverse_of(fa, None)
        inv_g = kfac_plain._inverse_of(fg, None)
        t1 = time.perf_counter()
        pre = inv_a(inv_g(grad).T).T
        t2 = time.perf_counter()
        assert np.all(np.isfinite(pre))
        best_f, best_s = min(best_f, t1 - t0), min(best_s, t2 - t1)
    return best_f, best_s


def parse(arg):
    if arg in LISTS:
        return LISTS[arg]
    return [tuple(int(v) for v in part.split(','))
            for part in arg.split(';')]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=3,
                    help="the configuration's check.steps")
    ap.add_argument('lists', nargs='+')
    args = ap.parse_args()
    kfac_plain = files.load_module('reference', 'kfac_plain')
    rng = np.random.default_rng(38)
    steps, updates = args.steps, 1
    for arg in args.lists:
        factor_s = solve_s = 0.0
        for d_in, d_out, count in parse(arg):
            f, s = time_layer(kfac_plain, d_in, d_out, rng)
            factor_s += count * f
            solve_s += count * s
            print(json.dumps({'list': arg, 'd_in': d_in, 'd_out': d_out,
                              'layers': count, 'factor_s_each': f,
                              'solve_s_each': s}), flush=True)
        host_s = updates * factor_s + steps * solve_s
        room = RUN_LIMIT_S - OTHER_PARTS_S
        print(json.dumps({
            'list': arg, 'steps': steps, 'decomposition_updates': updates,
            'factor_s': factor_s, 'solve_s_a_step': solve_s,
            'host_s': host_s, 'room_s': room,
            'fits_a_traced_run': host_s < room,
            'cpus': os.cpu_count()}), flush=True)


if __name__ == '__main__':
    main()
