#!/usr/bin/env python3
"""``factor_gap`` layer by layer, and what a routed layer's part of it is
made of.

    python3 benchmarks/tools/factor_gaps.py <cell> <out.jsonl> \
        [--lower all,kfac] [--steps N] <seed> [<seed> ...]

``run.py`` prints the worst of a configuration's ``check.sampled_layers``.
This reads, per seed and with the same functions (``program.build``, one
step, ``program.sampled_factors``; ``kfac_plain._device_step`` and the
running average from the identity), the gap of EVERY K-FAC layer's A and G
after the first step: program against reference, and program against each
``--lower`` control as ``run.py --lower`` compares them. For a cell with
routed layers (a plain model with ``route``) it also counts the routing
choices that differ between the program's forward pass and the
reference's on that batch, and reads what the differing rows alone do to
an expert's A: the reference's own activations averaged over the
program's rows against the same over the reference's rows
(``rows_gap``). Where ``rows_gap`` is the layer's ``A`` gap, the gap is
the near-ties of the top-k decided differently, not the statistics' code.
The program's choices come from a forward pass of its model compiled on
its own, not from inside the step program: near-ties may fall differently
there, so counts agree with the step's only about.

``--steps N`` drives the first seed's program N steps on and prints the
model's counters (``moe/rows_max``...) as the steps have them.
One JSON line a seed goes to ``out.jsonl``; a summary to stdout.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

from harness import files, program, weights  # noqa: E402


def _gap(fp, fr):
    return float(np.linalg.norm(fp - fr) / np.linalg.norm(fr))


def reference_gaps(kp, plain, cfg, traffic, key, mine, lower):
    """{layer: [gap A, gap G, rows]} of the program's running averages
    ``mine`` against the reference's (or the control's) after step 1."""
    import jax
    mcfg, act = cfg['model'], cfg['dtype']['activations']
    layers = plain.kfac_layers(mcfg)
    low_act = kp._LOWER_ACT[act] if lower == 'all' else None
    stat = 'bfloat16' if lower else 'float32'
    frozen = kp._Frozen(
        (mcfg, layers, act, low_act, stat, cfg['dtype']['matmul_precision']),
        (cfg['name'], act, low_act, stat))
    q = kp._bf16 if lower else (lambda x: x)
    shapes = plain.param_shapes(mcfg)
    make = weights.params_fn(cfg['init'])
    params = jax.jit(lambda k: make(shapes, k))(key)
    batch = plain.make_batch(
        mcfg, traffic, jax.random.fold_in(program.data_key(key), 0))
    loss, _, stats = kp._device_step(plain, frozen, params, batch, True)
    w = cfg['kfac']['ema_new_weight']
    out = {}
    for layer in layers:
        path = layer['path']
        if path not in mine:
            continue
        a, g, seen = jax.device_get(stats[path])
        pair = []
        for side, s in enumerate((a, g)):
            s = np.asarray(s, np.float64)
            avg = np.eye(len(s)) if seen == 0 else q(
                (1.0 - w) * np.eye(len(s)) + w * s)
            pair.append(_gap(mine[path][side], avg))
        out[path] = pair + [float(seen)]
    return float(loss), out


def routing_reader(plain, cfg, traffic, model):
    """-> f(key): the choices that differ on the seed's first batch, and
    ``rows_gap`` per held expert."""
    import jax
    import jax.numpy as jnp
    mcfg = cfg['model']
    dtype = jnp.dtype(cfg['dtype']['activations'])
    k, ids = mcfg['num_experts_per_tok'], mcfg['expert_ids']
    w = cfg['kfac']['ema_new_weight']
    moe = [i for i in range(mcfg['num_hidden_layers'])
           if i >= mcfg['first_k_dense_replace']]
    shapes = plain.param_shapes(mcfg)
    make = weights.params_fn(cfg['init'])

    @jax.jit
    def prog_logits(key, batch):
        params = weights.unflatten(make(shapes, key))
        _, state = model.apply(
            {'params': params}, batch['input'],
            capture_intermediates=lambda m, _: m.name == 'router',
            mutable=['intermediates'])
        inter = state['intermediates']
        return {i: inter[f'layer_{i}']['mlp']['router']['__call__'][0]
                for i in moe}

    def averaged(a, came):
        rows = a.astype(jnp.float32) * came[:, None]
        with jax.default_matmul_precision('highest'):
            big = rows.T @ rows / jnp.maximum(came.sum(), 1.0)
        return (1.0 - w) * jnp.eye(len(big)) + w * big

    @jax.jit
    def compare(key, batch, logits_p):
        params = make(shapes, key)
        _, acts = plain.forward(mcfg, params, batch, {}, dtype)
        out = {}
        for i in moe:
            p = f'layer_{i}/mlp'
            u = acts[f'{p}/shared/gate'].astype(jnp.float32)
            with jax.default_matmul_precision('highest'):
                logits_r = u @ params[f'{p}/router/kernel']
            bias = params[f'{p}/e_score_correction_bias']
            top, chosen_r = jax.lax.top_k(jax.nn.sigmoid(logits_r) + bias,
                                          k + 1)
            _, chosen_p = jax.lax.top_k(jax.nn.sigmoid(logits_p[i]) + bias,
                                        k)
            differ = jnp.sort(chosen_r[:, :k], -1) != jnp.sort(chosen_p, -1)
            noise = jnp.abs(logits_r - logits_p[i])
            row = {'tokens_differ': differ.any(-1).sum(),
                   'logit_noise_median': jnp.median(noise),
                   'logit_noise_max': noise.max(),
                   'logit_spread': logits_r.std(),
                   'score_margin_median': jnp.median(top[:, k - 1]
                                                     - top[:, k]),
                   'experts': []}
            for e, expert in enumerate(ids):
                came_r = acts[f'{p}/experts/down/{e}'][1]
                came_p = (chosen_p == expert).any(-1).astype(jnp.float32)
                one = {'rows_reference': came_r.sum(),
                       'rows_program': came_p.sum(),
                       'only_program': (came_p * (1 - came_r)).sum(),
                       'only_reference': (came_r * (1 - came_p)).sum()}
                for name in ('gate', 'down'):
                    a = acts[f'{p}/experts/{name}/{e}'][0]
                    fr = averaged(a, came_r)
                    one[f'rows_gap_{name}_A'] = (
                        jnp.linalg.norm(averaged(a, came_p) - fr)
                        / jnp.linalg.norm(fr))
                row['experts'].append(one)
            out[f'layer_{i}'] = row
        return out

    def read(key):
        batch = plain.make_batch(
            mcfg, traffic, jax.random.fold_in(program.data_key(key), 0))
        got = jax.device_get(compare(key, batch, prog_logits(key, batch)))
        return jax.tree.map(float, got)
    return read


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('cell')
    ap.add_argument('out')
    ap.add_argument('--lower', default='')
    ap.add_argument('--steps', type=int, default=0)
    ap.add_argument('seeds', type=int, nargs='+')
    args = ap.parse_args()
    import run
    run.place_cache()
    cell, _ = files.resolve_workload(args.cell)
    cfg, _ = files.load_json('configs', cell['config'])
    traffic, _ = files.load_json('traffic', cell['traffic'])
    traffic = dict(traffic, chips=cell['chips'])
    builder = files.load_module('builders', cfg['builder'])
    plain = files.load_module('reference', cfg['plain'])
    kp = files.load_module('reference', 'kfac_plain')
    modes = [False] + [m for m in args.lower.split(',') if m]
    names = {l['path'] for l in plain.kfac_layers(cfg['model'])}
    read_routing = None
    if hasattr(plain, 'route'):
        read_routing = routing_reader(
            plain, cfg, traffic,
            builder.build(cfg, traffic, kfac=False)['model'])
    for n, seed in enumerate(args.seeds):
        key = weights.seed_key(seed)
        line = {'cell': cell['name'], 'seed': seed}
        if read_routing:
            line['routing'] = read_routing(key)
        prog = program.build(builder, plain, cfg, traffic, seed)
        prog.state, mets = prog.step_fn(prog.state, prog.pool[0])
        line['loss_program'] = float(mets['loss'])
        counters = {0: {k: float(v) for k, v in mets.items()
                        if k != 'loss'}}
        mine = program.sampled_factors(prog.state, prog.precond, names)
        if n == 0:
            for i in range(1, args.steps):
                prog.state, mets = prog.step_fn(
                    prog.state, prog.pool[i % len(prog.pool)])
                if i in (1, 9, 10, 49, 99) or i == args.steps - 1:
                    counters[i] = {k: float(v) for k, v in mets.items()}
        line['step_metrics'] = counters
        del prog
        for mode in modes:
            loss, gaps = reference_gaps(kp, plain, cfg, traffic, key, mine,
                                        mode)
            line[f'gaps_{mode or "sound"}'] = gaps
            line[f'loss_{mode or "sound"}'] = loss
            worst = sorted(((max(v[:2]), p) for p, v in gaps.items()),
                           reverse=True)[:3]
            print(f'seed {seed} {mode or "sound"}: worst {worst}',
                  flush=True)
        del mine
        with open(args.out, 'a') as f:
            f.write(json.dumps(line) + '\n')


if __name__ == '__main__':
    main()
