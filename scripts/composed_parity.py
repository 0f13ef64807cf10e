"""Composed-mesh K-FAC parity gate: the CI driver behind the axis-aware
mesh-plan subsystem (kfac_pytorch_tpu/meshplan).

Each CPU leg runs ONE preconditioned K-FAC step on a composed mesh and
asserts it against the dp-only reference fed the same capture:

* **dp2xtp2** — replicated slice-capture operands, tensor-axis factor
  reduce LIVE in the trace. Gate: every preconditioned grad and every
  factor EMA is BITWISE equal to the dp2 reference (pmean of identical
  f32 values is exact for a power-of-2 world) and tp-invariant across
  model ranks.
* **dp2xep2** — per-expert capture operands. Gate: each expert rank's
  step is BITWISE the dp2 reference run on that expert's capture alone
  (owner-local factors: the zero-FactorComm claim, numerically).

The captures are ORACLE operands — acts/gs/grads enter the shard_map as
explicit inputs, never via in-body autodiff (the legacy shard_map shim
mis-transposes that; see tests/test_tp.py). The preconditioner's own
collectives are forward-only and exact, so the comparison is at lr=0
semantics: preconditioned gradients, no parameter update in the loop.

The ``multichip-*`` legs are STUBS: they record 'needs-chip' unless a
real multi-chip accelerator backend is attached (the on-chip queue runs
them; CI documents the pending surface the same way the comm-ledger job
documents bytes it cannot measure).

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  COMPOSED_PARITY_ASSERT=1 \
      python scripts/composed_parity.py [--leg dp2xtp2 --leg dp2xep2]

Env knobs:
  COMPOSED_PARITY_ASSERT '1' = violations exit nonzero (the CI gate);
                         unset = report-only
  COMPOSED_PARITY_JSON   summary artifact path
                         (default 'composed-parity.json')
"""

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

from kfac_pytorch_tpu.capture import LayerMeta       # noqa: E402
from kfac_pytorch_tpu.parallel import mesh as meshlib  # noqa: E402
from kfac_pytorch_tpu.parallel import moe, tp        # noqa: E402
from kfac_pytorch_tpu.preconditioner import KFAC     # noqa: E402

ND, B = 2, 8
CPU_LEGS = ('dp2xtp2', 'dp2xep2')
ALL_LEGS = CPU_LEGS + tuple('multichip-' + leg for leg in CPU_LEGS)


def _dense(name, din, dout):
    return LayerMeta(name=name, path=tuple(name.split('/')), kind='dense',
                     use_bias=True, in_dim=din + 1, out_dim=dout,
                     kernel_shape=(din, dout))


def _metas(leg):
    if 'tp' in leg:
        return ({('l1', 'slice'): _dense('l1/slice', 6, 4),
                 ('l2', 'slice'): _dense('l2/slice', 4, 5)},
                tp.axis_rules(column=('l1',), row=('l2',)))
    return ({('expert', 'w_in'): _dense('expert/w_in', 6, 4),
             ('expert', 'w_out'): _dense('expert/w_out', 4, 5)},
            moe.axis_rules(experts=('expert',)))


def _oracle_inputs(metas, seed, lead=(ND,)):
    rng = np.random.RandomState(seed)

    def arr(*shape):
        return jnp.asarray(rng.randn(*(lead + shape)), jnp.float32)

    acts, gs, grads = {}, {}, {}
    for path, m in metas.items():
        din, dout = m.kernel_shape
        na, ng, nr = acts, gs, grads
        for k in path[:-1]:
            na, ng, nr = (na.setdefault(k, {}), ng.setdefault(k, {}),
                          nr.setdefault(k, {}))
        na[path[-1]] = {'a': arr(B, din)}
        ng[path[-1]] = {'g': arr(B, dout)}
        nr[path[-1]] = {'kernel': arr(din, dout), 'bias': arr(dout)}
    return acts, gs, grads


def _mesh_step(pre, mesh, grads, acts, gs):
    from jax.sharding import PartitionSpec as P
    kspecs = pre.state_pspecs()
    names = tuple(n for n, _ in mesh.shape.items())
    lead = len(names)
    io_spec = P(*names)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(kspecs, io_spec, io_spec, io_spec),
                       out_specs=(io_spec, kspecs))
    def step(kstate, grads, acts, gs):
        sq = lambda t: jax.tree.map(  # noqa: E731
            lambda a: a.reshape(a.shape[lead:]), t)
        g2, st2 = pre.step(kstate, sq(grads), sq(acts), sq(gs))
        exp = lambda t: jax.tree.map(  # noqa: E731
            lambda a: a.reshape((1,) * lead + a.shape), t)
        return exp(g2), st2

    return step(pre.init(), grads, acts, gs)


def _dp_reference(metas, grads, acts, gs):
    pre = KFAC(variant='eigen', lr=0.1, damping=0.01,
               num_devices=ND, axis_name='data')
    pre.setup(metas)
    return _mesh_step(pre, meshlib.make_mesh(ND, axis_name='data'),
                      grads, acts, gs)


def _dup(tree, n):
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[:, None], (a.shape[0], n)
                                   + a.shape[1:]), tree)


def _max_mismatch(got, want, slicer):
    """(bitwise?, max |diff|) over tree leaves after slicing got."""
    worst = 0.0
    bitwise = True
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a = slicer(np.asarray(a))
        b = np.asarray(b).reshape(a.shape)
        if not np.array_equal(a, b):
            bitwise = False
            worst = max(worst, float(np.abs(a - b).max()))
    return bitwise, worst


def run_cpu_leg(leg):
    metas, rules = _metas(leg)
    pre = KFAC(variant='eigen', lr=0.1, damping=0.01,
               mesh_axes=leg, mesh_rules=rules)
    pre.setup(metas)
    mesh, _ = meshlib.make_composed_mesh(leg)
    res = {'leg': leg, 'status': 'ran', 'checks': {}}

    if 'tp' in leg:
        acts, gs, grads = _oracle_inputs(metas, seed=0)
        got, stc = _mesh_step(pre, mesh, _dup(grads, 2), _dup(acts, 2),
                              _dup(gs, 2))
        gref, stref = _dp_reference(metas, grads, acts, gs)
        tp_inv = all(np.array_equal(np.asarray(a)[:, 0], np.asarray(a)[:, 1])
                     for a in jax.tree_util.tree_leaves(got))
        bit, diff = _max_mismatch(got, gref, lambda a: a[:, 0])
        fbit, fdiff = _max_mismatch(stc.factors, stref.factors, lambda a: a)
        res['checks'] = {'tp_invariant': tp_inv,
                         'grads_bitwise': bit, 'grads_max_diff': diff,
                         'factors_bitwise': fbit,
                         'factors_max_diff': fdiff}
        res['ok'] = tp_inv and bit and fbit
    else:
        per_e = [_oracle_inputs(metas, seed=10 + e) for e in range(2)]
        stack = lambda i: jax.tree.map(  # noqa: E731
            lambda *a: jnp.stack(a, axis=1), *[pe[i] for pe in per_e])
        got, _ = _mesh_step(pre, mesh, stack(2), stack(0), stack(1))
        ok = True
        worst = 0.0
        for e in range(2):
            a_e, g_e, gr_e = per_e[e]
            want, _ = _dp_reference(metas, gr_e, a_e, g_e)
            bit, diff = _max_mismatch(got, want,
                                      lambda a, e=e: a[:, e])
            ok = ok and bit
            worst = max(worst, diff)
        res['checks'] = {'per_expert_bitwise': ok,
                         'max_diff': worst}
        res['ok'] = ok
    return res


def run_multichip_stub(leg):
    """Record the pending on-chip surface; runs only with a real
    multi-chip accelerator attached (the on-chip queue's job)."""
    base = leg.split('-', 1)[1]
    devs = jax.devices()
    if devs[0].platform == 'cpu' or len(devs) < 4:
        return {'leg': leg, 'status': 'needs-chip', 'ok': None,
                'note': f'requires >=4 accelerator devices for {base}; '
                        f'have {len(devs)} x {devs[0].platform}'}
    res = run_cpu_leg(base)
    res['leg'] = leg
    res['note'] = 'ran on-chip'
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--leg', action='append', choices=ALL_LEGS,
                    help='repeatable; default: all CPU legs + '
                         'multichip stubs')
    args = ap.parse_args(argv)
    legs = tuple(args.leg) if args.leg else ALL_LEGS

    results = []
    for leg in legs:
        res = (run_multichip_stub(leg) if leg.startswith('multichip-')
               else run_cpu_leg(leg))
        results.append(res)
        print(f"{leg:>20}: {res['status']:<10} ok={res['ok']} "
              f"{res.get('checks', res.get('note', ''))}")

    path = os.environ.get('COMPOSED_PARITY_JSON', 'composed-parity.json')
    with open(path, 'w') as f:
        json.dump({'results': results}, f, indent=1, sort_keys=True)
    print(f'wrote {path}')

    failed = [r['leg'] for r in results if r['ok'] is False]
    if failed:
        msg = f'COMPOSED_PARITY: FAILED legs {failed}'
        if os.environ.get('COMPOSED_PARITY_ASSERT') == '1':
            raise SystemExit(msg)
        print(msg)
    elif os.environ.get('COMPOSED_PARITY_ASSERT') == '1':
        ran = [r['leg'] for r in results if r['status'] == 'ran']
        print(f'COMPOSED_PARITY_ASSERT: parity gates passed ({ran})')


if __name__ == '__main__':
    main()
