"""Collective cost-model fitting: measure psum / all_gather / ppermute
latency vs message size and fit the alpha + beta * size linear model.

Capability parity with the reference's comm-model fitter
(reference: scripts/comm_models.py:8-50 — fits a latency/bandwidth line to
NCCL-broadcast log timings for the performance model behind DP-KFAC's
comm-volume argument). The TPU version measures the collectives this
framework actually issues (`lax.psum` for factor/grad allreduce,
`lax.all_gather` for owner-computed result exchange) over whatever mesh is
available — real ICI on a pod, or a virtual CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu)
for model-shape validation.

``--wire-dtype bf16|int8`` measures the collectives at the compressed
wire width (the comm_precision modes of parallel/collectives.py), and
``--analytic MODEL`` prints the closed-form FactorComm / InverseComm /
PredComm payload-byte model per wire dtype (FactorPlan.comm_volume) with
the compression factor each dtype buys — the analytic side of the
HLO-measured ledger in scripts/comm_count.py.

Usage: python scripts/comm_models.py [--sizes-kb 4 64 1024 16384]
           [--csv out] [--wire-dtype fp32|bf16|int8]
           [--analytic resnet20 --variant eigen --ndev 8]
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from scripts.utils import fit_linear, timeit

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def analytic_comm_volumes(model_name='resnet20', variant='eigen', ndev=8,
                          num_classes=10, hw=32):
    """{wire dtype: {phase: bytes}} for one full factor+inverse step of
    ``variant`` over ``model_name``'s factor plan — the analytic
    FactorComm/InverseComm/PredComm volume model with its compression
    factor, derived from the SAME plan layout the compiled step uses
    (FactorPlan.comm_volume), so it and the HLO ledger
    (scripts/comm_count.py) describe one object."""
    import jax as _jax
    import jax.numpy as _jnp

    import kfac_pytorch_tpu as kfac
    from kfac_pytorch_tpu import capture, models
    from kfac_pytorch_tpu.parallel.collectives import WIRE_DTYPES

    model = models.get_model(model_name, num_classes=num_classes)
    x = _jnp.zeros((2, hw, hw, 3), _jnp.float32)
    variables = capture.init(model, _jax.random.PRNGKey(0), x)
    metas = capture.collect_layer_meta(model, variables, x)
    pre = kfac.KFAC(variant=variant, num_devices=ndev, axis_name='batch',
                    assignment='balanced')
    plan = pre.setup(metas)
    return {wd: plan.comm_volume(stats_reduce=pre.stats_reduce,
                                 method=pre.method, comm_precision=wd)
            for wd in WIRE_DTYPES}


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--sizes-kb', nargs='+', type=int,
                   default=[4, 16, 64, 256, 1024, 4096, 16384])
    p.add_argument('--csv', default=None)
    p.add_argument('--wire-dtype', default='fp32',
                   choices=['fp32', 'bf16', 'int8'],
                   help='measure the collectives at this wire width '
                        '(the comm_precision modes)')
    p.add_argument('--analytic', default=None, metavar='MODEL',
                   help='print the closed-form FactorComm/InverseComm/'
                        'PredComm byte model per wire dtype for MODEL '
                        'and exit (no measurement)')
    p.add_argument('--variant', default='eigen',
                   help='K-FAC variant for --analytic')
    p.add_argument('--ndev', type=int, default=8,
                   help='mesh size for --analytic')
    args = p.parse_args()

    if args.analytic:
        vols = analytic_comm_volumes(args.analytic, args.variant,
                                     args.ndev)
        base = vols['fp32']
        print(f'analytic comm volumes: model={args.analytic} '
              f'variant={args.variant} ndev={args.ndev} '
              '(bytes per full factor+inverse step)')
        for wd, phases in vols.items():
            tot, btot = sum(phases.values()), sum(base.values())
            factor = (tot / btot) if btot else 1.0
            line = '  '.join(f'{ph}: {b / 2**20:8.3f} MiB'
                             for ph, b in sorted(phases.items()))
            print(f'{wd:>5}: {line}   total {tot / 2**20:8.3f} MiB '
                  f'(x{factor:.2f} of fp32)')
        return

    devices = jax.devices()
    n = len(devices)
    if n == 1:
        print('single device: collectives are no-ops; run under a pod or a '
              'virtual CPU mesh (--xla_force_host_platform_device_count=8)')
    mesh = Mesh(np.array(devices), ('x',))

    def make(coll):
        @functools.partial(jax.jit)
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=P('x'), out_specs=P('x'))
        def run(x):
            if coll == 'psum':
                return jax.lax.psum(x, 'x')
            if coll == 'all_gather':
                return jax.lax.all_gather(x[0], 'x').mean(0, keepdims=True)
            if coll == 'ppermute':
                return jax.lax.ppermute(
                    x, 'x', [(i, (i + 1) % n) for i in range(n)])
            raise ValueError(coll)
        return run

    rows = {}
    for coll in ('psum', 'all_gather', 'ppermute'):
        fn = make(coll)
        times, sizes_b = [], []
        for kb in args.sizes_kb:
            elems = kb * 1024 // 4
            x = jax.device_put(
                jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems),
                jax.sharding.NamedSharding(mesh, P('x')))
            t = timeit(fn, x)
            times.append(t)
            sizes_b.append(kb * 1024)
        alpha, beta = fit_linear(sizes_b, times)
        bw = (1.0 / beta / 1e9) if beta > 0 else float('inf')
        rows[coll] = list(zip(sizes_b, times))
        print(f'{coll:>11}: alpha={alpha * 1e6:8.2f} us   '
              f'beta={beta * 1e12:8.3f} ps/B   (~{bw:.2f} GB/s)')
        for sb, t in rows[coll]:
            print(f'    {sb // 1024:>8} KB  {t * 1e6:>10.1f} us')

    if args.csv:
        with open(args.csv, 'w') as f:
            f.write('collective,bytes,seconds\n')
            for coll, data in rows.items():
                for sb, t in data:
                    f.write(f'{coll},{sb},{t:.8f}\n')
        print('wrote', args.csv)


if __name__ == '__main__':
    main()
