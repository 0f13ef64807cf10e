"""Collective-semantics probes: sub-axis groups, owner-broadcast, barriers.

Capability parity with the reference's comm probes
(reference: scripts/test_allgather.py:19-43 — Horovod process-set allreduce
on even/odd rank subgroups and torch DDP allreduce). The TPU equivalents
this framework relies on:

  1. process-sets      -> mesh *sub-axes*: reshape the device list into a
     2-D mesh and psum over one axis only (the reference's even/odd
     process-set split is the ('group', 'member') factorization here);
  2. per-layer owner broadcast -> owner-computes + all_gather of the
     owner-row table (the masked-psum-friendly form the plan uses);
  3. barrier via dummy allreduce (reference:
     examples/pytorch_wikitext_rnn.py:140-151) -> psum of a scalar.

Run on any mesh; for an 8-way virtual mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python scripts/test_collectives.py
"""

import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from kfac_pytorch_tpu.parallel import collectives


def subgroup_allreduce(devices):
    """psum over a sub-axis == process-set allreduce on rank subgroups."""
    n = len(devices)
    if n % 2:
        print('subgroup_allreduce: need even device count, skipping')
        return
    mesh = Mesh(np.array(devices).reshape(2, n // 2), ('parity', 'member'))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P('parity', 'member'),
                       out_specs=P('parity', 'member'))
    def run(x):
        return jax.lax.psum(x, 'member')  # reduce within parity group only

    x = jax.device_put(
        jnp.arange(n, dtype=jnp.float32).reshape(2, n // 2),
        jax.sharding.NamedSharding(mesh, P('parity', 'member')))
    out = np.asarray(run(x))
    expect = np.tile(np.arange(n, dtype=np.float32).reshape(
        2, n // 2).sum(1, keepdims=True), (1, n // 2))
    assert np.allclose(out, expect), (out, expect)
    print(f'subgroup_allreduce: ok — even group sum {out[0, 0]:.0f}, '
          f'odd group sum {out[1, 0]:.0f}')


def owner_broadcast(devices):
    """Owner computes, everyone receives: the _communicate_pred pattern."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ('kfac',))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P('kfac'),
                       out_specs=P())
    def run(x):
        idx = jax.lax.axis_index('kfac')
        # each device "owns" its row: computes a result only it knows
        local = x * (idx + 1.0)
        # scatter-to-own-offset + psum: the framework's provably-replicated
        # all-gather (parallel/collectives.py)
        return collectives.all_gather_rows(local, 'kfac')

    x = jax.device_put(
        jnp.ones((n, 3), jnp.float32),
        jax.sharding.NamedSharding(mesh, P('kfac')))
    out = np.asarray(run(x))
    expect = np.tile(np.arange(1, n + 1, dtype=np.float32)[:, None], (1, 3))
    assert np.allclose(out, expect), (out, expect)
    print(f'owner_broadcast: ok — every device holds all {n} owner results')


def barrier(devices):
    """Scalar psum as a barrier (all devices must arrive to complete)."""
    mesh = Mesh(np.array(devices), ('kfac',))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P('kfac'),
                       out_specs=P())
    def run(x):
        return jax.lax.psum(x.sum(), 'kfac')

    x = jax.device_put(jnp.ones((len(devices),), jnp.float32),
                       jax.sharding.NamedSharding(mesh, P('kfac')))
    assert float(run(x)) == len(devices)
    print('barrier: ok')


def main():
    devices = jax.devices()
    print(f'{len(devices)} devices ({devices[0].platform})')
    subgroup_allreduce(devices)
    owner_broadcast(devices)
    barrier(devices)


if __name__ == '__main__':
    main()
