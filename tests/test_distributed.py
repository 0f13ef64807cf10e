"""Distributed semantics on a virtual 8-device CPU mesh.

Validates the core SPMD claims of the design (plan.py / engine.py):

1. MPD variants under shard_map == single-device full-batch run (factor
   pmean ≙ the reference allreduce, inv.py:94-103).
2. DP variants use the *owner's local-batch* statistics only — no factor
   communication (the paper's contribution, inv_dp.py:60-95).
3. The sharded factor state rows hold exactly what the owner computed.
"""

import functools

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, ops
from kfac_pytorch_tpu import nn as knn


class MLP(linen.Module):
    @linen.compact
    def __call__(self, x):
        x = knn.Dense(8, name='fc1')(x)
        x = linen.relu(x)
        x = knn.Dense(3, name='fc2')(x)
        return x


def _data(b=8):
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randn(b, 5), jnp.float32),
            jnp.asarray(rng.randn(b, 3), jnp.float32))


def _capture_full(model, variables, x, y):
    loss_fn = lambda out: jnp.mean((out - y) ** 2)
    return capture.value_and_grad_with_capture(model, loss_fn, variables, x)


def _sharded_step(model, precond, mesh, axis):
    pspecs = precond.state_pspecs(axis)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), pspecs, P(axis), P(axis)),
        out_specs=(P(), pspecs))
    def step(params, state, x, y):
        loss_fn = lambda out: jnp.mean((out - y) ** 2)
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            model, loss_fn, {'params': params}, x, axis_name=axis)
        # autodiff already psummed param grads across the axis
        grads = kfac.parallel.average_grads(grads, axis)
        return precond.step(state, grads, acts, gs, axis_name=axis)

    return step


@pytest.mark.parametrize('ndev,distribute', [(2, False), (8, None)])
def test_mpd_eigen_matches_single_device(ndev, distribute):
    """Sharded MPD == full-batch single device (also exercises the
    factor-wise split auto rule when ndev > #layers, eigen.py:66-71)."""
    model = MLP()
    x, y = _data(8)
    variables = capture.init(model, jax.random.PRNGKey(0), x)
    metas = capture.collect_layer_meta(model, variables, x)

    p1 = kfac.KFAC(variant='eigen', num_devices=1, axis_name=None,
                   bucket_fn=lambda d: 16)
    p1.setup(metas)
    _, _, grads, acts, gs, _ = _capture_full(model, variables, x, y)
    want, _ = p1.step(p1.init(), grads, acts, gs)

    mesh = Mesh(np.array(jax.devices()[:ndev]), ('batch',))
    pN = kfac.KFAC(variant='eigen', num_devices=ndev, axis_name='batch',
                   bucket_fn=lambda d: 16,
                   distribute_layer_factors=distribute)
    pN.setup(metas)
    if ndev == 8:
        assert pN.plan is not None
    step = _sharded_step(model, pN, mesh, 'batch')
    got, _ = step(variables['params'], pN.init(), x, y)
    for name in metas:
        np.testing.assert_allclose(np.asarray(got[name]['kernel']),
                                   np.asarray(want[name]['kernel']),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(got[name]['bias']),
                                   np.asarray(want[name]['bias']),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize('variant', ['eigen_dp', 'inverse_dp'])
def test_dp_uses_owner_local_stats(variant):
    """DP preds must come from owner-shard-only factors; oracle recomputes
    per-shard stats on the host."""
    ndev = 2
    lr, damping, decay, kl = 0.1, 0.003, 0.95, 0.001
    model = MLP()
    x, y = _data(8)
    variables = capture.init(model, jax.random.PRNGKey(0), x)
    metas = capture.collect_layer_meta(model, variables, x)

    mesh = Mesh(np.array(jax.devices()[:ndev]), ('batch',))
    pN = kfac.KFAC(variant=variant, num_devices=ndev, axis_name='batch',
                   bucket_fn=lambda d: 16, lr=lr, damping=damping,
                   factor_decay=decay, kl_clip=kl)
    pN.setup(metas)
    step = _sharded_step(model, pN, mesh, 'batch')
    got, new_state = step(variables['params'], pN.init(), x, y)

    # --- host oracle ----------------------------------------------------
    # per-shard capture (local loss = mean over local batch)
    shard_stats = []
    for d in range(ndev):
        xs, ys = x[d * 4:(d + 1) * 4], y[d * 4:(d + 1) * 4]
        _, _, sg, sa, sgs, _ = _capture_full(model, variables, xs, ys)
        shard_stats.append((sg, sa, sgs))
    # full-batch grads = pmean of shard grads
    grads = jax.tree.map(
        lambda *g: sum(np.asarray(v) for v in g) / ndev,
        *[s[0] for s in shard_stats])

    names = list(metas)
    preds, gmats = [], []
    for i, name in enumerate(names):
        owner = i % ndev  # round-robin (inv.py:62-77)
        _, sa, sgs = shard_stats[owner]
        A = np.asarray(ops.compute_a_dense(sa[name]['a'], True))
        G = np.asarray(ops.compute_g_dense(sgs[name]['g'], True))
        mA = decay * A + (1 - decay) * np.eye(A.shape[0], dtype=np.float32)
        mG = decay * G + (1 - decay) * np.eye(G.shape[0], dtype=np.float32)
        gm = np.concatenate([np.asarray(grads[name]['kernel']).T,
                             np.asarray(grads[name]['bias'])[:, None]], 1)
        if variant == 'eigen_dp':
            dA, QA = np.linalg.eigh(mA)
            dG, QG = np.linalg.eigh(mG)
            dA, dG = dA * (dA > 1e-10), dG * (dG > 1e-10)
            v2 = (QG.T @ gm @ QA) / (np.outer(dG, dA) + damping)
            preds.append(QG @ v2 @ QA.T)
        else:
            pi = np.sqrt((np.trace(mA) / mA.shape[0])
                         / (np.trace(mG) / mG.shape[0]))
            Ad = mA + np.sqrt(damping) * pi * np.eye(mA.shape[0])
            Gd = mG + np.sqrt(damping) / pi * np.eye(mG.shape[0])
            preds.append(np.linalg.inv(Gd) @ gm @ np.linalg.inv(Ad))
        gmats.append(gm)
    vg = sum(float(np.sum(p * g)) for p, g in zip(preds, gmats)) * lr ** 2
    nu = min(1.0, np.sqrt(kl / abs(vg)))

    for name, pred in zip(names, preds):
        gk = np.concatenate([np.asarray(got[name]['kernel']).T,
                             np.asarray(got[name]['bias'])[:, None]], 1)
        np.testing.assert_allclose(gk, pred * nu, rtol=1e-3, atol=1e-4)

    # --- sharded state rows hold the owner's local running averages -----
    b16 = np.asarray(new_state.factors['16'])
    # bucket rows are device-major: dev0 [fc1A, fc1G], dev1 [fc2A, fc2G]
    _, sa0, sgs0 = shard_stats[0]
    A0 = np.asarray(ops.compute_a_dense(sa0['fc1']['a'], True))
    want_row0 = decay * np.asarray(ops.identity_pad(jnp.asarray(A0), 16)) \
        + (1 - decay) * np.eye(16, dtype=np.float32)
    np.testing.assert_allclose(b16[0], want_row0, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize('variant', ['eigen_dp', None])
def test_init_train_state_on_mesh_is_born_sharded(variant):
    """init_train_state(mesh=, axis_name=) builds the state ON the mesh:
    same values as the one-device build, every leaf already in the
    sharding the mesh step takes — no device ever holds every factor
    (four v5e chips: 5.4 GB on device 0 against 1.6, PERF.md PR 21) and
    the first step call has nothing to reshard."""
    from kfac_pytorch_tpu import training
    from kfac_pytorch_tpu.models.tiny import TinyCNN
    nd = 4
    mesh = Mesh(np.array(jax.devices()[:nd]), ('batch',))
    model = TinyCNN(batch_norm=True)
    x = jnp.zeros((2 * nd, 8, 8, 3), jnp.float32)
    tx = training.sgd(0.1, momentum=0.9)

    def make():
        return None if variant is None else kfac.KFAC(
            variant=variant, num_devices=nd, axis_name='batch')

    plain = training.init_train_state(model, tx, make(),
                                      jax.random.PRNGKey(0), x)
    pre = make()
    born = training.init_train_state(model, tx, pre, jax.random.PRNGKey(0),
                                     x, mesh=mesh, axis_name='batch')
    assert (jax.tree.structure(born) == jax.tree.structure(plain))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), born, plain)
    everywhere = set(mesh.devices.flat)
    for leaf in jax.tree.leaves(born):
        assert {s.device for s in leaf.addressable_shards} == everywhere
    if variant is not None:
        # factor rows are split over the axis, not replicated
        for leaf in jax.tree.leaves(born.kfac_state.factors):
            assert leaf.sharding.spec == P('batch')
            assert leaf.addressable_shards[0].data.shape[0] * nd \
                == leaf.shape[0]
