"""Expert-parallel Switch MoE (parallel/moe.py) on the CPU mesh: with no
capacity overflow the all_to_all-dispatched computation must EXACTLY
equal the dense per-token mixture ``y_t = p_t * FFN_{e_t}(x_t)`` —
forward and gradients — and dropped tokens must zero out cleanly."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kfac_pytorch_tpu.parallel.moe import ExpertFFN, SwitchMoE

NE, TL, D, DH = 4, 8, 10, 16     # experts/ranks, tokens per rank, dims


def _params(seed):
    rng = np.random.RandomState(seed)
    gate = {'kernel': jnp.asarray(rng.randn(D, NE) * 0.5, jnp.float32),
            'bias': jnp.asarray(rng.randn(NE) * 0.1, jnp.float32)}
    experts = []
    for i in range(NE):
        r = np.random.RandomState(100 + i)
        experts.append({
            'w_in': {'kernel': jnp.asarray(r.randn(D, DH) * 0.4,
                                           jnp.float32),
                     'bias': jnp.asarray(r.randn(DH) * 0.1, jnp.float32)},
            'w_out': {'kernel': jnp.asarray(r.randn(DH, D) * 0.4,
                                            jnp.float32),
                      'bias': jnp.asarray(r.randn(D) * 0.1, jnp.float32)},
        })
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *experts)
    return gate, experts, stacked


def _dense_oracle(gate, experts, x):
    """y_t = p_t * FFN_{e_t}(x_t), computed expert-by-expert densely."""
    logits = x @ gate['kernel'] + gate['bias']
    probs = jax.nn.softmax(logits, axis=-1)
    e = jnp.argmax(probs, axis=-1)
    p = jnp.take_along_axis(probs, e[:, None], axis=1)[:, 0]
    outs = jnp.stack([
        ExpertFFN(D, DH).apply({'params': ep}, x) for ep in experts])
    y = jnp.take_along_axis(outs, e[None, :, None], axis=0)[0]
    return y * p[:, None]


def test_switch_moe_matches_dense_mixture():
    x = jnp.asarray(np.random.RandomState(0).randn(NE * TL, D),
                    jnp.float32)
    y_target = jnp.asarray(np.random.RandomState(1).randn(NE * TL, D),
                           jnp.float32)
    gate, experts, stacked = _params(7)
    mesh = Mesh(np.array(jax.devices()[:NE]), ('expert',))
    # capacity = ALL local tokens -> nothing can drop -> exact
    moe = SwitchMoE(D, DH, capacity=TL, axis='expert')
    especs = jax.tree.map(lambda _: P('expert'), stacked)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=({'gate': P(), 'expert': especs}, P('expert'),
                  P('expert')),
        out_specs=(P('expert'), P(), {'gate': P(),
                                      'expert': especs}))
    def run(params, x, y_target):
        local = {'gate': params['gate'],
                 'expert': jax.tree.map(lambda a: a[0], params['expert'])}

        def loss_fn(p):
            out, _ = moe.apply({'params': p}, x)
            return jax.lax.pmean(((out - y_target) ** 2).mean(),
                                 'expert'), out

        (loss, out), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(local)
        return out, loss, {'gate': grads['gate'],
                           'expert': jax.tree.map(lambda a: a[None],
                                                  grads['expert'])}

    params = {'gate': gate, 'expert': stacked}
    out_ep, loss_ep, grads_ep = run(params, x, y_target)

    def dense_loss(gp):
        out = _dense_oracle(gp['gate'], [
            jax.tree.map(lambda a: a[i], gp['expert'])
            for i in range(NE)], x)
        return ((out - y_target) ** 2).mean(), out

    (loss_d, out_d), grads_d = jax.value_and_grad(
        dense_loss, has_aux=True)({'gate': gate, 'expert': stacked})

    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_d),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss_ep), float(loss_d), rtol=1e-6)
    # expert grads: EP computes d(local-mean)/dtheta; pmean makes the
    # loss the global mean on both sides
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        grads_ep, grads_d)


def test_switch_moe_capacity_drops_zero():
    """capacity=1 forces overflow: dropped tokens produce EXACTLY zero
    output (Switch semantics) and the aux mask reports them."""
    x = jnp.asarray(np.random.RandomState(3).randn(TL, D), jnp.float32)
    gate, experts, _ = _params(8)
    moe = SwitchMoE(D, DH, capacity=1, axis=None)
    # axis=None: one local expert (index 0), gate width 1 -> everything
    # routes to it; tokens after the first must drop
    params = {'gate': {'kernel': gate['kernel'][:, :1],
                       'bias': gate['bias'][:1]},
              'expert': experts[0]}
    y, aux = moe.apply({'params': params}, x)
    assert bool(aux['dropped'][0]) is False
    assert bool(aux['dropped'][1:].all()) is True
    np.testing.assert_array_equal(np.asarray(y[1:]), 0)
    assert np.abs(np.asarray(y[0])).max() > 0

def test_moe_kfac_dp_ep_invariance():
    """One K-FAC step (MPD 'eigen' over the data axis) on a 2x2
    ('data', 'expert') mesh matches the expert-mesh-only full-batch run
    — data sharding must not change the preconditioned update with the
    expert capture riding the all_to_all dispatch.

    The loss fed to the capture MUST be the LOCAL mean (the framework's
    convention everywhere): the engine's G-factor scaling assumes
    local-mean cotangents, so a globally-psum-normalized loss makes the
    G scale depend on the shard size and breaks cross-mesh comparisons
    (diagnosed round 3 — looked like an engine bug, was a harness one).
    With the convention respected, (1,2)-vs-expert-only is EXACT and
    nd=2 matches to MPD-eigen tolerance."""
    import kfac_pytorch_tpu as kfac
    from kfac_pytorch_tpu import capture

    ND, NE2 = 2, 2
    T = NE2 * TL
    x = jnp.asarray(np.random.RandomState(5).randn(ND * T, D), jnp.float32)
    y = jnp.asarray(np.random.RandomState(6).randn(ND * T, D), jnp.float32)
    gate, experts, stacked = _params(11)
    gate = {'kernel': gate['kernel'][:, :NE2], 'bias': gate['bias'][:NE2]}
    stacked2 = jax.tree.map(lambda a: a[:NE2], stacked)
    local = SwitchMoE(D, DH, capacity=T, axis=None)

    def make_pre(nd, axis):
        pre = kfac.KFAC(variant='eigen', lr=0.1, damping=0.01,
                        fac_update_freq=1, kfac_update_freq=1,
                        num_devices=nd, axis_name=axis)
        xs = x[:T]
        variables = capture.init(local, jax.random.PRNGKey(0), xs)
        pre.setup(capture.collect_layer_meta(local, variables, xs))
        return pre

    especs = jax.tree.map(lambda _: P('expert'), stacked2)
    params = {'gate': gate, 'expert': stacked2}


    def run(mesh, axes, kfac_axis, nd, cap):
        # capacity = the mesh's LOCAL token count: no token can drop and
        # every expert's TOTAL buffer rows (sources x capacity, summed
        # over the K-FAC world) are equal across meshes — the factor
        # normalization counts buffer rows, so unequal buffers would
        # scale the factors differently and break the invariance
        moe = SwitchMoE(D, DH, capacity=cap, axis='expert')
        pre = make_pre(nd, kfac_axis)
        kstate = jax.tree.map(lambda a: jnp.stack([a] * NE2), pre.init())
        inner = (pre.state_pspecs(kfac_axis) if kfac_axis
                 else jax.tree.map(lambda _: P(),
                                   pre.state_pspecs(None)))
        kspecs = jax.tree.map(lambda s: P('expert', *s), inner,
                              is_leaf=lambda v: isinstance(v, P))
        xspec = P(axes) if isinstance(axes, str) else P(axes)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=({'gate': P(), 'expert': especs}, kspecs,
                      xspec, xspec),
            out_specs={'gate': P(), 'expert': especs})
        def step(params, kstate, x, y):
            local_p = {'gate': params['gate'],
                       'expert': jax.tree.map(lambda a: a[0],
                                              params['expert'])}
            all_axes = (('data', 'expert') if kfac_axis else 'expert')
            # LOCAL-mean loss (the capture convention) + explicit grad
            # averaging over the K-FAC world — NOT a globally-normalized
            # psum loss, which would scale the G factors by shard size
            _, _, grads, acts, gs, _ = \
                capture.value_and_grad_with_capture(
                    moe, lambda o: ((o[0] - y) ** 2).mean(),
                    {'params': local_p}, x, axis_name=all_axes)
            if kfac_axis:
                grads = kfac.parallel.average_grads(grads, kfac_axis)
            k = jax.tree.map(lambda a: a[0], kstate)
            new_grads, _ = pre.step(k, grads, acts, gs,
                                    axis_name=kfac_axis)
            return {'gate': new_grads['gate'],
                    'expert': jax.tree.map(lambda a: a[None],
                                           new_grads['expert'])}

        return step(params, kstate, x, y)

    total = ND * T
    mesh_e = Mesh(np.array(jax.devices()[:NE2]), ('expert',))
    want = run(mesh_e, 'expert', None, 1, cap=total // NE2)
    # (1, 2): same K-FAC world of one -> exact
    mesh_1 = Mesh(np.array(jax.devices()[:NE2]).reshape(1, NE2),
                  ('data', 'expert'))
    got1 = run(mesh_1, ('data', 'expert'), 'data', 1, cap=total // NE2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        got1, want)
    # (2, 2): distributed MPD world of two -> data sharding must not
    # change the math (grads differ only by f32 reduction order)
    mesh_2 = Mesh(np.array(jax.devices()[:ND * NE2]).reshape(ND, NE2),
                  ('data', 'expert'))
    got2 = run(mesh_2, ('data', 'expert'), 'data', ND,
               cap=total // (ND * NE2))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4),
        got2, want)
