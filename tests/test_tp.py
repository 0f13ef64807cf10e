"""Tensor-parallel layers + per-slice K-FAC (parallel/tp.py) on the CPU
mesh: forward/backward must be EXACTLY the unsharded dense math, and each
model-rank's K-FAC must equal an exact per-slice oracle (the same local
module run on one device with the other ranks' partial output folded into
the loss as a constant)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen
from jax.sharding import Mesh, PartitionSpec as P

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.parallel import tp

B, DIN, DH, DOUT, NM = 8, 6, 8, 5, 2     # NM model ranks; DH_local = DH/NM
DH_L = DH // NM
LR, DAMPING = 0.1, 0.01

PARAM_SPECS = {
    'l1': {'slice': {'kernel': P(None, 'model'), 'bias': P('model')}},
    'l2': {'slice': {'kernel': P('model', None)}, 'bias': P()},
}


class TPMLP(linen.Module):
    """Column -> relu -> Row; with axis=None this same module IS the
    single-device per-slice oracle (local widths, no reduction)."""
    axis: object = 'model'

    @linen.compact
    def __call__(self, x, train=True):
        x = tp.ColumnParallelDense(DH_L, axis=self.axis, name='l1')(x)
        x = linen.relu(x)
        return tp.RowParallelDense(DOUT, axis=self.axis, name='l2')(x)


def _data(seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, DIN), jnp.float32),
            jnp.asarray(rng.randint(0, DOUT, B)))


def _global_params(seed=1):
    rng = np.random.RandomState(seed)
    return {
        'l1': {'slice': {
            'kernel': jnp.asarray(rng.randn(DIN, DH) * 0.5, jnp.float32),
            'bias': jnp.asarray(rng.randn(DH) * 0.1, jnp.float32)}},
        'l2': {'slice': {
            'kernel': jnp.asarray(rng.randn(DH, DOUT) * 0.5, jnp.float32)},
            'bias': jnp.asarray(rng.randn(DOUT) * 0.1, jnp.float32)},
    }


def _slice_params(gp, i):
    """Model-rank i's local view of the global params."""
    s = slice(i * DH_L, (i + 1) * DH_L)
    return {
        'l1': {'slice': {'kernel': gp['l1']['slice']['kernel'][:, s],
                         'bias': gp['l1']['slice']['bias'][s]}},
        'l2': {'slice': {'kernel': gp['l2']['slice']['kernel'][s]},
               'bias': gp['l2']['bias']},
    }


def _ce(out, y):
    return optax.softmax_cross_entropy_with_integer_labels(out, y).mean()


def _model_mesh():
    return Mesh(np.array(jax.devices()[:NM]), ('model',))


def test_tp_forward_backward_exact():
    """The sharded column->row computation IS the full dense math: outputs
    match the unsharded model exactly, and every rank's parameter grads
    are the corresponding slices of the full model's grads."""
    x, y = _data()
    gp = _global_params()
    model = TPMLP(axis='model')

    @functools.partial(jax.shard_map, mesh=_model_mesh(),
                       in_specs=(PARAM_SPECS, P(), P()),
                       out_specs=(P(), PARAM_SPECS))
    def fwd_bwd(params, x, y):
        def loss_fn(p):
            return _ce(model.apply({'params': p}, x), y)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads

    loss_tp, grads_tp = fwd_bwd(gp, x, y)

    class FullMLP(linen.Module):
        @linen.compact
        def __call__(self, x):
            x = knn.Dense(DH, name='l1')(x)
            x = linen.relu(x)
            return knn.Dense(DOUT, name='l2')(x)

    full_params = {'l1': {'kernel': gp['l1']['slice']['kernel'],
                          'bias': gp['l1']['slice']['bias']},
                   'l2': {'kernel': gp['l2']['slice']['kernel'],
                          'bias': gp['l2']['bias']}}

    def full_loss(p):
        return _ce(FullMLP().apply({'params': p}, x), y)

    loss_full, grads_full = jax.value_and_grad(full_loss)(full_params)
    np.testing.assert_allclose(float(loss_tp), float(loss_full), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grads_tp['l1']['slice']['kernel']),
        np.asarray(grads_full['l1']['kernel']), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grads_tp['l1']['slice']['bias']),
        np.asarray(grads_full['l1']['bias']), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grads_tp['l2']['slice']['kernel']),
        np.asarray(grads_full['l2']['kernel']), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grads_tp['l2']['bias']),
        np.asarray(grads_full['l2']['bias']), atol=1e-6)


def _make_precond(variant, num_devices=1, axis_name=None):
    pre = kfac.KFAC(variant=variant, lr=LR, damping=DAMPING,
                    fac_update_freq=1, kfac_update_freq=1,
                    num_devices=num_devices, axis_name=axis_name)
    local = TPMLP(axis=None)
    x, _ = _data()
    variables = capture.init(local, jax.random.PRNGKey(0), x)
    metas = capture.collect_layer_meta(local, variables, x)
    pre.setup(metas)
    return pre


@pytest.mark.parametrize('variant', ['eigen_dp', 'inverse_dp'])
def test_tp_kfac_matches_per_slice_oracle(variant):
    """Each model-rank's preconditioned update equals the exact oracle:
    the SAME local module on one device, with the other ranks' partial
    output folded into the loss as a constant (so its capture sees
    exactly the rank's activations and cotangents)."""
    x, y = _data()
    gp = _global_params()
    model = TPMLP(axis='model')
    pre = _make_precond(variant)
    state0 = pre.init()
    # per-model-rank K-FAC state: identical init stacked on a leading
    # 'model'-sharded axis; each rank squeezes its own copy inside
    kstate = jax.tree.map(lambda a: jnp.stack([a] * NM), state0)
    kspecs = jax.tree.map(lambda _: P('model'), kstate)

    @functools.partial(jax.shard_map, mesh=_model_mesh(),
                       in_specs=(PARAM_SPECS, kspecs, P(), P()),
                       out_specs=PARAM_SPECS)
    def tp_step(params, kstate, x, y):
        # axis_name marks the taps varying over 'model': without it the
        # zero taps are axis-invariant and vma autodiff would psum their
        # cotangents across model ranks (x NM factor in every G)
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            model, lambda out: _ce(out, y), {'params': params}, x,
            axis_name='model')
        k = jax.tree.map(lambda a: a[0], kstate)
        new_grads, _ = pre.step(k, grads, acts, gs)
        return new_grads

    got = tp_step(gp, kstate, x, y)

    # full output for the constant-folding oracle loss
    class FullMLP(linen.Module):
        @linen.compact
        def __call__(self, x):
            x = knn.Dense(DH, name='l1')(x)
            x = linen.relu(x)
            return knn.Dense(DOUT, name='l2')(x)
    full_y = FullMLP().apply({'params': {
        'l1': {'kernel': gp['l1']['slice']['kernel'],
               'bias': gp['l1']['slice']['bias']},
        'l2': {'kernel': gp['l2']['slice']['kernel'],
               'bias': gp['l2']['bias']}}}, x)

    local = TPMLP(axis=None)
    for i in range(NM):
        sp = _slice_params(gp, i)
        own_y = local.apply({'params': sp}, x)
        const = jax.lax.stop_gradient(full_y - own_y)
        pre_i = _make_precond(variant)
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            local, lambda out: _ce(out + const, y), {'params': sp}, x)
        want, _ = pre_i.step(pre_i.init(), grads, acts, gs)
        s = slice(i * DH_L, (i + 1) * DH_L)
        np.testing.assert_allclose(
            np.asarray(got['l1']['slice']['kernel'][:, s]),
            np.asarray(want['l1']['slice']['kernel']),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got['l1']['slice']['bias'][s]),
            np.asarray(want['l1']['slice']['bias']),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got['l2']['slice']['kernel'][s]),
            np.asarray(want['l2']['slice']['kernel']),
            rtol=1e-4, atol=1e-5)
        # the replicated post-reduction bias is outside the slice factors:
        # its update is the plain gradient, identical on every rank
        np.testing.assert_allclose(np.asarray(got['l2']['bias']),
                                   np.asarray(want['l2']['bias']),
                                   rtol=1e-4, atol=1e-5)


def test_dp_tp_kfac_matches_model_only_full_batch():
    """2x2 ('data', 'model') mesh with the K-FAC world on the data axis
    (MPD 'eigen': pmean-reduced stats) == the model-only mesh run on the
    full batch — data sharding must not change the math."""
    ND = 2
    x, y = _data()
    gp = _global_params()
    model = TPMLP(axis='model')

    pre_dp = _make_precond('eigen', num_devices=ND, axis_name='data')
    state0 = pre_dp.init()
    kstate = jax.tree.map(lambda a: jnp.stack([a] * NM), state0)
    kpspecs = pre_dp.state_pspecs('data')
    # leading 'model' axis on every leaf, then the kfac world's own specs
    kspecs = jax.tree.map(lambda s: P('model', *s), kpspecs,
                          is_leaf=lambda v: isinstance(v, P))
    mesh = Mesh(np.array(jax.devices()[:ND * NM]).reshape(ND, NM),
                ('data', 'model'))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(PARAM_SPECS, kspecs, P('data'), P('data')),
        out_specs=PARAM_SPECS)
    def dp_tp_step(params, kstate, x, y):
        # taps must vary over EVERY mesh axis of the step ('data' AND
        # 'model') or their cotangents get cross-rank psummed
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            model, lambda out: _ce(out, y), {'params': params}, x,
            axis_name=('data', 'model'))
        grads = kfac.parallel.average_grads(grads, 'data')
        k = jax.tree.map(lambda a: a[0], kstate)
        new_grads, _ = pre_dp.step(k, grads, acts, gs, axis_name='data')
        return new_grads

    got = dp_tp_step(gp, kstate, x, y)

    pre_1 = _make_precond('eigen')
    k1 = jax.tree.map(lambda a: jnp.stack([a] * NM), pre_1.init())

    @functools.partial(jax.shard_map, mesh=_model_mesh(),
                       in_specs=(PARAM_SPECS,
                                 jax.tree.map(lambda _: P('model'), k1),
                                 P(), P()),
                       out_specs=PARAM_SPECS)
    def tp_step(params, kstate, x, y):
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            model, lambda out: _ce(out, y), {'params': params}, x,
            axis_name='model')
        k = jax.tree.map(lambda a: a[0], kstate)
        new_grads, _ = pre_1.step(k, grads, acts, gs)
        return new_grads

    want = tp_step(gp, k1, x, y)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4),
        got, want)


# ---------------------------------------------------------------------------
# Megatron transformer block
# ---------------------------------------------------------------------------

TD, TH, TDK, TDI, TL = 16, 4, 4, 32, 6   # d_model, heads, d_k=d_v, d_inner, L
TH_L, TDI_L = TH // NM, TDI // NM

TP_BLOCK_SPECS = {
    'self_attn': {
        'w_q': {'slice': {'kernel': P(None, 'model')}},
        'w_k': {'slice': {'kernel': P(None, 'model')}},
        'w_v': {'slice': {'kernel': P(None, 'model')}},
        'w_o': {'slice': {'kernel': P('model', None)}},
        'ln': {'scale': P(), 'bias': P()}},
    'ffn': {
        'w_1': {'slice': {'kernel': P(None, 'model'), 'bias': P('model')}},
        'w_2': {'slice': {'kernel': P('model', None)}, 'bias': P()},
        'ln': {'scale': P(), 'bias': P()}},
}


def _block_data(seed=3):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(B, TL, TD), jnp.float32)


def _plain_block_params(seed=4):
    from kfac_pytorch_tpu.models.transformer import EncoderLayer
    plain = EncoderLayer(TD, TDI, TH, TDK, TDK, dropout=0.0)
    params = plain.init(jax.random.PRNGKey(seed), _block_data(), None,
                        train=False)['params']
    return plain, params


def _tp_block_params(pp):
    """Global TP-structured params from the plain block's (head-block
    column slicing is contiguous, so the full arrays transfer as-is)."""
    a, f = pp['self_attn'], pp['ffn']
    return {
        'self_attn': {
            'w_q': {'slice': {'kernel': a['w_q']['kernel']}},
            'w_k': {'slice': {'kernel': a['w_k']['kernel']}},
            'w_v': {'slice': {'kernel': a['w_v']['kernel']}},
            'w_o': {'slice': {'kernel': a['w_o']['kernel']}},
            'ln': dict(a['ln'])},
        'ffn': {
            'w_1': {'slice': {'kernel': f['w_1']['kernel'],
                              'bias': f['w_1']['bias']}},
            'w_2': {'slice': {'kernel': f['w_2']['kernel']},
                    'bias': f['w_2']['bias']},
            'ln': dict(f['ln'])},
    }


def test_tp_encoder_block_matches_dense_block():
    """The full Megatron block (sharded attention heads + sharded FFN)
    reproduces models/transformer.EncoderLayer exactly — outputs AND the
    parameter gradients (slices thereof) on a 2-rank model mesh."""
    x = _block_data()
    plain, pp = _plain_block_params()
    tpp = _tp_block_params(pp)
    block = tp.TPEncoderLayer(TD, TDI_L, TH_L, TDK, TDK, dropout=0.0)

    @functools.partial(jax.shard_map, mesh=_model_mesh(),
                       in_specs=(TP_BLOCK_SPECS, P()),
                       out_specs=(P(), TP_BLOCK_SPECS))
    def fwd_bwd(params, x):
        def loss_fn(p):
            out = block.apply({'params': p}, x, None, train=False)
            return (out ** 2).mean(), out
        (loss, out), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, grads

    loss_tp, grads_tp = fwd_bwd(tpp, x)

    def plain_loss(p):
        out = plain.apply({'params': p}, x, None, train=False)
        return (out ** 2).mean()

    loss_pl, grads_pl = jax.value_and_grad(plain_loss)(pp)
    np.testing.assert_allclose(float(loss_tp), float(loss_pl), rtol=1e-6)
    flat_tp = _tp_block_params(grads_pl)  # plain grads in TP layout
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        grads_tp, flat_tp)


def test_tp_encoder_block_kfac_dp_tp_invariance():
    """One K-FAC step on the Megatron block over a 2x2 ('data', 'model')
    mesh (MPD 'eigen' over the data axis) equals the model-only mesh run
    on the full batch — data sharding must not change the math, with the
    TP block's full capture set (6 sliced dense sublayers) in play."""
    ND = 2
    x = _block_data()
    y = _block_data(seed=9)  # regression target
    _, pp = _plain_block_params()
    tpp = _tp_block_params(pp)
    block = tp.TPEncoderLayer(TD, TDI_L, TH_L, TDK, TDK, dropout=0.0)
    local = tp.TPEncoderLayer(TD, TDI_L, TH_L, TDK, TDK, axis=None,
                              dropout=0.0)

    def mse(out, target):
        return ((out - target) ** 2).mean()

    def make_pre(nd, axis):
        pre = kfac.KFAC(variant='eigen', lr=LR, damping=DAMPING,
                        fac_update_freq=1, kfac_update_freq=1,
                        num_devices=nd, axis_name=axis)
        variables = capture.init(local, jax.random.PRNGKey(0), x,
                                 None, train=False)
        pre.setup(capture.collect_layer_meta(local, variables, x, None,
                                             train=False))
        return pre

    pre_dp = make_pre(ND, 'data')
    kstate = jax.tree.map(lambda a: jnp.stack([a] * NM), pre_dp.init())
    kspecs = jax.tree.map(lambda s: P('model', *s),
                          pre_dp.state_pspecs('data'),
                          is_leaf=lambda v: isinstance(v, P))
    mesh = Mesh(np.array(jax.devices()[:ND * NM]).reshape(ND, NM),
                ('data', 'model'))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(TP_BLOCK_SPECS, kspecs, P('data'), P('data')),
        out_specs=TP_BLOCK_SPECS)
    def dp_tp_step(params, kstate, x, y):
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            block, lambda out: mse(out, y), {'params': params}, x, None,
            train=False, axis_name=('data', 'model'))
        grads = kfac.parallel.average_grads(grads, 'data')
        k = jax.tree.map(lambda a: a[0], kstate)
        new_grads, _ = pre_dp.step(k, grads, acts, gs, axis_name='data')
        return new_grads

    got = dp_tp_step(tpp, kstate, x, y)

    pre_1 = make_pre(1, None)
    k1 = jax.tree.map(lambda a: jnp.stack([a] * NM), pre_1.init())

    @functools.partial(jax.shard_map, mesh=_model_mesh(),
                       in_specs=(TP_BLOCK_SPECS,
                                 jax.tree.map(lambda _: P('model'), k1),
                                 P(), P()),
                       out_specs=TP_BLOCK_SPECS)
    def tp_step(params, kstate, x, y):
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            block, lambda out: mse(out, y), {'params': params}, x, None,
            train=False, axis_name='model')
        k = jax.tree.map(lambda a: a[0], kstate)
        new_grads, _ = pre_1.step(k, grads, acts, gs)
        return new_grads

    want = tp_step(tpp, k1, x, y)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4),
        got, want)


def test_tp_sp_block_3axis_matches_dense_block():
    """The FULL 3-D mesh: ('data', 'seq', 'model') 2x2x2 — batch sharded
    over data, tokens over seq (exact ring attention rotates K/V per
    local head group), heads+FFN over model. Output and grad slices must
    equal the dense EncoderLayer on the full batch, causal masking on."""
    ND, NS = 2, 2
    x = _block_data()          # [B, TL, TD]; TL=6 splits over NS=2
    plain, pp = _plain_block_params()
    tpp = _tp_block_params(pp)
    block = tp.TPEncoderLayer(TD, TDI_L, TH_L, TDK, TDK, seq_axis='seq',
                              causal=True, dropout=0.0)
    mesh = Mesh(np.array(jax.devices()[:ND * NS * NM]).reshape(ND, NS, NM),
                ('data', 'seq', 'model'))
    xspec = P('data', 'seq')

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(TP_BLOCK_SPECS, xspec),
                       out_specs=(xspec, TP_BLOCK_SPECS))
    def fwd_bwd(params, x):
        def loss_fn(p):
            out = block.apply({'params': p}, x, None, train=False)
            # global-mean loss: local sum / global count, then psum —
            # invariant over all three axes
            s = (out ** 2).sum() / (B * TL * TD)
            return jax.lax.psum(s, ('data', 'seq')), out
        (loss, out), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        del loss
        return out, grads

    out_tp, grads_tp = fwd_bwd(tpp, x)

    # dense oracle: the same math with a causal mask
    causal = jnp.tril(jnp.ones((TL, TL), bool))[None, None]

    def plain_loss(p):
        out = plain.apply({'params': p}, x, causal, train=False)
        return (out ** 2).mean(), out

    (_, out_pl), grads_pl = jax.value_and_grad(
        plain_loss, has_aux=True)(pp)
    np.testing.assert_allclose(np.asarray(out_tp), np.asarray(out_pl),
                               rtol=2e-4, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5),
        grads_tp, _tp_block_params(grads_pl))
