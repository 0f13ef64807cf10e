"""Comm-compressed, fully-overlapped factor exchange
(parallel/collectives.py wire dtypes + KFAC(comm_precision=,
comm_prefetch=)).

Pins the tentpole contracts:

1. Wire formats: per-row int8 quantization error bound, bf16 gathers
   exact w.r.t. bf16 rounding (the bitcast-u16 wire), reduce-scatter
   stats reduce == pmean + own-row slice, EF residual algebra.
2. world=1 (``axis_name=None``) is a zero-comm IDENTITY path: any
   ``comm_precision`` is bit-identical to fp32 on one device.
3. Convergence parity on the tiny-MLP micro harness over a real
   2-device mesh: bf16 tracks fp32 tightly, int8+EF within a pinned
   loss tolerance; the EF residual is live (non-zero) for lossy MPD
   runs and absent for DP/fp32 runs.
4. EF residual state survives checkpoint save/restore and is
   ZERO-FILLED by ``reshard_kfac_state`` on an elastic world change
   (like the E-KFAC scales — transport-transient error state).
5. Cross-step prefetch (``comm_prefetch``): the published decomposition
   is bit-identical to the unprefetched run's, THIS step preconditions
   with the previous table (no same-step consumer), the first
   decomposition of a run is never prefetched, and the dispatch records
   overlapping ``kfac.CommunicateInverse.prefetch`` /
   ``kfac.Precondition`` trace spans with ``consumer_step = step + 1``.
6. The analytic volume model (plan.FactorPlan.comm_volume) prices each
   wire dtype by its compression factor.
"""

import functools

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, training
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.obs.trace import TraceRecorder
from kfac_pytorch_tpu.parallel import collectives as coll

pytestmark = pytest.mark.core


class MLP(linen.Module):
    @linen.compact
    def __call__(self, x, train=True):
        x = knn.Dense(8, name='fc1')(x)
        x = linen.relu(x)
        x = knn.Dense(3, name='fc2')(x)
        return x


def _batch(n=8):
    rng = np.random.RandomState(0)
    return {'input': jnp.asarray(rng.randn(n, 5), jnp.float32),
            'label': jnp.asarray(rng.randint(0, 3, n))}


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _trainer(variant='eigen', ndev=1, comm_precision='fp32',
             comm_prefetch=False, kfac_freq=1, stagger=False, lr=0.1,
             tracer=None):
    model = MLP()
    mesh = (Mesh(np.array(jax.devices()[:ndev]), ('batch',))
            if ndev > 1 else None)
    axis = 'batch' if ndev > 1 else None
    pre = kfac.KFAC(variant=variant, lr=lr, damping=0.003,
                    kfac_update_freq=kfac_freq, num_devices=ndev,
                    axis_name=axis, bucket_fn=lambda d: 16,
                    comm_precision=comm_precision,
                    comm_prefetch=comm_prefetch, stagger=stagger)
    tx = training.sgd(lr, momentum=0.9)
    state = training.init_train_state(model, tx, pre,
                                      jax.random.PRNGKey(0),
                                      _batch()['input'])
    step = training.build_train_step(model, tx, pre, _ce, axis_name=axis,
                                     mesh=mesh, tracer=tracer)
    return step, state, pre


# ---------------------------------------------------------------------------
# wire formats
# ---------------------------------------------------------------------------

def test_quantize_rows_roundtrip_error_bound():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(5, 7, 7) * np.array(
        [1e-3, 1.0, 50.0, 0.0, 3.0])[:, None, None], jnp.float32)
    q, scale = coll.quantize_rows(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    back = coll.dequantize_rows(q, scale)
    # per-row absmax/254 error bound (half a quantization step... the
    # round() gives absmax/127/2 per entry); the all-zero row is exact
    absmax = np.abs(np.asarray(x)).max(axis=(1, 2))
    err = np.abs(np.asarray(back) - np.asarray(x)).max(axis=(1, 2))
    assert np.all(err <= absmax / 254 + 1e-12), (err, absmax)
    assert np.all(np.asarray(back)[3] == 0)


def test_check_wire_dtype_rejects_unknown():
    with pytest.raises(ValueError, match='comm_precision'):
        coll.check_wire_dtype('fp4')
    with pytest.raises(ValueError, match='comm_precision'):
        kfac.KFAC(variant='eigen', comm_precision='f16')


def test_comm_prefetch_validation():
    # comm_pred variants gather preconditioned grads — the step's own
    # consumer, cannot be deferred
    with pytest.raises(ValueError, match='comm_prefetch'):
        kfac.KFAC(variant='eigen_dp', comm_prefetch=True)
    with pytest.raises(ValueError, match='ekfac'):
        kfac.KFAC(variant='ekfac', comm_prefetch=True)
    # fine on the comm_inverse layouts
    kfac.KFAC(variant='eigen', comm_prefetch=True)
    kfac.KFAC(variant='inverse', communicate_inverse_or_not=True,
              comm_prefetch=True)


def _mesh8():
    return Mesh(np.array(jax.devices()[:8]), ('x',))


def test_pmean_scatter_matches_pmean_plus_slice():
    mesh = _mesh8()
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 16, 4, 4), jnp.float32)

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P('x'),
                       out_specs=(P('x'), P('x')))
    def f(xs):
        got, _ = coll.pmean_scatter_ef(xs[0], 'x', 'fp32', None)
        full = coll.pmean(xs[0], 'x')
        idx = coll.axis_index('x')
        want = jax.lax.dynamic_slice_in_dim(full, idx * 2, 2, axis=0)
        return got[None], want[None]

    got, want = f(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_pmean_scatter_ef_residual_algebra():
    """bf16 EF over a mesh: the residual equals (x + r) - bf16(x + r)
    per device, it stays bounded over repeated reduces (no blow-up),
    and the EF property holds — the TIME-AVERAGED output over k reduces
    of the same data is closer to the true mean than the residual-free
    reduce's (whose quantization bias never cancels). The remaining
    common floor is the collective's bf16 OUTPUT rounding, which EF by
    design cannot see (it compensates the send, not the sum)."""
    mesh = _mesh8()
    rng = np.random.RandomState(3)
    # values with bf16-visible rounding error
    x = jnp.asarray(1.0 + 0.001 * rng.randn(8, 16, 4, 4), jnp.float32)
    k = 8

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P('x'),
                       out_specs=(P('x'), P('x'), P('x'), P('x')))
    def f(xs):
        r = jnp.zeros_like(xs[0])
        tot_ef = tot_ne = first_r = None
        for _ in range(k):
            m, r = coll.pmean_scatter_ef(xs[0], 'x', 'bf16', r)
            tot_ef = m if tot_ef is None else tot_ef + m
            first_r = r if first_r is None else first_r
            mn, _ = coll.pmean_scatter_ef(xs[0], 'x', 'bf16',
                                          jnp.zeros_like(xs[0]))
            tot_ne = mn if tot_ne is None else tot_ne + mn
        return (tot_ef[None] / k, tot_ne[None] / k, first_r[None],
                r[None])

    ef, ne, r1, rk = (np.asarray(v) for v in f(x))
    xr = np.asarray(x).reshape(8, 16, 4, 4)
    want_r1 = xr - np.asarray(
        jnp.asarray(xr).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(r1.reshape(8, 16, 4, 4), want_r1,
                               rtol=0, atol=1e-7)
    # residuals stay bounded by a few quantization steps (no blow-up)
    assert np.abs(rk).max() <= np.abs(want_r1).max() * 4 + 1e-7
    true_mean = xr.mean(axis=0)                       # [16, 4, 4]
    e_ef = np.abs(ef.reshape(16, 4, 4) - true_mean).mean()
    e_ne = np.abs(ne.reshape(16, 4, 4) - true_mean).mean()
    assert e_ef < e_ne, (e_ef, e_ne)


@pytest.mark.parametrize('precision', ['bf16', 'int8'])
def test_all_gather_rows_compressed_mesh(precision):
    mesh = _mesh8()
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(8, 2, 6, 6), jnp.float32)

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P('x'),
                       out_specs=P(None))
    def f(xs):
        return coll.all_gather_rows_compressed(xs.reshape(2, 6, 6), 'x',
                                               precision)

    got = np.asarray(f(x))
    full = np.asarray(x).reshape(16, 6, 6)
    if precision == 'bf16':
        # the u16 bitcast wire is EXACT w.r.t. bf16 rounding
        want = np.asarray(jnp.asarray(full).astype(jnp.bfloat16)
                          .astype(jnp.float32))
        np.testing.assert_array_equal(got, want)
    else:
        absmax = np.abs(full).max(axis=(1, 2), keepdims=True)
        assert np.all(np.abs(got - full) <= absmax / 254 + 1e-12)


# ---------------------------------------------------------------------------
# world=1 identity + convergence parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('variant', ['eigen', 'eigen_dp'])
@pytest.mark.parametrize('precision', ['bf16', 'int8'])
def test_world1_identity_bitwise(variant, precision):
    """axis_name=None must stay a zero-comm identity path: any
    comm_precision is BIT-identical to fp32 on one device."""
    batch = _batch()

    def run(p):
        step, state, _ = _trainer(variant=variant, comm_precision=p)
        out = []
        for _ in range(5):
            state, m = step(state, batch, lr=0.1, damping=0.003)
            out.append(float(m['loss']))
        return out, state

    l32, s32 = run('fp32')
    lq, sq = run(precision)
    assert l32 == lq
    for a, b in zip(jax.tree.leaves(s32.params), jax.tree.leaves(sq.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('variant,lr,damping',
                         [('eigen', 0.1, 0.003),
                          ('inverse_dp', 0.05, 0.03)])
def test_convergence_parity_mesh(variant, lr, damping):
    """The micro harness over a real 2-device mesh: bf16 tracks fp32
    tightly, int8+EF within a pinned tolerance; the EF residual is live
    exactly when a lossy MPD reduce exists."""
    batch = _batch()

    def run(p, steps=12):
        step, state, pre = _trainer(variant=variant, ndev=2,
                                    comm_precision=p, lr=lr)
        losses = []
        for _ in range(steps):
            state, m = step(state, batch, lr=lr, damping=damping)
            losses.append(float(m['loss']))
        return losses, state, pre

    l32, s32, _ = run('fp32')
    l16, s16, p16 = run('bf16')
    l8, s8, _ = run('int8')
    drop = l32[0] - l32[-1]
    assert drop > 0.1, l32                       # the harness trains
    # bf16: indistinguishable at the loss level (EF'd stats reduce +
    # bf16-rounded gathers on a damped decomposition)
    assert abs(l16[-1] - l32[-1]) <= 0.02 * drop, (l32[-1], l16[-1])
    # int8+EF: within the pinned tolerance of fp32 (the quantized
    # eigenbasis adds a noise floor near convergence — the pin is that
    # int8 achieves >=85% of the fp32 loss drop on this harness)
    assert abs(l8[-1] - l32[-1]) <= 0.15 * drop, (l32[-1], l8[-1])
    if variant == 'eigen':
        # lossy MPD reduce -> EF residual live (non-zero after steps)
        assert s16.kfac_state.comm_err is not None
        total = sum(float(jnp.abs(v).sum())
                    for v in s16.kfac_state.comm_err.values())
        assert total > 0
        # fp32 carries NO residual state
        assert s32.kfac_state.comm_err is None
    else:
        # DP variants never reduce stats -> no residual under any wire
        assert s16.kfac_state.comm_err is None
        assert s8.kfac_state.comm_err is None


def test_ekfac_composes_with_compressed_wire():
    """The ekfac scales pmean rides the lossy wire (no EF — documented)
    and the run stays finite and training."""
    batch = _batch()
    step, state, _ = _trainer(variant='ekfac', ndev=2,
                              comm_precision='bf16')
    losses = []
    for _ in range(8):
        state, m = step(state, batch, lr=0.1, damping=0.03)
        losses.append(float(m['loss']))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# EF residual state: checkpoint + elastic reshard
# ---------------------------------------------------------------------------

def test_comm_err_checkpoint_roundtrip(tmp_path):
    from kfac_pytorch_tpu.utils.checkpoint import (restore_checkpoint,
                                                   save_checkpoint)
    batch = _batch()
    step, state, _ = _trainer(variant='eigen', ndev=2,
                              comm_precision='bf16')
    for _ in range(3):
        state, _ = step(state, batch, lr=0.1, damping=0.003)
    assert state.kfac_state.comm_err is not None
    save_checkpoint(str(tmp_path), 0, state)
    fresh_step, fresh, _ = _trainer(variant='eigen', ndev=2,
                                    comm_precision='bf16')
    restored = restore_checkpoint(str(tmp_path), 0, fresh)
    for k, v in state.kfac_state.comm_err.items():
        np.testing.assert_array_equal(
            np.asarray(restored.kfac_state.comm_err[k]), np.asarray(v))
    # and the restored state steps without re-seeding (structure
    # intact); decommit from the restore device first, as the elastic
    # resume path does, so the mesh can reshard it
    restored = jax.tree.map(np.asarray, restored)
    restored, m = fresh_step(restored, batch, lr=0.1, damping=0.003)
    assert np.isfinite(float(m['loss']))


def test_pre_compression_checkpoint_upgrades_host_side():
    """A state carrying comm_err=None (fp32 checkpoint) dispatched
    through a lossy-configured trainer is seeded with zeros BEFORE the
    jitted call — one state structure for every variant."""
    batch = _batch()
    step32, state32, _ = _trainer(variant='eigen', ndev=2,
                                  comm_precision='fp32')
    state32, _ = step32(state32, batch, lr=0.1, damping=0.003)
    assert state32.kfac_state.comm_err is None
    step16, _, _ = _trainer(variant='eigen', ndev=2,
                            comm_precision='bf16')
    out, m = step16(state32, batch, lr=0.1, damping=0.003)
    assert np.isfinite(float(m['loss']))
    assert out.kfac_state.comm_err is not None


def test_lossy_checkpoint_restores_into_fp32_run(tmp_path):
    """The DOWNGRADE direction: a checkpoint taken under a lossy
    comm_precision (carries KFACState.comm_err) restored by a run
    configured at fp32 (target has comm_err=None). auto_resume must
    rebuild a placeholder from the checkpoint's saved shapes, restore,
    and DISCARD the residual — not scan past the checkpoint as
    'unreadable' and silently restart from scratch."""
    from kfac_pytorch_tpu.utils.checkpoint import (auto_resume,
                                                   save_checkpoint)
    batch = _batch()
    step16, state16, _ = _trainer(variant='eigen', ndev=2,
                                  comm_precision='bf16')
    for _ in range(3):
        state16, _ = step16(state16, batch, lr=0.1, damping=0.003)
    assert state16.kfac_state.comm_err is not None
    save_checkpoint(str(tmp_path), 0, state16)
    step32, fresh32, _ = _trainer(variant='eigen', ndev=2,
                                  comm_precision='fp32')
    assert fresh32.kfac_state.comm_err is None
    restored, epoch = auto_resume(str(tmp_path), 5, fresh32)
    assert epoch == 0 and restored is not None
    assert restored.kfac_state.comm_err is None
    for k, v in state16.kfac_state.factors.items():
        np.testing.assert_array_equal(
            np.asarray(restored.kfac_state.factors[k]), np.asarray(v))
    restored = jax.tree.map(np.asarray, restored)
    restored, m = step32(restored, batch, lr=0.1, damping=0.003)
    assert np.isfinite(float(m['loss']))


def test_reshard_zero_fills_comm_err_on_grow():
    """Elastic grow 1 -> 2: factors transport exactly, the EF residual
    re-initializes to zeros in the NEW world's shape (like the ekfac
    scales — error state re-accumulates, it is never transported)."""
    from kfac_pytorch_tpu.utils.checkpoint import reshard_kfac_state
    batch = _batch()
    step1, state1, p1 = _trainer(variant='eigen', ndev=1,
                                 comm_precision='bf16')
    for _ in range(3):
        state1, _ = step1(state1, batch, lr=0.1, damping=0.003)
    k1 = state1.kfac_state
    # world=1 is the identity path: residual stays exactly zero
    assert all(not np.any(np.asarray(v)) for v in k1.comm_err.values())
    p2 = kfac.KFAC(variant='eigen', num_devices=2, axis_name='batch',
                   bucket_fn=lambda d: 16, comm_precision='bf16')
    p2.setup(p1.plan.metas)
    k2 = reshard_kfac_state(p1, p2, k1)
    assert k2.comm_err is not None
    for d in p2.plan.bucket_dims:
        b = p2.plan.buckets[d]
        assert k2.comm_err[str(d)].shape == (2 * b.n_rows, d, d)
        assert not np.any(np.asarray(k2.comm_err[str(d)]))
    # the factor statistics themselves transported exactly
    for i, meta in enumerate(p1.plan.metas):
        ba_o, ra_o, bg_o, rg_o, _ = p1.plan.layer_rows[i]
        ba_n, ra_n, bg_n, rg_n, _ = p2.plan.layer_rows[i]
        da, dg = meta.in_dim, meta.out_dim
        np.testing.assert_array_equal(
            np.asarray(k2.factors[str(ba_n)])[ra_n, :da, :da],
            np.asarray(k1.factors[str(ba_o)])[ra_o, :da, :da])
        np.testing.assert_array_equal(
            np.asarray(k2.factors[str(bg_n)])[rg_n, :dg, :dg],
            np.asarray(k1.factors[str(bg_o)])[rg_o, :dg, :dg])


# ---------------------------------------------------------------------------
# cross-step prefetch
# ---------------------------------------------------------------------------

def test_prefetch_publishes_same_table_consumes_previous():
    """comm_prefetch changes WHEN the gathered table is consumed, never
    what is published: the stored decomposition after every step is
    bit-identical to the unprefetched run's (frozen params via lr=0),
    while the refresh step's preconditioning uses the PREVIOUS table."""
    batch = _batch()
    step_p, state_p, _ = _trainer(variant='eigen', kfac_freq=2,
                                  comm_prefetch=True, lr=0.0)
    step_n, state_n, _ = _trainer(variant='eigen', kfac_freq=2,
                                  comm_prefetch=False, lr=0.0)
    for t in range(6):
        state_p, _ = step_p(state_p, batch, lr=0.0, damping=0.003)
        state_n, _ = step_n(state_n, batch, lr=0.0, damping=0.003)
        for a, b in zip(jax.tree.leaves(state_p.kfac_state.decomp),
                        jax.tree.leaves(state_n.kfac_state.decomp)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the first inverse update is NEVER prefetched (cold table): the
    # dispatch cache records pf=False for the first (uf, ui) key
    first_keys = [k for k in step_p.variants if len(k) == 5 and k[1]]
    assert any(k[4] is False for k in first_keys), step_p.variants


def test_prefetch_defers_consumption_one_step():
    """Direct engine-level pin: with prefetch, grads returned at an
    inverse-update step are preconditioned with the PREVIOUS stored
    decomposition."""
    model = MLP()
    batch = _batch()
    variables = capture.init(model, jax.random.PRNGKey(0),
                             batch['input'])
    metas = capture.collect_layer_meta(model, variables, batch['input'])
    pre = kfac.KFAC(variant='eigen', num_devices=1, axis_name=None,
                    bucket_fn=lambda d: 16, comm_prefetch=True)
    pre.setup(metas)
    loss_fn = lambda out: _ce(out, batch)  # noqa: E731
    _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
        model, loss_fn, variables, batch['input'])
    state0 = pre.init()
    _, state1 = pre.step(state0, grads, acts, gs)       # table A
    # prefetch step: publishes table B, preconditions with table A
    g_pref, state2 = pre.step(state1, grads, acts, gs, prefetch=True)
    # reference: precondition with table A, no inverse update
    g_prev, _ = pre.step(state1, grads, acts, gs, update_inverse=False)
    for a, b in zip(jax.tree.leaves(g_pref), jax.tree.leaves(g_prev)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the published table B is the fresh one, not A
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state2.decomp),
                        jax.tree.leaves(state1.decomp)))
    assert changed


@pytest.mark.parametrize('mode', ['prefetch', 'stagger'])
def test_prefetch_trace_spans_overlap(mode):
    """The dispatch records the schedule: a CommunicateInverse.prefetch
    span whose args pin consumer_step == step + 1 (no same-step
    consumer), wall-overlapping the Precondition span of the SAME step
    — the trace-level witness that the gather rides under the pred
    einsums."""
    batch = _batch()
    tracer = TraceRecorder(None)
    step, state, _ = _trainer(variant='eigen', kfac_freq=2,
                              comm_prefetch=(mode == 'prefetch'),
                              stagger=(mode == 'stagger'), tracer=tracer)
    for _ in range(5):
        state, _ = step(state, batch, lr=0.1, damping=0.003)
    evs = tracer.events()
    gathers = [e for e in evs
               if e.get('name') == 'kfac.CommunicateInverse.prefetch']
    preds = {e['args']['step']: e for e in evs
             if e.get('name') == 'kfac.Precondition'}
    assert gathers, [e.get('name') for e in evs]
    for g in gathers:
        step_i = g['args']['step']
        assert g['args']['consumer_step'] == step_i + 1
        if mode == 'stagger':
            assert g['args']['cohort'] == step_i % 2
        p = preds[step_i]
        # wall overlap of the two spans
        g0, g1 = g['ts'], g['ts'] + g['dur']
        p0, p1 = p['ts'], p['ts'] + p['dur']
        assert max(g0, p0) < min(g1, p1), (g, p)
    # step 0 (the cold full decomposition) must NOT be prefetched
    assert 0 not in {g['args']['step'] for g in gathers}


# ---------------------------------------------------------------------------
# analytic volume model
# ---------------------------------------------------------------------------

def test_plan_comm_volume_compression_factors():
    model = MLP()
    batch = _batch()
    variables = capture.init(model, jax.random.PRNGKey(0),
                             batch['input'])
    metas = capture.collect_layer_meta(model, variables, batch['input'])
    pre = kfac.KFAC(variant='eigen', num_devices=2, axis_name='batch',
                    bucket_fn=lambda d: 16)
    plan = pre.setup(metas)
    v32 = plan.comm_volume(stats_reduce='pmean', method='eigh',
                           comm_precision='fp32')
    v16 = plan.comm_volume(stats_reduce='pmean', method='eigh',
                           comm_precision='bf16')
    v8 = plan.comm_volume(stats_reduce='pmean', method='eigh',
                          comm_precision='int8')
    assert v32['FactorComm'] > 0 and v32['InverseComm'] > 0
    assert v32['PredComm'] == 0
    # bf16 halves both; int8 quarters the gather body (+ scale side
    # channel) while the reduce floors at bf16
    assert v16['FactorComm'] == v32['FactorComm'] // 2
    assert v16['InverseComm'] == v32['InverseComm'] // 2
    assert v8['FactorComm'] == v16['FactorComm']
    assert v8['InverseComm'] < v16['InverseComm']
    # DP layout: no factor reduce, pred gather instead
    pre_dp = kfac.KFAC(variant='eigen_dp', num_devices=2,
                       axis_name='batch', bucket_fn=lambda d: 16)
    plan_dp = pre_dp.setup(metas)
    vdp = plan_dp.comm_volume(stats_reduce='local', method='eigh',
                              comm_precision='bf16')
    assert vdp['FactorComm'] == 0 and vdp['InverseComm'] == 0
    assert vdp['PredComm'] > 0


def test_analytic_comm_model_cli_helper():
    from scripts.comm_models import analytic_comm_volumes
    vols = analytic_comm_volumes('resnet20', 'eigen', ndev=8)
    assert set(vols) == {'fp32', 'bf16', 'int8'}
    t32 = sum(vols['fp32'].values())
    t16 = sum(vols['bf16'].values())
    assert 0.4 <= t16 / t32 <= 0.55   # ~half, modulo the evals vector


def test_ef_rounding_is_reduce_precision_and_matches_the_bf16_cast():
    """pmean_scatter_ef takes the wire rounding with lax.reduce_precision
    — the form XLA never elides (on the TPU it folded
    ``xc - f32(bf16(xc))`` to exactly 0: PERF.md, PR 21) — and that form
    has the cast's bits, ties and edge values included."""
    r = np.random.RandomState(3)
    x = np.concatenate([
        r.randn(4096).astype(np.float32) * 10.0 ** r.randint(-30, 30, 4096),
        np.float32([0.0, -0.0, 1.00390625, 1.01171875, 3.3895314e38,
                    1e-40, np.inf, -np.inf])])
    x = jnp.asarray(x)
    rounded = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    cast = x.astype(jnp.bfloat16).astype(jnp.float32)     # eager: kept
    assert np.array_equal(np.asarray(rounded), np.asarray(cast))
    # and under jit, where the cast form is at the compiler's mercy
    jitted = jax.jit(lambda v: v - jax.lax.reduce_precision(
        v, exponent_bits=8, mantissa_bits=7))(x[:4096])
    assert np.array_equal(np.asarray(jitted),
                          np.asarray(x[:4096] - cast[:4096]))
    import inspect
    from kfac_pytorch_tpu.parallel import collectives
    assert 'reduce_precision' in inspect.getsource(
        collectives.pmean_scatter_ef)
