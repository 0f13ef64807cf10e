"""Staggered preconditioner refresh (plan.build_cohorts + the
engine cohort decompose/merge + KFAC(stagger=True)).

Pins the tentpole contracts:

1. Exactness: after any full ``kfac_update_freq`` window, every slot's
   stored decomposition equals what the unstaggered schedule would have
   computed at the step that slot's cohort refreshed on — the cohort
   eigh/Cholesky IS the full one, just row-subsetted (world=1 via the
   preconditioner API, world=2 through the jitted trainer on a fake
   mesh).
2. Bit-stability: rows outside the refreshed cohort keep their stored
   bits exactly (the merge scatter touches only cohort rows; padding
   writes re-write the stored value).
3. Compile-count guard: the cohort index is TRACED — turning stagger on
   compiles no more distinct step programs than leaving it off, for any
   ``kfac_update_freq``.
4. Cohort balance: max per-step Σ D³ over cohorts ≤ ~2x the mean, and
   max per-step refreshed rows ≤ ceil(total_rows / kfac_update_freq).
"""

import math

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, engine, training
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.capture import LayerMeta
from kfac_pytorch_tpu.plan import build_cohorts, build_plan, default_bucket_fn

pytestmark = pytest.mark.core


class MLP(linen.Module):
    @linen.compact
    def __call__(self, x, train=True):
        x = knn.Dense(8, name='fc1')(x)
        x = linen.relu(x)
        x = knn.Dense(3, name='fc2')(x)
        return x


def _setup(variant, batch=4, **kw):
    model = MLP()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 5), jnp.float32)
    y = jnp.asarray(rng.randn(batch, 3), jnp.float32)
    variables = capture.init(model, jax.random.PRNGKey(0), x)
    metas = capture.collect_layer_meta(model, variables, x)
    precond = kfac.KFAC(variant=variant, num_devices=1, axis_name=None,
                        bucket_fn=lambda d: 16, **kw)
    precond.setup(metas)
    state = precond.init()
    loss_fn = lambda out: jnp.mean((out - y) ** 2)  # noqa: E731
    _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
        model, loss_fn, variables, x)
    return precond, state, grads, acts, gs, metas


# ---------------------------------------------------------------------------
# satellite: default_bucket_fn boundary values
# ---------------------------------------------------------------------------

def test_default_bucket_fn_boundaries():
    # the next multiple of the MXU tile (128), 128 the floor
    assert default_bucket_fn(1) == 128
    assert default_bucket_fn(128) == 128
    assert default_bucket_fn(129) == 256
    assert default_bucket_fn(256) == 256
    assert default_bucket_fn(257) == 384
    assert default_bucket_fn(1024) == 1024
    assert default_bucket_fn(1025) == 1152   # one tile, not a ladder rung
    # multiples of the tile stay exact (ResNet-50's 4608 case)
    assert default_bucket_fn(4608) == 4608
    # a non-multiple rounds UP to the next multiple of 128
    assert default_bucket_fn(5000) == 5120
    assert default_bucket_fn(2304 + 1) == 2432
    # monotone, never below the input, never a whole tile above it
    prev = 0
    for d in (1, 64, 128, 129, 191, 192, 193, 767, 768, 769, 1024, 1025,
              1279, 1280, 4608, 5000):
        b = default_bucket_fn(d)
        assert b >= d and b >= prev and (b - d < 128 or b == 128)
        prev = b


# ---------------------------------------------------------------------------
# cohort layout: balance + row budget
# ---------------------------------------------------------------------------

def _synthetic_plan(dims, num_devices=1):
    metas = {}
    for i, (din, dout) in enumerate(dims):
        m = LayerMeta(name=f'l{i}', path=(f'l{i}',), kind='dense',
                      use_bias=False, in_dim=din, out_dim=dout,
                      kernel_shape=(din, dout))
        metas[m.name] = m
    return build_plan(metas, num_devices=num_devices, comm_mode='pred')


@pytest.mark.parametrize('num_cohorts', [2, 4, 8])
def test_cohort_cost_balance_and_row_budget(num_cohorts):
    # a mixed-size model: several bucket classes, enough slots per device
    dims = [(48, 96), (96, 96), (96, 192), (192, 192), (192, 384),
            (384, 384), (384, 192), (192, 96)]
    plan = _synthetic_plan(dims)
    cohorts = build_cohorts(plan, num_cohorts)
    costs = cohorts.cohort_cost[0]
    assert costs.sum() > 0
    # max per-step Σ D³ over cohorts ≤ ~2x the mean
    assert costs.max() <= 2.0 * costs.mean() + 1e-9, costs
    # every valid row appears in exactly one cohort; none dropped
    total = cohorts.total_rows()
    n_valid = sum(int(plan.buckets[b].valid.sum()) for b in plan.bucket_dims)
    assert total == n_valid
    # max per-step refreshed rows ≤ ceil(total / F) (count-first greedy
    # keeps cohort counts within ±1 at all times)
    assert cohorts.max_rows_per_step() <= math.ceil(total / num_cohorts)
    assert cohorts.cohort_count.max() - cohorts.cohort_count.min() <= 1


def test_cohort_padding_points_outside_cohort():
    """Padding rows must never collide with a real update in the same
    cohort — that is what makes the merge scatter deterministic."""
    dims = [(48, 96), (96, 192), (192, 384), (20, 30), (30, 40)]
    plan = _synthetic_plan(dims)
    cohorts = build_cohorts(plan, 4)
    for bdim in plan.bucket_dims:
        rows, valid = cohorts.rows[bdim], cohorts.valid[bdim]
        for f in range(cohorts.num_cohorts):
            for d in range(plan.num_devices):
                real = {int(r) for r, v in zip(rows[f, d], valid[f, d]) if v}
                pads = [int(r) for r, v in zip(rows[f, d], valid[f, d])
                        if not v]
                assert not (real & set(pads)), (bdim, f, d)


# ---------------------------------------------------------------------------
# exactness, world=1 (direct preconditioner API)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('variant', ['eigen_dp', 'inverse_dp', 'eigen',
                                     'inverse'])
def test_stagger_exactness_world1(variant):
    """Staggered cohort rows equal the unstaggered (full, every-step)
    schedule's decomposition at the refresh step; untouched rows are
    bit-stable."""
    F = 3
    ps, ss, grads, acts, gs, _ = _setup(variant, kfac_update_freq=F,
                                        stagger=True)
    pf, sf, *_ = _setup(variant, kfac_update_freq=1)
    # step 0: the cold start is a full decomposition in both schedules
    _, ss = ps.step(ss, grads, acts, gs)
    _, sf = pf.step(sf, grads, acts, gs)
    layout = ps.cohorts
    assert layout is not None and layout.num_cohorts == F
    comps = ['invs'] if ps.method == 'cholesky' else ['evals', 'evecs']
    for t in range(1, 2 * F + 1):
        prev = jax.tree.map(lambda a: np.asarray(a).copy(), ss.decomp)
        _, ss = ps.step(ss, grads, acts, gs, stagger_update=True)
        _, sf = pf.step(sf, grads, acts, gs)
        # factor trajectories identical by construction
        for k in ss.factors:
            np.testing.assert_array_equal(np.asarray(ss.factors[k]),
                                          np.asarray(sf.factors[k]))
        c = t % F
        for bdim in ps.plan.bucket_dims:
            key = str(bdim)
            touched = {int(r) for r, v in zip(layout.rows[bdim][c, 0],
                                              layout.valid[bdim][c, 0]) if v}
            for comp in comps:
                new = np.asarray(ss.decomp[comp][key])
                ref = np.asarray(sf.decomp[comp][key])
                old = prev[comp][key]
                for r in range(new.shape[0]):
                    if r in touched:
                        np.testing.assert_allclose(
                            new[r], ref[r], rtol=1e-5, atol=1e-6,
                            err_msg=f'{comp}[{key}] row {r} step {t}')
                    else:
                        np.testing.assert_array_equal(
                            new[r], old[r],
                            err_msg=f'{comp}[{key}] row {r} (untouched) '
                                    f'step {t}')


def test_stagger_double_buffer_pred_uses_previous_table():
    """The staggered step preconditions with the PREVIOUS stored table
    (the cohort it decomposes publishes next step): with unchanged
    factors, the staggered pred equals a no-update step's pred."""
    ps, ss, grads, acts, gs, metas = _setup('eigen_dp', kfac_update_freq=2,
                                            stagger=True)
    _, ss = ps.step(ss, grads, acts, gs)
    g_stale, _ = ps.step(ss, grads, update_factors=False,
                         update_inverse=False)
    g_stag, _ = ps.step(ss, grads, update_factors=False,
                        stagger_update=True)
    for name in metas:
        np.testing.assert_allclose(np.asarray(g_stag[name]['kernel']),
                                   np.asarray(g_stale[name]['kernel']),
                                   atol=0)


def test_stagger_merge_guard_keeps_stored_rows_on_nonfinite():
    """A blown cohort decomposition row falls back to the stored row
    (per-row screen in the merge), instead of poisoning the table."""
    ps, ss, grads, acts, gs, _ = _setup('eigen_dp', kfac_update_freq=2,
                                        stagger=True)
    _, ss = ps.step(ss, grads, acts, gs)
    layout = ps.cohorts
    cohort_idx = jnp.int32(1)
    cohort = engine.compute_cohort_decomposition(
        ps.plan, layout, ss.factors, cohort_idx, jnp.float32(ps.damping),
        ps.method, ps.eps, None)
    poisoned = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), cohort)
    merged = engine.merge_cohort_decomposition(
        ps.plan, layout, ss.decomp, poisoned, cohort_idx, None,
        ps.comm_mode, ps.method, guard=True)
    for comp in ('evals', 'evecs'):
        for key in merged[comp]:
            np.testing.assert_array_equal(np.asarray(merged[comp][key]),
                                          np.asarray(ss.decomp[comp][key]))
    # guard off: the NaNs land (proves the screen is what saved it)
    merged_raw = engine.merge_cohort_decomposition(
        ps.plan, layout, ss.decomp, poisoned, cohort_idx, None,
        ps.comm_mode, ps.method, guard=False)
    assert any(not np.isfinite(np.asarray(v)).all()
               for comp in ('evals', 'evecs')
               for v in merged_raw[comp].values())


@pytest.mark.filterwarnings('ignore::UserWarning')
def test_stagger_validation():
    with pytest.raises(ValueError, match='stagger'):
        kfac.KFAC(variant='eigen_dp', stagger=True, basis_update_freq=10,
                  num_devices=1, axis_name=None)
    with pytest.raises(ValueError, match='stagger'):
        kfac.KFAC(variant='inverse_dp', stagger=True, warm_start_basis=True,
                  num_devices=1, axis_name=None)
    with pytest.raises(ValueError, match='ekfac'):
        kfac.KFAC(variant='ekfac_dp', stagger=True, num_devices=1,
                  axis_name=None)


def test_scheduler_rebases_cohort_layout():
    """KFACParamScheduler rescaling kfac_update_freq must rebase the
    cohort layout (the satellite mirror of the last_full_step rebase)."""
    ps, *_ = _setup('eigen_dp', kfac_update_freq=4, stagger=True)
    assert ps.cohorts.num_cohorts == 4
    sched = kfac.KFACParamScheduler(ps, update_freq_alpha=2,
                                    update_freq_schedule=[1])
    sched.step(1)
    assert ps.kfac_update_freq == 8
    assert ps.cohorts.num_cohorts == 8
    # every valid slot still covered exactly once per window
    total = sum(int(ps.plan.buckets[b].valid.sum())
                for b in ps.plan.bucket_dims)
    assert ps.cohorts.total_rows() == total


# ---------------------------------------------------------------------------
# trainer integration: compile-count guard + world=2 exactness
# ---------------------------------------------------------------------------

def _batch(n=8):
    rng = np.random.RandomState(0)
    return {'input': jnp.asarray(rng.randn(n, 5), jnp.float32),
            'label': jnp.asarray(rng.randint(0, 3, n))}


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _trainer(stagger, kfac_freq, fac_freq=1, ndev=1, mesh=None, lr=0.05,
             variant='eigen_dp'):
    model = MLP()
    precond = kfac.KFAC(variant=variant, lr=lr, damping=0.003,
                        fac_update_freq=fac_freq, kfac_update_freq=kfac_freq,
                        num_devices=ndev,
                        axis_name='batch' if ndev > 1 else None,
                        bucket_fn=lambda d: 16, stagger=stagger)
    tx = training.sgd(lr, momentum=0.9)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0),
                                      _batch()['input'])
    step = training.build_train_step(
        model, tx, precond, _ce,
        axis_name='batch' if ndev > 1 else None, mesh=mesh)
    return step, state, precond


@pytest.mark.parametrize('fac_freq,kfac_freq', [(1, 4), (2, 4)])
def test_stagger_compile_count_guard(fac_freq, kfac_freq):
    """The cohort index must be traced, not a Python-level cache key:
    with stagger on, build_train_step's variant cache compiles no more
    distinct programs than with it off, over a schedule covering several
    full windows."""
    batch = _batch()

    def run(stagger):
        step, state, _ = _trainer(stagger, kfac_freq, fac_freq)
        for _ in range(3 * kfac_freq):
            state, _ = step(state, batch, lr=0.05, damping=0.003)
        return step.variants

    v_off = run(False)
    v_on = run(True)
    assert len(v_on) <= len(v_off), (sorted(map(str, v_on)),
                                     sorted(map(str, v_off)))
    # and the stagger keys carry the cohort count, not the cohort index
    stag_keys = [k for k in v_on if 'stagger' in k]
    assert stag_keys and all(k[2] == kfac_freq for k in stag_keys)


def test_stagger_phases_reported():
    """step_fn.last_phases must reflect the staggered dispatch (feeds
    the PhaseTimers/kfac_phase_ms observability)."""
    batch = _batch()
    step, state, _ = _trainer(True, 2, fac_freq=2)
    state, _ = step(state, batch, lr=0.05, damping=0.003)   # full
    assert 'decomp' in step.last_phases
    state, _ = step(state, batch, lr=0.05, damping=0.003)   # stagger, no uf
    assert step.last_phases == ('pred', 'decomp')
    state, _ = step(state, batch, lr=0.05, damping=0.003)   # stagger + uf
    assert step.last_phases == ('pred', 'stats', 'decomp')


@pytest.mark.parametrize('variant', ['eigen_dp', 'eigen'])
def test_stagger_world2_trainer_exactness(variant):
    """Through the jitted trainer on a 2-device fake mesh, with frozen
    params (lr=0) so both runs see identical factor trajectories: the
    staggered run's cohort rows equal the full-every-step run's rows at
    the refresh step, untouched rows bit-stable. 'eigen' additionally
    routes the cohort through the comm_inverse double-buffered gather
    (only the cohort rows travel; the merged table is replicated)."""
    ndev, F = 2, 2
    mesh = Mesh(np.array(jax.devices()[:ndev]), ('batch',))
    batch = _batch(8)
    step_s, state_s, ps = _trainer(True, F, ndev=ndev, mesh=mesh, lr=0.0,
                                   variant=variant)
    step_f, state_f, pf = _trainer(False, 1, ndev=ndev, mesh=mesh, lr=0.0,
                                   variant=variant)
    # step 0: full decomposition in both
    state_s, _ = step_s(state_s, batch, lr=0.0, damping=0.003)
    state_f, _ = step_f(state_f, batch, lr=0.0, damping=0.003)
    layout = ps.cohorts
    for t in range(1, 2 * F + 1):
        prev = jax.tree.map(lambda a: np.asarray(a).copy(),
                            state_s.kfac_state.decomp)
        state_s, _ = step_s(state_s, batch, lr=0.0, damping=0.003)
        state_f, _ = step_f(state_f, batch, lr=0.0, damping=0.003)
        for k in state_s.kfac_state.factors:
            np.testing.assert_array_equal(
                np.asarray(state_s.kfac_state.factors[k]),
                np.asarray(state_f.kfac_state.factors[k]))
        c = t % F
        for bdim in ps.plan.bucket_dims:
            key = str(bdim)
            b = ps.plan.buckets[bdim]
            touched = set()
            for d in range(ndev):
                for r, v in zip(layout.rows[bdim][c, d],
                                layout.valid[bdim][c, d]):
                    if v:
                        touched.add(d * b.per_dev + int(r))
            for comp in ('evals', 'evecs'):
                new = np.asarray(state_s.kfac_state.decomp[comp][key])
                ref = np.asarray(state_f.kfac_state.decomp[comp][key])
                old = prev[comp][key]
                for r in range(new.shape[0]):
                    if r in touched:
                        np.testing.assert_allclose(
                            new[r], ref[r], rtol=1e-5, atol=1e-6,
                            err_msg=f'{comp}[{key}] row {r} step {t}')
                    else:
                        np.testing.assert_array_equal(
                            new[r], old[r],
                            err_msg=f'{comp}[{key}] row {r} (untouched) '
                                    f'step {t}')


def test_stagger_eigh_fault_drill_heals(monkeypatch):
    """Chaos parity with the full path: an injected eigh blowup on a
    staggered step (KFAC_FAULT_EIGH_STEP) is healed by the merge's
    per-row screen — training continues finite, and the poisoned
    cohort's stored rows keep serving the previous decomposition."""
    monkeypatch.setenv('KFAC_FAULT_EIGH_STEP', '2')
    batch = _batch(16)
    step, state, _ = _trainer(True, 2, lr=0.1)
    for _ in range(6):
        state, m = step(state, batch, lr=0.1, damping=0.003)
        assert np.isfinite(float(m['loss']))
    for comp in ('evals', 'evecs'):
        for v in state.kfac_state.decomp[comp].values():
            assert np.isfinite(np.asarray(v)).all()


def test_stagger_training_reduces_loss():
    """End-to-end sanity: a staggered K-FAC run still trains."""
    batch = _batch(16)
    step, state, _ = _trainer(True, 3, lr=0.1)
    losses = []
    for _ in range(8):
        state, m = step(state, batch, lr=0.1, damping=0.003)
        losses.append(float(m['loss']))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# satellite: phase timers + epoch-line suffix
# ---------------------------------------------------------------------------

def test_phase_timers_marginal_attribution():
    from kfac_pytorch_tpu.utils.metrics import PhaseTimers
    t = PhaseTimers()
    for _ in range(4):
        t.record(('pred',), 0.010)
    for _ in range(2):
        t.record(('pred', 'stats'), 0.014)
    t.record(('pred', 'stats', 'decomp', 'gather'), 0.050)
    out = t.epoch_flush()
    assert abs(out['pred'] - 10.0) < 1e-6
    assert abs(out['stats'] - 4.0) < 1e-6
    assert abs(out['decomp+gather'] - 36.0) < 1e-6
    assert abs(out['step_max'] - 50.0) < 1e-6
    assert out['step_mean'] > 0
    # flushed: second call is empty
    assert t.epoch_flush() == {}


def test_kfac_phase_suffix_format():
    from kfac_pytorch_tpu.utils.runlog import kfac_phase_suffix
    assert kfac_phase_suffix({}) == ''
    s = kfac_phase_suffix({'pred': 1.234, 'decomp+gather': 10.0})
    assert s.startswith(' kfac_phase_ms=')
    assert 'decomp+gather:10.00' in s and 'pred:1.23' in s
