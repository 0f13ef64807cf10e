"""Numerical-health guard chaos drills (beyond reference, health.py).

The acceptance drill: with a NaN-gradient fault injected at step k,
training runs to completion with finite loss, and params/opt_state and
the K-FAC factor state are BIT-identical to a run whose data schedule
simply skipped batch k — the EMA is uncontaminated and the trajectory
never forks. Plus: ladder escalation/degrade/recover semantics, and the
no-new-compiled-variants guarantee on the healthy path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import engine, faults, ops, training
from kfac_pytorch_tpu import health as health_lib
from kfac_pytorch_tpu.utils.metrics import HealthMonitor
from kfac_pytorch_tpu.utils.runlog import health_suffix

from tests.helpers import TinyCNN


def _batches(n_batches, n=8, hw=8, seed=0):
    rng = np.random.RandomState(seed)
    return [{'input': jnp.asarray(rng.randn(n, hw, hw, 3), jnp.float32),
             'label': jnp.asarray(rng.randint(0, 10, n))}
            for _ in range(n_batches)]


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _run(batches, health=True, variant='eigen_dp'):
    """Fresh model/precond/state, one step per batch; returns the final
    state, the per-step metrics and the step_fn (variant introspection)."""
    model = TinyCNN()
    precond = kfac.KFAC(variant=variant, lr=0.05, damping=0.003,
                        fac_update_freq=1, kfac_update_freq=1,
                        num_devices=1, axis_name=None, health=health)
    tx = training.sgd(0.05, momentum=0.9)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0),
                                      batches[0]['input'])
    step = training.build_train_step(model, tx, precond, _ce)
    mets = []
    for b in batches:
        state, m = step(state, b, lr=0.05, damping=0.003)
        mets.append({k: float(v) for k, v in m.items()})
    return state, mets, step


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_nan_batch_skips_update_and_ema(monkeypatch):
    """The acceptance chaos drill: NaN gradients at step 2 -> that batch
    is skipped in-jit, the run finishes finite, and params/opt_state/
    factors/decomp are BIT-identical to a run whose schedule never
    contained batch 2."""
    batches = _batches(5)
    monkeypatch.setenv(faults.ENV_NAN_GRAD, '2')
    faulted, mets, _ = _run(batches)
    monkeypatch.delenv(faults.ENV_NAN_GRAD)
    control, cmets, _ = _run(batches[:2] + batches[3:])

    # the fault fired exactly once, at step 2, and every loss is finite
    assert [m['health/ok'] for m in mets] == [1, 1, 0, 1, 1]
    assert mets[-1]['health/skipped'] == 1
    assert all(np.isfinite(m['loss']) for m in mets)
    # an isolated failure must not climb the damping ladder (that would
    # fork the post-skip trajectory from the control run)
    assert mets[-1]['health/rung'] == 0

    _assert_trees_equal(faulted.params, control.params)
    _assert_trees_equal(faulted.opt_state, control.opt_state)
    _assert_trees_equal(faulted.kfac_state.factors,
                        control.kfac_state.factors)
    _assert_trees_equal(faulted.kfac_state.decomp, control.kfac_state.decomp)
    # only the counters differ: the faulted run saw one more batch
    assert int(faulted.step) == 5 and int(control.step) == 4
    assert int(faulted.kfac_state.step) == 5


def test_consecutive_failures_climb_ladder_then_recover(monkeypatch):
    """4 consecutive bad batches: the ladder climbs to the top rung
    (degraded SGD), healthy steps then reset it after recover_after."""
    cfg = health_lib.HealthConfig(escalate_after=2, damping_factor=10.0,
                                  max_rungs=2, recover_after=2)
    monkeypatch.setenv(faults.ENV_NAN_GRAD, '2:6')
    batches = _batches(10, seed=1)
    state, mets, _ = _run(batches, health=cfg)

    assert [m['health/ok'] for m in mets] == [1, 1, 0, 0, 0, 0, 1, 1, 1, 1]
    assert mets[-1]['health/skipped'] == 4
    # rung after each step: 1st failure doesn't escalate, 2nd does, top
    # rung holds through the streak AND through the first healthy step,
    # then recover_after healthy steps reset it
    assert [m['health/rung'] for m in mets] == [0, 0, 0, 1, 2, 2, 2, 0, 0, 0]
    assert all(np.isfinite(m['loss']) for m in mets)
    for leaf in jax.tree.leaves(state.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_transition_functions():
    """Pure-function semantics of the ladder state machine."""
    cfg = health_lib.HealthConfig(escalate_after=2, damping_factor=10.0,
                                  max_rungs=3, recover_after=2)
    h = health_lib.HealthState.init()
    h = health_lib.on_bad_batch(h, cfg)
    assert int(h.bad_streak) == 1 and int(h.rung) == 0
    h = health_lib.on_bad_batch(h, cfg)
    assert int(h.rung) == 1 and int(h.skipped) == 2
    # non-finite preconditioner output escalates like a skipped batch
    h = health_lib.on_good_batch(h, cfg, jnp.asarray(False))
    assert int(h.rung) == 2 and int(h.fallbacks) == 1
    assert float(health_lib.effective_damping(h, 0.003, cfg)) == (
        pytest.approx(0.3))
    assert not bool(health_lib.degraded(h, cfg))
    h = health_lib.on_bad_batch(h, cfg)
    assert int(h.rung) == 3 and bool(health_lib.degraded(h, cfg))
    # rung saturates at max_rungs
    h = health_lib.on_bad_batch(h, cfg)
    assert int(h.rung) == 3
    # recovery: recover_after consecutive healthy steps reset the ladder
    h = health_lib.on_good_batch(h, cfg, jnp.asarray(True))
    assert int(h.rung) == 3 and int(h.bad_streak) == 0
    h = health_lib.on_good_batch(h, cfg, jnp.asarray(True))
    assert int(h.rung) == 0 and int(h.good_streak) == 2


def test_healthy_path_compiles_same_variant_count(monkeypatch):
    """The guard adds no compiled step variants: same dispatch keys with
    health on, health off, and health on + a configured (unfired) fault."""
    batches = _batches(4, seed=2)
    _, _, step_on = _run(batches, health=True)
    _, _, step_off = _run(batches, health=False)
    assert set(step_on.variants) == set(step_off.variants)
    monkeypatch.setenv(faults.ENV_NAN_GRAD, '100')  # never fires in 4 steps
    _, mets, step_armed = _run(batches, health=True)
    assert set(step_armed.variants) == set(step_on.variants)
    assert all(m['health/ok'] == 1 for m in mets)


def test_stats_fault_triggers_skip(monkeypatch):
    """NaN captured (a, g) statistics with FINITE gradients still skip the
    batch — the screen covers the factor statistics, not just grads."""
    monkeypatch.setenv(faults.ENV_STATS, '1')
    batches = _batches(3, seed=3)
    state, mets, _ = _run(batches)
    assert [m['health/ok'] for m in mets] == [1, 0, 1]
    assert mets[-1]['health/skipped'] == 1
    for leaf in jax.tree.leaves(state.kfac_state.factors):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_guard_off_nan_contaminates(monkeypatch):
    """Negative control: with health=False the same injected batch
    permanently poisons params — the guard is what prevents it."""
    monkeypatch.setenv(faults.ENV_NAN_GRAD, '1')
    batches = _batches(3, seed=4)
    state, mets, _ = _run(batches, health=False)
    assert not any(k.startswith('health/') for k in mets[0])
    assert state.health is None
    bad = any(not np.all(np.isfinite(np.asarray(leaf)))
              for leaf in jax.tree.leaves(state.params))
    assert bad, 'NaN batch should contaminate an unguarded run'


def test_health_monitor_and_suffix():
    """Host-side monitor: diffs cumulative counters, counts per-epoch
    deltas, formats the run-log suffix (empty when clean)."""
    mon = HealthMonitor()
    mon.update({'health/ok': 1, 'health/skipped': 0, 'health/fallbacks': 0,
                'health/rung': 0, 'health/bad_streak': 0})
    assert health_suffix(mon.epoch_flush()) == ''
    mon.update({'health/ok': 0, 'health/skipped': 2, 'health/fallbacks': 1,
                'health/rung': 1, 'health/bad_streak': 2})
    s = health_suffix(mon.epoch_flush())
    assert s == ' [health: skipped=2 sgd_fallbacks=1 max_rung=1]'
    # flush reset the epoch accumulators; cumulative totals keep running
    assert health_suffix(mon.epoch_flush()) == ''
    assert mon.skipped == 2 and mon.fallbacks == 1
    # metrics without health/* are a no-op (guard disabled)
    mon.update({'loss': 1.0})


def test_resolve():
    assert health_lib.resolve(True) == health_lib.HealthConfig()
    assert health_lib.resolve(False) is None
    assert health_lib.resolve(None) is None
    cfg = health_lib.HealthConfig(max_rungs=5)
    assert health_lib.resolve(cfg) is cfg
    with pytest.raises(TypeError):
        health_lib.resolve('yes')


# -- the guard's flags come from what the update step makes anyway ---------
# (inverse_dp, the variant the benchmark's cells run: PERF.md, PR 42)

def _one_element(tree, leaf, hit):
    """``tree`` with ONE element of its ``leaf``-th inexact leaf NaN where
    ``hit``: an element in the middle of the tensor, which a statistic
    reads into a whole row and column of off-diagonal entries."""
    leaves, treedef = jax.tree.flatten(tree)
    floats = [i for i, x in enumerate(leaves)
              if jnp.issubdtype(x.dtype, jnp.inexact) and x.ndim > 1]
    x = leaves[floats[leaf]]
    where = tuple(n // 2 for n in x.shape)
    leaves[floats[leaf]] = x.at[where].set(
        jnp.where(hit, jnp.nan, x[where]))
    return jax.tree.unflatten(treedef, leaves)


@pytest.mark.parametrize('route', ['solves', 'structured'])
@pytest.mark.parametrize('fault', ['nan', 'inf', 'tiny_pivot', 'none'])
def test_inverse_flag_is_read_from_the_diagonal_on_both_routes(
        fault, route, monkeypatch):
    """One OFF-diagonal element of one Cholesky FACTOR not finite (or one
    pivot so small that ``L^-1`` is finite and its squares are not): the
    inverse made of it has a row that is not finite, and
    ``ops.inverse_rows_finite`` says so from the inverse's diagonal alone,
    for that row and no other. On the two dense solves and on the blocked
    triangular inverse and product (blocks of 16 over 72 rows: the last
    one partial, the poisoned element in the rows of another block than
    its column)."""
    from kfac_pytorch_tpu.ops import linalg
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM',
                        32 if route == 'structured' else 10 ** 6)
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_BLOCK', 16)
    rows, d = 4, 72
    assert ops.inverse_route(d) == route
    a = jax.random.normal(jax.random.PRNGKey(3), (rows, d, d), jnp.float32)
    chol = jnp.linalg.cholesky(
        jnp.einsum('nij,nkj->nik', a, a) / d + 0.1 * jnp.eye(d))
    chol = {'nan': chol.at[2, 50, 9].set(jnp.nan),
            'inf': chol.at[2, 50, 9].set(jnp.inf),
            'tiny_pivot': chol.at[2, 37, 37].set(1e-30),
            'none': chol}[fault]
    inv = np.asarray(jax.jit(lambda c: linalg._inverse_of_factor(c))(chol))
    every = np.isfinite(inv).all(axis=(1, 2))
    assert list(every) == [True, True, fault == 'none', True]
    flag = np.asarray(ops.inverse_rows_finite(jnp.asarray(inv)))
    np.testing.assert_array_equal(flag, every)
    if fault != 'none':
        # the witness is on the diagonal; the poison was not
        assert not np.isfinite(np.diagonal(inv[2])).all()


@pytest.fixture(scope='module')
def healthy_inverse_dp():
    """Four batches through ``inverse_dp`` with the guard on: the control
    of the cases below (one run for all of them)."""
    batches = _batches(4, seed=9)
    return batches, _run(batches, variant='inverse_dp')


@pytest.mark.parametrize('operand', ['activation', 'output_gradient'])
def test_one_poisoned_captured_element_refuses_the_batch(
        monkeypatch, healthy_inverse_dp, operand):
    """One NaN in one captured tensor, gradients finite: the screen reads
    no captured tensor any more, and the statistic that read the element
    (``engine.stats_finite``) refuses the batch all the same. Params,
    optimizer state, factors and decomposition end bit-identical to a run
    that never saw the batch."""
    batches, (control, _, _) = healthy_inverse_dp

    def poison(cfg, step, acts, gs):
        if acts is None:
            return acts, gs
        if operand == 'activation':
            return _one_element(acts, 1, step == 1), gs
        return acts, _one_element(gs, 1, step == 1)

    monkeypatch.setattr(faults, 'corrupt_captured', poison)
    faulted, mets, _ = _run(batches[:1] + batches[:1] + batches[1:],
                            variant='inverse_dp')
    assert [m['health/ok'] for m in mets] == [1, 0, 1, 1, 1]
    assert mets[-1]['health/skipped'] == 1
    _assert_trees_equal(faulted.params, control.params)
    _assert_trees_equal(faulted.opt_state, control.opt_state)
    _assert_trees_equal(faulted.kfac_state.factors,
                        control.kfac_state.factors)
    _assert_trees_equal(faulted.kfac_state.decomp, control.kfac_state.decomp)


def test_guard_on_is_bit_identical_to_guard_off(healthy_inverse_dp):
    """A healthy factor+decomposition step: the guard's flags and its
    (idle) repair loops leave params, factors and decomposition as
    ``health=False`` computes them, to the bit. One step: from the second
    on the two programs' optimizer updates round differently in the last
    bit on the CPU (they did before the guard read its flags this way,
    too), which says nothing of the guard."""
    batches, _ = healthy_inverse_dp
    on, mets, _ = _run(batches[:1], variant='inverse_dp')
    off, _, _ = _run(batches[:1], health=False, variant='inverse_dp')
    assert mets[0]['health/ok'] == 1
    _assert_trees_equal(on.params, off.params)
    _assert_trees_equal(on.kfac_state.factors, off.kfac_state.factors)
    _assert_trees_equal(on.kfac_state.decomp, off.kfac_state.decomp)


def test_healthy_update_step_reads_no_operand_for_the_guard_alone(
        healthy_inverse_dp):
    """The lowered factor+decomposition step of ``inverse_dp`` on one
    device: no ``is_finite`` over a captured tensor, and over a
    ``[rows, D, D]`` bucket one a bucket, the running average's own in the
    pass that writes it (one fusion on the chip:
    tests/test_chip_compile.py) — none over a stored factor or inverse
    bucket, none over a fresh inverse. Every other flag is read from at
    most ``[rows, D]`` values."""
    import re
    from kfac_pytorch_tpu import capture
    batches, (state, _, step) = healthy_inverse_dp
    update = step.variants[(True, True, True, False, False)]
    hyper = kfac.KFACHyperParams(lr=jnp.float32(0.05),
                                 damping=jnp.float32(0.003))
    text = update.lower(state, batches[0], hyper).as_text()
    screened = re.findall(
        r'stablehlo\.is_finite %\S+ : \(?tensor<([0-9x]+)xf32>', text)
    assert screened
    model = TinyCNN()
    _, _, _, acts, gs, _ = jax.eval_shape(
        lambda v, x: capture.value_and_grad_with_capture(
            model, lambda o: _ce(o, batches[0]), v, x),
        {'params': state.params}, batches[0]['input'])
    captured = {'x'.join(map(str, x.shape))
                for x in jax.tree.leaves((acts, gs)) if x.ndim > 1}
    assert captured and not captured & set(screened), screened
    buckets = ['x'.join(map(str, v.shape))
               for v in state.kfac_state.factors.values()]
    assert [screened.count(b) for b in buckets] == [1] * len(buckets)
    # nothing the size of a bucket, or of a captured tensor, is selected
    # row against row either
    assert not re.search(r'kfac\.HealthGuard\.factors', text)


def test_guard_passes_in_the_setup_record(caplog):
    """``kfac.precond.setup`` counts the whole-operand passes the guard
    still makes: none for the Cholesky variants on the reference capture
    path, the eigenvectors' for ``eigen_dp``, none with the guard off."""
    import logging

    from kfac_pytorch_tpu import capture
    model = TinyCNN()
    x = _batches(1)[0]['input']
    params = capture.init(model, jax.random.PRNGKey(0), x)['params']
    metas = capture.collect_layer_meta(model, {'params': params}, x)
    seen = []
    with caplog.at_level(logging.INFO, logger='kfac_pytorch_tpu'):
        for variant, health in (('inverse_dp', True), ('eigen_dp', True),
                                ('eigen_dp', False)):
            pre = kfac.KFAC(variant=variant, num_devices=1, axis_name=None,
                            health=health)
            pre.setup(metas)
            seen.append(pre.guard_passes())
    n = len(pre.plan.bucket_dims)
    assert seen == [{}, {'eigenvectors': 2 * n}, {}]
    said = [r.getMessage() for r in caplog.records
            if 'precond.setup' in r.getMessage()]
    assert 'guard_passes 0' in said[0] and 'guard_passes 0' in said[2]
    assert (f'guard_passes {2 * n}' in said[1]
            and "guard_passes_over {'eigenvectors'" in said[1])
