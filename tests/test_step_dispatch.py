"""The host runs ahead of the device: between the caller's call and the
step's dispatch ``training.step_fn`` touches the device not at all.

The step counter lives on the host (it is read back only where the state
was replaced from outside, by object identity of ``state.step``) and the
two scalars of the step go in as NumPy float32 with the jitted call.
All on the CPU; what it is worth on the chip is PERF.md's (PR 44).
"""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import training
from kfac_pytorch_tpu.obs import trace

from tests.helpers import TinyCNN

CADENCE = 5


def _batch(n=4, poison=False):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 8, 8, 3).astype(np.float32)
    if poison:
        x[0, 0, 0, 0] = np.nan
    return {'input': jnp.asarray(x),
            'label': jnp.asarray(rng.randint(0, 10, n))}


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _trainer(with_kfac=True, health=False, mesh=None, **step_kw):
    """-> (step_fn, state, make_state, batch): one step function, a
    fresh state and the means to make another."""
    batch = _batch(8 if mesh is not None else 4)
    axis = 'batch' if mesh is not None else None
    precond = None
    if with_kfac:
        precond = kfac.KFAC(
            variant='inverse_dp', lr=0.05, damping=0.003,
            fac_update_freq=CADENCE, kfac_update_freq=CADENCE,
            num_devices=mesh.size if mesh is not None else 1,
            axis_name=axis, health=health)
    model, tx = TinyCNN(), training.sgd(0.05)

    def make_state():
        return training.init_train_state(
            model, tx, precond, jax.random.PRNGKey(0), batch['input'],
            health=health)

    state = make_state()    # sets the preconditioner up
    step = training.build_train_step(
        model, tx, precond, _ce, axis_name=axis, mesh=mesh, health=health,
        **step_kw)
    return step, state, make_state, batch


def _expected_phases(step, with_kfac=True):
    if not with_kfac:
        return ()
    return (('pred', 'stats', 'decomp') if step % CADENCE == 0
            else ('pred',))


def _dispatched_steps(rec):
    """The step index each dispatch ran under: what ``step_fn`` took the
    counter for (the recorder's ``kfac.dispatch`` span carries it)."""
    return [e['args']['step'] for e in rec.events()
            if e['ph'] == 'X' and e['name'] == 'kfac.dispatch']


def _same_bits(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)


def _spy(step, seen):
    """Put a recorder of the ``hyper`` argument in front of every variant
    the step function has built; -> the jitted variants themselves."""
    jitted = list(step.variants.values())
    for key, fn in list(step.variants.items()):
        def wrapped(state, batch, hyper, _fn=fn):
            seen.append(hyper)
            return _fn(state, batch, hyper)
        step.variants[key] = wrapped
    return jitted


# -- (a) the counter is read once ----------------------------------------------


@pytest.mark.parametrize('with_kfac', [True, False], ids=['kfac', 'sgd'])
def test_twelve_steps_read_the_device_counter_once(with_kfac):
    step, state, _, batch = _trainer(with_kfac)
    assert step.step_reads == 0
    phases = []
    for _ in range(12):
        state, mets = step(state, batch)
        phases.append(step.last_phases)
    assert step.step_reads == 1
    # the cadence int(state.step) would have given
    assert phases == [_expected_phases(k, with_kfac) for k in range(12)]
    assert int(state.step) == 12
    assert np.isfinite(float(mets['loss']))


# -- (b) the same arithmetic, bit for bit --------------------------------------


@pytest.mark.parametrize('case', ['kfac', 'sgd', 'kfac-guard-refused',
                                  'sgd-guard-refused'])
def test_counting_on_the_host_equals_reading_every_step(case):
    with_kfac = case.startswith('kfac')
    guard = case.endswith('refused')
    good, bad = _batch(), _batch(poison=True)

    def run(force_read):
        step, state, _, _ = _trainer(with_kfac, health=guard)
        phases, mets = [], None
        for k in range(12):
            if force_read:
                # a state rebuilt round a new counter array: the parent's
                # behaviour, one read a step
                state = state.replace(step=jnp.asarray(
                    int(state.step), state.step.dtype))
            state, mets = step(state, bad if guard and k == 6 else good)
            phases.append(step.last_phases)
        return step, state, phases, mets

    step_a, state_a, phases_a, mets_a = run(force_read=False)
    step_b, state_b, phases_b, mets_b = run(force_read=True)
    assert (step_a.step_reads, step_b.step_reads) == (1, 12)
    assert phases_a == phases_b
    assert phases_a == [_expected_phases(k, with_kfac) for k in range(12)]
    _same_bits(state_a.params, state_b.params)
    _same_bits(state_a.opt_state, state_b.opt_state)
    if with_kfac:
        _same_bits(state_a.kfac_state, state_b.kfac_state)
    # a refused batch advances the counter by one like any other
    assert int(state_a.step) == int(state_b.step) == 12
    if guard:
        assert float(mets_a['health/skipped']) == 1
        assert float(mets_b['health/skipped']) == 1


# -- (c) a state replaced from outside is read again and followed --------------


def test_a_restored_state_is_read_and_its_cadence_followed():
    rec = trace.TraceRecorder(None)
    step, state, _, batch = _trainer(tracer=rec)
    state = state.replace(step=jnp.asarray(7, state.step.dtype))
    phases = []
    for _ in range(5):
        state, _ = step(state, batch)
        phases.append(step.last_phases)
    assert _dispatched_steps(rec) == [7, 8, 9, 10, 11]
    assert step.step_reads == 1
    # no decomposition yet: statistics alone on their schedule, and the
    # first inverse update (step 10 of a cadence of 5) is a full one
    assert phases == [(), (), (), ('pred', 'stats', 'decomp'), ('pred',)]
    assert int(state.step) == 12


def test_replacing_the_counter_costs_one_read_and_is_followed():
    rec = trace.TraceRecorder(None)
    step, state, _, batch = _trainer(tracer=rec)
    for _ in range(3):
        state, _ = step(state, batch)
    state = state.replace(step=jnp.asarray(20, state.step.dtype))
    for _ in range(2):
        state, _ = step(state, batch)
    assert _dispatched_steps(rec) == [0, 1, 2, 20, 21]
    assert step.step_reads == 2
    assert int(state.step) == 22


def test_replacing_other_fields_costs_no_read():
    rec = trace.TraceRecorder(None)
    step, state, _, batch = _trainer(health=True, tracer=rec)
    for _ in range(4):
        # a pre-health state every time: step_fn's own upgrade replaces
        # the field, and neither touches the counter's array
        state, _ = step(state.replace(health=None), batch)
    assert _dispatched_steps(rec) == [0, 1, 2, 3]
    assert step.step_reads == 1


def test_a_fresh_init_after_ten_steps_starts_again_at_zero():
    rec = trace.TraceRecorder(None)
    step, state, make_state, batch = _trainer(tracer=rec)
    for _ in range(10):
        state, _ = step(state, batch)
    state = make_state()
    for _ in range(2):
        state, _ = step(state, batch)
    assert _dispatched_steps(rec) == list(range(10)) + [0, 1]
    assert step.step_reads == 2


def test_the_same_state_passed_twice_is_read_twice():
    rec = trace.TraceRecorder(None)
    step, s0, _, batch = _trainer(tracer=rec, donate=False)
    s1, _ = step(s0, batch)
    s1_again, _ = step(s0, batch)          # not what was handed out last
    assert step.step_reads == 2
    s2, _ = step(s1_again, batch)          # handed back: counted
    assert step.step_reads == 2
    step(s1, batch)                        # an older one: read
    assert step.step_reads == 3
    assert _dispatched_steps(rec) == [0, 0, 1, 1]
    assert int(s2.step) == 2
    _same_bits(s1.params, s1_again.params)


# -- (d) no executable of step_fn's own ----------------------------------------


def test_step_fn_compiles_its_variants_and_nothing_else(caplog):
    warm, state, _, batch = _trainer()
    for _ in range(2):
        # whatever the first call's one-time probe of a restored
        # decomposition launches is compiled here, once a process
        state, _ = warm(state, batch)
    step, state, _, batch = _trainer()
    compiled = []
    old = jax.config.jax_log_compiles
    jax.config.update('jax_log_compiles', True)
    try:
        with caplog.at_level(logging.WARNING, logger='jax'):
            for _ in range(6):
                caplog.clear()
                state, _ = step(state, batch)
                compiled.append(re.findall(
                    r'Compiling jit\(([^)]*)\)', caplog.text))
    finally:
        jax.config.update('jax_log_compiles', old)
    # over six steps the process compiles the two variants and no program
    # of step_fn's own (a jnp.float32() would be `convert_element_type`)
    assert compiled == [['kfac_step_pred_stats_decomp'], ['kfac_step_pred'],
                        [], [], [], []]
    assert len(step.variants) == 2 and step.step_reads == 1
    assert all(fn._cache_size() == 1 for fn in step.variants.values())


@pytest.mark.parametrize('with_kfac', [True, False], ids=['kfac', 'sgd'])
def test_the_two_scalars_go_in_as_host_float32(with_kfac):
    step, state, _, batch = _trainer(with_kfac)
    for _ in range(2):                     # builds both variants
        state, _ = step(state, batch, lr=0.1, damping=0.01)
    seen = []
    jitted = _spy(step, seen)
    state, _ = step(state, batch, lr=0.1, damping=0.01)
    state, _ = step(state, batch)          # the preconditioner's defaults
    assert [type(h.lr) for h in seen] == [np.float32] * 2
    assert [type(h.damping) for h in seen] == [np.float32] * 2
    assert [h.lr for h in seen] == [
        np.float32(0.1), np.float32(0.05 if with_kfac else 0.0)]
    assert [h.damping for h in seen] == [
        np.float32(0.01), np.float32(0.003 if with_kfac else 0.0)]
    # one trace and one cache entry a variant, whichever way they came
    assert [fn._cache_size() for fn in jitted] == [1] * len(jitted)


# -- (e) a schedule computed on the device stays there -------------------------


def test_device_scalars_are_passed_through_untouched():
    step, state, _, batch = _trainer()
    lr, damping = jnp.float32(0.1), jnp.float32(0.01)
    for _ in range(2):                     # builds both variants
        state, _ = step(state, batch, lr=lr, damping=damping)
    seen = []
    _spy(step, seen)
    state, mets = step(state, batch, lr=lr, damping=damping)
    # the very arrays: no copy, no read back to the host
    assert seen[0].lr is lr and seen[0].damping is damping
    assert np.isfinite(float(mets['loss']))


# -- (f) the mesh path ---------------------------------------------------------


def test_the_mesh_step_takes_the_host_scalars_and_counts_on_the_host():
    mesh = Mesh(np.array(jax.devices()[:4]), ('batch',))
    step, state, _, batch = _trainer(mesh=mesh)
    for _ in range(2):                     # builds both variants
        state, _ = step(state, batch)
    seen, phases = [], []
    _spy(step, seen)
    for _ in range(2, CADENCE + 1):
        state, mets = step(state, batch)
        phases.append(step.last_phases)
    assert step.step_reads == 1
    assert phases == [_expected_phases(k) for k in range(2, CADENCE + 1)]
    assert len(seen) == len(phases)
    assert {type(h.lr) for h in seen} == {np.float32}
    assert {type(h.damping) for h in seen} == {np.float32}
    assert int(state.step) == CADENCE + 1
    assert np.isfinite(float(mets['loss']))
