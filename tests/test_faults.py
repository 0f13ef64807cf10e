"""Deterministic fault-injection harness drills (faults.py).

Each drill arms an env-configured fault, runs the real trainer/checkpoint
path, and asserts the matching guard absorbs it: eigh blowup -> last-good
/identity decomposition fallback, corrupted factor block -> identity
re-init heal, SIGTERM -> PreemptionGuard flag, truncated/failed
checkpoint writes -> atomic save + scan-downward auto_resume.
"""

import os
import pickle
import signal

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import engine, faults, ops, training
from kfac_pytorch_tpu.utils import checkpoint

from tests.helpers import TinyCNN


def _batches(n_batches, n=8, hw=8, seed=0):
    rng = np.random.RandomState(seed)
    return [{'input': jnp.asarray(rng.randn(n, hw, hw, 3), jnp.float32),
             'label': jnp.asarray(rng.randint(0, 10, n))}
            for _ in range(n_batches)]


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _build(batches, variant='eigen_dp'):
    model = TinyCNN()
    precond = kfac.KFAC(variant=variant, lr=0.05, damping=0.003,
                        fac_update_freq=1, kfac_update_freq=1,
                        num_devices=1, axis_name=None)
    tx = training.sgd(0.05, momentum=0.9)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0),
                                      batches[0]['input'])
    step = training.build_train_step(model, tx, precond, _ce)
    return state, step


def _all_finite(tree):
    return all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in jax.tree.leaves(tree))


def test_parse_steps():
    assert faults.parse_steps(None) == ()
    assert faults.parse_steps('') == ()
    assert faults.parse_steps('7') == (7,)
    assert faults.parse_steps('3,5,9') == (3, 5, 9)
    assert faults.parse_steps('4:8') == (4, 5, 6, 7)
    assert faults.parse_steps('1, 3:5,3') == (1, 3, 4)


def test_from_env_validation(monkeypatch):
    monkeypatch.setenv(faults.ENV_CKPT, 'bogus')
    with pytest.raises(ValueError):
        faults.from_env()
    monkeypatch.setenv(faults.ENV_CKPT, 'truncate')
    assert faults.from_env().ckpt_mode == 'truncate'
    monkeypatch.setenv(faults.ENV_CKPT, 'eio_once')
    assert faults.from_env().ckpt_mode == 'eio_once'
    monkeypatch.delenv(faults.ENV_CKPT)
    assert faults.from_env() == faults.FaultConfig()
    assert not faults.from_env().any_injit


def test_from_env_rejects_unknown_fault_vars(monkeypatch):
    """A typo'd drill variable must fail the build loudly — a chaos test
    whose fault never armed would otherwise pass vacuously."""
    monkeypatch.setenv('KFAC_FAULT_NAN_GRAD_STEPS', '3')  # plural typo
    with pytest.raises(ValueError, match='NAN_GRAD_STEPS'):
        faults.from_env()


def test_from_env_rejects_malformed_specs(monkeypatch):
    monkeypatch.setenv(faults.ENV_EIGH, '3:x')
    with pytest.raises(ValueError, match='malformed step spec'):
        faults.from_env()
    monkeypatch.delenv(faults.ENV_EIGH)
    monkeypatch.setenv(faults.ENV_HANG, 'seven')
    with pytest.raises(ValueError, match=faults.ENV_HANG):
        faults.from_env()
    monkeypatch.delenv(faults.ENV_HANG)
    monkeypatch.setenv(faults.ENV_SLOW_SECS, 'fast')
    with pytest.raises(ValueError, match=faults.ENV_SLOW_SECS):
        faults.from_env()
    monkeypatch.delenv(faults.ENV_SLOW_SECS)
    monkeypatch.setenv(faults.ENV_CRASH_MODE, 'sigsegv')
    with pytest.raises(ValueError, match=faults.ENV_CRASH_MODE):
        faults.from_env()


def test_maybe_slow_uses_injected_sleep(monkeypatch):
    monkeypatch.setenv(faults.ENV_SLOW, '2,4')
    monkeypatch.setenv(faults.ENV_SLOW_SECS, '3.5')
    cfg = faults.from_env()
    slept = []
    for s in range(6):
        faults.maybe_slow(cfg, s, sleep=slept.append)
    assert slept == [3.5, 3.5]


def test_once_dir_latch_fires_exactly_once_across_processes(tmp_path,
                                                            monkeypatch):
    """The cross-RESTART latch: the first claimant wins, every later
    claim (same step, e.g. a supervised relaunch replaying the faulted
    epoch) is refused — this is what makes the supervisor chaos drills
    terminate."""
    monkeypatch.setenv(faults.ENV_ONCE_DIR, str(tmp_path))
    assert faults._claim_once('crash-5')
    assert not faults._claim_once('crash-5')
    assert faults._claim_once('hang-5')  # distinct fault, own token
    monkeypatch.delenv(faults.ENV_ONCE_DIR)
    # without the dir the latch always fires
    assert faults._claim_once('crash-5')


def test_eigh_blowup_falls_back_to_identity_then_recovers(monkeypatch):
    """Non-finite decomposition output on the COLD first inverse update:
    the guard substitutes the identity (plain pass-through), the stored
    state stays finite, and the next (unfaulted) decomposition recovers a
    real eigenbasis."""
    monkeypatch.setenv(faults.ENV_EIGH, '0')
    batches = _batches(3, seed=5)
    state, step = _build(batches)
    rungs = []
    for b in batches:
        state, m = step(state, b, lr=0.05, damping=0.003)
        rungs.append(float(m['health/rung']))
        assert np.isfinite(float(m['loss']))
        assert _all_finite(state.kfac_state.decomp)
        assert _all_finite(state.params)
    # the blowup was absorbed in-engine: the batch itself stayed applied
    # and never counted against the trainer-level ladder
    assert float(m['health/skipped']) == 0
    assert rungs == [0.0, 0.0, 0.0]
    # step 0's guarded decomposition is the identity basis; step 1's is a
    # real eigh again (eigenvectors differ from the identity)
    evecs = np.asarray(next(iter(state.kfac_state.decomp['evecs'].values())))
    eye = np.eye(evecs.shape[-1])
    assert not np.allclose(evecs[0], eye)


def test_eigh_blowup_warm_keeps_last_good(monkeypatch):
    """An eigh blowup AFTER a good decomposition exists keeps the last
    good one bit-exactly (not the identity)."""
    monkeypatch.setenv(faults.ENV_EIGH, '1')
    batches = _batches(3, seed=6)
    state, step = _build(batches)
    state, _ = step(state, batches[0], lr=0.05, damping=0.003)
    good = jax.tree.map(np.asarray, state.kfac_state.decomp)
    state, m = step(state, batches[1], lr=0.05, damping=0.003)
    for k in good['evecs']:
        np.testing.assert_array_equal(
            np.asarray(state.kfac_state.decomp['evecs'][k]),
            good['evecs'][k])
    assert np.isfinite(float(m['loss']))
    state, _ = step(state, batches[2], lr=0.05, damping=0.003)
    assert _all_finite(state.kfac_state.decomp)


def test_factor_corruption_heals_by_identity_reinit(monkeypatch):
    """Silent-data-corruption drill: a stored factor block corrupted at
    step 1 (post-guard, exactly as a flipped bit would land) is detected
    at step 2's factor update and re-initialized to the identity; the
    decomposition guard bridges the corrupted step."""
    monkeypatch.setenv(faults.ENV_FACTOR, '1')
    batches = _batches(4, seed=7)
    state, step = _build(batches)
    state, _ = step(state, batches[0], lr=0.05, damping=0.003)
    state, m1 = step(state, batches[1], lr=0.05, damping=0.003)
    # corruption landed in the stored factors...
    assert not _all_finite(state.kfac_state.factors)
    # ...but never reached the decomposition or the params
    assert _all_finite(state.kfac_state.decomp)
    assert _all_finite(state.params)
    assert np.isfinite(float(m1['loss']))
    # next factor update heals: corrupted rows re-init to identity
    state, m2 = step(state, batches[2], lr=0.05, damping=0.003)
    assert _all_finite(state.kfac_state.factors)
    state, m3 = step(state, batches[3], lr=0.05, damping=0.003)
    assert _all_finite(state.params) and np.isfinite(float(m3['loss']))


def _spd(key, rows, dim):
    m = jax.random.normal(key, (rows, dim, 2 * dim))
    return jnp.einsum('rij,rkj->rik', m, m) / (2 * dim)


@pytest.mark.parametrize('value', [np.nan, np.inf, -np.inf])
def test_one_poisoned_stored_factor_element_heals_by_identity(value):
    """One OFF-diagonal element of one stored factor row corrupt (a
    flipped bit): the row's flag comes out of the pass that writes its new
    average, the row starts again from the identity and every other row is
    the unguarded average: what the whole-bucket
    ``where_finite_rows(..., reinit_identity=True)`` gave."""
    pre = kfac.KFAC(variant='inverse_dp', num_devices=1, axis_name=None)
    plan = pre.setup(_metas())
    stored = {k: _spd(jax.random.PRNGKey(i), *v.shape[:2])
              for i, (k, v) in enumerate(pre.init().factors.items())}
    stats = {k: _spd(jax.random.PRNGKey(10 + i), *v.shape[:2])
             for i, (k, v) in enumerate(stored.items())}
    key = max(stored, key=lambda k: stored[k].shape[0])
    d = int(key)
    assert stored[key].shape[0] > 1
    stored[key] = stored[key].at[1, 2, d - 3].set(value)

    def update(**guard):
        return jax.jit(lambda f, s: engine.update_factors(
            plan, f, s, 0.95, 'local', None, **guard)[0])(stored, stats)
    want = engine.where_finite_rows(update(), stored, reinit_identity=True)
    got = update(guard=True)
    for k in want:
        # (two programs: the CPU's compiler contracts the average's
        # multiply-add differently beside the flag's reduction)
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(got[key][1], np.eye(d))
    # a batch that is not committed heals nothing and writes nothing
    kept = update(guard=True, commit=jnp.zeros((), bool))
    for k in want:
        np.testing.assert_array_equal(kept[k], stored[k])


def _metas():
    from kfac_pytorch_tpu import capture
    model, x = TinyCNN(), _batches(1)[0]['input']
    params = capture.init(model, jax.random.PRNGKey(0), x)['params']
    return capture.collect_layer_meta(model, {'params': params}, x)


@pytest.mark.parametrize('route', ['solves', 'structured'])
@pytest.mark.parametrize('value,stored_yet', [
    (np.nan, True), (np.inf, True), (-np.inf, True), (np.nan, False)])
def test_one_poisoned_factor_element_keeps_the_last_good_inverse(
        value, stored_yet, route, monkeypatch):
    """One OFF-diagonal element of one damped factor not finite: its
    Cholesky inverse is not finite, the guard reads that from the
    inverse's DIAGONAL alone (``ops.inverse_rows_finite``), and the row
    falls back to the stored inverse (the identity where none is stored
    yet) while every other row is the fresh inverse to the bit: what the
    whole-bucket guard gave with a read of every element of both. On both
    routes from the factor to the inverse (``ops.inverse_route``): two
    dense solves, and the blocked triangular inverse and product (here by
    blocks of 64, the last one partial)."""
    from kfac_pytorch_tpu.ops import linalg
    rows, d = 5, 160            # two diagonal tiles, the second partial
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM',
                        128 if route == 'structured' else 10 ** 6)
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_BLOCK', 64)
    assert ops.inverse_route(d) == route
    x = ops.add_scaled_identity(_spd(jax.random.PRNGKey(0), rows, d), 0.05)
    # (a fresh function a jit: its cache does not see a patched constant)
    stored = (jax.jit(lambda a: ops.psd_inverse(a))(x * 1.5) if stored_yet
              else jnp.zeros_like(x))
    fresh = jax.jit(lambda a: ops.psd_inverse(a))(
        x.at[2, 7, 140].set(value))
    assert not np.isfinite(np.asarray(fresh[2])).all()
    got = jax.jit(lambda n, p: engine.guard_decomposition(
        {'invs': {'160': n}}, {'invs': {'160': p}}, 'cholesky'))(
        fresh, stored)['invs']['160']
    # the whole-bucket guard this replaces, spelt out
    good = np.isfinite(np.asarray(fresh)).all(axis=(1, 2))
    cold = ~(np.asarray(stored) != 0).any(axis=(1, 2))
    want = np.where(good[:, None, None], fresh, np.where(
        cold[:, None, None], np.eye(d, dtype=np.float32), stored))
    assert list(good) == [True, True, False, True, True]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got[2], stored[2] if stored_yet else np.eye(d))
    # not committed (a refused batch, hoisted update): stored rows stay,
    # cold ones too
    kept = jax.jit(lambda n, p: engine.guard_decomposition(
        {'invs': {'160': n}}, {'invs': {'160': p}}, 'cholesky',
        commit=jnp.zeros((), bool)))(fresh, stored)['invs']['160']
    np.testing.assert_array_equal(kept, stored)


def test_sigterm_fault_trips_preemption_guard(monkeypatch):
    """Host-side SIGTERM at step 1: PreemptionGuard converts it into the
    cooperative stop flag; the one-shot latch fires exactly once."""
    monkeypatch.setenv(faults.ENV_SIGTERM, '1')
    faults.reset_sigterm_fault()
    guard = checkpoint.PreemptionGuard()
    try:
        batches = _batches(3, seed=8)
        state, step = _build(batches)
        state, _ = step(state, batches[0], lr=0.05, damping=0.003)
        assert not guard.triggered
        state, _ = step(state, batches[1], lr=0.05, damping=0.003)
        assert guard.triggered
        # one-shot: replaying the fault step doesn't re-deliver
        guard._flag = False
        faults.maybe_sigterm(faults.from_env(), 1)
        assert not guard.triggered
    finally:
        guard.uninstall()
        faults.reset_sigterm_fault()


def test_checkpoint_truncate_then_auto_resume_falls_back(tmp_path,
                                                         monkeypatch):
    """'truncate' drill: a torn object under the FINAL name, with no
    manifest. The manifest-aware resume scan now refuses the epoch
    outright (it used to select it and rely on auto_resume crashing into
    the truncation), and auto_resume lands on the older committed one."""
    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    payload = {'w': np.arange(1000, dtype=np.float32), 'epoch': np.int32(0)}
    checkpoint.save_checkpoint(tmp_path, 0, payload)
    monkeypatch.setenv(faults.ENV_CKPT, 'truncate')
    checkpoint.save_checkpoint(tmp_path, 1, {'w': np.ones(1000)})
    monkeypatch.delenv(faults.ENV_CKPT)
    assert (tmp_path / 'checkpoint-1.pkl').exists()
    assert not (tmp_path / 'checkpoint-1.manifest.json').exists()
    with pytest.raises(Exception):
        checkpoint.restore_checkpoint(tmp_path, 1, payload)
    # the torn epoch is skipped without ever being read
    assert checkpoint.find_resume_epoch(tmp_path, 10) == 0
    restored, epoch = checkpoint.auto_resume(tmp_path, 10, payload)
    assert epoch == 0
    np.testing.assert_array_equal(restored['w'], payload['w'])


def test_checkpoint_fail_leaves_no_final_file(tmp_path, monkeypatch):
    """'fail' drill: the write dies after a partial tmp file — the atomic
    path must leave no final file behind, so resume never sees it."""
    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    monkeypatch.setenv(faults.ENV_CKPT, 'fail')
    with pytest.raises(OSError):
        checkpoint.save_checkpoint(tmp_path, 3, {'w': np.zeros(100)})
    assert not (tmp_path / 'checkpoint-3.pkl').exists()
    assert (tmp_path / 'checkpoint-3.pkl.tmp').exists()
    # the partial tmp is invisible to resume scanning and pruning
    assert checkpoint.find_resume_epoch(tmp_path, 10) is None
    monkeypatch.delenv(faults.ENV_CKPT)
    checkpoint.save_checkpoint(tmp_path, 3, {'w': np.zeros(100)})
    assert (tmp_path / 'checkpoint-3.pkl').exists()
    assert checkpoint.find_resume_epoch(tmp_path, 10) == 3


def test_auto_resume_nothing_restorable(tmp_path, monkeypatch):
    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    state, epoch = checkpoint.auto_resume(tmp_path, 10, None)
    assert state is None and epoch is None
    # ALL checkpoints corrupt -> still (None, None), not a crash
    for e in (0, 2):
        (tmp_path / f'checkpoint-{e}.pkl').write_bytes(b'garbage')
    state, epoch = checkpoint.auto_resume(tmp_path, 10, None)
    assert state is None and epoch is None
