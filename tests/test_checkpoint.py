"""Checkpoint / resume tests (reference semantics: rank-0 save of
{model, optimizer} (examples/utils.py:11-18), ImageNet auto-resume by
scanning checkpoint-{epoch} downward (pytorch_imagenet_resnet.py:162-167,
305-312); upgrade: K-FAC factor state round-trips too)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import models, training
from kfac_pytorch_tpu.utils import checkpoint


@pytest.fixture(scope='module')
def trained_state():
    model = models.get_model('resnet20')
    precond = kfac.KFAC(variant='eigen_dp', lr=0.1, damping=0.003)
    tx = training.sgd(0.1, momentum=0.9)
    x = jnp.ones((4, 16, 16, 3), jnp.float32)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0), x)

    def ce(outputs, b):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, b['label']).mean()

    step = training.build_train_step(model, tx, precond, ce,
                                     extra_mutable=('batch_stats',))
    batch = {'input': x, 'label': jnp.asarray([0, 1, 2, 3])}
    state, _ = step(state, batch, lr=0.1, damping=0.003)
    return state


def test_save_restore_roundtrip(tmp_path, trained_state):
    checkpoint.save_checkpoint(tmp_path, 3, trained_state)
    target = jax.tree.map(np.zeros_like, trained_state)
    restored = checkpoint.restore_checkpoint(tmp_path, 3, target)
    for a, b in zip(jax.tree.leaves(trained_state),
                    jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_without_kfac_state(tmp_path, trained_state):
    # reference behavior: K-FAC state NOT checkpointed; factors rebuild
    checkpoint.save_checkpoint(tmp_path, 1, trained_state,
                               include_kfac=False)
    target = jax.tree.map(np.zeros_like,
                          trained_state.replace(kfac_state=None))
    restored = checkpoint.restore_checkpoint(tmp_path, 1, target)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(restored.params)[0]),
        np.asarray(jax.tree.leaves(trained_state.params)[0]))
    assert restored.kfac_state is None


def test_find_resume_epoch_scans_downward(tmp_path, trained_state):
    assert checkpoint.find_resume_epoch(tmp_path, 10) is None
    checkpoint.save_checkpoint(tmp_path, 2, trained_state)
    checkpoint.save_checkpoint(tmp_path, 5, trained_state)
    # scans from max_epoch downward and returns the newest present
    assert checkpoint.find_resume_epoch(tmp_path, 10) == 5
    assert checkpoint.find_resume_epoch(tmp_path, 4) == 2


@pytest.mark.slow
def test_preemption_guard_saves_and_exits(tmp_path):
    """SIGTERM drill (beyond-reference §5.3): the trainer saves the live
    TrainState inside the grace window, exits cleanly, and the
    checkpoint restores."""
    import os
    import re
    import signal
    import subprocess
    import sys
    import time as _time

    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=1')
    logf = tmp_path / 'out.log'
    with open(logf, 'w') as f:
        proc = subprocess.Popen(
            [sys.executable, 'examples/cifar10_resnet.py', '--model',
             'resnet20', '--epochs', '50', '--batch-size', '16',
             '--kfac-update-freq', '5', '--kfac-cov-update-freq', '5',
             '--num-devices', '1',
             '--checkpoint-dir', str(tmp_path / 'ckpt')],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env, stdout=f, stderr=subprocess.STDOUT)
        try:
            deadline = _time.time() + 420
            while _time.time() < deadline:
                if 'epoch 0:' in logf.read_text():
                    break
                if proc.poll() is not None:
                    raise AssertionError(
                        'trainer died early:\n' + logf.read_text()[-2000:])
                _time.sleep(2)
            else:
                raise AssertionError('epoch 0 never appeared:\n'
                                     + logf.read_text()[-2000:])
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=180)
        finally:
            if proc.poll() is None:
                proc.kill()
    out = logf.read_text()
    assert rc == 0, (rc, out[-2000:])
    assert ('preempted in epoch' in out          # mid-train-loop save path
            or 'preempted after epoch' in out), out[-2000:]  # post-val path
    epochs = [int(m) for m in re.findall(r'checkpoint-(\d+)',
                                         ' '.join(os.listdir(tmp_path / 'ckpt')))]
    assert epochs, os.listdir(tmp_path / 'ckpt')
    # the saved checkpoint restores into a fresh state skeleton
    model = models.resnet20()
    precond = kfac.KFAC(variant='eigen_dp', lr=0.1, damping=0.003,
                        fac_update_freq=5, kfac_update_freq=5,
                        num_devices=1, axis_name=None)
    # the trainer passes an lr *schedule* into sgd — match its opt_state
    # tree structure, not just its shapes
    tx = training.sgd(lambda s: 0.1, momentum=0.9, weight_decay=5e-4)
    skel = training.init_train_state(model, tx, precond,
                                     jax.random.PRNGKey(0),
                                     jnp.zeros((16, 32, 32, 3)))
    restored = checkpoint.restore_checkpoint(str(tmp_path / 'ckpt'),
                                             max(epochs), skel)
    assert int(restored.step) > 0


def test_prune_and_find_mixed_layouts(tmp_path):
    """Retention x resume scanning on a directory holding BOTH orbax-style
    checkpoint dirs and pickle-fallback ``.pkl`` files (a run that crossed
    an environment change)."""
    import os

    from kfac_pytorch_tpu.utils.checkpoint import (find_resume_epoch,
                                                   prune_checkpoints)
    (tmp_path / 'checkpoint-0').mkdir()
    (tmp_path / 'checkpoint-1.pkl').write_bytes(b'x')
    (tmp_path / 'checkpoint-2').mkdir()
    (tmp_path / 'checkpoint-3.pkl').write_bytes(b'x')
    # a stale atomic-write tmp file must be invisible to both
    (tmp_path / 'checkpoint-4.pkl.tmp').write_bytes(b'x')
    assert find_resume_epoch(tmp_path, 10) == 3
    assert find_resume_epoch(tmp_path, 2) == 2
    prune_checkpoints(str(tmp_path), 2)
    assert sorted(os.listdir(tmp_path)) == [
        'checkpoint-2', 'checkpoint-3.pkl', 'checkpoint-4.pkl.tmp']
    assert find_resume_epoch(tmp_path, 10) == 3
    # retention removes dir and pkl layouts alike
    prune_checkpoints(str(tmp_path), 1)
    assert not (tmp_path / 'checkpoint-2').exists()
    assert find_resume_epoch(tmp_path, 10) == 3
    assert (tmp_path / 'checkpoint-4.pkl.tmp').exists()


def test_pkl_save_is_atomic(tmp_path, monkeypatch):
    """The pickle fallback writes tmp-then-rename: after a successful save
    no ``.pkl.tmp`` residue exists and the file round-trips."""
    import numpy as _np

    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    payload = {'w': _np.arange(16, dtype=_np.float32)}
    checkpoint.save_checkpoint(tmp_path, 7, payload)
    assert (tmp_path / 'checkpoint-7.pkl').exists()
    assert not (tmp_path / 'checkpoint-7.pkl.tmp').exists()
    restored = checkpoint.restore_checkpoint(tmp_path, 7, payload)
    _np.testing.assert_array_equal(restored['w'], payload['w'])


def test_auto_resume_restores_pre_health_checkpoint(tmp_path,
                                                    trained_state):
    """A checkpoint written before the health guard existed (no
    ``TrainState.health`` subtree) must still auto-resume: the structure
    mismatch is NOT corruption — auto_resume retries against a
    health-less target and the trainer re-seeds the counters."""
    old_state = trained_state.replace(health=None)
    checkpoint.save_checkpoint(tmp_path, 4, old_state)
    target = jax.tree.map(np.zeros_like, trained_state)
    assert target.health is not None  # current-code skeleton HAS the leaf
    restored, epoch = checkpoint.auto_resume(tmp_path, 10, target)
    assert epoch == 4
    assert restored.health is None  # step_fn upgrades on first call
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(restored.params)[0]),
        np.asarray(jax.tree.leaves(old_state.params)[0]))


def test_preemption_guard_uninstall():
    """uninstall() restores the previously-installed handlers: no chained
    guard leaks across constructions (tests / long-lived drivers)."""
    import signal

    before = signal.getsignal(signal.SIGTERM)
    g1 = checkpoint.PreemptionGuard()
    assert signal.getsignal(signal.SIGTERM) == g1._handler
    g2 = checkpoint.PreemptionGuard()
    assert signal.getsignal(signal.SIGTERM) == g2._handler
    # un-nest in reverse order: each uninstall restores what it displaced
    g2.uninstall()
    assert signal.getsignal(signal.SIGTERM) == g1._handler
    g1.uninstall()
    assert signal.getsignal(signal.SIGTERM) == before
    # idempotent: a second uninstall is a no-op
    g1.uninstall()
    assert signal.getsignal(signal.SIGTERM) == before


def test_prune_checkpoints(tmp_path):
    """Retention keeps the N newest epochs, ignores orbax tmp dirs and
    foreign names, and is a no-op with keep=0/None."""
    import os

    from kfac_pytorch_tpu.utils.checkpoint import prune_checkpoints
    for e in (0, 1, 2, 10):
        os.makedirs(tmp_path / f'checkpoint-{e}')
    (tmp_path / 'checkpoint-11.orbax-checkpoint-tmp').mkdir()
    (tmp_path / 'other-file').write_text('x')
    prune_checkpoints(str(tmp_path), None)
    prune_checkpoints(str(tmp_path), 0)
    assert sorted(os.listdir(tmp_path)) == [
        'checkpoint-0', 'checkpoint-1', 'checkpoint-10', 'checkpoint-11'
        '.orbax-checkpoint-tmp', 'checkpoint-2', 'other-file']
    prune_checkpoints(str(tmp_path), 2)
    assert sorted(p for p in os.listdir(tmp_path)
                  if p.startswith('checkpoint-') and '.' not in p) == [
        'checkpoint-10', 'checkpoint-2']
    # tmp dir and foreign file untouched
    assert (tmp_path / 'checkpoint-11.orbax-checkpoint-tmp').exists()
    assert (tmp_path / 'other-file').exists()


# -- the durable checkpoint plane (manifests + object store) --------------

def test_save_commits_content_hash_manifest(tmp_path, trained_state):
    """Every successful save writes a manifest LAST: the content hashes
    of every blob, stamped with the world.json lineage when present."""
    import json

    checkpoint.write_world_stamp(tmp_path, 4, gen=2, lineage=1)
    checkpoint.save_checkpoint(tmp_path, 6, trained_state)
    manifest = json.loads(
        (tmp_path / 'checkpoint-6.manifest.json').read_text())
    assert manifest['epoch'] == 6 and manifest['blobs']
    assert manifest['num_devices'] == 4
    assert manifest['gen'] == 2 and manifest['lineage'] == 1
    from kfac_pytorch_tpu.store import PosixStore
    from kfac_pytorch_tpu.store.manifest import verify_epoch
    assert verify_epoch(PosixStore(str(tmp_path)), manifest) == []


def test_async_save_defers_manifest_until_durable(tmp_path,
                                                  trained_state):
    """block=False: the manifest (the commit point) must not exist
    before wait_for_checkpoints confirms the tree is durable."""
    if not checkpoint._HAS_ORBAX:
        pytest.skip('orbax not available')
    checkpoint.save_checkpoint(tmp_path, 1, trained_state, block=False)
    manifest = tmp_path / 'checkpoint-1.manifest.json'
    checkpoint.wait_for_checkpoints()
    assert manifest.exists()


def test_corrupt_manifested_epoch_scans_down(tmp_path, monkeypatch,
                                             caplog):
    """Bit-rot inside a COMMITTED epoch: the restore's hash check
    raises CheckpointCorruptError and auto_resume lands on the older
    committed epoch — the same length is the corruption shape only a
    content hash catches."""
    import logging

    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    payload = {'w': np.arange(64, dtype=np.float32)}
    checkpoint.save_checkpoint(tmp_path, 0, payload)
    checkpoint.save_checkpoint(tmp_path, 1, payload)
    raw = bytearray((tmp_path / 'checkpoint-1.pkl').read_bytes())
    raw[-1] ^= 0xFF
    (tmp_path / 'checkpoint-1.pkl').write_bytes(bytes(raw))
    assert checkpoint.find_resume_epoch(tmp_path, 10) == 1
    with pytest.raises(checkpoint.CheckpointCorruptError):
        checkpoint.restore_checkpoint(tmp_path, 1, payload)
    with caplog.at_level(logging.WARNING):
        restored, epoch = checkpoint.auto_resume(tmp_path, 10, payload)
    assert epoch == 0
    np.testing.assert_array_equal(restored['w'], payload['w'])
    assert any('ckpt: corrupt blob key=checkpoint-1.pkl epoch=1 '
               'reason=hash_mismatch' in rec.getMessage()
               for rec in caplog.records)


def test_store_give_up_exits_rc_120(tmp_path, monkeypatch, caplog):
    """A dead object store is LOUD: save exits SystemExit(120)
    (RC_STORE_LOST), never a silent scan-down or a wedge."""
    import logging

    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    monkeypatch.setenv('KFAC_STORE_BACKEND', 'http')
    monkeypatch.setenv('KFAC_STORE_ADDR', '127.0.0.1:1')
    with caplog.at_level(logging.ERROR):
        with pytest.raises(SystemExit) as exc:
            checkpoint.save_checkpoint(tmp_path, 0,
                                       {'w': np.zeros(8)})
    assert exc.value.code == 120
    assert any('checkpoint store lost' in rec.getMessage()
               and 'store_lost=1' in rec.getMessage()
               for rec in caplog.records)


def test_pickle_roundtrip_through_http_store(tmp_path, monkeypatch):
    """KFAC_STORE_BACKEND=http: the pickle save/resume path runs
    entirely against the object server — no checkpoint blobs or
    manifests on the local filesystem."""
    from kfac_pytorch_tpu.store import StoreHttpServer
    monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    srv = StoreHttpServer('127.0.0.1', 0).start()
    try:
        monkeypatch.setenv('KFAC_STORE_BACKEND', 'http')
        monkeypatch.setenv('KFAC_STORE_ADDR', srv.address)
        payload = {'w': np.arange(32, dtype=np.float32)}
        checkpoint.save_checkpoint(tmp_path, 2, payload)
        assert not (tmp_path / 'checkpoint-2.pkl').exists()
        assert checkpoint.find_resume_epoch(tmp_path, 10) == 2
        restored, epoch = checkpoint.auto_resume(tmp_path, 10, payload)
        assert epoch == 2
        np.testing.assert_array_equal(restored['w'], payload['w'])
        # retention applies to the remote copies too
        checkpoint.save_checkpoint(tmp_path, 3, payload)
        checkpoint.prune_checkpoints(str(tmp_path), 1)
        assert checkpoint.find_resume_epoch(tmp_path, 10) == 3
        assert checkpoint.auto_resume(tmp_path, 2, payload) == (None,
                                                                None)
    finally:
        srv.stop()


@pytest.mark.parametrize('orbax', [True, False], ids=['orbax', 'pickle'])
def test_old_layout_kfac_state_does_not_load_silently(tmp_path, monkeypatch,
                                                      orbax):
    """A K-FAC state written under the bucket ladder the tile rule
    replaced (769 -> 1,024; now 896) shares bucket key '768' with the new
    plan and would otherwise restore (pickle: with no check at all) onto
    rows that mean other layers. Restore compares bucket keys and row
    counts with the plan's and says what to do; auto_resume raises it
    too, since no older epoch is any different."""
    from kfac_pytorch_tpu.capture import LayerMeta
    if not orbax:
        monkeypatch.setattr(checkpoint, '_HAS_ORBAX', False)
    metas = [LayerMeta(name=f'l{i}', path=(f'l{i}',), kind='dense',
                       use_bias=True, in_dim=257, out_dim=256,
                       kernel_shape=(256, 256)) for i in range(2)]

    def state(bucket_fn):
        pre = kfac.KFAC(variant='inverse_dp', bucket_fn=bucket_fn)
        pre.setup(metas)
        return training.TrainState(
            step=jnp.zeros((), jnp.int32), params={'w': jnp.ones(3)},
            opt_state={}, kfac_state=pre.init(), extra_vars={})

    old = state(lambda d: 256 if d <= 256 else 512)    # 257: a rung up
    new = state(None)                                  # 257 -> 384
    assert (set(old.kfac_state.factors) & set(new.kfac_state.factors)
            == {'256'})
    checkpoint.save_checkpoint(tmp_path, 2, old)
    # the same layout restores
    back = checkpoint.restore_checkpoint(tmp_path, 2, old)
    assert set(back.kfac_state.factors) == {'256', '512'}
    for fn in (lambda: checkpoint.restore_checkpoint(tmp_path, 2, new),
               lambda: checkpoint.auto_resume(tmp_path, 5, new)):
        with pytest.raises(checkpoint.KFACLayoutError,
                           match='reshard_kfac_state') as e:
            fn()
        assert "'512': 2" in str(e.value) and "'384': 2" in str(e.value)
