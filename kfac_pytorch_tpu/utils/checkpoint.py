"""Checkpoint / resume via orbax.

Parity and upgrade over the reference (examples/utils.py:11-18 rank-0
torch.save of {model, optimizer}; auto-resume by scanning
checkpoint-{epoch} downward, examples/pytorch_imagenet_resnet.py:162-167,
305-312). Upgrade: the K-FAC factor/decomposition state is checkpointed
too (the reference explicitly does NOT checkpoint m_A/m_G — factors
rebuild from running averages after resume; restoring them here makes
resume bit-faithful). Set ``include_kfac=False`` for reference-equivalent
behavior.
"""

import os
import re

import jax
import numpy as np

from kfac_pytorch_tpu import store as _store
from kfac_pytorch_tpu.store import manifest as _manifest

try:
    import orbax.checkpoint as ocp
    _HAS_ORBAX = True
except Exception:  # pragma: no cover
    _HAS_ORBAX = False


def _ckpt_dir(base, epoch):
    return os.path.join(os.path.abspath(base), f'checkpoint-{epoch}')


_ASYNC_CKPTR = None  # lazily-created persistent checkpointer (async saves)

#: (base_dir, epoch) of an async orbax save whose manifest commit is
#: deferred until the save is durable — the manifest IS the commit
#: point, so it may only ever be written after wait_until_finished
_PENDING_MANIFEST = None


class CheckpointCorruptError(OSError):
    """A restored blob failed its manifest hash/size check — silent
    storage corruption, not a transient read failure. ``auto_resume``
    treats it like any unreadable checkpoint: log and scan down."""


class KFACLayoutError(ValueError):
    """The checkpoint's K-FAC state was written under another stacked-
    bucket layout than the restoring preconditioner's plan lays down
    (other bucket dims, or other row counts in a bucket): loading it
    would put every factor on some other layer's row. Not corruption,
    and no older epoch is any different: ``auto_resume`` raises it
    instead of scanning down."""


def _check_kfac_layout(saved_factors, target_state, epoch):
    """Compare the saved K-FAC factors' bucket keys and row counts
    (``{bucket key: array or shape-carrying metadata}``) with those of
    ``target_state.kfac_state`` — which ``KFAC.init`` made from the
    plan. A restore must not relabel rows silently: the buckets a plan
    lays down follow from ``plan.default_bucket_fn`` / ``fold_buckets``
    and the world size, and a row's layer from its place in them."""
    target = getattr(target_state, 'kfac_state', None)
    if target is None or not saved_factors:
        return
    saved = {k: int(v.shape[0]) for k, v in saved_factors.items()}
    want = {k: int(v.shape[0]) for k, v in target.factors.items()}
    if saved != want:
        raise KFACLayoutError(
            f'checkpoint-{epoch} holds K-FAC state of another layout: '
            f'buckets {{dim: rows}} {saved} in the checkpoint, {want} in '
            'this plan (a change of the bucket rule or of the world '
            'size). Restore it into a preconditioner set up as it was '
            'written (its bucket_fn, its num_devices) and carry it over '
            'with utils.reshard_kfac_state, or restore without the '
            'K-FAC state and let the factors rebuild.')


def _store_for(base_dir):
    """The object-store stack for a checkpoint namespace (posix by
    default — byte-compatible with the pre-store file layout;
    ``KFAC_STORE_BACKEND=http`` routes everything through the
    kfac-store-serve object server)."""
    return _store.store_from_env(os.path.abspath(str(base_dir)))


def _store_guard(fn):
    """Run one store operation; a spent retry budget means the
    durability plane is GONE — exit loudly with the dedicated rc
    rather than letting the trainer continue with nothing durable
    behind it (or mis-classify the failure as a corrupt checkpoint)."""
    try:
        return fn()
    except _store.StoreGiveUp as e:
        import logging
        logging.getLogger(__name__).error(
            'checkpoint store lost — %s; exiting rc=%d '
            '[resilience: store_lost=1]', e, _store.RC_STORE_LOST)
        raise SystemExit(_store.RC_STORE_LOST) from e


def _commit_manifest(base_dir, store, epoch, kind, blobs):
    """The atomic commit point: every blob is already durable, the
    manifest names them all (content hash + size each) and lands
    LAST with one atomic put. Lineage/gen/world provenance is copied
    from the ``world.json`` stamp written through the
    :func:`write_world_stamp` fence, so a fenced fork's manifest is
    refusable by the same monotonic-lineage rule."""
    stamp = read_world_stamp_info(base_dir)
    manifest = _manifest.build_manifest(epoch, kind, blobs, stamp=stamp)
    raw = _manifest.encode_manifest(manifest)
    _store_guard(
        lambda: store.put(_manifest.manifest_key(epoch), raw))
    import logging
    logging.getLogger(__name__).info(
        'ckpt: committed manifest epoch=%d blobs=%d kind=%s',
        int(epoch), len(manifest['blobs']), kind)


def _commit_manifest_tree(base_dir, epoch):
    """Hash (and, on a remote store, upload) a finished orbax
    checkpoint tree, then commit its manifest. Rank-0 only, called
    strictly AFTER the async writer reported the tree durable."""
    root = _ckpt_dir(base_dir, epoch)
    if not os.path.isdir(root):
        return
    store = _store_for(base_dir)
    local = _store.local_root(store) == os.path.abspath(str(base_dir))
    rel_root = f'checkpoint-{int(epoch)}'
    blobs = {}
    for dirpath, _dirs, files in os.walk(root):
        for name in sorted(files):
            path = os.path.join(dirpath, name)
            with open(path, 'rb') as f:
                data = f.read()
            key = (rel_root + '/'
                   + os.path.relpath(path, root).replace(os.sep, '/'))
            if not local:
                _store_guard(
                    lambda key=key, data=data: store.put(key, data))
            blobs[key] = (_manifest.blob_sha256(data), len(data))
    _commit_manifest(base_dir, store, epoch, 'orbax', blobs)


def _flush_pending_manifest():
    global _PENDING_MANIFEST
    if _PENDING_MANIFEST is None:
        return
    base_dir, epoch = _PENDING_MANIFEST
    _PENDING_MANIFEST = None
    _commit_manifest_tree(base_dir, epoch)


def save_checkpoint(base_dir, epoch, state, include_kfac=True, block=True,
                    retry=None):
    """Write one checkpoint (one copy on disk — the reference's rank-0
    torch.save semantics, examples/utils.py:11-18).

    ``block=False`` returns as soon as the on-device state is snapshotted
    and lets orbax write to disk in the background — the save hides
    behind the next epoch's compute (beyond reference, which blocks on
    torch.save). Call :func:`wait_for_checkpoints` before process exit
    (and before acting on a just-saved preemption checkpoint).

    ``retry``: an optional ``resilience.RetryPolicy`` — a transient
    write failure (flaky NFS/GCS mount returning EIO) is retried with
    backoff instead of ending the run. Safe to replay: the pickle path
    is atomic tmp+rename and orbax's ``force=True`` overwrites. A
    PERSISTENT failure still raises the underlying ``OSError`` once the
    policy is exhausted. Single-process/pickle only for now — under the
    orbax multi-process barrier a lone rank replaying the save would
    desynchronize the barrier, so multi-process runs should keep
    ``retry=None`` there.

    Multi-process note: on the orbax path EVERY process must call this —
    orbax's save opens with a global process barrier and coordinates who
    writes what (single-file rank-0 output is an orbax detail, not an
    early-return here; an early return would strand the other ranks in
    the barrier). The pickle fallback is genuinely rank-0-only.
    """
    if retry is not None:
        from kfac_pytorch_tpu.resilience.retry import call_with_retry
        return call_with_retry(
            lambda: _save_checkpoint_once(base_dir, epoch, state,
                                          include_kfac, block),
            policy=retry, label=f'save checkpoint-{epoch}')
    return _save_checkpoint_once(base_dir, epoch, state, include_kfac,
                                 block)


def _save_checkpoint_once(base_dir, epoch, state, include_kfac, block):
    payload = state
    if not include_kfac:
        payload = state.replace(kfac_state=None)
    path = _ckpt_dir(base_dir, epoch)
    if _HAS_ORBAX:
        from kfac_pytorch_tpu import faults as _faults
        fault = (_faults.checkpoint_fault_mode()
                 if jax.process_index() == 0 else None)
        if fault == 'eio_once':
            if _faults.claim_ckpt_eio_once():
                import errno
                import logging
                logging.getLogger(__name__).warning(
                    'CHAOS FAULT ACTIVE: %s=eio_once — failing this '
                    'checkpoint write once', _faults.ENV_CKPT)
                raise OSError(errno.EIO,
                              'injected transient checkpoint write '
                              f'failure ({_faults.ENV_CKPT}=eio_once)')
            fault = None
        if fault:
            import logging
            logging.getLogger(__name__).warning(
                'CHAOS FAULT ACTIVE: %s=%s — deliberately corrupting the '
                'checkpoint write for epoch %s', _faults.ENV_CKPT, fault,
                epoch)
        if jax.process_index() == 0:
            os.makedirs(base_dir, exist_ok=True)
        global _ASYNC_CKPTR, _PENDING_MANIFEST
        if _ASYNC_CKPTR is None:
            _ASYNC_CKPTR = ocp.StandardCheckpointer()
        else:
            # surface a PREVIOUS async save's failure here, attributed to
            # this call site's logs, rather than letting it abort an
            # unrelated later save (e.g. the preemption grace-window one)
            try:
                _ASYNC_CKPTR.wait_until_finished()
            except Exception:  # noqa: BLE001 — log and keep checkpointing
                import logging
                _PENDING_MANIFEST = None  # that save never became durable
                logging.getLogger(__name__).exception(
                    'a previous async checkpoint save failed; attempting '
                    'this save anyway')
                _ASYNC_CKPTR = ocp.StandardCheckpointer()
            else:
                _flush_pending_manifest()
        _ASYNC_CKPTR.save(path, payload, force=True)
        if block or fault:
            _ASYNC_CKPTR.wait_until_finished()
        if jax.process_index() != 0:
            return
        if fault == 'truncate':
            # chaos drill: silent storage corruption AFTER the tree
            # landed — one published file truncated in place, and no
            # manifest, so the resume scan refuses the epoch outright
            for dirpath, _dirs, files in sorted(os.walk(path)):
                for name in sorted(files):
                    target = os.path.join(dirpath, name)
                    size = os.path.getsize(target)
                    with open(target, 'r+b') as f:
                        f.truncate(max(1, size // 2))
                    return
            return
        if fault == 'fail':
            # the commit dies between the tree and its manifest — the
            # exact torn-commit window the manifest-last protocol makes
            # harmless (epoch uncommitted, scan-down resumes older)
            raise OSError('injected checkpoint write failure '
                          f'({_faults.ENV_CKPT}=fail)')
        if block:
            _commit_manifest_tree(base_dir, epoch)
        else:
            _PENDING_MANIFEST = (os.path.abspath(str(base_dir)),
                                 int(epoch))
    else:
        if jax.process_index() != 0:
            return
        os.makedirs(base_dir, exist_ok=True)
        import pickle

        from kfac_pytorch_tpu import faults as _faults
        blob = pickle.dumps(jax.tree.map(np.asarray, payload))
        key = f'checkpoint-{epoch}.pkl'
        fault = _faults.checkpoint_fault_mode()
        if fault == 'eio_once':
            # transient-storage drill: the FIRST write attempt dies with
            # EIO before touching disk; a retry policy turns this into a
            # logged hiccup, no policy into the crash it used to be
            if _faults.claim_ckpt_eio_once():
                import errno
                import logging
                logging.getLogger(__name__).warning(
                    'CHAOS FAULT ACTIVE: %s=eio_once — failing this '
                    'checkpoint write once', _faults.ENV_CKPT)
                raise OSError(errno.EIO,
                              'injected transient checkpoint write '
                              f'failure ({_faults.ENV_CKPT}=eio_once)')
            fault = None
        if fault:
            # loud by design: a drill env var leaking into a real run
            # must be visible in its logs, not discovered at next resume
            import logging
            logging.getLogger(__name__).warning(
                'CHAOS FAULT ACTIVE: %s=%s — deliberately corrupting the '
                'checkpoint write for epoch %s', _faults.ENV_CKPT, fault,
                epoch)
        store = _store_for(base_dir)
        if fault == 'truncate':
            # chaos drill: a torn object lands under the FINAL key with
            # no manifest — the manifest-aware resume scan refuses the
            # epoch without ever reading it (pre-manifest behavior was
            # to select it and crash into the truncation)
            _store_guard(lambda: store.put(
                key, blob[:max(1, len(blob) // 2)]))
            return
        if fault == 'fail':
            # the write dies mid-upload: a partial tmp file, never a
            # final object and never a manifest
            with open(path + '.pkl.tmp', 'wb') as f:
                f.write(blob[:max(1, len(blob) // 2)])
                f.flush()
            raise OSError('injected checkpoint write failure '
                          f'({_faults.ENV_CKPT}=fail)')
        # atomic put (posix: full write to a tmp name, fsync, rename) —
        # a crash at any point leaves either the old object or the new
        # one, never a truncated final object — then the manifest LAST:
        # the epoch is committed only once its content hash is recorded
        _store_guard(lambda: store.put(key, blob))
        _commit_manifest(base_dir, store, epoch, 'pickle', {key: blob})


def reshard_kfac_state(pre_old, pre_new, kfac_state, carry_decomp=False):
    """Elastic world-size resume (beyond the reference): re-lay the
    K-FAC FACTOR state from ``pre_old``'s plan (its ``num_devices``)
    into ``pre_new``'s — restore a checkpoint taken at one world size
    into a differently-sized mesh.

    The stacked-bucket layout is device-major per world size (plan.py),
    so a num_devices change reshuffles which row of which bucket holds
    each layer's factor — both plans' ``layer_rows`` maps make the
    transport exact, and in BOTH directions: shrinking packs the rows
    into fewer shards, growing spreads them over more (any pad rows the
    new, less-even layout needs start from the fresh zero init and are
    never read — pad-row-exact, pinned by the N->M->N roundtrip tests). Only the FACTORS (the accumulated statistics —
    the state that takes thousands of steps to rebuild) are carried by
    default; decompositions re-initialize to zero and are recomputed at
    the first inverse update, exactly the fresh-start degrade path the
    trainer already handles (training.py seen-inverse gating; E-KFAC
    scales likewise re-accumulate — they are basis-bound). The step
    counter is preserved.

    ``carry_decomp`` (ISSUE 14, the live-replanning transport): when
    both preconditioners decompose by the SAME method, also transport
    the stored decompositions through the identical per-layer row
    remap — each row's decomposition is a property of that row's
    (identity-padded) factor alone, so a FULL-row move is exact at any
    world size (true-block slicing would be wrong here: eigh orders
    eigenvalues globally, interleaving the pad block's unit eigenpairs
    with the true spectrum). The relaunched/replanned run then resumes
    *preconditioning* immediately instead of passing gradients through
    until the next inverse refresh — the shrink/grow relaunch critical
    path the replan routing cuts. New pad rows stay at the zero init
    (never read); E-KFAC scales stay transport-transient either way
    (their group layout is comm-mode bound, not row bound). Ignored
    when the methods differ (an eigen<->cholesky replan rebuilds the
    decomposition from the carried factors).

    Host-side numpy: call OUTSIDE jit, with the old state fully
    addressable (single-host restore, or after a replicated restore).
    Both preconditioners must be set up on the same layer list.
    """
    plan_o, plan_n = pre_old.plan, pre_new.plan
    assert plan_o is not None and plan_n is not None, 'call setup() first'
    sig_o = [(m.path, m.in_dim, m.out_dim) for m in plan_o.metas]
    sig_n = [(m.path, m.in_dim, m.out_dim) for m in plan_n.metas]
    assert sig_o == sig_n, (
        'elastic resume requires the same layer set (paths AND dims — a '
        f'width change invalidates the statistics): {sig_o} != {sig_n}')
    fresh = pre_new.init()
    factors = {k: np.array(v) for k, v in fresh.factors.items()}
    old = {k: np.asarray(v) for k, v in kfac_state.factors.items()}
    carry_decomp = (carry_decomp and pre_old.method == pre_new.method)
    decomp = None
    old_decomp = None
    if carry_decomp:
        # leaf groups that are per-row bucket stacks (scales are group-
        # keyed and comm-mode shaped — never row-transported)
        decomp = {grp: {k: np.array(v) for k, v in leaves.items()}
                  for grp, leaves in fresh.decomp.items()
                  if grp in ('evals', 'evecs', 'invs')}
        old_decomp = {grp: {k: np.asarray(v) for k, v in leaves.items()}
                      for grp, leaves in kfac_state.decomp.items()
                      if grp in decomp}
    for i, meta in enumerate(plan_o.metas):
        ba_o, ra_o, bg_o, rg_o, _ = plan_o.layer_rows[i]
        ba_n, ra_n, bg_n, rg_n, _ = plan_n.layer_rows[i]
        da, dg = meta.in_dim, meta.out_dim
        factors[str(ba_n)][ra_n, :da, :da] = old[str(ba_o)][ra_o, :da, :da]
        factors[str(bg_n)][rg_n, :dg, :dg] = old[str(bg_o)][rg_o, :dg, :dg]
        if carry_decomp:
            for grp in decomp:
                dst, src = decomp[grp], old_decomp[grp]
                # (the inverse of a layer's damped A has a row of its own
                # where several layers keep one A factor: plan.inv_row_a)
                dst[str(ba_n)][plan_n.inv_row_a[i]] = src[str(ba_o)][
                    plan_o.inv_row_a[i]]
                dst[str(bg_n)][rg_n] = src[str(bg_o)][rg_o]
    import jax.numpy as jnp
    out = fresh.replace(
        step=jnp.asarray(np.asarray(kfac_state.step)),
        factors={k: jnp.asarray(v) for k, v in factors.items()})
    if carry_decomp:
        new_decomp = dict(out.decomp)
        for grp, leaves in decomp.items():
            new_decomp[grp] = {k: jnp.asarray(v) for k, v in leaves.items()}
        out = out.replace(decomp=new_decomp)
    return out


class StaleLineageError(RuntimeError):
    """This process belongs to an abandoned (fenced) fork of the pod:
    the on-disk ``world.json`` records a NEWER lineage epoch than the
    one this process was launched with. Resuming — or re-stamping —
    would clobber the surviving lineage's state, so both refuse."""


def write_world_stamp(base_dir, num_devices, gen=None, lineage=None):
    """Record the K-FAC world size the checkpoints in ``base_dir`` were
    taken at (``world.json``, atomic, rank-0 only). The elastic resume
    path (``resilience.elastic.elastic_resume``) compares this stamp to
    the relaunched trainer's world and routes a mismatch — in EITHER
    direction: a shrunken pod reshards down, a re-grown one reshards up
    — through :func:`reshard_kfac_state`; without the stamp the relaunch
    would try to restore factor buckets shaped for the old mesh and die
    on a structure mismatch. ``gen`` (optional) records the pod
    generation the stamp was written under (``KFAC_POD_GEN`` from the
    pod supervisor) — provenance for churn forensics, not protocol
    state.

    ``lineage`` (optional, ``KFAC_LINEAGE`` from the pod supervisor) is
    PROTOCOL state: the monotonic lineage epoch of the membership this
    trainer belongs to. The stamp may never move backward — a writer at
    a LOWER lineage than the one on disk is a fenced fork's straggler,
    and overwriting here would be exactly the split-brain clobber the
    quorum gate exists to prevent: it raises :class:`StaleLineageError`
    instead (commit fencing's last line of defense; the first is that a
    fenced supervisor never relaunches its trainer at all)."""
    if jax.process_index() != 0:
        return
    from kfac_pytorch_tpu.resilience import atomic_write_json
    os.makedirs(base_dir, exist_ok=True)
    stamp = {'num_devices': int(num_devices)}
    if gen is not None:
        stamp['gen'] = int(gen)
    target = os.path.join(os.path.abspath(base_dir), 'world.json')
    if lineage is None:
        atomic_write_json(target, stamp)
        return
    # check-then-write must be atomic against a CONCURRENT higher-
    # lineage writer (the race: a fenced straggler reads the old stamp,
    # the majority writes the new one, the straggler's replace moves it
    # backward) — serialize through an advisory lock next to the stamp.
    # Best-effort: on filesystems without flock semantics (gcsfuse) the
    # check still runs unserialized, and the OTHER two fencing layers
    # (the fenced supervisor killing its trainer; elastic_resume
    # refusing a newer-lineage stamp) carry the guarantee.
    import contextlib
    lock_cm = contextlib.nullcontext()
    try:
        import fcntl
        lock_f = open(target + '.lock', 'w')
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        lock_cm = lock_f  # closing releases the lock
    except (ImportError, OSError):
        pass
    with lock_cm:
        existing = read_world_stamp_info(base_dir)
        if (existing is not None
                and isinstance(existing.get('lineage'), int)
                and existing['lineage'] > int(lineage)):
            raise StaleLineageError(
                f'world stamp in {base_dir} is at lineage '
                f'{existing["lineage"]} but this process is at lineage '
                f'{int(lineage)}: refusing to move the stamp backward '
                '(this host belongs to an abandoned fork of the pod)')
        stamp['lineage'] = int(lineage)
        atomic_write_json(target, stamp)


def read_world_stamp_info(base_dir):
    """The full ``world.json`` payload (``num_devices`` plus the
    optional ``gen`` provenance), or None. A corrupt/absent stamp reads
    as None — same-world resume, never a crash."""
    import json
    path = os.path.join(os.path.abspath(base_dir), 'world.json')
    try:
        with open(path) as f:
            stamp = json.load(f)
        stamp['num_devices'] = int(stamp['num_devices'])
        return stamp
    except (OSError, ValueError, KeyError, TypeError):
        return None


def read_world_stamp(base_dir):
    """The ``num_devices`` recorded by :func:`write_world_stamp`, or
    None (no stamp — pre-elastic checkpoints resume as same-world)."""
    stamp = read_world_stamp_info(base_dir)
    return None if stamp is None else stamp['num_devices']


def wait_for_checkpoints():
    """Block until all in-flight async saves are durable on disk, then
    commit any deferred manifest — only after this returns is the last
    ``block=False`` save actually restorable."""
    global _PENDING_MANIFEST
    if _ASYNC_CKPTR is not None:
        try:
            _ASYNC_CKPTR.wait_until_finished()
        except Exception:
            _PENDING_MANIFEST = None  # that save never became durable
            raise
    _flush_pending_manifest()


def prune_checkpoints(base_dir, keep):
    """Keep only the ``keep`` newest distinct checkpoint epochs (orbax
    CheckpointManager-style retention; the reference keeps every epoch).
    Rank-0 only — pure filesystem, no barrier.

    Safe to call right after an async ``save_checkpoint(block=False)``
    because of two invariants this function RELIES on: (a) save_checkpoint
    waits for the previous async save before issuing a new one, so every
    finalized ``checkpoint-{e}`` name here is durable, and (b) the
    in-flight orbax write lives under a ``.orbax-checkpoint-tmp`` suffix
    the pattern below cannot match. If either invariant changes, call
    :func:`wait_for_checkpoints` first."""
    if keep is None or keep <= 0 or jax.process_index() != 0:
        return
    pat = re.compile(r'^checkpoint-(\d+)(\.pkl|\.manifest\.json)?$')
    by_epoch = {}
    for name in (os.listdir(base_dir) if os.path.isdir(base_dir) else ()):
        m = pat.match(name)
        if m:
            by_epoch.setdefault(int(m.group(1)), []).append(name)
    for epoch in sorted(by_epoch)[:-keep]:
        for name in by_epoch[epoch]:
            target = os.path.join(base_dir, name)
            if os.path.isdir(target):
                import shutil
                shutil.rmtree(target, ignore_errors=True)
            else:
                os.remove(target)
    # a REMOTE store holds its own copies of the same epochs — apply
    # the identical retention there (manifest first, so a crash mid-
    # prune leaves an uncommitted epoch, never a committed torso).
    # Housekeeping only: a store outage here must not kill the trainer.
    store = _store_for(base_dir)
    if _store.local_root(store) == os.path.abspath(str(base_dir)):
        return
    try:
        epochs = _manifest.manifest_epochs(store)
        for epoch in sorted(epochs)[:-keep]:
            manifest = _manifest.read_manifest(store, epoch)
            store.delete(epochs[epoch])
            for bkey in (sorted(manifest['blobs'])
                         if manifest is not None else ()):
                store.delete(bkey)
    except OSError:
        import logging
        logging.getLogger(__name__).warning(
            'store-side checkpoint prune failed; will retry at the '
            'next prune', exc_info=True)


def find_resume_epoch(base_dir, max_epoch):
    """Scan checkpoint-{epoch} downward from max_epoch (reference:
    pytorch_imagenet_resnet.py:162-167). Returns the epoch or None.

    Manifest-aware: an epoch whose manifest exists is COMMITTED and
    always eligible. Local files newer than the newest manifest but
    without one of their own are torn commits (the writer died between
    the blobs and the manifest) and are skipped. Files older than every
    manifest are legacy pre-manifest checkpoints and stay eligible —
    upgrading the code must not orphan existing checkpoints."""
    store = _store_for(base_dir)
    manifested = _store_guard(
        lambda: set(_manifest.manifest_epochs(store)))
    newest = max(manifested) if manifested else None
    for e in range(max_epoch, -1, -1):
        if e in manifested:
            return e
        present = (os.path.isdir(_ckpt_dir(base_dir, e))
                   or os.path.exists(_ckpt_dir(base_dir, e) + '.pkl'))
        if not present:
            continue
        if newest is not None and e > newest:
            import logging
            logging.getLogger(__name__).warning(
                'checkpoint-%d in %s has no manifest (torn commit); '
                'skipping it in the resume scan', e, base_dir)
            continue
        return e
    return None


def restore_checkpoint(base_dir, epoch, target_state, retry=None):
    """Restore into the structure of ``target_state``. ``retry``: an
    optional ``resilience.RetryPolicy`` for transient read failures (a
    corrupt/truncated file fails identically every attempt and still
    raises — that case belongs to :func:`auto_resume`'s scan-downward)."""
    if retry is not None:
        from kfac_pytorch_tpu.resilience.retry import call_with_retry
        return call_with_retry(
            lambda: _restore_checkpoint_once(base_dir, epoch, target_state),
            policy=retry, label=f'restore checkpoint-{epoch}')
    return _restore_checkpoint_once(base_dir, epoch, target_state)


def _restore_checkpoint_once(base_dir, epoch, target_state):
    store = _store_for(base_dir)
    manifest = _store_guard(lambda: _manifest.read_manifest(store, epoch))
    if manifest is not None:
        return _restore_manifested(base_dir, epoch, manifest, store,
                                   target_state)
    # legacy pre-manifest checkpoint: restore straight off the files
    path = _ckpt_dir(base_dir, epoch)
    if _HAS_ORBAX and os.path.isdir(path):
        return _restore_orbax(path, epoch, target_state)
    import pickle
    with open(path + '.pkl', 'rb') as f:
        return _checked_pickle(pickle.load(f), epoch, target_state)


def _restore_orbax(path, epoch, target_state):
    """Orbax restore into ``target_state``, the saved K-FAC layout
    compared with the target's first (from the checkpoint's own
    metadata: orbax's structure error names neither)."""
    try:
        saved = _saved_kfac_meta(path).get('factors')
    except Exception:  # noqa: BLE001 — metadata unreadable: restore says
        saved = None
    _check_kfac_layout(saved, target_state, epoch)
    return ocp.StandardCheckpointer().restore(path, target_state)


def _saved_kfac_meta(path):
    """The ``kfac_state`` subtree of an orbax checkpoint's own metadata
    (orbax 0.11: ``StepMetadata.item_metadata`` is the saved tree, its
    leaves carrying shape and dtype); {} where none was saved."""
    meta = ocp.StandardCheckpointer().metadata(path).item_metadata
    return meta.get('kfac_state') or {}


def _checked_pickle(restored, epoch, target_state):
    """A pickle restores without a target: compare what came back."""
    k = getattr(restored, 'kfac_state', None)
    _check_kfac_layout(getattr(k, 'factors', None), target_state, epoch)
    return restored


def _verified_blob(store, key, spec):
    """Fetch one manifested blob and verify it against its recorded
    hash/size; ``(data, None)`` or ``(None, reason)``."""
    blob = _store_guard(lambda: store.get(key))
    if blob is None:
        return None, 'missing'
    if len(blob.data) != spec['size']:
        return None, 'size_mismatch'
    if _manifest.blob_sha256(blob.data) != spec['sha256']:
        return None, 'hash_mismatch'
    return blob.data, None


def _restore_manifested(base_dir, epoch, manifest, store, target_state):
    """Restore a COMMITTED epoch: every blob is re-verified against the
    manifest's content hash before a byte of it reaches the trainer —
    silent corruption surfaces here as :class:`CheckpointCorruptError`
    (which ``auto_resume`` turns into a scan-down), never as a
    mysterious unpickling/orbax failure three layers deeper."""
    import logging
    log = logging.getLogger(__name__)
    problems = []
    blobs = {}
    local = _store.local_root(store) == os.path.abspath(str(base_dir))
    for key in sorted(manifest['blobs']):
        data, reason = _verified_blob(store, key, manifest['blobs'][key])
        if reason is not None:
            log.warning('ckpt: corrupt blob key=%s epoch=%d reason=%s',
                        key, int(epoch), reason)
            problems.append((key, reason))
            continue
        blobs[key] = data
    if problems:
        raise CheckpointCorruptError(
            f'checkpoint-{epoch} failed manifest verification: '
            + ', '.join(f'{k} ({r})' for k, r in problems))
    if manifest.get('kind') == 'pickle':
        import pickle
        (data,) = blobs.values()
        return _checked_pickle(pickle.loads(data), epoch, target_state)
    # orbax tree: materialize verified bytes locally when the store is
    # remote (orbax restores from a directory), then restore as usual
    if not local:
        for key, data in blobs.items():
            target = os.path.join(os.path.abspath(str(base_dir)),
                                  *key.split('/'))
            os.makedirs(os.path.dirname(target), exist_ok=True)
            tmp = target + f'.tmp-{os.getpid()}'
            with open(tmp, 'wb') as f:
                f.write(data)
            os.replace(tmp, target)
    return _restore_orbax(_ckpt_dir(base_dir, epoch), epoch, target_state)


def _saved_comm_err_zeros(path):
    """Zero arrays shaped like a saved ``KFACState.comm_err`` subtree —
    the restore placeholder for the comm_precision DOWNGRADE direction
    (lossy-era checkpoint into an fp32-configured run, see
    :func:`auto_resume`). ``None`` when the checkpoint carries no
    residual, or when orbax is unavailable (the pickle path restores
    without structure matching and never needs this)."""
    if not _HAS_ORBAX or not os.path.isdir(path):
        return None
    try:
        err = _saved_kfac_meta(path).get('comm_err')
        if not isinstance(err, dict) or not err:
            return None
        import jax.numpy as jnp
        return {key: jnp.zeros(m.shape, m.dtype)
                for key, m in err.items()}
    except Exception:  # noqa: BLE001 — metadata unreadable: not ours
        return None


def auto_resume(base_dir, max_epoch, target_state, retry=None):
    """Corruption-tolerant auto-resume: ``(restored_state, epoch)``, or
    ``(None, None)`` when nothing restorable exists. ``retry`` (a
    ``resilience.RetryPolicy``) is applied per restore attempt, so a
    TRANSIENT read hiccup on the newest checkpoint is retried in place
    rather than silently costing an epoch of progress to the
    scan-downward.

    Extends the reference's scan-downward resume
    (pytorch_imagenet_resnet.py:162-167) to UNREADABLE checkpoints: where
    a bare ``restore_checkpoint(find_resume_epoch(...))`` crashes the run
    on a truncated/corrupt file (e.g. a non-atomic write interrupted
    mid-save, or silent storage corruption), this keeps scanning to the
    next-older epoch — the same degrade-don't-die posture the in-jit
    health guard (health.py) applies to numerical blowups. Every skipped
    epoch is logged as a warning with the failure attached.
    """
    import logging
    log = logging.getLogger(__name__)
    epoch = find_resume_epoch(base_dir, max_epoch)
    while epoch is not None:
        try:
            return (restore_checkpoint(base_dir, epoch, target_state,
                                       retry=retry), epoch)
        except KFACLayoutError:
            raise
        except Exception:  # noqa: BLE001 — any unreadable ckpt: scan on
            # NOT necessarily corruption: a structure mismatch from a
            # checkpoint taken before an OPTIONAL state subtree existed
            # — no TrainState.health (pre-health code) and/or no
            # KFACState.comm_err (taken at fp32 before comm_precision
            # was enabled) — makes orbax reject the restore. Retry
            # against targets with those subtrees dropped: the trainer
            # re-seeds a None HealthState AND a None EF residual
            # host-side on the first step (training.py), so the
            # restored run is whole either way.
            for drop_err, drop_health, note in (
                    (True, False, 'predates comm_precision (no EF '
                                  'residual); residual starts at zero'),
                    (False, True, 'predates the health guard (no '
                                  'HealthState); counters start fresh'),
                    (True, True, 'predates the health guard and '
                                 'comm_precision; both start fresh')):
                fb = target_state
                if drop_err:
                    k = getattr(fb, 'kfac_state', None)
                    if k is None or getattr(k, 'comm_err', None) is None:
                        continue
                    fb = fb.replace(kfac_state=k.replace(comm_err=None))
                if drop_health:
                    if getattr(fb, 'health', None) is None:
                        continue
                    fb = fb.replace(health=None)
                try:
                    restored = restore_checkpoint(base_dir, epoch, fb,
                                                  retry=retry)
                    log.info('checkpoint-%d %s', epoch, note)
                    return restored, epoch
                except Exception:  # noqa: BLE001 — try the next target
                    pass
            # ... and the DOWNGRADE direction: the checkpoint CARRIES a
            # comm_err residual (taken under a lossy comm_precision) but
            # this run's target has none (fp32, or the knob reverted).
            # Build a zero placeholder from the checkpoint's own saved
            # shapes, restore, then discard the residual — it only
            # compensates a lossy wire, so dropping it loses one step's
            # quantization error at most, vs losing ALL progress to a
            # 'unreadable' restart-from-scratch.
            k = getattr(target_state, 'kfac_state', None)
            if k is not None and getattr(k, 'comm_err', None) is None:
                zeros = _saved_comm_err_zeros(_ckpt_dir(base_dir, epoch))
                if zeros is not None:
                    try:
                        restored = restore_checkpoint(
                            base_dir, epoch,
                            target_state.replace(
                                kfac_state=k.replace(comm_err=zeros)),
                            retry=retry)
                        restored = restored.replace(
                            kfac_state=restored.kfac_state.replace(
                                comm_err=None))
                        log.info(
                            'checkpoint-%d carries an EF residual '
                            '(comm_err) the current comm_precision does '
                            'not use; residual discarded', epoch)
                        return restored, epoch
                    except Exception:  # noqa: BLE001 — genuinely bad
                        pass
            log.warning(
                'checkpoint-%d in %s is unreadable; falling back to the '
                'next-older epoch', epoch, base_dir, exc_info=True)
        epoch = find_resume_epoch(base_dir, epoch - 1) if epoch > 0 else None
    return None, None


class PreemptionGuard:
    """Preemption-aware checkpoint trigger (beyond reference, SURVEY §5.3).

    Cloud TPU VMs are frequently preemptible: the platform delivers
    SIGTERM with a short grace window before killing the process. The
    reference's failure story is crash-stop + scan-downward auto-resume
    (examples/pytorch_imagenet_resnet.py:162-167), losing everything
    since the last epoch checkpoint. The guard converts the signal into
    a cooperative flag: trainers poll ``triggered`` at step boundaries,
    break out, save the CURRENT TrainState (step counter and K-FAC state
    included, so the LR schedule and factors resume exactly), and exit
    cleanly inside the grace window.

    Install once before the training loop; handlers chain to any
    previously-installed ones. In multi-host training poll
    :meth:`should_stop` (NOT the raw flag): hosts can receive the signal
    at different batch boundaries, and a rank leaving the loop alone
    would strand the others in a collective — ``should_stop`` OR-reduces
    the flag across processes so every rank exits at the same step.
    """

    def __init__(self, signals=None, sync_every=20):
        import signal as _signal

        self._flag = False
        self._stopped = False
        self.sync_every = max(1, sync_every)
        self._prev = {}
        for s in signals or (_signal.SIGTERM,):
            self._prev[s] = _signal.signal(s, self._handler)

    def _handler(self, signum, frame):
        self._flag = True
        prev = self._prev.get(signum)
        if callable(prev):
            prev(signum, frame)

    def uninstall(self):
        """Put back the handlers that were installed before this guard.

        Without this every construction chains another handler for
        process lifetime — harmless for one trainer, but it leaks across
        tests and long-lived drivers (each leaked guard keeps its whole
        trainer state reachable, and a later SIGTERM still flips a flag
        nobody polls). Idempotent; un-nesting guards out of construction
        order restores each signal to what THIS guard saw, which may drop
        a later guard's handler — uninstall in reverse order.
        """
        import signal as _signal
        for s, prev in self._prev.items():
            # a None previous handler means "not installed from Python"
            # (signal.getsignal convention) — restore the default
            _signal.signal(s, prev if prev is not None else _signal.SIG_DFL)
        self._prev = {}

    @property
    def triggered(self):
        """Local flag only — safe to act on in single-process runs."""
        return self._flag

    def should_stop(self, step=None):
        """Cross-host consensus on the flag.

        Single process: the local flag. Multi-process: an OR-reduce over
        hosts, refreshed every ``sync_every`` steps when ``step`` is given
        (every call otherwise) — the collective runs on the same local
        step count on every host, so the calls pair up and all ranks
        observe the stop at the same batch boundary.
        """
        if jax.process_count() == 1:
            return self._flag
        if self._stopped:
            return True
        if step is not None and step % self.sync_every != 0:
            return False
        from jax.experimental import multihost_utils
        flags = multihost_utils.process_allgather(
            np.asarray(self._flag, np.int32))
        self._stopped = bool(np.any(flags))
        return self._stopped
