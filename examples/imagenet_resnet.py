"""ImageNet ResNet-50 / InceptionV4 trainer with DP-KFAC — the flagship
workload (BASELINE.md north-star: 55-epoch K-FAC schedule vs 90-epoch
SGD).

Flag-surface parity with the reference entrypoint
(examples/pytorch_imagenet_resnet.py): checkpoint/auto-resume
(:162-167, 305-312), label smoothing (:321), KFACParamScheduler wiring
(:281-287), batches-per-allreduce gradient accumulation (:355-367),
warmup + multi-step LR scaled by world size (:219-231). Reads an
ImageFolder-style numpy cache from ``--train-dir`` if present, else
deterministic synthetic ImageNet-shaped data.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import data as kdata
from kfac_pytorch_tpu import models, training, utils


def parse_args():
    p = argparse.ArgumentParser(description='ImageNet K-FAC trainer (TPU)')
    p.add_argument('--model', default='resnet50')
    p.add_argument('--train-dir', default=None)
    p.add_argument('--val-dir', default=None)
    p.add_argument('--batch-size', type=int, default=32)
    p.add_argument('--val-batch-size', type=int, default=32)
    p.add_argument('--batches-per-allreduce', type=int, default=1)
    p.add_argument('--epochs', type=int, default=55)
    p.add_argument('--base-lr', type=float, default=0.0125)
    p.add_argument('--lr-decay', nargs='+', type=int,
                   default=[25, 35, 40, 45, 50])
    p.add_argument('--warmup-epochs', type=int, default=5)
    p.add_argument('--wd', type=float, default=5e-5)
    p.add_argument('--label-smoothing', type=float, default=0.1)
    p.add_argument('--img-size', type=int, default=224)
    # K-FAC (reference defaults: train_imagenet.sh)
    p.add_argument('--kfac-update-freq', type=int, default=1)
    p.add_argument('--kfac-basis-update-freq', type=int, default=0,
                   help='full eigendecomposition cadence; intermediate '
                        'inverse updates refresh eigenvalues in the '
                        'retained basis (0 = always full)')
    p.add_argument('--kfac-warm-start', action='store_true',
                   help='warm-start decompositions from the stored one: '
                        'eigen variants track the previous eigenbasis '
                        '(KFAC_EIGH_IMPL=subspace|auto|jacobi), Cholesky '
                        'variants Newton-Schulz-iterate the previous '
                        'inverse')
    p.add_argument('--kfac-stagger', action='store_true',
                   help='staggered inverse refresh: decompose one cost-'
                        'balanced cohort of factors per step instead of '
                        'ALL factors every --kfac-update-freq steps — '
                        'same staleness contract, no periodic eigh spike '
                        '(see README "Staggered refresh")')
    p.add_argument('--kfac-comm-precision',
                   default=os.environ.get('KFAC_COMM_PRECISION', 'fp32'),
                   choices=['fp32', 'bf16', 'int8'],
                   help='wire dtype of the K-FAC factor collectives '
                        '(default from $KFAC_COMM_PRECISION): bf16 '
                        'halves, int8 quarters the gather payloads; '
                        'lossy stats reduces carry an error-feedback '
                        'residual; the gradient allreduce is never '
                        'compressed (see README "Communication '
                        'compression")')
    p.add_argument('--kfac-comm-mode',
                   default=os.environ.get('KFAC_COMM_MODE') or None,
                   choices=['inverse', 'pred'],
                   help='override the variant\'s comm mode (default from '
                        '$KFAC_COMM_MODE; unset = the variant default): '
                        "'inverse' gathers decompositions once per "
                        "refresh, 'pred' gathers preconditioned "
                        'gradients every step. A runtime knob since the '
                        'live replanning path — with --kfac-autotune the '
                        'controller probes the other mode and applies a '
                        'winning switch mid-run via KFAC.replan (see '
                        'README "Live replanning")')
    p.add_argument('--kfac-comm-prefetch', action='store_true',
                   help='comm_inverse variants only: publish each '
                        "inverse update's gathered decomposition for "
                        'the NEXT step so the gather overlaps the pred '
                        'einsums (one step of decomposition staleness)')
    p.add_argument('--kfac-capture-impl',
                   default=os.environ.get('KFAC_CAPTURE_IMPL') or None,
                   choices=['xla', 'pallas', 'auto'],
                   help='capture kernels (default from '
                        '$KFAC_CAPTURE_IMPL; unset = the legacy '
                        'capture path, hidden from the autotuner): '
                        'xla = patch-extract + factor GEMM + EMA as '
                        'separate XLA ops; pallas = the fused Pallas '
                        'kernels (no HBM patch matrix, EMA / wire-'
                        'quantize folded into the epilogues); auto = '
                        'the fused rung. An explicit value makes this '
                        'a live autotuner ladder rung (see README '
                        '"Capture hot path")')
    p.add_argument('--kfac-decomp-impl',
                   default=os.environ.get('KFAC_DECOMP_IMPL') or None,
                   choices=['xla', 'auto', 'jacobi', 'subspace',
                            'newton_schulz'],
                   help='decomposition kernel (default from '
                        '$KFAC_DECOMP_IMPL; unset = the legacy '
                        'KFAC_EIGH_IMPL env contract): xla = cold '
                        'QDWH eigh / Cholesky; subspace|jacobi (eigh '
                        'variants) and newton_schulz (Cholesky '
                        'variants) are warm iterative kernels that '
                        'replace the decomposition with GEMMs; auto '
                        'picks the warm kernel for the variant. An '
                        'explicit value makes this a live autotuner '
                        'ladder rung (see README "Attacking the '
                        'decomposition wall")')
    p.add_argument('--kfac-decomp-shard', action='store_true',
                   default=os.environ.get('KFAC_DECOMP_SHARD', '') == '1',
                   help='mesh-sharded decomposition: repartition each '
                        'refresh cohort cost-balanced across ALL '
                        'devices instead of owner-local (~P x shorter '
                        'decomposition critical path for two bounded '
                        'DecompComm gathers per step; implies '
                        '--kfac-stagger semantics)')
    p.add_argument('--kfac-autotune', action='store_true',
                   default=os.environ.get('KFAC_AUTOTUNE', '') == '1',
                   help='closed-loop autotuning: one online controller '
                        'hill-climbs kfac/fac_update_freq and the comm '
                        'wire dtype from measured step times through '
                        'the knob arbiter (defaults on when '
                        '$KFAC_AUTOTUNE=1; see README "Closed-loop '
                        'autotuning")')
    p.add_argument('--kfac-cov-update-freq', type=int, default=1)
    p.add_argument('--kfac-name', default='eigen_dp',
                   choices=list(kfac.KFAC_VARIANTS))
    p.add_argument('--stat-decay', type=float, default=0.95)
    p.add_argument('--damping', type=float, default=0.002)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--damping-alpha', type=float, default=0.5)
    p.add_argument('--damping-decay', nargs='+', type=int, default=None)
    p.add_argument('--kfac-update-freq-alpha', type=float, default=10)
    p.add_argument('--kfac-update-freq-decay', nargs='+', type=int,
                   default=None)
    p.add_argument('--exclude-parts', default='')
    p.add_argument('--assignment', default='balanced')
    p.add_argument('--num-devices', type=int, default=1)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--speed', action='store_true')
    p.add_argument('--bf16', action='store_true', default=True)
    p.add_argument('--log-dir', default='./logs')
    p.add_argument('--tb-dir', default=None,
                   help='write TensorBoard scalar summaries here (rank 0; '
                        'reference pytorch_imagenet_resnet.py:169-178, '
                        '405-408 — gated there, first-class here)')
    p.add_argument('--checkpoint-format', default='./checkpoints')
    p.add_argument('--keep-checkpoints', type=int, default=0,
                   help='retain only the N newest checkpoints '
                        '(0 = keep all, reference behavior)')
    p.add_argument('--synthetic-size', type=int, default=1024)
    # resilient runtime (kfac_pytorch_tpu/resilience/)
    p.add_argument('--step-deadline', type=float, default=0,
                   help='seconds a single step may block before the '
                        'watchdog dumps all-thread stacks and exits '
                        'rc=114 for the supervisor (0 = off)')
    p.add_argument('--straggler-budget', type=float, default=0,
                   help='seconds/step EMA budget; above it the K-FAC '
                        'update freqs stretch until the host recovers '
                        '(0 = off)')
    p.add_argument('--io-retries', type=int, default=3,
                   help='retry budget for checkpoint I/O and next-batch '
                        'transients (0 = fail fast)')
    # observability (kfac_pytorch_tpu/obs/)
    p.add_argument('--trace', default=None, metavar='DIR',
                   help='write Chrome-trace spans (per-step phase spans, '
                        'resilience instants) to DIR/trace-host<i>.jsonl '
                        'and epoch metric snapshots to '
                        'DIR/metrics.jsonl; merge a pod\'s files with '
                        'kfac-obs (defaults to $KFAC_TRACE_DIR when set)')
    p.add_argument('--prom-file',
                   default=os.environ.get('KFAC_PROM_FILE'),
                   metavar='PATH',
                   help='export the metrics registry as a Prometheus '
                        'textfile at PATH after every epoch (rank 0; '
                        'defaults to $KFAC_PROM_FILE — the training '
                        'service sets it per tenant job, and the path '
                        'is namespaced by tenant/job id either way)')
    return p.parse_args()


def get_data(args):
    if args.train_dir and os.path.exists(
            os.path.join(args.train_dir, 'images.npy')):
        x = np.load(os.path.join(args.train_dir, 'images.npy'),
                    mmap_mode='r')
        y = np.load(os.path.join(args.train_dir, 'labels.npy'))
        return (x, y), (x[:1024], y[:1024])
    shape = (args.img_size, args.img_size, 3)
    # same draw + split: train/val must share the class means
    x, y = kdata.synthetic_classification(args.synthetic_size + 256, shape,
                                          1000, seed=1)
    return (x[:-256], y[:-256]), (x[-256:], y[-256:])


def main():
    from kfac_pytorch_tpu.parallel import mesh as kmesh
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    kmesh.maybe_initialize_distributed()
    args = parse_args()
    from kfac_pytorch_tpu.utils.runlog import setup_run_logging
    log, _ = setup_run_logging(
        args.log_dir, 'imagenet', args.model,
        f'kfac{args.kfac_update_freq}', args.kfac_name,
        f'basis{args.kfac_basis_update_freq}'
        if args.kfac_basis_update_freq else None,
        'warm' if args.kfac_warm_start else None,
        f'bs{args.batch_size}', f'nd{args.num_devices}')
    log.info('args: %s', vars(args))

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = models.get_model(args.model, num_classes=1000, dtype=dtype)
    (train_x, train_y), (val_x, val_y) = get_data(args)
    train_loader = kdata.Loader(train_x, train_y, args.batch_size,
                                train=True, seed=args.seed)
    val_loader = kdata.Loader(val_x, val_y, args.val_batch_size, train=False)

    steps_per_epoch = train_loader.steps_per_epoch
    scale = max(1, args.num_devices * args.batches_per_allreduce)
    lr_fn = utils.warmup_multistep(args.base_lr, steps_per_epoch,
                                   args.warmup_epochs, args.lr_decay,
                                   scale=scale)
    tx = training.sgd(lr_fn, momentum=0.9, weight_decay=args.wd)
    if args.batches_per_allreduce > 1:
        tx = optax.MultiSteps(tx, args.batches_per_allreduce)

    use_kfac = args.kfac_update_freq > 0
    precond = None
    scheduler = None
    if use_kfac:
        precond = kfac.get_kfac_module(args.kfac_name)(
            lr=args.base_lr, damping=args.damping,
            fac_update_freq=args.kfac_cov_update_freq,
            kfac_update_freq=args.kfac_update_freq,
            basis_update_freq=(args.kfac_basis_update_freq or None),
            warm_start_basis=args.kfac_warm_start,
            stagger=args.kfac_stagger,
            comm_precision=args.kfac_comm_precision,
            comm_mode=args.kfac_comm_mode,
            comm_prefetch=args.kfac_comm_prefetch,
            decomp_impl=args.kfac_decomp_impl,
            capture_impl=args.kfac_capture_impl,
            decomp_shard=args.kfac_decomp_shard,
            kl_clip=args.kl_clip, factor_decay=args.stat_decay,
            exclude_parts=args.exclude_parts,
            num_devices=args.num_devices,
            axis_name='batch' if args.num_devices > 1 else None,
            assignment=args.assignment)

    mesh, axis = None, None
    if args.num_devices > 1:
        mesh = Mesh(np.array(jax.devices()[:args.num_devices]), ('batch',))
        axis = 'batch'

    def loss_fn(outputs, batch):
        return utils.label_smoothing_cross_entropy(
            outputs, batch['label'], smoothing=args.label_smoothing)

    sample = jnp.zeros((args.batch_size, args.img_size, args.img_size, 3),
                       dtype)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(args.seed), sample,
                                      mesh=mesh, axis_name=axis)
    if use_kfac:
        scheduler = kfac.KFACParamScheduler(
            precond, damping_alpha=args.damping_alpha,
            damping_schedule=args.damping_decay,
            update_freq_alpha=args.kfac_update_freq_alpha,
            update_freq_schedule=args.kfac_update_freq_decay)

    # resilient runtime (kfac_pytorch_tpu/resilience/): retrying I/O,
    # step watchdog, straggler-driven freq degradation
    from kfac_pytorch_tpu import resilience
    io_retry = (resilience.RetryPolicy(attempts=args.io_retries + 1)
                if args.io_retries > 0 else None)
    governor = None
    if args.straggler_budget > 0 and precond is not None:
        governor = resilience.StragglerGovernor(
            precond, args.straggler_budget, log=log)
    watchdog = None
    if args.step_deadline > 0:
        watchdog = resilience.StepWatchdog(args.step_deadline, log=log)
    # closed-loop autotuner: proposes knob changes, from measured step
    # times, to the same arbiter the scheduler/governor feed
    from kfac_pytorch_tpu import autotune
    tuner = autotune.controller_from_args(
        precond, enabled=args.kfac_autotune, trace_dir=args.trace,
        log=log)

    # auto-resume (reference: pytorch_imagenet_resnet.py:162-167,305-312),
    # hardened: an unreadable newest checkpoint (truncated write, storage
    # corruption) falls back to the next-older epoch instead of crashing;
    # a TRANSIENT read failure retries in place (io_retry). World-aware:
    # a checkpoint stamped with a different mesh size (the pod shrank)
    # routes through reshard_kfac_state instead of dying on a structure
    # mismatch.
    def make_old_precond(nd):
        pre = kfac.get_kfac_module(args.kfac_name)(
            lr=args.base_lr, damping=args.damping,
            fac_update_freq=args.kfac_cov_update_freq,
            kfac_update_freq=args.kfac_update_freq,
            exclude_parts=args.exclude_parts, num_devices=nd,
            axis_name='batch' if nd > 1 else None,
            assignment=args.assignment,
            # the restore target must match the checkpoint's state
            # structure (an EF residual is carried iff lossy)
            comm_precision=args.kfac_comm_precision,
            comm_mode=args.kfac_comm_mode)
        pre.setup(precond.plan.metas)
        return pre

    rescaled = []

    def on_world_change(ow, nw):
        # elastic shrink/grow hook: the loader feeds the GLOBAL batch
        # whatever the mesh size, so the global batch is the invariant
        # and the linear-scaling rule keeps the lr (lr_factor 1) and
        # the checkpoint's schedule; the WORLD_RESCALE line records it
        # for the churn timeline. A per-host-batch deployment would
        # pass per_host_batch= — a non-identity result then rebuilds
        # the lr schedule below.
        res = training.world_change_rescale(ow, nw, lr=args.base_lr,
                                            global_batch=args.batch_size)
        log.info(res.log_line())
        # provenance: the elastic verdict rides the knob arbiter's
        # record stream (composes nothing — the lr schedule stays
        # trainer-owned) so the decision log shows WHY a cadence or lr
        # changed around a world change
        from kfac_pytorch_tpu import autotune
        autotune.arbiter_for(precond).propose('elastic',
                                              **res._asdict())
        if res.lr != args.base_lr:
            args.base_lr = res.lr
            rescaled.append(res)

    start_epoch = 0
    restored, resume, old_world = resilience.elastic_resume(
        args.checkpoint_format, args.epochs, precond, state,
        make_precond=make_old_precond, retry=io_retry,
        on_world_change=on_world_change, log=log)
    if resume is not None:
        state = restored
        start_epoch = resume + 1
        if scheduler is not None:
            scheduler.step(start_epoch)
        if old_world is not None:
            log.info('RESHARDED from_world=%d to_world=%d step=%d',
                     old_world, args.num_devices, int(state.step))
        if rescaled:
            # the hook actually changed the base lr (per-host-batch
            # deployments): the schedule re-derives from it
            lr_fn = utils.warmup_multistep(
                args.base_lr, steps_per_epoch, args.warmup_epochs,
                args.lr_decay,
                scale=max(1, args.num_devices
                          * args.batches_per_allreduce))
            tx = training.sgd(lr_fn, momentum=0.9, weight_decay=args.wd)
            if args.batches_per_allreduce > 1:
                tx = optax.MultiSteps(tx, args.batches_per_allreduce)
        log.info('resumed from checkpoint-%d', resume)
    utils.write_world_stamp(args.checkpoint_format, args.num_devices,
                            gen=os.environ.get('KFAC_POD_GEN'),
                            lineage=os.environ.get('KFAC_LINEAGE'))
    # pod peer liveness (KFAC_HB_* from launch_tpu.sh/kfac-pod-supervise):
    # a dead peer aborts this trainer RC_PEER_DEAD within the heartbeat
    # deadline instead of hanging in a collective
    hb = resilience.heartbeat_from_env(log=log)
    if hb is not None:
        hb.start()

    # observability: trace recorder + metrics registry (obs/)
    from kfac_pytorch_tpu import obs
    tracer, reg = obs.setup_trainer(trace_dir=args.trace,
                                    prom_file=args.prom_file,
                                    governor=governor, tuner=tuner)

    step = training.build_train_step(model, tx, precond, loss_fn,
                                     axis_name=axis, mesh=mesh,
                                     extra_mutable=('batch_stats',),
                                     straggler=governor, heartbeat=hb,
                                     tracer=tracer, autotune=tuner)

    @jax.jit
    def eval_step(params, extra_vars, batch):
        out = model.apply({'params': params, **extra_vars},
                          batch['input'].astype(dtype), train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            out.astype(jnp.float32), batch['label']).mean()
        return loss, utils.accuracy(out, batch['label'])

    if args.speed:
        from kfac_pytorch_tpu.utils import profiling
        batch = next(train_loader.epoch())
        batch = {'input': jnp.asarray(batch['input'], dtype),
                 'label': jnp.asarray(batch['label'])}
        profiling.speed_report(
            log, step, state, batch, len(batch['label']), unit='imgs/sec',
            kw_fn=lambda i: dict(lr=lr_fn(i)), tracer=tracer,
            damping=precond.damping if precond else 0.0)
        return

    from kfac_pytorch_tpu.utils.summary import log_epoch_scalars, maybe_writer
    tb = maybe_writer(args.tb_dir)
    if tb is not None:
        reg.add_exporter(obs.metrics.TensorBoardExporter(tb))
    guard = utils.PreemptionGuard()
    monitor = utils.HealthMonitor(log, state=state, registry=reg)
    if tuner is not None:
        # numerical-health gate for the tuner: a knob probe window that
        # skipped batches or fell back to raw SGD never commits, however
        # fast it looked (the decomp_impl ladder's accuracy backstop)
        tuner.quality_gate = monitor.quality_signal
    # per-phase step timing (stats/decomp/gather/pred) for the epoch
    # lines — makes the refresh spike (and its removal under
    # --kfac-stagger) visible as step_max vs step_mean; with a tracer,
    # every step also lands as a kfac.step span
    timers = utils.PhaseTimers(tracer=tracer, registry=reg,
                               histogram=True)
    lr_now = args.base_lr
    for epoch in range(start_epoch, args.epochs):
        t0 = time.time()
        tm = utils.Metric('train_loss')
        for batch in train_loader.epoch(retry=io_retry):
            if guard.should_stop(int(state.step)):
                break
            b = {'input': jnp.asarray(batch['input'], dtype),
                 'label': jnp.asarray(batch['label'])}
            lr_now = float(lr_fn(int(state.step)))
            if watchdog is not None:
                watchdog.arm(tag=f'step {int(state.step)}')
            t_step = time.perf_counter()
            state, m = step(state, b, lr=lr_now,
                            damping=precond.damping if precond else 0.0)
            tm.update(m['loss'])
            # the update above materialized the step result: this wall
            # time covers dispatch + device execution of the whole step
            timers.record(step.last_phases, time.perf_counter() - t_step)
            if watchdog is not None:
                watchdog.disarm()
            monitor.update(m, step=int(state.step) - 1)
        if guard.should_stop():
            # preemption grace window: save the live state and exit clean.
            # Tag with the LAST completed epoch: auto-resume then replays
            # the interrupted epoch instead of skipping its tail and
            # advancing the KFAC scheduler early (at-least-once; the step
            # counter keeps the lr schedule exact). The final blocking
            # save legitimately exceeds any step deadline — keep the
            # watchdog disarmed for its whole duration.
            tag = max(epoch - 1, 0)
            import contextlib
            with (watchdog.paused() if watchdog is not None
                  else contextlib.nullcontext()):
                utils.save_checkpoint(args.checkpoint_format, tag, state,
                                      retry=io_retry)
            log.info('preempted in epoch %d (step %d): state saved as '
                     'checkpoint-%d, exiting', epoch, int(state.step), tag)
            return
        vl, va = utils.Metric('vl'), utils.Metric('va')
        for batch in val_loader.epoch():
            if guard.triggered:
                # local break only — every rank still reaches the metric
                # sync below, so no collective is stranded
                break
            b = {'input': jnp.asarray(batch['input']),
                 'label': jnp.asarray(batch['label'])}
            l, a = eval_step(state.params, state.extra_vars, b)
            vl.update(l)
            va.update(a)
        # sync() is a cross-process collective — call it on ALL ranks here
        # and reuse the values in the rank-0-only tb block below
        tl, vl_avg, va_avg = (tm.sync().avg, vl.sync().avg, va.sync().avg)
        # one registry call replaces the hand-plumbed suffix juggling —
        # byte-identical rendering (obs.metrics.Registry.epoch_suffixes)
        log.info('epoch %d: train_loss %.4f val_loss %.4f val_acc %.4f '
                 '(%.1fs)%s', epoch, tl, vl_avg, va_avg,
                 time.time() - t0, reg.epoch_suffixes())
        monitor.epoch_flush()  # reset the monitor's own epoch window
        reg.export(step=epoch)
        if tracer is not None:
            tracer.flush()
        log_epoch_scalars(tb, epoch, tl, lr_now, vl_avg, va_avg)
        if scheduler is not None:
            scheduler.step(epoch + 1)
        # async: the write hides behind the next epoch's compute
        utils.save_checkpoint(args.checkpoint_format, epoch, state,
                              block=False, retry=io_retry)
        if args.keep_checkpoints:
            # the PREVIOUS save is durable (save waits on it), so pruning
            # can never touch an in-flight write
            utils.prune_checkpoints(args.checkpoint_format,
                                    args.keep_checkpoints)
        if guard.should_stop():
            # preempted during validation: the train epoch completed, so
            # the normal checkpoint-{epoch} above is the resume point
            utils.wait_for_checkpoints()
            log.info('preempted after epoch %d: exiting', epoch)
            return
    utils.wait_for_checkpoints()
    if args.keep_checkpoints:
        utils.prune_checkpoints(args.checkpoint_format,
                                args.keep_checkpoints)
    if watchdog is not None:
        watchdog.stop()
    if hb is not None:
        hb.stop()
    if tracer is not None:
        tracer.flush()
    reg.close()


if __name__ == '__main__':
    main()
