"""CIFAR-10/100 ResNet trainer — the canonical K-FAC example.

Flag-surface parity with the reference entrypoint
(examples/pytorch_cifar10_resnet.py:44-107): same names for model, batch
size, lr schedule, K-FAC hyper-parameters (`--kfac-update-freq 0` = pure
SGD baseline, README.md:80), `--exclude-parts` phase ablation, and the
SPEED profiling mode (mean/std iteration time over ~60 steady-state
iterations, reference :39-40, 333-344). Runs on real CIFAR if
``--dir`` points at the standard archives, else deterministic synthetic
data (dataset-free container).

Usage (single chip):
  python examples/cifar10_resnet.py --model resnet32 --epochs 3
Multi-device mesh:
  python examples/cifar10_resnet.py --num-devices 8 --model resnet110
"""

import argparse
import contextlib
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

SPEED_ITERS = 60


def parse_args():
    p = argparse.ArgumentParser(description='CIFAR K-FAC trainer (TPU)')
    p.add_argument('--model', default='resnet32')
    p.add_argument('--dataset', default='cifar10',
                   choices=['cifar10', 'cifar100'])
    p.add_argument('--dir', default=None, help='dataset directory')
    p.add_argument('--batch-size', type=int, default=128)
    p.add_argument('--val-batch-size', type=int, default=128)
    p.add_argument('--epochs', type=int, default=100)
    p.add_argument('--base-lr', type=float, default=0.1)
    p.add_argument('--lr-decay', nargs='+', type=int, default=[35, 75, 90])
    p.add_argument('--warmup-epochs', type=int, default=5)
    p.add_argument('--wd', type=float, default=5e-4)
    p.add_argument('--momentum', type=float, default=0.9)
    # K-FAC (reference: pytorch_cifar10_resnet.py:75-95)
    p.add_argument('--kfac-update-freq', type=int, default=10,
                   help='0 disables K-FAC (pure SGD)')
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
    p.add_argument('--kfac-type', '--fisher-type', default='Femp',
                   choices=['Femp', 'F1mc'],
                   help='Fisher estimator: empirical-gradient (Femp) or '
                        '1-sample MC with model-sampled pseudo labels '
                        '(F1mc; reference pytorch_cifar10_resnet.py:74-75)')
    p.add_argument('--kfac-name', default='eigen_dp',
                   choices=list(kfac.KFAC_VARIANTS))
    p.add_argument('--stat-decay', type=float, default=0.95)
    p.add_argument('--damping', type=float, default=0.003)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--damping-alpha', type=float, default=0.5)
    p.add_argument('--damping-decay', nargs='+', type=int, default=None)
    p.add_argument('--kfac-update-freq-alpha', type=float, default=10)
    p.add_argument('--kfac-update-freq-decay', nargs='+', type=int,
                   default=None)
    p.add_argument('--exclude-parts', default='')
    p.add_argument('--assignment', default='round_robin',
                   choices=['round_robin', 'balanced'])
    # mesh / runtime
    p.add_argument('--num-devices', type=int, default=1)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--speed', action='store_true',
                   help='SPEED mode: time ~60 iterations and exit')
    p.add_argument('--log-dir', default='./logs')
    p.add_argument('--tb-dir', default=None,
                   help='write TensorBoard scalar summaries here (rank 0)')
    p.add_argument('--checkpoint-dir', default=None)
    p.add_argument('--keep-checkpoints', type=int, default=0,
                   help='retain only the N newest checkpoints '
                        '(0 = keep all, reference behavior)')
    # resilient runtime (kfac_pytorch_tpu/resilience/)
    p.add_argument('--resume', action='store_true',
                   help='auto-resume from the newest readable checkpoint '
                        'in --checkpoint-dir (scan-downward; what a '
                        'kfac-supervise relaunch relies on)')
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


def main():
    from kfac_pytorch_tpu.parallel import mesh as kmesh
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    kmesh.maybe_initialize_distributed()
    args = parse_args()
    num_classes = 10 if args.dataset == 'cifar10' else 100
    use_kfac = args.kfac_update_freq > 0

    from kfac_pytorch_tpu.utils.runlog import setup_run_logging
    # non-default estimator/amortization knobs go into the filename too,
    # or distinct configs are indistinguishable by name; the timestamp
    # suffix gives each run its own file (no ambiguous appends)
    log, _ = setup_run_logging(
        args.log_dir, args.dataset, args.model,
        f'kfac{args.kfac_update_freq}', args.kfac_name,
        args.kfac_type if args.kfac_type != 'Femp' else None,
        f'basis{args.kfac_basis_update_freq}'
        if args.kfac_basis_update_freq else None,
        'warm' if args.kfac_warm_start else None,
        'stagger' if args.kfac_stagger else None,
        f'bs{args.batch_size}', f'nd{args.num_devices}')
    log.info('args: %s', vars(args))

    (train_x, train_y), (val_x, val_y) = kdata.get_cifar(
        args.dir, num_classes)
    train_loader = kdata.Loader(train_x, train_y, args.batch_size,
                                train=True, augment=kdata.augment_cifar,
                                seed=args.seed)
    val_loader = kdata.Loader(val_x, val_y, args.val_batch_size, train=False)

    model = models.get_model(args.model, num_classes=num_classes)
    steps_per_epoch = train_loader.steps_per_epoch
    lr_fn = utils.warmup_multistep(
        args.base_lr, steps_per_epoch, args.warmup_epochs, args.lr_decay,
        scale=max(1, args.num_devices * args.batch_size // 128))
    tx = training.sgd(lr_fn, momentum=args.momentum, weight_decay=args.wd)

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
        scheduler = kfac.KFACParamScheduler(
            precond, damping_alpha=args.damping_alpha,
            damping_schedule=args.damping_decay,
            update_freq_alpha=args.kfac_update_freq_alpha,
            update_freq_schedule=args.kfac_update_freq_decay)

    mesh = None
    axis = None
    if args.num_devices > 1:
        mesh = Mesh(np.array(jax.devices()[:args.num_devices]), ('batch',))
        axis = 'batch'

    def loss_fn(outputs, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, batch['label']).mean()

    sample = jnp.zeros((args.batch_size, 32, 32, 3), jnp.float32)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(args.seed), sample)

    # resilient runtime: retrying I/O, auto-resume, step watchdog,
    # straggler-driven freq degradation, pod heartbeat + elastic resume
    # (kfac_pytorch_tpu/resilience/)
    from kfac_pytorch_tpu import resilience
    io_retry = (resilience.RetryPolicy(attempts=args.io_retries + 1)
                if args.io_retries > 0 else None)

    def make_old_precond(nd):
        # elastic resume: the checkpoint's world-size preconditioner
        # over the SAME layer list the current plan discovered
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
        # elastic shrink/grow hook: this trainer's loader produces the
        # GLOBAL batch (args.batch_size) regardless of mesh size, so
        # the global batch is the invariant and the linear-scaling rule
        # leaves the lr alone (lr_factor 1) — the WORLD_RESCALE line
        # records that for the churn timeline, and the schedule below
        # stays exactly the checkpoint's. A deployment feeding per-host
        # batches would pass per_host_batch= instead; a non-identity
        # result then rebuilds the schedule from the rescaled base lr.
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
    if args.resume and args.checkpoint_dir:
        restored, resume, old_world = resilience.elastic_resume(
            args.checkpoint_dir, args.epochs, precond, state,
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
                    scale=max(1, args.num_devices * args.batch_size
                              // 128))
                tx = training.sgd(lr_fn, momentum=args.momentum,
                                  weight_decay=args.wd)
            log.info('resumed from checkpoint-%d (step %d)', resume,
                     int(state.step))
    # pod peer liveness: configured by launch_tpu.sh / kfac-pod-supervise
    # via KFAC_HB_* env; a dead peer aborts this trainer RC_PEER_DEAD
    # within the heartbeat deadline instead of hanging in a collective
    hb = resilience.heartbeat_from_env(log=log)
    if hb is not None:
        hb.start()
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

    # observability: trace recorder (per-step spans + resilience
    # instants, flushed on the runlog SIGTERM/atexit chain) and the
    # metrics registry that renders the epoch-line suffixes and feeds
    # the exporters (obs/)
    from kfac_pytorch_tpu import obs
    tracer, reg = obs.setup_trainer(trace_dir=args.trace,
                                    prom_file=args.prom_file,
                                    governor=governor, tuner=tuner)

    step = training.build_train_step(model, tx, precond, loss_fn,
                                     axis_name=axis, mesh=mesh,
                                     extra_mutable=('batch_stats',),
                                     fisher_type=args.kfac_type,
                                     fisher_seed=args.seed,
                                     straggler=governor, heartbeat=hb,
                                     tracer=tracer, autotune=tuner)

    @jax.jit
    def eval_step(params, extra_vars, batch):
        out = model.apply({'params': params, **extra_vars}, batch['input'],
                          train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            out, batch['label']).mean()
        acc = utils.accuracy(out, batch['label'])
        return loss, acc

    if args.speed:
        from kfac_pytorch_tpu.utils import profiling
        batch = next(train_loader.epoch())
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        profiling.speed_report(
            log, step, state, batch, len(batch['label']), unit='imgs/sec',
            iters=SPEED_ITERS, kw_fn=lambda i: dict(lr=lr_fn(i)),
            tracer=tracer, damping=precond.damping if precond else 0.0)
        return

    from kfac_pytorch_tpu.utils.summary import log_epoch_scalars, maybe_writer
    tb = maybe_writer(args.tb_dir)
    if tb is not None:
        # the registry's scalars land in the same event files the loss/
        # lr scalars already use (one TensorBoard run per trainer run)
        reg.add_exporter(obs.metrics.TensorBoardExporter(tb))
    guard = utils.PreemptionGuard()
    # health-guard event log: skipped batches / ladder escalations surface
    # as WARNINGs at the step they happen, plus a per-epoch summary suffix
    # (published through the registry)
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
    if args.checkpoint_dir:
        # world-size stamp: lets a shrunken (or re-grown) pod's relaunch
        # route this run's checkpoints through the factor reshard
        # (elastic_resume); the generation rides along as provenance,
        # the lineage epoch as commit fencing (the stamp never moves
        # backward — a fenced fork's straggler cannot clobber it)
        utils.write_world_stamp(args.checkpoint_dir, args.num_devices,
                                gen=os.environ.get('KFAC_POD_GEN'),
                                lineage=os.environ.get('KFAC_LINEAGE'))
    lr_now = args.base_lr
    for epoch in range(start_epoch, args.epochs):
        train_loss = utils.Metric('train_loss')
        t0 = time.time()
        for batch in train_loader.epoch(retry=io_retry):
            if guard.should_stop(int(state.step)):
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            lr_now = float(lr_fn(int(state.step)))
            if watchdog is not None:
                watchdog.arm(tag=f'step {int(state.step)}')
            t_step = time.perf_counter()
            state, m = step(state, batch, lr=lr_now,
                            damping=precond.damping if precond else 0.0)
            train_loss.update(m['loss'], len(batch['label']))
            # the update above materialized the step result: this wall
            # time covers dispatch + device execution of the whole step
            timers.record(step.last_phases, time.perf_counter() - t_step)
            if watchdog is not None:
                # the float() above materialized the step result: the
                # blocking window the deadline covers is over
                watchdog.disarm()
            monitor.update(m, step=int(state.step) - 1)
        if guard.should_stop():
            # preemption grace window: save the live state and exit clean.
            # The epoch is incomplete — tag the checkpoint with the LAST
            # completed epoch so a resume replays the interrupted one
            # (at-least-once; the step counter keeps the lr schedule exact).
            # The final blocking save legitimately exceeds any step
            # deadline: keep the watchdog disarmed for its whole duration.
            tag = max(epoch - 1, 0)
            with (watchdog.paused() if watchdog is not None
                  else contextlib.nullcontext()):
                if args.checkpoint_dir:
                    utils.save_checkpoint(args.checkpoint_dir, tag, state,
                                          retry=io_retry)
                    log.info('preempted in epoch %d (step %d): state saved '
                             'as checkpoint-%d, exiting', epoch,
                             int(state.step), tag)
                else:
                    log.info('preempted in epoch %d (step %d): no '
                             '--checkpoint-dir configured, state lost',
                             epoch, int(state.step))
            return
        val_loss = utils.Metric('val_loss')
        val_acc = utils.Metric('val_acc')
        for batch in val_loader.epoch():
            if guard.triggered:
                # local break only — every rank still reaches the metric
                # sync below, so no collective is stranded
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            l, a = eval_step(state.params, state.extra_vars, batch)
            val_loss.update(l, len(batch['label']))
            val_acc.update(a, len(batch['label']))
        # sync() is a cross-process collective — call it on ALL ranks here
        # and reuse the values in the rank-0-only tb block below
        tl, vl_avg, va_avg = (train_loss.sync().avg, val_loss.sync().avg,
                              val_acc.sync().avg)
        # one registry call replaces the old hand-plumbed health /
        # resilience / kfac_phase suffix juggling — byte-identical
        # rendering (obs.metrics.Registry.epoch_suffixes, pinned by
        # tests/test_obs.py)
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
        if args.checkpoint_dir:
            # async: the write hides behind the next epoch's compute
            utils.save_checkpoint(args.checkpoint_dir, epoch, state,
                                  block=False, retry=io_retry)
            if args.keep_checkpoints:
                # the PREVIOUS save is durable (save waits on it first)
                utils.prune_checkpoints(args.checkpoint_dir,
                                        args.keep_checkpoints)
        if guard.should_stop():
            # preempted during validation: the train epoch completed, so
            # the checkpoint above (if configured) is the resume point
            utils.wait_for_checkpoints()
            log.info('preempted after epoch %d: exiting', epoch)
            return
    utils.wait_for_checkpoints()
    if args.checkpoint_dir and args.keep_checkpoints:
        utils.prune_checkpoints(args.checkpoint_dir, args.keep_checkpoints)
    if watchdog is not None:
        watchdog.stop()
    if hb is not None:
        hb.stop()
    if tracer is not None:
        tracer.flush()
    reg.close()


if __name__ == '__main__':
    main()
