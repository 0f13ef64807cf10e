"""Training-loop integration: the canonical K-FAC + SGD step.

The reference hot loop (examples/pytorch_cifar10_resnet.py:292-327) is

    zero_grad -> forward (hooks save a) -> backward (hooks save g)
    -> optimizer.synchronize (grad allreduce) -> preconditioner.step()
    -> optimizer.step()

Here the whole iteration is ONE jitted function per (update_factors,
update_inverse) combination — the steps-%-freq gating picks a compiled
variant on the host, so non-update steps never pay capture or
decomposition cost (the hook-gating semantics of
kfac_preconditioner_base.py:122-130 at zero runtime price). Under a mesh
the step runs inside shard_map: forward/backward on the local batch shard,
param grads psummed by autodiff (the gradient allreduce), K-FAC engine
collectives over the same axis.
"""

import contextlib
import functools
from typing import Any, Callable, NamedTuple, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_pytorch_tpu import capture, faults
from kfac_pytorch_tpu import health as health_lib
from kfac_pytorch_tpu.obs import trace as obs_trace
from kfac_pytorch_tpu.parallel import collectives as coll
from kfac_pytorch_tpu.preconditioner import KFACHyperParams


class TrainState(flax.struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    kfac_state: Any
    extra_vars: Any  # batch_stats etc. (non-param collections)
    # numerical-health counters (health.HealthState) — None when the
    # guard is disabled; defaulted so pre-health TrainState constructions
    # (and checkpoints) keep working unchanged
    health: Any = None


def sgd(lr_schedule, momentum=0.9, weight_decay=0.0, nesterov=False):
    """torch.optim.SGD-equivalent optax chain (reference harness optimizer,
    examples/pytorch_cifar10_resnet.py:222-229): grad + wd*param, then
    momentum buffer, then lr scaling. K-FAC preconditioning happens before
    this chain, matching preconditioner.step() -> optimizer.step()."""
    parts = []
    if weight_decay:
        parts.append(optax.add_decayed_weights(weight_decay))
    parts.append(optax.trace(decay=momentum, nesterov=nesterov))
    parts.append(optax.scale_by_learning_rate(lr_schedule))
    return optax.chain(*parts)


class WorldRescale(NamedTuple):
    """What the batch geometry and learning rate become after an
    elastic world change (:func:`world_change_rescale`)."""
    old_world: int
    new_world: int
    global_batch: int          # achieved global batch AFTER the change
    per_host_batch: int        # achieved per-host batch AFTER the change
    lr: float                  # rescaled learning rate
    lr_factor: float           # lr multiplier actually applied

    def log_line(self):
        """The machine-greppable trainer protocol line
        (``incident.EVENT_PATTERNS`` 'world_rescale'): emit it verbatim
        so the churn timeline can show what the hyper-parameters became
        on each shrink/grow."""
        return (f'WORLD_RESCALE from_world={self.old_world} '
                f'to_world={self.new_world} '
                f'global_batch={self.global_batch} '
                f'lr={self.lr:g} lr_factor={self.lr_factor:g}')


def world_change_rescale(old_world, new_world, *, lr,
                         global_batch=None, per_host_batch=None,
                         lr_scaling='linear'):
    """Batch-size / learning-rate hook for an elastic shrink or grow:
    liveness is the supervisor's job, this keeps the ACCURACY contract
    across the world change.

    Exactly one of ``global_batch`` / ``per_host_batch`` names the
    deployment's batch invariant:

    - ``global_batch``: the GLOBAL batch is fixed (single-process
      trainers whose loader already produces the full batch; pods that
      re-split a fixed token budget). The per-host share re-derives as
      ``ceil(global / new_world)`` and the optimization trajectory is
      unchanged, so ``lr_factor`` is exactly 1 — the hook's job is to
      RECORD that nothing needed rescaling.
    - ``per_host_batch``: the PER-HOST batch is fixed (the common pod
      shape — each host feeds its local batch and the global batch IS
      ``per_host * world``). The global batch scales with the world, and
      the lr follows it under ``lr_scaling``: ``'linear'`` (Goyal et
      al. — the rule the reference's warmup_multistep scale already
      applies at launch time), ``'sqrt'``, or ``'none'`` (record only).

    Returns a :class:`WorldRescale`; trainers log ``result.log_line()``
    (the ``world_rescale`` event form) and apply ``result.lr`` /
    ``result.per_host_batch``. Typically wired through
    ``resilience.elastic_resume(on_world_change=...)`` so the hook
    fires exactly when a cross-world transport happened.
    """
    old_world, new_world = int(old_world), int(new_world)
    if old_world < 1 or new_world < 1:
        raise ValueError('world sizes must be >= 1, got '
                         f'{old_world} -> {new_world}')
    if (global_batch is None) == (per_host_batch is None):
        raise ValueError('pass exactly one of global_batch / '
                         'per_host_batch (the batch invariant)')
    if lr_scaling not in ('linear', 'sqrt', 'none'):
        raise ValueError(f'lr_scaling must be linear/sqrt/none, '
                         f'got {lr_scaling!r}')
    if global_batch is not None:
        global_batch = int(global_batch)
        per_host = max(1, -(-global_batch // new_world))  # ceil div
        factor = 1.0
        new_global = global_batch
    else:
        per_host = int(per_host_batch)
        old_global = per_host * old_world
        new_global = per_host * new_world
        ratio = new_global / old_global
        factor = {'linear': ratio, 'sqrt': float(np.sqrt(ratio)),
                  'none': 1.0}[lr_scaling]
    return WorldRescale(old_world=old_world, new_world=new_world,
                        global_batch=new_global, per_host_batch=per_host,
                        lr=float(lr) * factor, lr_factor=factor)


def _warm_basis_gate(precond, seen, step, ui, ub):
    """Host-side warm/cold decision for a full decomposition, mutating
    the run's ``seen`` record: warm only once a prior full exists (the
    stored basis must be orthogonal, not zeros), and every
    ``cold_restart_every``-th full goes cold to reset the orthogonality
    error the chained basis ``Q <- Q @ V'`` accumulates. An explicit
    iterative ``decomp_impl`` (``precond.warm_impl``) warms through the
    same gate — the tuner's ladder rung needs no separate
    ``warm_start_basis`` opt-in."""
    streak = seen.get('warm_streak', 0)
    warm = ((getattr(precond, 'warm_start_basis', False)
             or getattr(precond, 'warm_impl', False))
            and 'last_full' in seen
            and streak < getattr(precond, 'cold_restart_every', 50))
    if ui and ub:
        seen['last_full'] = step
        seen['warm_streak'] = streak + 1 if warm else 0
    return warm


def _host_scalar(value):
    """A step's ``lr`` / ``damping`` as the jitted call takes it: a
    ``jax.Array`` (a schedule computed on the device) as it is, never
    pulled back to the host; anything else as a NumPy float32 scalar,
    whose abstract value is ``jnp.float32()``'s (one trace a variant) and
    whose transfer rides the call: ``jnp.float32()`` of a Python float is
    a ``convert_element_type`` program of its own in front of every
    dispatch."""
    return value if isinstance(value, jax.Array) else np.float32(value)


def build_train_step(model, tx, precond, loss_fn, axis_name=None, mesh=None,
                     extra_mutable=(), sync_extra_vars=True, donate=True,
                     dropout_seed=None, batch_specs=None, check_vma=None,
                     fisher_type='Femp', fisher_loss_fn=None,
                     fisher_sample_fn=None, fisher_seed=0, health='auto',
                     straggler=None, heartbeat=None, tracer=None,
                     autotune=None):
    """Build the per-iteration function family.

    Args:
      model: Flax module built from kfac_pytorch_tpu.nn layers.
      tx: optax transformation (e.g. ``sgd(...)``).
      precond: a set-up ``KFAC`` instance, or None for the pure-SGD baseline
        (the ``kfac=0`` convention, reference README.md:80).
      loss_fn: ``loss_fn(outputs, batch) -> scalar``, and it MUST be the
        LOCAL-mean loss: the mean over this shard's examples only.
        Under data parallelism do NOT psum/pmean-normalize the loss
        inside ``loss_fn`` — the step averages the GRADIENTS across the
        K-FAC world itself (``parallel.average_grads``) and pmeans the
        reported loss metric separately. Why it matters: the capture
        backward's cotangents feed the K-FAC G factors, whose scaling
        assumes local-mean cotangents; a globally-normalized loss
        multiplies every G by the shard count, so the preconditioner
        (and anything tuned against it — lr, damping) silently changes
        with the mesh shape. This exact mistake cost round 3 a day of
        debugging (scripts/repro_mpd_eigen_orthogonal_axis.py); a free
        trace-time guard (``capture.check_local_mean_loss``) now rejects
        it — unless ``check_vma=False``, which disables both the guard
        AND the cross-axis cotangent psums capture relies on (see README
        "Loss conventions").
      axis_name/mesh: data-parallel axis; None for single device.
      extra_mutable: extra mutable collections (e.g. ('batch_stats',)).
      sync_extra_vars: pmean mutated collections across the axis so
        replicated state stays replicated (BN running stats).
      batch_specs: shard_map PartitionSpec (or pytree of specs) for the
        batch; default ``P(axis_name)`` (data-parallel on axis 0). Pass
        e.g. ``P(None, 'seq')`` for sequence-parallel token streams.
      check_vma: shard_map varying-manual-axes checking. Default (None)
        enables it except when the environment routes attention through
        the Pallas interpreter (test-only; its block-index machinery
        rejects vma-tagged scalar-prefetch args). Pass an explicit bool
        when selecting ``block_impl='pallas_interpret'`` per-call instead
        of via KFAC_ATTN_IMPL.
      fisher_type: 'Femp' (default) estimates the Fisher from the
        empirical-gradient backward; 'F1mc' is the true-Fisher 1-sample MC
        estimator — on factor-update steps a second capture backward runs
        against labels sampled from the model's own predictive
        distribution, and its (a, g) feed the factors while the parameter
        update still uses the real-loss gradients. The reference declares
        this choice (examples/utils.py:82-90 generate_pseudo_labels) but
        never wires it into a trainer; here it is first-class. Both
        backwards live in one compiled program (XLA CSEs the shared
        forward), so the extra cost lands only on fac_update_freq steps.
      fisher_loss_fn: F1mc sampling loss ``(outputs, pseudo_labels) ->
        scalar`` (local mean). Default: softmax cross-entropy over the
        last axis, which covers classifiers and LM token heads.
      fisher_sample_fn: F1mc label sampler ``(rng, outputs) ->
        pseudo_labels``; must draw from the predictive distribution
        implied by ``fisher_loss_fn`` (override BOTH together — e.g. a
        Gaussian head needs a Gaussian sampler, not the default
        categorical). Default: ``utils.losses.sample_pseudo_labels``.
      fisher_seed: base seed for the pseudo-label sampler (folded with the
        step counter and, under data parallelism, the device index).
      health: the in-jit numerical-health guard (health.py). 'auto'
        (default) inherits the preconditioner's ``health`` config (off
        for the pure-SGD baseline); True/False/HealthConfig override it
        explicitly — pass ``health=True`` to give a precond-less SGD run
        the bad-batch skip too. When enabled, the step screens the loss,
        gradients and captured factor statistics for NaN/Inf INSIDE the
        jitted program: a bad batch skips the optimizer AND factor-EMA
        updates via ``lax.cond`` (params/opt_state/m_A/m_G stay bit-
        identical to a schedule that never contained the batch), repeated
        failures climb a damping-escalation ladder and finally degrade
        the step to plain SGD until recovery (see health.HealthConfig).
        Metrics gain ``health/*`` counters (utils.metrics.HealthMonitor
        consumes them). The guard adds no compiled step variants and no
        per-step host sync: the skip decision is a replicated on-device
        scalar (one extra psum under a mesh).
      straggler: a ``resilience.StragglerGovernor`` (or None). When set,
        every host step ticks the governor with the inter-arrival time
        of step_fn calls — which includes the caller's blocking metric
        read and next-batch assembly, i.e. the true host step — and a
        sustained over-budget EMA stretches the preconditioner's
        ``fac_update_freq``/``kfac_update_freq`` through the same
        host-side freq gating the scheduler uses (restored on
        recovery): a slow host degrades preconditioner freshness
        instead of throughput.
      heartbeat: a ``resilience.PeerHeartbeat`` (or None). When set,
        every host step calls ``heartbeat.tick(step)`` — stamping the
        current step into the published liveness payload (so a peer's
        incident report can say how far the dead host got) and arming
        the silent-death chaos drill (``KFAC_FAULT_HB_STOP_STEP``).
        Liveness itself rides the heartbeat's own background thread,
        not this tick: a trainer wedged in a collective stops ticking
        but keeps beating, which is exactly the split the pod needs —
        the heartbeat answers "alive?", the watchdog answers
        "progressing?".
      autotune: an ``autotune.KnobController`` (or None). When set,
        every host step ticks the controller with the inter-arrival
        time of step_fn calls (the same full-host-step measurement the
        straggler governor uses) attributed to the PREVIOUS dispatch's
        phase set — the closed loop's measurement feed. The
        controller's knob changes flow through the preconditioner's
        single arbiter; a frequency change reuses this step_fn's
        compiled variant cache, a ``comm_precision`` change clears it
        (the arbiter invalidator registered below) so no stale program
        can keep the old wire dtype.
      tracer: an ``obs.trace.TraceRecorder`` (or None). With or without
        it, step_fn writes its host spans into the profiler's own trace
        (``obs.trace.annotation``: ``kfac.step`` and its children
        ``.read_step`` (only in a call that reads the counter from the
        device, below) / ``.hooks`` / ``.select`` / ``.build/<variant>`` /
        ``.dispatch/<phases>``; an inactive check each when no
        ``jax.profiler`` session is open). When a recorder is given, the
        dispatch span has two sinks from its one call: the profiler's
        trace, and the recorder under its own name ``kfac.dispatch``
        with the step index and the dispatched phase set in the
        exclude-parts ledger taxonomy as args. That span covers dispatch
        only (the call returns before the device finishes under async
        dispatch); the full host-side step span — including the
        blocking metric read — is ``PhaseTimers(tracer=...)``'s
        ``kfac.step``, so a recorder's file shows both how long the host
        spent submitting and how long the step really took. The
        ``kfac.sched`` spans of a prefetched gather draw a schedule and
        stay the recorder's alone.

    Returns ``step_fn(state, batch, lr, damping) -> (state, metrics)``;
    dispatches between up to four compiled variants using the
    preconditioner's host-side update frequencies.

    ``step_fn`` never waits on the device: between the caller's call and
    the step's dispatch it reads nothing back and launches no program of
    its own, so the host runs ahead and step k+1 is queued while step k
    runs. The step counter that picks the variant lives on the host: hand
    back the state ``step_fn`` returned (other fields may be replaced) and
    the counter is the last one + 1, every branch of the step adding
    exactly 1. Any other state (the first call, a restored checkpoint, a
    fresh ``init_train_state``, ``state.replace(step=...)``, the same
    state passed twice) is told by its ``step`` array not being the one
    last returned and costs one ``int(state.step)``, which waits for the
    step that made it; ``step_fn.step_reads`` counts those reads (1 in an
    uninterrupted run). ``lr`` / ``damping`` go in with the jitted call
    as NumPy float32 scalars; a ``jax.Array`` (a schedule computed on the
    device) is passed through as it is and never pulled to the host. With a
    ``KFAC(stagger=True)`` preconditioner, the first inverse update is
    still one full decomposition; afterwards every step dispatches the
    staggered variant (traced cohort index — the variant count does not
    grow with ``kfac_update_freq``), and the dispatch rebases the cohort
    layout whenever the scheduler or straggler governor rescaled the
    frequency. ``step_fn.last_phases`` names the K-FAC phases the last
    dispatch ran ('pred'/'stats'/'decomp'/'gather') for
    ``utils.metrics.PhaseTimers``.
    """
    if fisher_type not in ('Femp', 'F1mc'):
        raise ValueError(f'fisher_type must be Femp or F1mc, '
                         f'got {fisher_type!r}')
    if (axis_name is None
            and getattr(precond, 'mesh_axes', None) is not None):
        # mesh-planned preconditioner: the K-FAC world derives from the
        # mesh spec's data axes — inherit it so callers name the mesh
        # in exactly one place (KFAC(mesh_axes=...))
        axis_name = precond.axis_name
    if health == 'auto':
        health_cfg = getattr(precond, 'health', None)
    else:
        health_cfg = health_lib.resolve(health)
    # deterministic chaos faults (faults.py): the env snapshot happens
    # once, here, so the traced fault steps are static — enabling a fault
    # never changes the compiled-variant count or adds host syncs
    fault_cfg = faults.from_env()
    if fisher_loss_fn is None:
        def fisher_loss_fn(outputs, pseudo_labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                outputs, pseudo_labels).mean()
    if fisher_sample_fn is None:
        from kfac_pytorch_tpu.utils.losses import sample_pseudo_labels
        fisher_sample_fn = sample_pseudo_labels

    def one_step(state, batch, hyper, update_factors, update_inverse,
                 update_basis=True, warm_basis=False, factors_only=False,
                 stagger_update=False, prefetch=False):
        x = batch['input']
        variables = {'params': state.params, **state.extra_vars}
        use_capture = precond is not None and update_factors
        rngs = None
        if dropout_seed is not None:
            key = jax.random.fold_in(jax.random.PRNGKey(dropout_seed),
                                     state.step)
            if axis_name is not None:
                # per-device dropout masks (DistributedSampler-style
                # decorrelation of the local batches)
                key = jax.random.fold_in(key, coll.axis_index(axis_name))
            rngs = {'dropout': key}

        # device scopes for what runs outside the engine's kfac.* ones
        # (train., NOT kfac.: a trace reader takes "outside kfac." for
        # the model's and optimizer's time). Inside train.grad JAX's own
        # path tells the passes apart: a backward operation carries
        # transpose(jvp(, a forward one jvp( without it
        if use_capture:
            with jax.named_scope('train.grad'):
                loss, out, grads, acts, gs, mutated = \
                    capture.value_and_grad_with_capture(
                        model, lambda o: loss_fn(o, batch), variables, x,
                        mutable=extra_mutable, axis_name=axis_name,
                        rngs=rngs)
            # trace-time convention guard (free): the capture loss must
            # be the LOCAL mean, or every G factor scales with the
            # shard count (the round-3 postmortem bug)
            capture.check_local_mean_loss(loss, batch, axis_name)
            if fisher_type == 'F1mc':
                # true-Fisher MC estimate: re-capture (a, g) from a backward
                # against labels sampled from the model's own distribution;
                # the parameter update keeps the real-loss grads above.
                # 0xF15C domain tag keeps this stream distinct from the
                # dropout stream even when dropout_seed == fisher_seed.
                with jax.named_scope('train.grad.fisher'):
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(fisher_seed), 0xF15C)
                    key = jax.random.fold_in(key, state.step)
                    if axis_name is not None:
                        key = jax.random.fold_in(
                            key, coll.axis_index(axis_name))
                    pseudo = fisher_sample_fn(key,
                                              jax.lax.stop_gradient(out))
                    floss, _, _, acts, gs, _ = \
                        capture.value_and_grad_with_capture(
                            model, lambda o: fisher_loss_fn(o, pseudo),
                            variables, x, mutable=extra_mutable,
                            axis_name=axis_name, rngs=rngs)
                capture.check_local_mean_loss(floss, pseudo, axis_name)
        else:
            def plain_loss(params):
                out, mutated = model.apply(
                    {'params': params, **state.extra_vars}, x,
                    mutable=list(extra_mutable), rngs=rngs)
                return loss_fn(out, batch), (out, mutated)

            with jax.named_scope('train.grad'):
                (loss, (out, mutated)), grads = jax.value_and_grad(
                    plain_loss, has_aux=True)(state.params)
            acts = gs = None
            # same convention on the SGD path: average_grads below
            # divides the psummed grads by world size, so a pre-pmean'd
            # loss would double-normalize the update
            capture.check_local_mean_loss(loss, batch, axis_name)

        # chaos faults fire BEFORE the health screen — the screen is what
        # is being drilled (pass-through unless env-configured)
        grads = faults.corrupt_grads(fault_cfg, state.step, grads)
        acts, gs = faults.corrupt_captured(fault_cfg, state.step, acts, gs)

        loss_local = loss
        with jax.named_scope('train.grad_reduce'):
            grads = coll.average_grads(grads, axis_name)
            loss = coll.pmean(loss, axis_name)

        # the batch's factor statistics are made here, before the guard's
        # cond: the screen reads their flags (the statistics are Gram
        # products, engine.stats_finite) in place of every captured tensor
        stats = None
        if use_capture and health_cfg is not None:
            stats = precond.layer_stats(acts, gs)

        # a plan with buckets too large for the cond (KFAC.hoists_update)
        # has its factor and inverse updates run before it, committed by
        # the batch screen's own flag; the branches then start from that
        # state and the true one only preconditions
        hoisted = {}

        def apply_update(hstate):
            """The normal K-FAC + optimizer update (the only path when
            the health guard is off; the lax.cond true-branch otherwise).
            """
            kfac_state = hoisted.get('state', state.kfac_state)
            new_grads = grads
            precond_ok = jnp.ones((), bool)
            if precond is not None:
                h = hyper
                if health_cfg is not None:
                    # damping-escalation ladder: rung r multiplies the
                    # damping fed to decomposition + preconditioning
                    h = hyper.replace(damping=health_lib.effective_damping(
                        hstate, hyper.damping, health_cfg))
                pgrads, kfac_state = precond.step(
                    kfac_state, grads, acts, gs, hyper=h,
                    update_factors=update_factors and not hoisted,
                    update_inverse=update_inverse and not hoisted,
                    update_basis=update_basis,
                    warm_basis=warm_basis, factors_only=factors_only,
                    stagger_update=stagger_update, prefetch=prefetch,
                    axis_name=axis_name, stats=stats)
                if health_cfg is None:
                    new_grads = pgrads
                else:
                    # a non-finite preconditioner output (or the ladder's
                    # top rung) degrades THIS step to raw SGD gradients;
                    # factor statistics above still accumulated
                    with jax.named_scope('train.health_screen'):
                        precond_ok = capture.all_finite(pgrads)
                        use_precond = jnp.logical_and(
                            precond_ok,
                            jnp.logical_not(
                                health_lib.degraded(hstate, health_cfg)))
                        new_grads = jax.tree.map(
                            lambda p, r: jnp.where(use_precond, p, r),
                            pgrads, grads)

            with jax.named_scope('train.optimizer'):
                updates, opt_state = tx.update(new_grads, state.opt_state,
                                               state.params)
                params = optax.apply_updates(state.params, updates)

                extra_vars = dict(state.extra_vars)
                for k in extra_mutable:
                    if k in mutated:
                        v = mutated[k]
                        if sync_extra_vars:
                            v = coll.pmean(v, axis_name)
                        extra_vars[k] = v

            if health_cfg is not None:
                with jax.named_scope('train.health_screen'):
                    hstate = health_lib.on_good_batch(hstate, health_cfg,
                                                      precond_ok)
            return state.replace(step=state.step + 1, params=params,
                                 opt_state=opt_state,
                                 kfac_state=kfac_state,
                                 extra_vars=extra_vars, health=hstate)

        if health_cfg is None:
            new_state = apply_update(state.health)
            return new_state, {'loss': loss, **capture.counter_metrics(
                new_state.extra_vars)}

        def skip_update(hstate):
            """Bad batch: params, opt_state, factor EMAs and extra_vars
            stay bit-exactly as if the batch never happened; only the
            step counters and health counters advance."""
            kfac_state = hoisted.get('state', state.kfac_state)
            if kfac_state is not None:
                # keep KFACState.step in lockstep with TrainState.step so
                # in-engine fault steps stay aligned with trainer steps
                kfac_state = kfac_state.replace(step=kfac_state.step + 1)
            with jax.named_scope('train.health_screen'):
                hstate = health_lib.on_bad_batch(hstate, health_cfg)
            return state.replace(step=state.step + 1,
                                 kfac_state=kfac_state, health=hstate)

        # one replicated scalar decides the branch — no host sync, and
        # every device agrees (batch_ok psums the per-shard bad flags)
        with jax.named_scope('train.health_screen'):
            if stats is not None:
                ok = health_lib.batch_ok(axis_name, grads, loss_local,
                                         flags=(stats.ok_a, stats.ok_g))
            else:
                # no statistics to read (no capture this step, or a
                # capture path that never materialises them)
                ok = health_lib.batch_ok(axis_name, grads, loss_local,
                                         acts, gs)
        if (precond is not None and precond.hoists_update
                and (update_factors or update_inverse)
                and not (factors_only or stagger_update or prefetch)):
            _, hoisted['state'] = precond.step(
                state.kfac_state, None, acts, gs,
                hyper=hyper.replace(damping=health_lib.effective_damping(
                    state.health, hyper.damping, health_cfg)),
                update_factors=update_factors,
                update_inverse=update_inverse, update_basis=update_basis,
                warm_basis=warm_basis, axis_name=axis_name,
                update_only=True, commit=ok, stats=stats)
        new_state = jax.lax.cond(ok, apply_update, skip_update,
                                 state.health)
        # what the model counts (capture.COUNTERS), as the new state has
        # it: a refused batch leaves a cumulative counter where it was
        mets = {'loss': loss,
                **capture.counter_metrics(new_state.extra_vars)}
        with jax.named_scope('train.health_screen'):
            mets.update({'health/' + k: v for k, v in
                         health_lib.metrics(new_state.health, ok).items()})
        return new_state, mets

    state_specs_cache = {}

    def variant_phases(update_factors, update_inverse, factors_only=False,
                       stagger_update=False, **_):
        """The K-FAC phases a variant (``make_variant``'s arguments) runs
        ('pred'/'stats'/'decomp'/'gather'): what ``step_fn.last_phases``
        reports, what the dispatch span carries and what the step
        program is named after."""
        if precond is None:
            return ()
        if factors_only:
            return ('stats',) if update_factors else ()
        ph = ['pred']
        if update_factors:
            ph.append('stats')
        if update_inverse or stagger_update:
            ph.append('decomp')
            if precond.comm_mode == 'inverse':
                ph.append('gather')
        return tuple(ph)

    def variant_name(update_factors, update_inverse, update_basis=True,
                     warm_basis=False, factors_only=False,
                     stagger_update=False, prefetch=False):
        """The step program's name (the device's ``XLA Modules`` line
        shows ``jit_<name>``): ``sgd_step`` without a preconditioner,
        else ``kfac_step_<phases>`` plus whatever the phase set does not
        say (an eigenvalue-only ``refresh``, a ``warm`` basis, the
        ``stagger`` cohort step, a ``prefetch``-ed gather)."""
        if precond is None:
            return 'sgd_step'
        phases = variant_phases(update_factors, update_inverse,
                                factors_only, stagger_update)
        tags = [t for t, on in (
            ('refresh', update_inverse and not update_basis),
            ('warm', warm_basis), ('stagger', stagger_update),
            ('prefetch', prefetch)) if on]
        return '_'.join(['kfac_step', *(phases or ('none',)), *tags])

    def make_variant(update_factors, update_inverse, update_basis=True,
                     warm_basis=False, factors_only=False,
                     stagger_update=False, prefetch=False):
        static = dict(update_factors=update_factors,
                      update_inverse=update_inverse,
                      update_basis=update_basis, warm_basis=warm_basis,
                      factors_only=factors_only,
                      stagger_update=stagger_update, prefetch=prefetch)

        def fn(state, batch, hyper):
            return one_step(state, batch, hyper, **static)

        if axis_name is not None:
            sspecs = _state_specs(precond, axis_name)
            bspecs = P(axis_name) if batch_specs is None else batch_specs
            vma = (not _interpreted_kernels(precond) if check_vma is None
                   else check_vma)
            fn = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(sspecs, bspecs, P()),
                out_specs=(sspecs, P()),
                check_vma=vma)
        # jit names the program after the function: a stable name per
        # variant, so a trace tells the step programs apart
        fn.__name__ = fn.__qualname__ = variant_name(**static)
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    variants = {}
    seen_inverse = {}  # host-side: does a decomposition exist yet?
    # host-side step counter: the ``step`` array of the state last
    # returned and the value it will hold (the array object is kept so
    # that no other can take its identity; donated, it holds no memory)
    counter = {'array': None, 'value': 0}
    annotation = obs_trace.annotation

    def step_fn(state, batch, lr=None, damping=None):
        # the step's host spans go into the profiler's own trace
        # (obs.trace.annotation), one per part of this function, so every
        # gap between device operations can be put to what the host was
        # doing in it; with no profiler session each is an inactive check
        with contextlib.ExitStack() as spans:
            spans.enter_context(annotation('kfac.step'))
            if state.step is counter['array']:
                # the caller handed back the state this function returned
                # (with or without other fields replaced): every branch of
                # one_step adds exactly 1, so the host knows the counter
                # and the previous step need not have finished
                step = counter['value']
            else:
                # any other state (first call, a restored or rebuilt one,
                # state.replace(step=...), the same state twice): read it,
                # which waits for whatever step made it
                with annotation('kfac.step.read_step'):
                    step = int(state.step)
                step_fn.step_reads += 1
            with annotation('kfac.step.hooks'):
                # straggler governor: measure the inter-arrival of host
                # steps (tick BEFORE the fault hooks so an injected slow
                # step lands in the NEXT tick's interval, like any real
                # stall would)
                if straggler is not None:
                    straggler.tick(step)
                if autotune is not None:
                    # the interval that just ended covered the PREVIOUS
                    # dispatch's phase set — attribute it there, like the
                    # PhaseTimers wall-time bucketing
                    autotune.tick(step, step_fn.last_phases)
                if heartbeat is not None:
                    heartbeat.tick(step)
                # host-side chaos drills (all no-ops unless
                # env-configured): SIGTERM (PreemptionGuard), crash
                # (supervisor restart), hang (step watchdog), slow
                # (straggler governor)
                faults.maybe_sigterm(fault_cfg, step)
                faults.maybe_crash(fault_cfg, step)
                faults.maybe_hang(fault_cfg, step)
                faults.maybe_slow(fault_cfg, step,
                                  sleep=(straggler.sleep
                                         if straggler is not None
                                         else None))
                if (precond is not None
                        and getattr(precond, 'pending_replan', None)):
                    # a queued live replan (the arbiter's applied
                    # comm_mode switch, or a direct request_replan):
                    # apply it HERE — the between-steps boundary where no
                    # traced program is running — before anything below
                    # reads the preconditioner's config or retraces
                    # against the (already-invalidated) variant cache. A
                    # pure comm-mode switch carries the state verbatim; a
                    # layout change transports it host-side.
                    state = state.replace(
                        kfac_state=precond.apply_pending_replan(
                            state.kfac_state))
                if health_cfg is not None and state.health is None:
                    # one-time upgrade of a pre-health TrainState (old
                    # checkpoint or a hand-built state): done host-side
                    # BEFORE the jitted call so every variant only ever
                    # sees one state structure
                    state = state.replace(
                        health=health_lib.HealthState.init())
                if (precond is not None and state.kfac_state is not None
                        and getattr(precond, '_tracks_comm_err', False)
                        and state.kfac_state.comm_err is None):
                    # same one-time upgrade for the EF residual: a
                    # checkpoint taken before comm_precision was enabled
                    # (or at fp32) carries no residual — seed zeros
                    # host-side so every variant sees one state structure
                    state = state.replace(
                        kfac_state=state.kfac_state.replace(
                            comm_err=precond._zero_comm_err()))
                if (precond is not None and state.kfac_state is not None
                        and not getattr(precond, '_tracks_comm_err', False)
                        and state.kfac_state.comm_err is not None):
                    # the DOWNGRADE direction of the same upgrade: the
                    # autotuner (or a restart at fp32) switched the wire
                    # dtype off a lossy mode mid-run — drop the EF
                    # residual host-side so every variant sees one state
                    # structure; the residual is a correction term, never
                    # load-bearing (discarding it costs one reduce's
                    # worth of feedback, the same contract the
                    # lossy-checkpoint-into-fp32 restore already accepts)
                    state = state.replace(
                        kfac_state=state.kfac_state.replace(comm_err=None))
                if 'yes' not in seen_inverse:
                    # one-time: a restored checkpoint may already carry a
                    # decomposition (utils/checkpoint.py include_kfac=True)
                    seen_inverse['yes'] = bool(
                        state.kfac_state is not None
                        and any(bool(jnp.any(x != 0)) for x in
                                jax.tree.leaves(state.kfac_state.decomp)))
            with annotation('kfac.step.select'):
                st = False
                pf = False
                if precond is None:
                    uf = ui = False
                    ub, warm = True, False
                else:
                    # hook_enabled=False freezes factor capture/updates
                    # (reference set_hook_enabled,
                    # kfac_preconditioner_base.py:117-130); the existing
                    # decomposition keeps preconditioning. Before ANY
                    # decomposition exists the gradients pass through
                    # unmodified while factor statistics still accumulate
                    # on schedule (the reference would have no factors to
                    # read at all here).
                    enabled = getattr(precond, 'hook_enabled', True)
                    uf = enabled and precond.should_update_factors(step)
                    st = (getattr(precond, 'stagger', False) and enabled
                          and seen_inverse['yes'])
                    if st:
                        # staggered refresh: after the first (full)
                        # decomposition EVERY step decomposes one
                        # cost-balanced cohort — the cohort index is
                        # traced, so this is ONE compiled variant per uf
                        # setting, not one per cohort
                        ui, ub, warm = False, True, False
                    else:
                        ui = enabled and precond.should_update_inverse(step)
                        # eigenvalue-only refresh needs a basis to
                        # refresh: the first inverse update of this run
                        # is always a full decomposition (no last_full
                        # yet — covers fresh starts, resumes, and the
                        # stagger cold start alike)
                        ub = (not seen_inverse['yes']
                              or precond.should_update_basis(
                                  step, seen_inverse.get('last_full')))
                        warm = _warm_basis_gate(precond, seen_inverse,
                                                step, ui, ub)
                        # cross-step prefetch: publish this inverse
                        # update's gathered table for the NEXT step —
                        # only once a prior table exists (the first
                        # decomposition must be consumed same-step or the
                        # pred would read zeros)
                        pf = (getattr(precond, 'comm_prefetch', False)
                              and ui and seen_inverse['yes'])
                        seen_inverse['yes'] = seen_inverse['yes'] or ui
                        if not ui:
                            # unused w/o an inverse update
                            ub, warm = True, False
                        if not ub:
                            # refresh path has no eigh to warm
                            warm = False
                key = (uf, ui, ub, warm, pf)
                build = dict(update_factors=uf, update_inverse=ui,
                             update_basis=ub, warm_basis=warm, prefetch=pf)
                if st:
                    # the cohort layout derives from kfac_update_freq: a
                    # scheduler/straggler rescale rebases it here, and
                    # the cohort count rides in the cache key so the
                    # rebuilt (static) tables get a fresh trace — same
                    # freq back again reuses the old one
                    layout = precond.rebase_cohorts()
                    key = (uf, 'stagger', layout.num_cohorts)
                    build = dict(update_factors=uf, update_inverse=False,
                                 stagger_update=True)
                if precond is not None and not seen_inverse['yes']:
                    key = (uf, False, 'factors_only')
                    build = dict(update_factors=uf, update_inverse=False,
                                 factors_only=True)
                # host-visible phase set of THIS dispatch (consumed by
                # utils.metrics.PhaseTimers for the kfac_phase_ms epoch
                # suffix)
                step_fn.last_phases = variant_phases(**build)
                hyper = KFACHyperParams(
                    lr=_host_scalar(lr if lr is not None
                                    else getattr(precond, 'lr', 0.0)),
                    damping=_host_scalar(
                        damping if damping is not None
                        else getattr(precond, 'damping', 0.0)))
            if key not in variants:
                # a cache miss: jit builds (traces, compiles) at the
                # first call, so the build span stays open over it
                spans.enter_context(annotation(
                    'kfac.step.build/' + variant_name(**build)))
                variants[key] = make_variant(**build)
            # the phase set goes in the span's NAME: a trace reader
            # keeps a host event's name, not its args ('/' before it,
            # not ':': the profiler's converter takes 'name:word' for a
            # TensorFlow 'op:type' and keeps 'word' alone)
            dispatch = 'kfac.step.dispatch/' + (
                '+'.join(step_fn.last_phases)
                or ('sgd' if precond is None else 'none'))
            if tracer is None:
                spans.enter_context(annotation(dispatch))
            else:
                spans.enter_context(tracer.span(
                    'kfac.dispatch', cat='kfac.step', annotate=dispatch,
                    step=step,
                    phases=obs_trace.taxonomy_phases(step_fn.last_phases)))
                # does THIS dispatch publish a gathered table for the
                # NEXT step? (stagger's double-buffered cohort gather, or
                # comm_prefetch on a full inverse update) — recorded as
                # overlapping schedule spans so a trace shows the
                # CommunicateInverse gather riding under the pred einsums
                # with no same-step consumer. A drawing of the schedule,
                # not an interval of work: the recorder's alone
                if (pf or st) and 'gather' in step_fn.last_phases:
                    spans.enter_context(tracer.span(
                        'kfac.Precondition', cat='kfac.sched',
                        annotate=False, step=step, table='stored'))
                    spans.enter_context(tracer.span(
                        'kfac.CommunicateInverse.prefetch',
                        cat='kfac.sched', annotate=False, step=step,
                        cohort=(step % layout.num_cohorts if st
                                else None),
                        consumer_step=step + 1))
            try:
                new_state, mets = variants[key](state, batch, hyper)
            except Exception as e:
                # per-call block_impl='pallas_interpret' cannot be seen
                # by the check_vma auto-detection (it only reads
                # KFAC_ATTN_IMPL), and the resulting shard_map trace
                # error is cryptic — point at the escape hatch
                msg = str(e)
                if check_vma is None and ('vma' in msg or 'Varying' in msg
                                          or 'varying' in msg):
                    raise RuntimeError(
                        msg + '\n[kfac_pytorch_tpu] If this model routes '
                        'attention through the Pallas interpreter per-call '
                        "(block_impl='pallas_interpret') rather than via "
                        'KFAC_ATTN_IMPL, pass check_vma=False to '
                        'build_train_step.') from e
                raise
            counter['array'], counter['value'] = new_state.step, step + 1
            return new_state, mets

    # Warm-tracking host state, exposed for checkpoint/resume: three
    # scalars ('yes', 'last_full', 'warm_streak') that are per-process
    # and NOT part of the on-device TrainState. Resume semantics WITHOUT
    # restoring it are safe by construction: the first inverse update of
    # a resumed run is always a full cold decomposition (no 'last_full'
    # yet) and the cold_restart_every streak restarts from zero — only
    # the *cadence* of future cold restarts shifts, never correctness.
    # Callers wanting bit-identical cadence across preemption can dump
    # this dict (plain ints/bools, json-safe) next to the checkpoint and
    # assign it back onto the new step_fn: step_fn.warm_tracking.update(
    # saved). Pinned by tests/test_training.py::
    # test_warm_tracking_resume_semantics.
    step_fn.warm_tracking = seen_inverse
    # which K-FAC phases the LAST dispatch ran ('pred'/'stats'/'decomp'/
    # 'gather') — host-side knowledge the examples feed to
    # utils.metrics.PhaseTimers together with the step's wall time, so
    # epoch lines can attribute time per phase (runlog.kfac_phase_suffix)
    step_fn.last_phases = ()
    # how many calls read the step counter back from the device: 1 in a
    # loop that hands back the state it was given, one more for every
    # state replaced from outside
    step_fn.step_reads = 0
    # the jitted variant cache + constructor, exposed for introspection:
    # scripts/comm_count.py builds a variant via make_variant and lowers
    # it WITHOUT executing a step (AOT lower/compile only)
    step_fn.variants = variants
    step_fn.make_variant = make_variant
    if precond is not None:
        # trace-affecting knob changes (comm_precision / decomp_impl /
        # an applied comm_mode replan through the knob arbiter —
        # scheduler/straggler/tuner frequency changes are host-side
        # gating and deliberately NOT invalidating) clear the
        # compiled-variant cache so no stale program keeps the old wire
        # dtype or plan; the next dispatch retraces against the new
        # config
        def _invalidate_variants():
            variants.clear()
            # a replan may have dropped the stored decomposition (a
            # cross-method variant switch zeroes it): re-derive the
            # "seen a decomposition" record from the STATE on the next
            # dispatch, and restart the warm-streak bookkeeping — the
            # next full decomposition after any trace-affecting change
            # goes cold (never warm-seed across a swapped plan; only
            # the cold-restart cadence shifts, never correctness)
            for k in ('yes', 'last_full', 'warm_streak'):
                seen_inverse.pop(k, None)

        from kfac_pytorch_tpu.autotune import arbiter_for
        arbiter_for(precond).add_invalidator(_invalidate_variants)
    return step_fn


def _state_specs(precond, axis_name):
    """PartitionSpecs of a TrainState over the K-FAC axis: everything
    replicated but the K-FAC state's sharded rows (health counters are
    replicated scalars; P() matches the empty subtree too when the
    guard, or the preconditioner, is off)."""
    kspecs = (precond.state_pspecs(axis_name) if precond is not None
              else P())
    return TrainState(step=P(), params=P(), opt_state=P(),
                      kfac_state=kspecs, extra_vars=P(), health=P())


def _interpreted_kernels(precond):
    """Does the step hold a Pallas kernel that runs INTERPRETED (off-TPU:
    ``KFAC_ATTN_IMPL=pallas_interpret`` attention, or the fused capture
    kernels of ``capture_impl='pallas'``)? The interpreter's loop carries
    drop the varying axes shard_map's checker tracks, so such a step
    needs ``check_vma=False``; the compiled kernels do not."""
    from .parallel.ring_attention import interpreted_attention_active
    if interpreted_attention_active():
        return True
    if getattr(precond, 'resolved_capture_impl', None) != 'pallas':
        return False
    from .ops import pallas_capture
    return pallas_capture.interpret_default()


def init_train_state(model, tx, precond, rng, sample_input, health='auto',
                     mesh=None, axis_name=None):
    """Initialize params, optimizer and K-FAC state (plus discovery of the
    capture layer metadata if the preconditioner isn't set up yet).

    ``health`` mirrors build_train_step's argument: 'auto' seeds the
    HealthState counters iff the preconditioner's guard is on; pass
    True/False/HealthConfig to override (match what the step uses —
    step_fn upgrades a missing HealthState on first call anyway).

    ``mesh`` / ``axis_name`` (as given to build_train_step): build the
    state ON the mesh, every leaf born with the sharding the mesh step
    takes it in. Without them the whole state — every factor and
    decomposition — is built on one device and the first step call
    reshards it: that device held 5.4 GB where its peers held 1.6
    (ResNet-50 on four v5e chips, PERF.md PR 21).
    """
    if mesh is not None:
        build = functools.partial(init_train_state, model, tx, precond,
                                  health=health)
        # discovers the layers (precond.setup) so the specs exist
        jax.eval_shape(build, rng, sample_input)
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            _state_specs(precond, axis_name),
            is_leaf=lambda v: isinstance(v, P))
        return jax.jit(build, out_shardings=shardings)(rng, sample_input)
    # provide a dropout stream too: models that train with dropout (LSTM,
    # transformer) request it at init since their __call__ defaults to
    # train=True
    rngs = {'params': rng, 'dropout': jax.random.fold_in(rng, 1)}
    variables = capture.init(model, rngs, sample_input)
    params = variables.pop('params')
    kfac_state = None
    if precond is not None:
        if precond.plan is None:
            metas = capture.collect_layer_meta(
                model, {'params': params, **variables}, sample_input,
                rngs={'dropout': jax.random.fold_in(rng, 2)})
            precond.setup(metas)
        kfac_state = precond.init()
    if health == 'auto':
        health_cfg = getattr(precond, 'health', None)
    else:
        health_cfg = health_lib.resolve(health)
    hstate = (health_lib.HealthState.init() if health_cfg is not None
              else None)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=tx.init(params), kfac_state=kfac_state,
                      extra_vars=variables, health=hstate)
