"""In-jit numerical-health guard: skip, escalate, degrade, recover.

The reference's failure story is crash-stop + scan-downward resume
(SURVEY §5.3); this repo already survives preemption (PreemptionGuard)
and elastic resharding. This module closes the remaining gap: a single
bad batch producing NaN/Inf gradients would permanently contaminate the
``m_A``/``m_G`` running averages and poison every subsequent
eigendecomposition — nothing in the hot path checked ``isfinite``.

The guard is entirely IN-JIT (no per-step host sync, no extra compiled
step variants): the trainer screens the batch's loss, its gradients and,
on a factor-update step, the diagonals of its factor statistics
(``engine.stats_finite``), and a ``lax.cond`` routes the step —

- **healthy batch**: the normal K-FAC + optimizer update runs;
- **non-finite batch**: BOTH the optimizer update and the factor-EMA
  update are skipped, so params, opt_state and ``m_A``/``m_G`` stay
  bit-exactly as if the batch never happened (only the step counter and
  the health counters advance).

A :class:`HealthState` rides in the TrainState and drives a damping
escalation ladder: *consecutive* failures (skipped batches or non-finite
preconditioner output) climb the ladder — each rung multiplies the
damping fed to the decomposition by ``damping_factor`` — and at the top
rung the step degrades to plain SGD (raw averaged gradients, factor
statistics still accumulating) until ``recover_after`` consecutive
healthy steps reset the ladder and K-FAC preconditioning resumes.

An ISOLATED failure deliberately does not touch the ladder
(``escalate_after=2``): a one-off skipped batch must leave the
subsequent trajectory bit-identical to a run whose data schedule simply
never contained that batch — escalating damping on the first failure
would silently fork the two trajectories (pinned by
tests/test_health.py::test_nan_batch_skips_update_and_ema).

What is screened, and what is not (PR 42). The screen reads the loss,
every gradient and the ``[d]`` diagonal of every factor statistic of the
batch; it does NOT read the captured activations and output-gradients
again. A statistic is a Gram product of them, so every captured element
it reads is squared into a diagonal entry, where nothing cancels it: a
NaN or an Inf there refuses the batch exactly as before
(``tests/test_health.py::test_stats_fault_triggers_skip`` and the
one-element cases beside it). Two edges moved. A captured element that no
statistic and no gradient reads (the odd pixels under a 1x1 convolution
of stride 2) can no longer refuse a batch: it reaches no state. A finite
captured value above about 1.8e19, whose square overflows float32, now
refuses the batch where it used to hold only that factor row back:
stricter, never looser. Where a step makes no statistics the screen can
read (the fused Pallas capture, the ComputeFactor ablation) it reads the
captured tensors as it did.

The guarantee: no non-finite value reaches params, optimizer state,
factors or decompositions, and a refused batch leaves all of them (and
``extra_vars``) bit-identical.

The companion engine-level guards are wired in ``KFAC.step``: a factor
row's flag comes out of the pass that writes its running average and a
row whose STORED value is corrupt restarts from the identity
(``engine.settle_factor_rows``); a fresh Cholesky
inverse's flag is read from its diagonal and a row at fault falls back to
the last good inverse, the identity when cold
(``engine.guard_decomposition``, ``ops.settle_inverse_rows``). Neither
reads or writes a whole operand on a healthy step; what still does (an
eigendecomposition, E-KFAC's scales, the error-feedback residual,
statistics reduced over several devices) is counted by
``KFAC.guard_passes``.
"""

import dataclasses
from typing import Optional

import flax.struct
import jax
import jax.numpy as jnp

from kfac_pytorch_tpu.capture import all_finite
from kfac_pytorch_tpu.parallel import collectives as coll


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Static (host-side) knobs of the self-healing ladder.

    escalate_after: consecutive failures before the damping ladder
      climbs a rung. The default (2) means an isolated bad batch is
      skipped WITHOUT side effects on later steps — required for the
      skipped-batch bit-identity guarantee (module docstring).
    damping_factor: per-rung damping multiplier — at rung r the
      decomposition sees ``damping * damping_factor**r``.
    max_rungs: ladder height; at ``rung == max_rungs`` the step degrades
      to plain SGD (raw averaged gradients) while factor statistics keep
      accumulating, so recovery resumes preconditioning from fresh
      curvature rather than from scratch.
    recover_after: consecutive healthy steps that reset the ladder to
      rung 0 (and leave degraded-SGD mode).
    """
    escalate_after: int = 2
    damping_factor: float = 10.0
    max_rungs: int = 3
    recover_after: int = 10


class HealthState(flax.struct.PyTreeNode):
    """On-device health counters carried in the TrainState (all i32
    scalars; replicated under a mesh — every update derives from
    cross-axis-reduced flags, so the counters agree on every device).

    bad_streak:  consecutive unhealthy steps (skipped batch OR
                 non-finite preconditioner output).
    good_streak: consecutive fully-healthy steps since the last failure.
    rung:        current damping-ladder rung, 0..max_rungs.
    skipped:     total batches skipped (cumulative).
    fallbacks:   total steps whose preconditioner output was discarded
                 for raw-SGD gradients (cumulative; includes the
                 degraded-mode steps only when the output was actually
                 non-finite — the mode itself is ``rung``-visible).
    """
    bad_streak: jnp.ndarray
    good_streak: jnp.ndarray
    rung: jnp.ndarray
    skipped: jnp.ndarray
    fallbacks: jnp.ndarray

    @classmethod
    def init(cls):
        # five DISTINCT buffers: the TrainState is donated to the jitted
        # step, and donating one buffer through two leaves is an error
        z = lambda: jnp.zeros((), jnp.int32)
        return cls(bad_streak=z(), good_streak=z(), rung=z(), skipped=z(),
                   fallbacks=z())


def batch_ok(axis_name, grads, *local_trees, flags=()):
    """Scalar bool: is this batch numerically usable on EVERY device?

    ``grads`` are already cross-axis reduced (replicated), so their
    finiteness is checked locally; ``local_trees`` (the pre-pmean loss;
    captured activations / output-gradients where no statistics exist to
    stand for them) and ``flags`` (bool arrays already reduced from this
    device's own data: ``engine.stats_finite``) are per-device, so their
    bad-flags are psummed over the axis — one scalar of communication, and
    the returned flag is replicated (a valid ``lax.cond`` predicate under
    shard_map).
    """
    ok_local = all_finite(*local_trees)
    for f in flags:
        ok_local = jnp.logical_and(ok_local, jnp.all(f))
    bad = coll.psum(jnp.where(ok_local, 0.0, 1.0), axis_name)
    return jnp.logical_and(all_finite(grads), bad == 0)


def effective_damping(hstate: HealthState, damping, cfg: HealthConfig):
    """Ladder-escalated damping: ``damping * damping_factor**rung``."""
    scale = jnp.power(jnp.float32(cfg.damping_factor),
                      hstate.rung.astype(jnp.float32))
    return jnp.asarray(damping, jnp.float32) * scale


def degraded(hstate: HealthState, cfg: HealthConfig):
    """True while the ladder's top rung forces the plain-SGD step."""
    return hstate.rung >= cfg.max_rungs


def _escalate(hstate: HealthState, cfg: HealthConfig):
    streak = hstate.bad_streak + 1
    rung = jnp.where(streak >= cfg.escalate_after,
                     jnp.minimum(hstate.rung + 1, cfg.max_rungs),
                     hstate.rung)
    return streak, rung


def on_bad_batch(hstate: HealthState, cfg: HealthConfig) -> HealthState:
    """Transition for a skipped (non-finite) batch."""
    streak, rung = _escalate(hstate, cfg)
    return hstate.replace(bad_streak=streak,
                          good_streak=jnp.zeros((), jnp.int32),
                          rung=rung, skipped=hstate.skipped + 1)


def on_good_batch(hstate: HealthState, cfg: HealthConfig,
                  precond_ok) -> HealthState:
    """Transition for an applied step.

    ``precond_ok=False`` (the preconditioner output was non-finite and
    raw gradients were used instead) counts as a failure for the ladder;
    a fully-healthy step extends ``good_streak`` and resets the ladder
    once ``recover_after`` is reached.
    """
    streak, esc_rung = _escalate(hstate, cfg)
    gstreak = jnp.where(precond_ok, hstate.good_streak + 1, 0)
    rung = jnp.where(
        precond_ok,
        jnp.where(gstreak >= cfg.recover_after, 0, hstate.rung),
        esc_rung)
    return hstate.replace(
        bad_streak=jnp.where(precond_ok, 0, streak),
        good_streak=gstreak, rung=rung,
        fallbacks=hstate.fallbacks
        + jnp.where(precond_ok, 0, 1).astype(jnp.int32))


def metrics(hstate: HealthState, ok) -> dict:
    """Per-step health metrics dict (replicated scalars, returned next
    to the loss; utils.metrics.HealthMonitor consumes it host-side)."""
    return {'ok': ok, 'skipped': hstate.skipped, 'rung': hstate.rung,
            'fallbacks': hstate.fallbacks, 'bad_streak': hstate.bad_streak}


def resolve(health) -> Optional[HealthConfig]:
    """Normalize a user-facing ``health`` argument: True -> defaults,
    False/None -> disabled, a HealthConfig -> itself."""
    if health is True:
        return HealthConfig()
    if not health:
        return None
    if not isinstance(health, HealthConfig):
        raise TypeError('health must be a bool or HealthConfig, got '
                        f'{health!r}')
    return health
