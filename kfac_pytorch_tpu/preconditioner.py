"""The K-FAC preconditioner facade: four variants behind one engine.

Reference surface parity (kfac/__init__.py:8-16 and the four
kfac_preconditioner_*.py classes) via three orthogonal engine switches:

  variant       stats_reduce   method      comm_mode
  ----------    ------------   ---------   -------------------------------
  inverse       pmean (MPD)    cholesky    'pred' (default) or 'inverse'
                                           per communicate_inverse_or_not
                                           (inv.py:41)
  eigen         pmean (MPD)    eigh        'inverse' (forced, eigen.py:52)
  inverse_dp    local  (DP)    cholesky    'pred' (forced, inv_dp.py:52)
  eigen_dp      local  (DP)    eigh        'pred' (forced — the flagship,
                                           train_cifar10.sh:19)

Unlike the reference's stateful ``torch.optim.Optimizer`` subclass, the
preconditioner is a pure-functional transformation: ``step`` maps
``(state, grads, captured stats) -> (preconditioned grads, state)`` and is
designed to be traced inside jit / shard_map. Host-side knobs
(``fac_update_freq`` / ``kfac_update_freq`` / ``damping``) select static
step variants and feed traced scalars — the KFACParamScheduler mutates them
without recompilation.
"""

import dataclasses
import logging
from typing import Any, Dict, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kfac_pytorch_tpu import engine, faults
from kfac_pytorch_tpu import health as health_lib
from kfac_pytorch_tpu.obs import trace as obs_trace
from kfac_pytorch_tpu.plan import (build_cohorts, build_decomp_shard,
                                   build_plan, pred_layout_record,
                                  without_input_groups)

#: decomposition-implementation knob values (the autotuner's ladder
#: restates this tuple in autotune.DECOMP_IMPLS — it must stay
#: stdlib-importable; cross-module agreement is pinned by test).
#: 'xla' = the cold kernel (QDWH eigh / batched Cholesky); 'subspace' /
#: 'jacobi' = warm eigh kernels (eigh variants only); 'newton_schulz' =
#: the warm GEMM inverse (cholesky variants only); 'auto' resolves per
#: method to the MXU-shaped warm kernel.
DECOMP_IMPLS = ('xla', 'auto', 'jacobi', 'subspace', 'newton_schulz')

#: capture-kernel ladder (ISSUE 19; autotune.CAPTURE_IMPLS restates
#: this tuple — cross-module agreement is pinned by test). 'xla' = the
#: reference ops/factors.py path; 'pallas' = the fused capture kernels
#: (ops/pallas_capture.py: patch-extract + factor GEMM + EMA epilogue,
#: interpreter mode off-TPU); 'auto' resolves to 'pallas'. None keeps
#: the legacy path untouched AND hides the rung from the tuner.
CAPTURE_IMPLS = ('xla', 'pallas', 'auto')

#: impls that warm-start from the stored decomposition — an explicit
#: iterative ``decomp_impl`` implies warm seeding without requiring
#: ``warm_start_basis`` (the tuner flips the knob mid-run; the seeds
#: are what make the iterative rung cheap).
_WARM_IMPLS = ('auto', 'jacobi', 'subspace', 'newton_schulz')


class KFACState(flax.struct.PyTreeNode):
    """Factor + decomposition state, stacked-bucket layout (plan.py).

    ``factors``/decomposition arrays are globally shaped ``[rows, D, D]``;
    under a mesh the factor rows are sharded over the kfac axis (see
    ``KFAC.state_pspecs``). The reference equivalents are the per-module
    dicts m_A/m_G/m_inv_A/m_inv_G/m_QA/m_dA/...
    (kfac_preconditioner_base.py:107-110).

    ``comm_err`` is the error-feedback residual of the lossy factor-stats
    reduce (``comm_precision`` in {'bf16','int8'} on an MPD variant):
    per device, the quantization error of its LAST compressed stats
    contribution, keyed like the stats stack and re-entered into the
    next reduce (collectives.pmean_scatter_ef). None when no lossy reduce
    exists (fp32, DP variants) — defaulted so pre-compression
    constructions and checkpoints keep working unchanged. Like the
    E-KFAC scales it is transport-transient: ``reshard_kfac_state``
    zero-fills it on an elastic world change and it re-accumulates.
    """
    step: jnp.ndarray
    factors: Dict[str, jnp.ndarray]
    decomp: Dict[str, Dict[str, jnp.ndarray]]
    comm_err: Optional[Dict[str, jnp.ndarray]] = None


@flax.struct.dataclass
class KFACHyperParams:
    """Traced hyper-parameters (schedulable without recompile)."""
    lr: jnp.ndarray
    damping: jnp.ndarray


_VARIANTS = {
    'inverse': dict(stats_reduce='pmean', method='cholesky', comm_mode=None),
    'eigen': dict(stats_reduce='pmean', method='eigh', comm_mode='inverse'),
    'inverse_dp': dict(stats_reduce='local', method='cholesky',
                       comm_mode='pred'),
    'eigen_dp': dict(stats_reduce='local', method='eigh', comm_mode='pred'),
    # beyond reference: E-KFAC (George et al. 2018) — the eigen layout
    # plus per-example second moments in the joint eigenbasis replacing
    # the Kronecker eigenvalue product (engine.update_ekfac_scales);
    # 'ekfac_dp' applies DP-KFAC's owner-local-statistics semantics to
    # the moments too (engine.update_ekfac_scales_local — zero scale
    # communication, composing with the comm_pred flagship layout)
    'ekfac': dict(stats_reduce='pmean', method='eigh',
                  comm_mode='inverse', ekfac=True),
    'ekfac_dp': dict(stats_reduce='local', method='eigh',
                     comm_mode='pred', ekfac=True),
}


_EKFAC_DAMPING_WARNED = False


def _warn_ekfac_damping_once(damping):
    """One-time heads-up that ekfac variants want their own damping.

    The exact second-moment denominators are systematically >= the
    Kronecker eigenvalue product (the eigen variants' denominators), so
    a lambda tuned for 'eigen'/'eigen_dp' can under-damp ekfac — on the
    NOTES r4 MLP ladder the preferred lambda was 10x the eigen recipe's,
    while on conv the shared value worked. Fires once per process
    (VERDICT r4 #4); silence with ``warnings.filterwarnings``.
    """
    global _EKFAC_DAMPING_WARNED
    if _EKFAC_DAMPING_WARNED:
        return
    _EKFAC_DAMPING_WARNED = True
    import warnings
    warnings.warn(
        f'ekfac variants replace the Kronecker eigenvalue product with '
        f'exact (typically larger) second moments in the denominator — '
        f'a damping calibrated for an eigen variant (got {damping}) may '
        'be too small here. If this config was tuned on eigen/eigen_dp, '
        'sweep damping upward (3x/10x) before judging ekfac; see the '
        'KFAC docstring damping note and the NOTES r4 ladder.',
        stacklevel=3)


class KFAC:
    """Distributed K-FAC gradient preconditioner.

    Args mirror the reference constructor (kfac_preconditioner_base.py:66-99)
    plus the mesh placement knobs:

      variant: one of 'inverse' | 'eigen' | 'inverse_dp' | 'eigen_dp'
        (reference parity) or 'ekfac' | 'ekfac_dp' (beyond reference).
        DAMPING NOTE for the ekfac variants: their denominators are
        exact per-example second moments in the joint eigenbasis, which
        are systematically >= the Kronecker eigenvalue product they
        replace (Cauchy-Schwarz on the cross terms) — so a ``damping``
        calibrated for an eigen variant can be too SMALL relative to
        the ekfac spectrum. On an MLP task the preferred lambda was 10x
        the eigen recipe's (NOTES r4 damping ladder: .832 at 0.3 vs
        .678 at 0.03); on conv the shared recipe value worked. When
        switching a tuned eigen config to ekfac, sweep damping upward
        (3x/10x) before judging the variant; a one-time warning points
        here (pinned by tests/test_warm_accuracy_gate.py's ladder).
      lr, damping, fac_update_freq, kfac_update_freq, kl_clip,
      factor_decay, exclude_vocabulary_size, hook_enabled, exclude_parts:
        reference semantics.
      communicate_inverse_or_not: 'inverse' variant only — communicate
        inverse KFs instead of preconditioned grads (inv.py:41).
      num_devices / axis_name: size of the kfac mesh axis and its name
        inside shard_map; axis_name=None is the world=1 zero-comm path.
      mesh_axes: composed-mesh spec ('dp2xtp2', 'dp4xep2', a parsed
        ``meshplan.AxisSpec`` tuple) — the axis-aware lane (README
        "K-FAC on composed meshes"). The K-FAC world derives from its
        data/sequence axes (so num_devices/axis_name must be left
        unset), the factor plan stays the plain data-world plan, and
        tensor-replicated factor rows (column-A / row-G per
        ``mesh_rules``) gain a pmean over the tensor axis; expert- and
        pipeline-axis factors are owner-local — zero factor bytes on
        those axes. Live moves go through ``replan(mesh_axes=...)``.
      mesh_rules: per-layer ``meshplan.LayerAxisRule`` tuple (default:
        the stock parallel/ families — ``tp.axis_rules()`` names; use
        ``tp.axis_rules(column=..., row=...)`` / ``moe.axis_rules``
        for custom layer names). Requires mesh_axes.
      assignment: 'round_robin' (reference) | 'balanced' (LPT scheduler).
      distribute_layer_factors: eigen variant — put A and G of one layer on
        different devices when the mesh outnumbers layers (eigen.py:66-71);
        default auto.
      basis_update_freq: eigh variants only (beyond reference) — full
        eigendecomposition every this-many steps; intermediate
        ``kfac_update_freq`` hits re-fit only the eigenvalues in the
        retained eigenbasis (E-KFAC-style amortization, two matmuls per
        bucket instead of an eigh). None (default) = every inverse update
        is a full decomposition, the reference cadence.
      warm_start_basis: beyond reference — decompositions after the
        first start from the previous one. Eigh variants: the stored
        eigenbasis seeds perturbative tracking (ops.subspace_eigh,
        KFAC_EIGH_IMPL='subspace'/'auto' — the MXU-shaped warm kernel,
        chosen by real-chip measurement) or rotated Jacobi sweeps
        ('jacobi'); composes with basis_update_freq. Cholesky variants:
        the stored inverse seeds Newton-Schulz iteration
        (ops.newton_schulz_inverse) with a residual-gated Cholesky
        fallback — pure matmuls on the inverse-update hot path.
      warm_sweeps: iteration count for warm-started full decompositions:
        Jacobi sweeps (None = the kernel's warm default, 5), subspace
        tracking steps (None = 2), or Newton-Schulz iterations
        (None = 2). The defaults are calibrated for the
        stat_decay=0.95 / <=10-step full-interval drift regime — raise
        for longer intervals between fulls (large basis_update_freq /
        kfac_update_freq) or faster factor decay: the stored
        decomposition drifts further between fulls and the default can
        under-converge (Newton-Schulz self-reports: a stale seed fails
        the residual gate and falls back to Cholesky).
      cold_restart_every: with warm_start_basis, force a cold (from
        scratch) full decomposition after this many consecutive warm
        ones — the chained basis Q <- Q @ V' accumulates ~1e-7
        orthogonality error per warm full, and the periodic cold full
        resets it. Must be a positive int.
      stagger: staggered inverse refresh (beyond reference — the KAISA /
        Osawa et al. amortization done evenly): instead of decomposing
        EVERY factor on ``kfac_update_freq``-boundary steps (a periodic
        multi-x step-time spike), the device-major rows are partitioned
        into ``kfac_update_freq`` cost-balanced cohorts
        (plan.build_cohorts, eigh cost ~ D^3) and every step decomposes
        only cohort ``step % kfac_update_freq`` — the same per-slot
        staleness contract (each slot refreshed once per window), cost
        spread evenly so the second-order work hides behind the
        first-order step. The cohort index is a TRACED scalar, so the
        trainer's compiled-variant count does not grow with the freq.
        Double-buffered publish: the step preconditions with the
        PREVIOUS stored table while the freshly decomposed cohort rows
        are merged (and, in comm_mode='inverse', all-gathered at
        ~1/kfac_update_freq of the full volume, overlappable with the
        pred einsums) into the state for the NEXT step — one extra step
        of staleness for the refreshed cohort, well inside the contract
        ``kfac_update_freq`` already accepts. Mutually exclusive with
        the basis_update_freq / warm_start_basis amortizations and the
        ekfac variants (those re-use the full-refresh structure).
        The first decomposition of a run is always a full one (the
        trainer's cold-start gate); staggering begins after it.
      comm_precision: wire dtype of the factor collectives (beyond
        reference — EF-SGD lineage, Seide et al. 2014 / Karimireddy et
        al. 2019): 'fp32' (default, bit-identical to the uncompressed
        path), 'bf16' (2x byte reduction on every factor collective), or
        'int8' (4x on the gather collectives via per-row absmax scales;
        the stats REDUCE floors at bf16 — an XLA all-reduce cannot
        integer-accumulate without overflow). Lossy modes compensate the
        stats reduce with an error-feedback residual carried in
        ``KFACState.comm_err`` (folds into the factor EMAs — every
        device's time-averaged contribution stays unbiased); the gathers
        quantize per owner (one contributor per row — no accumulation
        error). The gradient allreduce is NEVER compressed: the SGD
        floor is untouched. ``axis_name=None`` stays a zero-comm,
        zero-compression identity path.
      comm_prefetch: comm_mode='inverse' only (beyond reference) —
        extend PR 4's double-buffer to the FULL refresh: on an
        inverse-update step the freshly gathered decomposition is
        published for the NEXT step while THIS step preconditions with
        the previous table, so the CommunicateInverse gather has no
        same-step consumer and XLA can overlap it with the pred einsums
        (one step of decomposition staleness, well inside the
        ``kfac_update_freq`` contract — the same trade ``stagger``
        already makes per cohort). The trainer keeps the first
        decomposition of a run un-prefetched (a cold state would
        precondition with zeros). Redundant (but harmless) with
        ``stagger``, which is always double-buffered.
      decomp_impl: the decomposition implementation, promoted to a
        first-class runtime knob (beyond reference — autotune.KNOB_ATTRS
        rung; README "Attacking the decomposition wall"): 'xla' (the
        cold kernel — QDWH eigh / batched Cholesky), 'subspace' or
        'jacobi' (warm eigh kernels, eigh variants only),
        'newton_schulz' (the warm GEMM inverse, Cholesky variants
        only), or 'auto' (the MXU-shaped warm kernel for the method).
        An EXPLICIT iterative value implies warm seeding from the
        stored decomposition — no separate ``warm_start_basis`` needed
        (the per-row NS acceptance gate / subspace degeneracy handling
        keep accuracy safe; see ops/linalg.py). None (default)
        preserves the legacy KFAC_EIGH_IMPL env contract exactly. The
        KnobController ladders this attribute through the arbiter; a
        change retraces the step (the arbiter fires the variant-cache
        invalidators, like comm_precision).
      decomp_shard: mesh-sharded decomposition (beyond reference — the
        tentpole of ROADMAP item 5): the active refresh cohort's rows
        are repartitioned cost-balanced (D³ model) across ALL devices
        instead of decomposed owner-local, shrinking the per-step
        decomposition critical path from ``Σ_b R_b·D³`` to
        ``Σ_b S_b·D³ ≈ 1/P`` of the cohort total — the most-loaded
        owner's cohort stops serializing its idle peers. Costs two
        bounded ``DecompComm`` gathers per step (damped cohort factors
        out, results back), priced in closed form by
        ``FactorPlan.comm_volume(decomp_shard=...)`` and pinned
        byte-for-byte against the compiled HLO by
        scripts/comm_count.py. Implies ``stagger=True`` (the cohort
        tables ARE the work description) and therefore inherits
        stagger's exclusions; incompatible with the
        CommunicateInverse ablation. ``axis_name=None`` degenerates to
        the owner-local path bit-exactly.
      health: the numerical-health guard (beyond reference, health.py).
        True (default) enables the in-engine screens with the default
        ladder: factor-EMA rows and decomposition rows that come back
        non-finite fall back to the last good value (identity when
        cold), so one blown eigh/Cholesky can never poison the state.
        Pass a ``health.HealthConfig`` to tune the damping-escalation
        ladder the trainer drives (escalate_after / damping_factor /
        max_rungs / recover_after), or False to disable every screen —
        the guards are pure pass-through selects when the inputs are
        finite, so disabling only buys back their (tiny) compile cost.
    """

    def __init__(self, variant='eigen_dp', lr=0.1, damping=0.001,
                 fac_update_freq=1, kfac_update_freq=1,
                 communicate_inverse_or_not=False, kl_clip=0.001,
                 factor_decay=0.95, exclude_vocabulary_size=None,
                 hook_enabled=True, exclude_parts='', batch_averaged=True,
                 num_devices=1, axis_name=None, assignment='round_robin',
                 distribute_layer_factors=None, bucket_fn=None, eps=1e-10,
                 basis_update_freq=None, warm_start_basis=False,
                 warm_sweeps=None, cold_restart_every=50, stagger=False,
                 health=True, comm_precision='fp32', comm_prefetch=False,
                 decomp_impl=None, decomp_shard=False, comm_mode=None,
                 capture_impl=None, mesh_axes=None, mesh_rules=None):
        if variant not in _VARIANTS:
            raise KeyError(f'unknown variant {variant!r}')
        cfg = dict(_VARIANTS[variant])
        if cfg['comm_mode'] is None:  # 'inverse' variant honors the flag
            cfg['comm_mode'] = ('inverse' if communicate_inverse_or_not
                                else 'pred')
        if comm_mode is not None:
            # ISSUE 14: comm_mode is a RUNTIME knob now — the variant
            # only picks the starting mode, and an explicit override
            # (the trainers' --kfac-comm-mode, a kfac-serve relaunch
            # carrying an autotune-adopted switch) starts on the other
            # road of the same layout. The live switch is KFAC.replan.
            if comm_mode not in ('inverse', 'pred'):
                raise ValueError("comm_mode must be 'inverse' or 'pred', "
                                 f'got {comm_mode!r}')
            cfg['comm_mode'] = comm_mode
        self.variant = variant
        self.stats_reduce = cfg['stats_reduce']
        self.method = cfg['method']
        self.comm_mode = cfg['comm_mode']
        self.ekfac = cfg.get('ekfac', False)
        if self.ekfac:
            _warn_ekfac_damping_once(damping)
        self.lr = lr
        self.damping = damping
        self.fac_update_freq = fac_update_freq
        self.kfac_update_freq = kfac_update_freq
        self.kl_clip = kl_clip if (kl_clip is not None and kl_clip > 0) \
            else None
        self.factor_decay = factor_decay
        self.exclude_vocabulary_size = exclude_vocabulary_size
        self.hook_enabled = hook_enabled
        self.batch_averaged = batch_averaged
        self.num_devices = num_devices
        self.axis_name = axis_name
        # mesh-plan subsystem: a composed-mesh spec ('dp2xtp2' or parsed
        # AxisSpec tuple) makes the preconditioner axis-aware — the
        # K-FAC world (num_devices/axis_name) derives from the DATA
        # axes, and setup() builds a MeshFactorPlan whose base is the
        # plain data-world plan (the step path reads only that; the one
        # mesh-specific seam is engine.update_factors' extra_reduce)
        self.mesh_axes = None
        self.mesh_rules = mesh_rules
        self._mesh_plan = None
        if mesh_axes is not None:
            from kfac_pytorch_tpu.meshplan import axes as _ma
            _axes = _ma.parse_mesh_spec(mesh_axes)
            world = _ma.world_size(_axes)
            dnames = _ma.data_axis_names(_axes)
            derived = dnames[0] if len(dnames) == 1 else dnames
            if num_devices not in (1, world):
                raise ValueError(
                    f'mesh_axes={_ma.format_mesh_spec(_axes)!r} has a '
                    f'{world}-way data world but num_devices={num_devices} '
                    '— drop num_devices (it derives from the mesh spec)')
            if axis_name is not None and axis_name != derived:
                raise ValueError(
                    f'mesh_axes={_ma.format_mesh_spec(_axes)!r} puts the '
                    f'K-FAC world on {derived!r} but axis_name='
                    f'{axis_name!r} — drop axis_name (it derives from '
                    'the mesh spec)')
            self.mesh_axes = _axes
            self.num_devices = world
            self.axis_name = derived
        elif mesh_rules is not None:
            raise ValueError('mesh_rules without mesh_axes has nothing '
                             'to apply to — pass mesh_axes')
        self.assignment = assignment
        self.distribute_layer_factors = distribute_layer_factors
        # None: plan.build_plan's default layout (tile rounding + fold)
        self.bucket_fn = bucket_fn
        self.eps = eps
        if basis_update_freq is not None and self.method != 'eigh':
            raise ValueError('basis_update_freq applies to eigh variants')
        self.basis_update_freq = basis_update_freq
        if warm_start_basis and self.method == 'eigh':
            import os
            import warnings
            if os.environ.get('KFAC_EIGH_IMPL', 'xla') == 'xla':
                warnings.warn(
                    'warm_start_basis has no effect on the XLA eigh path '
                    "(QDWH cannot warm-start) — set KFAC_EIGH_IMPL="
                    "'subspace' (or 'auto'/'jacobi') to use it",
                    stacklevel=2)
        self.warm_start_basis = warm_start_basis
        if warm_start_basis and warm_sweeps is None:
            interval = basis_update_freq or kfac_update_freq
            if interval > 10:
                import warnings
                warnings.warn(
                    f'warm_start_basis with a {interval}-step interval '
                    'between full decompositions: the default warm_sweeps '
                    '(5) is calibrated for <=10-step basis drift — pass '
                    'warm_sweeps>=8 if eigen accuracy degrades',
                    stacklevel=2)
        self.warm_sweeps = warm_sweeps
        # every warm full compounds ~1e-7 orthogonality error into the
        # chained basis Q <- Q @ V'; a periodic cold full resets it.
        # The default (50) keeps the accumulated error ~5e-6 — far below
        # the f32 decomposition noise floor
        if not (isinstance(cold_restart_every, int)
                and cold_restart_every > 0):
            raise ValueError('cold_restart_every must be a positive int '
                             f'(got {cold_restart_every!r})')
        self.cold_restart_every = cold_restart_every
        # decomposition-implementation knob (tentpole b): an EXPLICIT
        # value routes through the traced programs (ops.sym_eig impl /
        # the NS warm inverse) and joins the autotuner's KNOB_ATTRS
        # ladder; None preserves the legacy KFAC_EIGH_IMPL env path
        # exactly (env read at trace time, warm only with
        # warm_start_basis) so existing configs are untouched
        if decomp_impl is not None:
            if decomp_impl not in DECOMP_IMPLS:
                raise ValueError(
                    f'decomp_impl must be one of {DECOMP_IMPLS}, '
                    f'got {decomp_impl!r}')
            if (decomp_impl in ('subspace', 'jacobi')
                    and self.method != 'eigh'):
                raise ValueError(
                    f'decomp_impl={decomp_impl!r} is an eigh kernel; '
                    f'variant {variant!r} decomposes by Cholesky — use '
                    "'newton_schulz' (or 'auto') there")
            if decomp_impl == 'newton_schulz' and self.method != 'cholesky':
                raise ValueError(
                    "decomp_impl='newton_schulz' replaces the Cholesky "
                    f'inverse; variant {variant!r} eigendecomposes — '
                    "use 'subspace' (or 'auto') there")
        self.decomp_impl = decomp_impl
        # capture-implementation knob (ISSUE 19): an EXPLICIT value
        # routes factor capture through ops/pallas_capture.py (fused
        # patch-extract + statistic GEMMs + EMA/wire epilogues) and
        # joins the autotuner's KNOB_ATTRS ladder; None preserves the
        # ops/factors.py path exactly, so existing configs are untouched
        if capture_impl is not None and capture_impl not in CAPTURE_IMPLS:
            raise ValueError(
                f'capture_impl must be one of {CAPTURE_IMPLS}, '
                f'got {capture_impl!r}')
        self.capture_impl = capture_impl
        self.decomp_shard = bool(decomp_shard)
        if self.decomp_shard and not stagger:
            # sharding repartitions the ACTIVE COHORT's rows — it is a
            # stagger-family feature, so the flag implies the staggered
            # schedule (and inherits its exclusions below)
            stagger = True
        self.stagger = bool(stagger)
        if self.decomp_shard and 'CommunicateInverse' in exclude_parts:
            raise ValueError(
                'decomp_shard IS a communication pattern — the '
                'CommunicateInverse ablation cannot exclude the shard '
                'exchange (drop decomp_shard for that ablation)')
        if self.stagger:
            if self.ekfac:
                raise ValueError(
                    'stagger is not supported for the ekfac variants: the '
                    'per-example moment rotation assumes a whole-table '
                    'basis change, not a per-cohort one')
            if basis_update_freq is not None or warm_start_basis:
                raise ValueError(
                    'stagger is an alternative amortization of the inverse '
                    'refresh — it does not compose with basis_update_freq '
                    'or warm_start_basis (pick one; see README '
                    '"Staggered refresh")')
        self._cohorts = None
        self._shard_plan = None
        # per-bucket stagger cadence overrides ({bucket dim: stretch},
        # plan.build_cohorts bucket_freq) — set via replan(); empty =
        # the uniform cadence
        self.bucket_stagger_freq = {}
        # resolved factor distribution (setup records it; replan keeps
        # it except where comm_pred forbids the factor-wise split)
        self._distributed = None
        # a queued replan spec (request_replan): the trainer applies it
        # host-side between steps (apply_pending_replan) — the
        # double-buffered swap point where no traced program is running
        self._pending_replan = None
        from kfac_pytorch_tpu.parallel import collectives as _coll
        self.comm_precision = _coll.check_wire_dtype(comm_precision)
        self.comm_prefetch = bool(comm_prefetch)
        if self.comm_prefetch:
            if self.comm_mode != 'inverse':
                raise ValueError(
                    "comm_prefetch applies to comm_mode='inverse' (the "
                    'decomposition gathers); the comm_pred variants '
                    'gather preconditioned gradients, which ARE the '
                    "step's consumer and cannot be deferred")
            if self.ekfac:
                raise ValueError(
                    'comm_prefetch is not supported for the ekfac '
                    'variants: the scale moments must be estimated in '
                    'the same basis the pred consumes, which prefetch '
                    'splits across steps')
        self.health = health_lib.resolve(health)
        # deterministic fault injection (chaos tests): the env snapshot
        # happens here, at construction, so the traced step is static
        self._faults = faults.from_env()
        # exclude_parts ablation flags (kfac_preconditioner_base.py:96-99)
        self.exclude_communicate_inverse = 'CommunicateInverse' in exclude_parts
        self.exclude_compute_inverse = 'ComputeInverse' in exclude_parts
        self.exclude_communicate_factor = 'CommunicateFactor' in exclude_parts
        self.exclude_compute_factor = 'ComputeFactor' in exclude_parts
        self.plan = None
        # the single writer of the runtime knobs (fac/kfac_update_freq,
        # damping, comm_precision): lazily created by
        # autotune.arbiter_for — KFACParamScheduler, the straggler
        # governor and the online tuner all PROPOSE to it instead of
        # assigning these attributes (tests/test_autotune.py pins that
        # nothing else writes them)
        self._knob_arbiter = None

    # -- setup ------------------------------------------------------------

    def setup(self, metas):
        """Build the static factor plan from capture layer metadata.

        ≙ _register_module_hooks + schedule_module_ranks (reference:
        kfac_preconditioner_base.py:132-149, inv.py:62-77). The vocab-size
        exclusion is applied here if not already filtered.

        ``metas`` is the ``{path: LayerMeta}`` dict from
        ``capture.collect_layer_meta``, or a plain meta list — e.g.
        another plan's ``.metas``, which is how the elastic resume path
        (``resilience.elastic_resume``) rebuilds the OLD world's plan
        over the layer list the new world's plan discovered.
        """
        if not isinstance(metas, dict):
            metas = {m.name: m for m in metas}
        if self.exclude_vocabulary_size is not None:
            from kfac_pytorch_tpu.capture import filter_vocab_head
            metas = filter_vocab_head(metas, self.exclude_vocabulary_size)
        distribute = self.distribute_layer_factors
        if self.variant in ('eigen', 'ekfac') and distribute is None:
            # reference auto rule: factor-wise split iff world > #layers
            # (eigen.py:66-71) — but comm_pred forbids the factor-wise
            # split (rank_a == rank_g), so a comm_mode='pred' override
            # (ctor or replan) collapses the auto rule to whole-layer
            # ownership, mirroring replan()'s resolution: any config
            # the live switch can land on must be constructible cold
            # (the adopted-knobs relaunch restarts trainers there)
            distribute = (self.comm_mode != 'pred'
                          and self.num_devices > len(metas))
        metas = self._plan_metas(metas, distribute_layer_factors=distribute)
        if self.mesh_axes is not None:
            from kfac_pytorch_tpu.meshplan.plan import build_mesh_plan
            self._mesh_plan = build_mesh_plan(
                metas, self.mesh_axes, comm_mode=self.comm_mode,
                assignment=self.assignment,
                distribute_layer_factors=bool(distribute),
                bucket_fn=self.bucket_fn, rules=self.mesh_rules)
            # the step path reads the plain data-world base plan — the
            # mesh layer only adds the extra_reduce tables at step time
            self.plan = self._mesh_plan.base
        else:
            self._mesh_plan = None
            self.plan = build_plan(
                metas, num_devices=self.num_devices,
                comm_mode=self.comm_mode, assignment=self.assignment,
                distribute_layer_factors=bool(distribute),
                bucket_fn=self.bucket_fn)
        self._distributed = bool(distribute)
        self._cohorts = None
        if self.stagger:
            self.rebase_cohorts()
        # the layout as the apply consumes it: one record, in the
        # recorder's trace and in the run's log
        record = pred_layout_record(self.plan)
        passes = self.guard_passes()
        record['guard_passes'] = sum(passes.values())
        if passes:
            record['guard_passes_over'] = passes
        obs_trace.instant('kfac.precond.setup', cat='kfac.step',
                          buckets=len(self.plan.bucket_dims), **record)
        logging.getLogger(__name__).info(
            'precond.setup: %d buckets %s, %s', len(self.plan.bucket_dims),
            self.plan.bucket_dims,
            ', '.join(f'{k} {v}' for k, v in record.items()))
        if record['decomp_groups'] and not self.hoists_update:
            # said once, not left to a compile that runs out of memory
            logging.getLogger(__name__).warning(
                'precond.setup: buckets %s are too large to invert whole, '
                'and this variant (eigh, stagger, E-KFAC or prefetch) '
                'updates them inside the health guard\'s cond, where the '
                'compiler copies each before a branch writes to it: the '
                'Cholesky variants hoist that update out (hoists_update)',
                sorted(record['decomp_groups']))
        return self.plan

    def _plan_metas(self, metas, **target):
        """``metas`` as the plan is built from them: with their input
        groups (layers that read one input keep ONE ``A`` factor and an
        inverse of it each, ``plan.build_plan``) where this configuration
        reads such rows, without them where it does not. It does on the
        Cholesky variants' path on one device; the eigh and E-KFAC paths,
        staggered cohorts (and ``decomp_shard`` with them), a mesh plan,
        more than one device and factor-wise ownership take factor and
        decomposition rows for one and the same, and keep an ``A`` a layer
        rather than a stale copy: said once, by name, and decided here
        alone (``plan.build_plan`` refuses grouped metas it cannot lay
        out). ``target``: the fields a replan is about to set."""
        if not any(m.input_group is not None for m in metas.values()):
            return metas
        get = lambda k: target.get(k, getattr(self, k))  # noqa: E731
        why = [name for name, hit in (
            ('eigh', get('method') == 'eigh'), ('E-KFAC', get('ekfac')),
            ('stagger', get('stagger')),
            ('mesh_axes', get('mesh_axes') is not None),
            ('more than one device', get('num_devices') > 1),
            ('distribute_layer_factors',
             bool(get('distribute_layer_factors')))) if hit]
        if not why:
            return metas
        logging.getLogger(__name__).info(
            'precond.setup: layers that read one input keep an A each '
            '(%s does not read a shared A factor)', ', '.join(why))
        return without_input_groups(metas)

    def rebase_cohorts(self):
        """(Re)build the staggered cohort layout for the CURRENT
        ``kfac_update_freq``. Called by :meth:`setup`, by
        KFACParamScheduler after a frequency rescale, and lazily by the
        trainer on every staggered dispatch (which also covers the
        StragglerGovernor's temporary frequency stretches). No-op when
        the layout already matches; returns the layout (None when
        stagger is off or setup hasn't run)."""
        if not self.stagger or self.plan is None:
            return None
        f = max(1, int(self.kfac_update_freq))
        overrides = {int(k): max(1, int(v))
                     for k, v in (self.bucket_stagger_freq or {}).items()}
        if (self._cohorts is None or self._cohorts.base_freq != f
                or self._cohorts.bucket_freq != overrides):
            self._cohorts = build_cohorts(self.plan, f,
                                          bucket_freq=overrides)
            self._shard_plan = None
        if self.decomp_shard and self._shard_plan is None:
            self._shard_plan = build_decomp_shard(self.plan, self._cohorts)
        return self._cohorts

    @property
    def cohorts(self):
        """The current staggered cohort layout (plan.CohortPlan)."""
        return self._cohorts

    @property
    def decomp_shard_plan(self):
        """The mesh-sharded decomposition layout
        (plan.DecompShardPlan), or None when ``decomp_shard`` is off."""
        return self._shard_plan

    @property
    def mesh_plan(self):
        """The axis-aware :class:`~kfac_pytorch_tpu.meshplan.plan.
        MeshFactorPlan` (or None without ``mesh_axes``). Its ``base``
        IS ``self.plan``; the per-axis comm ledger is
        ``mesh_plan.comm_volume(...)``."""
        return self._mesh_plan

    # -- live replanning (ISSUE 14) ---------------------------------------

    @property
    def pending_replan(self):
        """The queued :meth:`request_replan` spec (or None). The trainer
        checks this at the top of every host step and applies it via
        :meth:`apply_pending_replan` — the atomic between-steps swap."""
        return self._pending_replan

    def request_replan(self, _invalidate=True, **spec):
        """Queue a replan to be applied at the next step boundary.

        The knob arbiter calls this when the tuner commits a
        ``comm_mode`` switch (it cannot apply the switch itself — the
        factor state lives in the trainer's TrainState, and the swap
        must happen between steps, never under a traced program).
        Later requests merge per key; ``_invalidate=False`` records
        that the caller already fired the variant-cache invalidators
        (the arbiter fires them exactly once at commit time). The flag
        ORs across merged requests: one caller that still needs the
        invalidation keeps it armed even when an arbiter request (which
        already fired) merges in after it."""
        pend = dict(self._pending_replan or {'_invalidate': False})
        invalidate = bool(pend.get('_invalidate', False)) or _invalidate
        pend.update(spec)
        pend['_invalidate'] = invalidate
        self._pending_replan = pend
        return pend

    def apply_pending_replan(self, kfac_state):
        """Apply (and clear) the queued replan against ``kfac_state``;
        returns the (possibly verbatim) transported state. No-op when
        nothing is pending."""
        spec = self._pending_replan
        self._pending_replan = None
        if not spec:
            return kfac_state
        return self.replan(kfac_state, **spec)

    def replan(self, kfac_state=None, *, comm_mode=None, num_devices=None,
               bucket_overrides=None, variant=None,
               axis_name='__unchanged__', mesh_axes='__unchanged__',
               _invalidate=True):
        """Rebuild the :class:`~kfac_pytorch_tpu.plan.FactorPlan` (and
        the staggered cohort/shard tables) MID-RUN and transport the
        factor state into the new layout — the primitive behind applied
        comm-mode switching, per-bucket cadence tuning and
        zero-relaunch elasticity (ROADMAP item 2).

        Args (every one optional — unset keeps the current value):
          kfac_state: the live :class:`KFACState` to transport; None
            rebuilds the plan only (no state exists yet). Host-side:
            call OUTSIDE jit with the state addressable. When the row
            layout is unchanged (a pure comm-mode switch) the state is
            carried VERBATIM — not a byte moves, only the traced
            programs change.
          comm_mode: 'inverse' | 'pred' — the applied switch between
            communicating decompositions and communicating
            preconditioned gradients. Factor EMAs, decompositions and
            the EF residual all carry exactly (same rows, same
            owners); E-KFAC scale moments are comm-mode shaped and
            re-accumulate (their existing transport contract).
          num_devices: the new world size — the elastic lane.
            Factors AND (same-method) decompositions transport through
            ``reshard_kfac_state``'s per-layer row remap, so the
            resumed world preconditions immediately instead of passing
            gradients through until the next refresh.
          bucket_overrides: per-bucket stagger cadence
            ``{bucket dim: stretch}`` (``plan.build_cohorts``
            bucket_freq; ``{}`` clears). Stagger configs only.
          variant: switch the variant family (e.g. 'eigen' <->
            'inverse_dp'): stats_reduce/method/comm_mode re-derive from
            the variant table (an explicit ``comm_mode=`` still wins).
            Cross-METHOD switches rebuild the decomposition from the
            carried factors at the next inverse update (the trainer's
            seen-inverse gate re-arms through the invalidator).
          axis_name: the mesh axis of the new plan (elastic 1<->N
            moves); default keeps the current one.
          mesh_axes: a composed-mesh spec ('dp2xtp2' / AxisSpec tuple /
            None to clear) — the axis-aware lane. The K-FAC world
            (num_devices + axis_name) derives from its data axes, so
            it is mutually exclusive with passing those directly. A
            move that keeps the data world (dp2xtp2 -> dp2) keeps the
            base row layout — the factor state carries VERBATIM, only
            the extra tensor-axis reduce enters/leaves the trace.

        The swap is atomic at the host level: the new plan, tables and
        transported state are fully built BEFORE any attribute of this
        preconditioner changes, so a failed replan leaves the run
        untouched. The KnobArbiter invalidators fire exactly once per
        replan (``_invalidate=False`` when the arbiter already fired
        them at commit time), so every attached trainer retraces
        against the new plan and nothing else recompiles.
        """
        import copy
        import logging
        assert self.plan is not None, 'call setup() first'
        from kfac_pytorch_tpu.plan import same_row_layout
        old_plan = self.plan
        log = logging.getLogger(__name__)

        # -- resolve the target configuration -----------------------------
        new_variant = self.variant if variant is None else variant
        if new_variant not in _VARIANTS:
            raise KeyError(f'unknown variant {new_variant!r}')
        cfg = dict(_VARIANTS[new_variant])
        if variant is None:
            new_mode = self.comm_mode
        else:
            new_mode = cfg['comm_mode'] or 'pred'
        if comm_mode is not None:
            if comm_mode not in ('inverse', 'pred'):
                raise ValueError("comm_mode must be 'inverse' or 'pred', "
                                 f'got {comm_mode!r}')
            new_mode = comm_mode
        new_method = cfg['method'] if variant is not None else self.method
        new_reduce = (cfg['stats_reduce'] if variant is not None
                      else self.stats_reduce)
        new_ekfac = (cfg.get('ekfac', False) if variant is not None
                     else self.ekfac)
        new_P = self.num_devices if num_devices is None else int(num_devices)
        if new_P < 1:
            raise ValueError(f'num_devices must be >= 1, got {new_P}')
        new_axis = (self.axis_name if axis_name == '__unchanged__'
                    else axis_name)
        if mesh_axes == '__unchanged__':
            new_mesh = self.mesh_axes
            if (new_mesh is not None
                    and (num_devices is not None
                         or axis_name != '__unchanged__')):
                raise ValueError(
                    'this preconditioner is mesh-planned — resize its '
                    "K-FAC world through mesh_axes ('dp4xtp2', ...), "
                    'not num_devices/axis_name, so the axis tables '
                    'move with it')
        else:
            if num_devices is not None or axis_name != '__unchanged__':
                raise ValueError(
                    'mesh_axes derives num_devices and axis_name from '
                    'its data axes — do not also pass them')
            if mesh_axes is None:
                new_mesh = None  # clear: plain plan over current world
            else:
                from kfac_pytorch_tpu.meshplan import axes as _ma
                new_mesh = _ma.parse_mesh_spec(mesh_axes)
                new_P = _ma.world_size(new_mesh)
                dnames = _ma.data_axis_names(new_mesh)
                new_axis = dnames[0] if len(dnames) == 1 else dnames
        mesh_changed = new_mesh != self.mesh_axes
        if bucket_overrides is None:
            new_overrides = dict(self.bucket_stagger_freq or {})
        else:
            if not self.stagger:
                raise ValueError(
                    'bucket_overrides tune the STAGGERED cohort cadence '
                    '(KFAC(stagger=True)); this preconditioner refreshes '
                    'whole tables')
            new_overrides = {int(k): int(v)
                             for k, v in dict(bucket_overrides).items()}
            if any(v < 1 for v in new_overrides.values()):
                raise ValueError('bucket_overrides stretches must be '
                                 f'>= 1, got {new_overrides}')
            if any(v & (v - 1) or v > 64 for v in new_overrides.values()):
                # power-of-two stretches keep the cohort-table window at
                # F * max(stretch); coprime stretches would lcm-explode
                # the static tables (231x for {3,7,11}) that get baked
                # into every traced program
                raise ValueError('bucket_overrides stretches must be '
                                 'powers of two <= 64, got '
                                 f'{new_overrides}')
            unknown = sorted(set(new_overrides)
                             - set(old_plan.bucket_dims))
            if unknown:
                # validated HERE, before the atomic commit — a bad dim
                # failing later inside rebase_cohorts would leave the
                # preconditioner half-swapped and wedge every
                # subsequent staggered dispatch
                raise ValueError(
                    f'bucket_overrides names unknown bucket dims '
                    f'{unknown} (plan has {old_plan.bucket_dims})')

        # -- validate the combination (the ctor rules, re-checked) --------
        if new_mode == 'pred' and self.comm_prefetch:
            raise ValueError(
                "cannot replan to comm_mode='pred' with comm_prefetch: "
                'the pred gather IS the step consumer and cannot be '
                'deferred (drop comm_prefetch first)')
        if new_ekfac and self.stagger:
            raise ValueError('cannot replan a staggered preconditioner '
                             'onto an ekfac variant (stagger exclusion)')
        if self.decomp_impl is not None:
            if (self.decomp_impl in ('subspace', 'jacobi')
                    and new_method != 'eigh'):
                raise ValueError(
                    f'decomp_impl={self.decomp_impl!r} is an eigh kernel '
                    f'but the replan target decomposes by {new_method} — '
                    'switch decomp_impl first')
            if (self.decomp_impl == 'newton_schulz'
                    and new_method != 'cholesky'):
                raise ValueError(
                    "decomp_impl='newton_schulz' replaces the Cholesky "
                    f'inverse but the replan target uses {new_method} — '
                    'switch decomp_impl first')
        # comm_pred forbids the factor-wise split (reference asserts
        # rank_a == rank_g there): a distributed eigen layout replans to
        # pred by collapsing back to whole-layer ownership. The
        # resolution MIRRORS setup() exactly for the target config —
        # the ctor's explicit flag, else the eigen/ekfac auto rule
        # re-resolved for the new world/variant (a non-eigen target
        # never auto-distributes) — because a replanned plan must be
        # the plan a fresh setup of that config would build, or the
        # adopted-knobs relaunch would land state on a different row
        # layout than the live-switched incarnation ran.
        distribute = self.distribute_layer_factors
        if distribute is None and new_variant in ('eigen', 'ekfac'):
            distribute = (new_mode != 'pred'
                          and new_P > len(old_plan.metas))
        distribute = bool(distribute)
        if new_mode == 'pred':
            distribute = False

        # -- build the new layout + transported state FIRST ---------------
        replan_metas = self._plan_metas(
            {m.path: m for m in old_plan.metas}, method=new_method,
            ekfac=new_ekfac, mesh_axes=new_mesh, num_devices=new_P,
            distribute_layer_factors=distribute)
        new_mesh_plan = None
        if new_mesh is not None:
            from kfac_pytorch_tpu.meshplan.plan import build_mesh_plan
            new_mesh_plan = build_mesh_plan(
                replan_metas, new_mesh,
                comm_mode=new_mode, assignment=self.assignment,
                distribute_layer_factors=distribute,
                bucket_fn=self.bucket_fn, rules=self.mesh_rules)
            new_plan = new_mesh_plan.base
        else:
            new_plan = build_plan(
                replan_metas, num_devices=new_P,
                comm_mode=new_mode, assignment=self.assignment,
                distribute_layer_factors=distribute,
                bucket_fn=self.bucket_fn)
        clone = copy.copy(self)
        clone.variant = new_variant
        clone.stats_reduce = new_reduce
        clone.method = new_method
        clone.comm_mode = new_mode
        clone.ekfac = new_ekfac
        clone.num_devices = new_P
        clone.axis_name = new_axis
        clone.plan = new_plan
        clone.mesh_axes = new_mesh
        clone._mesh_plan = new_mesh_plan
        clone._distributed = distribute
        clone.bucket_stagger_freq = new_overrides
        clone._cohorts = None
        clone._shard_plan = None

        same_layout = same_row_layout(old_plan, new_plan)
        new_state = kfac_state
        verbatim = False
        if kfac_state is not None:
            verbatim = (
                same_layout and self.method == clone.method
                # scales are comm-mode shaped; the EF residual only
                # exists on lossy MPD reduces — both must agree for a
                # byte-for-byte carry
                and (not (self.ekfac or clone.ekfac)
                     or (self.ekfac == clone.ekfac
                         and self.comm_mode == clone.comm_mode))
                and self._tracks_comm_err == clone._tracks_comm_err
                and ((kfac_state.comm_err is None)
                     == (not clone._tracks_comm_err)))
            if not verbatim:
                from kfac_pytorch_tpu.utils.checkpoint import \
                    reshard_kfac_state
                new_state = reshard_kfac_state(self, clone, kfac_state,
                                               carry_decomp=True)

        # -- commit: swap every table/attr atomically between steps -------
        trace_changed = (
            not same_layout or new_mode != self.comm_mode
            or new_method != self.method or new_reduce != self.stats_reduce
            or new_ekfac != self.ekfac or new_axis != self.axis_name
            or mesh_changed
            or new_overrides != (self.bucket_stagger_freq or {}))
        try:
            from kfac_pytorch_tpu.autotune import _applying
        except ImportError:  # pragma: no cover — autotune is stdlib
            import contextlib
            _applying = contextlib.nullcontext
        with _applying():
            # comm_mode is a KNOB_ATTRS member: the write happens under
            # the arbiter's applying guard (single-writer discipline),
            # and the arbiter re-bases below so it never reads this as
            # a foreign write to adopt
            self.comm_mode = new_mode
        self.variant = new_variant
        self.stats_reduce = new_reduce
        self.method = new_method
        self.ekfac = new_ekfac
        self.num_devices = new_P
        self.axis_name = new_axis
        self.plan = new_plan
        self.mesh_axes = new_mesh
        self._mesh_plan = new_mesh_plan
        self._distributed = distribute
        self.bucket_stagger_freq = new_overrides
        self._cohorts = None
        self._shard_plan = None
        if self.stagger:
            self.rebase_cohorts()
        arb = self._knob_arbiter
        if arb is not None:
            arb.sync_knobs(comm_mode=new_mode)
        log.info(
            'kfac: replan applied comm_mode=%s world=%d%s%s '
            '(layout %s, state %s)', new_mode, new_P,
            f' variant={new_variant}' if variant is not None else '',
            f' bucket_overrides={new_overrides}' if new_overrides else '',
            'unchanged' if same_layout else 'rebuilt',
            'carried verbatim' if verbatim else
            ('transported' if kfac_state is not None else 'none'))
        if _invalidate and trace_changed and arb is not None:
            arb.invalidate()
        elif _invalidate and trace_changed:
            # no arbiter yet -> no trainer registered an invalidator;
            # create it lazily so later trainers still attach to one
            from kfac_pytorch_tpu.autotune import arbiter_for
            arbiter_for(self).invalidate()
        return new_state

    @property
    def _fuses_capture(self):
        """Whether the factor update is the fused Pallas capture (one
        kernel a factor row, statistics never materialised):
        ``capture_impl`` resolved to 'pallas', local statistics, one
        device."""
        reduce = ('local' if self.exclude_communicate_factor
                  else self.stats_reduce)
        return (self.resolved_capture_impl == 'pallas' and reduce == 'local'
                and self.plan is not None and self.plan.num_devices == 1)

    def layer_stats(self, acts, gs):
        """This batch's per-layer factor statistics with their ``isfinite``
        flags (``engine.LayerStats``): a pure function of the captured
        tensors that writes no state, for :meth:`step`'s ``stats``. None
        where the step makes no such statistics (the fused capture, the
        ComputeFactor ablation): the caller then screens the captured
        tensors themselves."""
        if self.exclude_compute_factor or self._fuses_capture:
            return None
        with jax.named_scope('kfac.ComputeFactor'):
            return engine.layer_stats(
                self.plan, acts, gs, self.batch_averaged,
                capture_impl=self.resolved_capture_impl)

    def guard_passes(self):
        """``{operand: count}``: the passes a healthy factor+decomposition
        step of this plan still makes over a whole operand for the health
        guard alone (an ``isfinite`` or ``!= 0`` reduction that no writer
        of the operand carries, a ``jnp.where`` over it). Empty where every
        flag comes from what the step makes anyway: ``inverse_dp`` /
        ``inverse`` with local statistics on the reference capture path."""
        if self.health is None or self.plan is None:
            return {}
        n = len(self.plan.bucket_dims)
        out = {}
        if self._fuses_capture:
            # the kernels emit no flag: the batch screen reads every
            # captured tensor, the factor guard new and stored rows
            out['captured'] = 2 * len(self.plan.metas)
            out['factors'] = 2 * n
        elif (self.stats_reduce == 'pmean'
                and not self.exclude_communicate_factor):
            out['reduced_stats'] = n    # rows off the wire: read once
        if self.method == 'eigh':
            # fresh and stored eigenvectors: no witness smaller than all
            out['eigenvectors'] = 2 * n
        elif self.stagger:
            out['cohort_inverses'] = n  # the merge's per-row screen
        if self.ekfac:
            out['ekfac_scales'] = len(self.plan.pred_groups)
        if self._tracks_comm_err:
            out['comm_err'] = n
        return out

    @property
    def hoists_update(self):
        """Whether the trainer runs this plan's factor and inverse updates
        outside the health guard's ``cond`` (``step(update_only=True,
        commit=ok)``) and preconditions inside. True where a bucket is
        inverted tile by tile (``engine.tiled_buckets``): inside a
        ``cond`` the compiler copies such a bucket before the branch
        writes to it (a second 2 GB beside the sparse decoder's 2,048
        bucket; sandbox compiles, PERF.md PR 39), outside it the groups
        are written where they lie. From the plan's shapes alone; the
        Cholesky variants without stagger, E-KFAC or prefetch."""
        return bool(self.plan is not None and self.method != 'eigh'
                    and not self.stagger and not self.ekfac
                    and not self.comm_prefetch
                    and engine.tiled_buckets(self.plan))

    @property
    def resolved_decomp_impl(self):
        """The kernel the traced step actually selects: 'auto' resolves
        per method (subspace for eigh, Newton-Schulz for Cholesky);
        None stays None — engine falls back to the legacy
        KFAC_EIGH_IMPL env read."""
        impl = self.decomp_impl
        if impl == 'auto':
            return 'subspace' if self.method == 'eigh' else 'newton_schulz'
        return impl

    @property
    def resolved_capture_impl(self):
        """The capture path the traced step actually selects: 'auto'
        resolves to the fused Pallas kernels; None stays None — engine
        keeps the ops/factors.py reference path."""
        impl = self.capture_impl
        if impl == 'auto':
            return 'pallas'
        return impl

    @property
    def warm_impl(self):
        """Does the EXPLICIT decomp_impl warm-start from the stored
        decomposition? (The trainer's warm gate ORs this with
        ``warm_start_basis`` — an env-selected impl deliberately does
        NOT auto-warm, preserving the legacy contract.)"""
        return self.decomp_impl in _WARM_IMPLS

    def init(self):
        """Initial state: identity factors (reference initializes running
        averages at identity, inv.py:82-90), zero decompositions
        (eigen.py:100-107)."""
        assert self.plan is not None, 'call setup() first'
        plan = self.plan
        factors, dzero = {}, {}
        for bdim in plan.bucket_dims:
            b = plan.buckets[bdim]
            # (rows that hold an inverse alone have no factor: plan.Bucket)
            factors[str(bdim)] = jnp.broadcast_to(
                jnp.eye(bdim, dtype=jnp.float32),
                (b.n_factor_rows, bdim, bdim))
        if self.method == 'eigh':
            decomp = {
                'evals': {str(d): jnp.zeros(
                    (plan.buckets[d].n_rows, d), jnp.float32)
                    for d in plan.bucket_dims},
                'evecs': {str(d): jnp.zeros(
                    (plan.buckets[d].n_rows, d, d), jnp.float32)
                    for d in plan.bucket_dims},
            }
            if self.ekfac:
                decomp['scales'] = self._zero_scales()
        else:
            decomp = {
                'invs': {str(d): jnp.zeros(
                    (plan.buckets[d].n_rows, d, d), jnp.float32)
                    for d in plan.bucket_dims},
            }
        return KFACState(step=jnp.zeros((), jnp.int32), factors=factors,
                         decomp=decomp, comm_err=self._zero_comm_err())

    @property
    def _tracks_comm_err(self):
        """Does this config carry an error-feedback residual? Only the
        lossy-wire MPD stats reduce compensates (the gathers have one
        contributor per row — nothing accumulates to feed back)."""
        return (self.comm_precision != 'fp32'
                and self.stats_reduce == 'pmean')

    def _zero_comm_err(self):
        """Fresh EF residual: zeros shaped like the stats stack PER
        DEVICE — globally ``[num_devices * n_rows, D, D]`` sharded over
        the kfac axis, so each device's shard is its own residual for
        the full stacked stats it contributes to the reduce."""
        if not self._tracks_comm_err:
            return None
        return {str(d): jnp.zeros(
                    (self.plan.num_devices * self.plan.buckets[d].n_rows,
                     d, d), jnp.float32)
                for d in self.plan.bucket_dims}

    def state_pspecs(self, axis_name=None):
        """PartitionSpecs matching the state layout: factor rows sharded
        over the kfac axis; decompositions sharded in comm_pred mode,
        replicated (post-gather) in comm_inverse mode; the EF residual
        (per-device error state) sharded like the factors."""
        axis_name = axis_name or self.axis_name
        sharded = P(axis_name)
        replicated = P()
        factors = {k: sharded for k in (str(d) for d in self.plan.bucket_dims)}
        dspec = sharded if self.comm_mode == 'pred' else replicated
        decomp = jax.tree.map(lambda _: dspec, self._decomp_structure())
        comm_err = ({k: sharded for k in factors}
                    if self._tracks_comm_err else None)
        return KFACState(step=replicated, factors=factors, decomp=decomp,
                         comm_err=comm_err)

    def _zero_scales(self, local=False):
        # replicated layout: one row per group member; comm_pred layout:
        # device-major local slots (K per device), like the factor rows.
        # ``local=True`` builds the PER-DEVICE shape — required when the
        # default is materialized inside the shard_map trace (the
        # pre-ekfac-checkpoint fallback in step); the global shape is
        # the host-side init()/state layout
        if self.comm_mode == 'pred':
            mult = 1 if local else self.plan.num_devices
            return {f'g{gi}': jnp.zeros(
                        (mult * pg.local_member.shape[1],
                         pg.dg, pg.da), jnp.float32)
                    for gi, pg in enumerate(self.plan.pred_groups)}
        return {f'g{gi}': jnp.zeros(
                    (len(pg.layer_idx), pg.dg, pg.da), jnp.float32)
                for gi, pg in enumerate(self.plan.pred_groups)}

    def _decomp_structure(self):
        if self.method == 'eigh':
            out = {'evals': {str(d): 0 for d in self.plan.bucket_dims},
                   'evecs': {str(d): 0 for d in self.plan.bucket_dims}}
            if self.ekfac:
                out['scales'] = {
                    f'g{gi}': 0
                    for gi in range(len(self.plan.pred_groups))}
            return out
        return {'invs': {str(d): 0 for d in self.plan.bucket_dims}}

    # -- host-side gating (trainer chooses compiled step variants) --------

    def should_update_factors(self, step: int) -> bool:
        return self.hook_enabled and step % self.fac_update_freq == 0

    def should_update_inverse(self, step: int) -> bool:
        return step % self.kfac_update_freq == 0

    def should_update_basis(self, step: int,
                            last_full_step: Optional[int] = None) -> bool:
        """Full eigendecomposition vs eigenvalue-only refresh at an
        inverse-update step (meaningful only when basis_update_freq is
        set and should_update_inverse(step) holds).

        Staleness-based (steps since the last full decomposition), not
        step-modulo: a modulo rule would alias against kfac_update_freq
        (full eigh only at the lcm of the two) and silently starve the
        basis when KFACParamScheduler rescales kfac_update_freq.
        """
        if self.basis_update_freq is None or last_full_step is None:
            return True
        return step - last_full_step >= self.basis_update_freq

    # -- the step ---------------------------------------------------------

    def step(self, state: KFACState, grads, acts=None, gs=None,
             hyper: Optional[KFACHyperParams] = None, *,
             update_factors: bool = True, update_inverse: bool = True,
             update_basis: bool = True, warm_basis: bool = False,
             factors_only: bool = False, stagger_update: bool = False,
             prefetch: bool = False, axis_name: str = '__default__',
             update_only: bool = False, commit=None, stats=None):
        """One K-FAC step: (state, grads, captured stats) ->
        (preconditioned grads, new state).

        Pure and traceable; call inside jit / shard_map. ``update_factors``
        and ``update_inverse`` are STATIC — the trainer picks them from
        ``should_update_*`` (the steps-%-freq gating of
        kfac_preconditioner_base.py:198-213 moved to the host).

        ``prefetch`` (STATIC; requires ``comm_prefetch=True``) applies
        PR 4's double-buffer to a FULL inverse update: the freshly
        gathered decomposition is published for the NEXT step while this
        step preconditions with the previous stored table — the
        CommunicateInverse gather has no same-step consumer. The trainer
        sets it only once a prior decomposition exists (a cold state
        would precondition with zeros).

        ``stagger_update`` (STATIC; requires ``stagger=True``) replaces
        the windowed full refresh: cohort ``state.step % kfac_update_freq``
        (a TRACED index — one compiled program serves every cohort) is
        decomposed and merged into the stored decomposition for the NEXT
        step, while THIS step preconditions with the previous table (the
        double-buffered publish). ``update_inverse`` is ignored when set.
        The stored decomposition must already be populated (the trainer
        runs one full decomposition first); a cold state would
        precondition with zeros.

        ``update_only`` (STATIC) with ``commit`` (a traced bool): the
        hoisted form of a factor and inverse update (:attr:`hoists_update`).
        Statistics, running averages and decomposition run as ever and the
        state comes back WITHOUT the step counted and without a gradient
        touched (``grads`` may be None); where ``commit`` is False every
        factor and inverse keeps its stored value. The trainer calls it
        outside the health guard's ``cond`` and preconditions inside with a
        plain ``step(update_factors=False, update_inverse=False)``.

        ``stats``: this batch's :meth:`layer_stats`, where the caller has
        made them already (the trainer does, before the health guard's
        ``cond``: its batch screen reads their flags); made here otherwise.

        Parity with step() (kfac_preconditioner_base.py:185-230): factor
        stats + running-avg update (+ pmean for MPD), decomposition on the
        local shard, gather/owner-pred per comm mode, KL-clipped write-back.
        """
        assert self.plan is not None, 'call setup() first'
        plan = self.plan
        if axis_name == '__default__':
            axis_name = self.axis_name
        if hyper is None:
            hyper = KFACHyperParams(lr=jnp.float32(self.lr),
                                    damping=jnp.float32(self.damping))
        damping = jnp.asarray(hyper.damping, jnp.float32)
        lr = jnp.asarray(hyper.lr, jnp.float32)

        factors = state.factors
        decomp = state.decomp
        comm_err = state.comm_err

        if update_factors and not self.exclude_compute_factor:
            reduce = self.stats_reduce
            if self.exclude_communicate_factor:
                reduce = 'local'
            cap_impl = self.resolved_capture_impl
            guard = self.health is not None
            if self._fuses_capture:
                # single-device local stats: the whole capture chain
                # (patch-extract -> factor GEMM -> EMA) collapses into
                # one fused kernel per factor — the UpdateFactors pass
                # disappears from the trace by design
                with jax.named_scope('kfac.ComputeFactor'):
                    factors = engine.update_factors_fused(
                        plan, factors, acts, gs, self.batch_averaged,
                        self.factor_decay)
                if guard:
                    # the kernels emit no flag: non-finite EMA rows keep
                    # the last good factor, a row whose STORED value is
                    # corrupt too re-initializes to the identity, by a
                    # read of both (guard_passes counts it)
                    with jax.named_scope('kfac.HealthGuard.factors'):
                        factors = engine.where_finite_rows(
                            factors, state.factors, reinit_identity=True)
                if commit is not None:
                    factors = {k: jnp.where(commit, v, state.factors[k])
                               for k, v in factors.items()}
            else:
                # named scopes mirror the reference's phase taxonomy
                # (exclude_parts names): a profiler's trace attributes
                # device time by them (benchmarks/reducers)
                if stats is None:
                    stats = self.layer_stats(acts, gs)
                rowwise = engine.rowwise_buckets(plan, reduce)
                with jax.named_scope('kfac.ComputeFactor'):
                    stacked = engine.stack_stats(
                        plan, stats.a_list, stats.g_list, skip=rowwise)
                # a row's flags are settled in the pass that writes it:
                # one whose statistic is not finite keeps its average, one
                # whose STORED value is corrupt (silent data corruption)
                # re-initializes to the identity and re-accumulates
                stat_ok = engine.rows_ok(plan, stats) if guard else None
                with jax.named_scope('kfac.UpdateFactors'):
                    # the pmean inside carries its own CommunicateFactor
                    # scope
                    extra = (self._mesh_plan.extra_reduce()
                             if self._mesh_plan is not None else ())
                    factors, comm_err = engine.update_factors(
                        plan, factors, stacked, self.factor_decay, reduce,
                        axis_name, comm_precision=self.comm_precision,
                        comm_err=comm_err, capture_impl=cap_impl,
                        extra_reduce=extra,
                        seen=engine.rows_seen(plan, acts), guard=guard,
                        stat_ok=stat_ok, commit=commit)
                    # the largest buckets: a run of rows at a time over
                    # the stored rows
                    for key in rowwise:
                        factors[key] = engine.update_factor_rows(
                            plan, int(key), state.factors[key],
                            stats.a_list, stats.g_list, stats.stacks,
                            self.factor_decay, guard=guard, commit=commit,
                            stat_ok=None if stat_ok is None
                            else stat_ok[key])
            if guard and comm_err is not None:
                # a non-finite residual row resets to zero (the always-
                # safe EF state: feedback is a correction, never load-
                # bearing) instead of re-injecting NaN into every later
                # stats reduce
                with jax.named_scope('kfac.HealthGuard.comm_err'):
                    comm_err = engine.where_finite_rows(
                        comm_err,
                        {k: jnp.zeros_like(v) for k, v in comm_err.items()})
            # SDC drill: corrupt a stored factor block AFTER the guard,
            # so the corruption lands in the state exactly as a flipped
            # bit would (tests/test_faults.py heal drill)
            factors = faults.corrupt_factors(self._faults, state.step,
                                             factors)

        if factors_only:
            # accumulate statistics but leave gradients untouched — used
            # before the first decomposition exists (an all-zero decomp
            # would zero the gradients)
            return grads, state.replace(step=state.step + 1,
                                        factors=factors, comm_err=comm_err)

        if self.exclude_compute_inverse:
            # ablation: no decomposition -> grads pass through
            # (kfac_preconditioner_base.py:206-226)
            return grads, state.replace(step=state.step + 1,
                                        factors=factors, comm_err=comm_err)

        if stagger_update:
            update_inverse = False  # stagger replaces the windowed refresh

        scales_prev = None
        if self.ekfac:
            # a state restored from a pre-ekfac checkpoint has no
            # 'scales' key: default to zeros so the pred path's validity
            # guard falls back to the Kronecker denominator instead of
            # crashing in the scale update/rotation
            scales_prev = decomp.get('scales')
            if scales_prev is None:
                scales_prev = self._zero_scales(local=True)
        if update_inverse:
            if self.method == 'eigh' and not update_basis:
                # eigenvalue-only refresh in the retained eigenbasis
                decomp_prev = decomp
                with jax.named_scope('kfac.ComputeInverse.refresh'):
                    decomp = engine.refresh_decomposition(
                        plan, factors, decomp_prev, self.eps, axis_name,
                        self.comm_mode,
                        communicate=not self.exclude_communicate_inverse,
                        comm_precision=self.comm_precision)
                if self.health is not None:
                    with jax.named_scope('kfac.HealthGuard.decomp'):
                        decomp = engine.guard_decomposition(
                            decomp, decomp_prev, 'eigh')
                # basis unchanged -> stored moments stay valid as-is
            else:
                basis_local = invs_prev = None
                if (self.warm_start_basis or self.warm_impl) and warm_basis:
                    # warm_basis is STATIC, set by the trainer only after
                    # a full decomposition exists (a zero basis would
                    # silently corrupt the rotated eigh problem; a zero
                    # inverse seed is caught by the NS residual gate).
                    # An explicit iterative decomp_impl implies warm
                    # seeding — that is what makes its rung cheap
                    if self.method == 'eigh':
                        basis_local = engine.local_evecs(
                            plan, decomp, axis_name, self.comm_mode)
                    else:
                        invs_prev = engine.local_invs(
                            plan, decomp, axis_name, self.comm_mode)
                # buckets inverted tile by tile are written over their
                # stored rows and screened as they are written
                tiled = (engine.tiled_buckets(plan)
                         if self.method != 'eigh' else ())
                stored = (engine.local_decomposition(
                    plan, decomp, axis_name, self.comm_mode, self.method)
                    if tiled else None)
                with jax.named_scope('kfac.ComputeInverse'):
                    decomp_local = engine.compute_decomposition(
                        plan, factors, damping, self.method, self.eps,
                        axis_name, basis_local=basis_local,
                        warm_sweeps=self.warm_sweeps,
                        invs_prev_local=invs_prev,
                        impl=self.resolved_decomp_impl,
                        stored_local=stored,
                        guard=self.health is not None, commit=commit)
                # chaos drill: simulated eigh/Cholesky blowup, injected
                # BEFORE the guard so the guard is what survives it
                decomp_local = faults.corrupt_decomposition(
                    self._faults, state.step, decomp_local)
                if self.health is not None or commit is not None:
                    # a non-finite decomposition row falls back to the
                    # last good one (identity when cold) instead of
                    # poisoning every later preconditioned gradient, and
                    # a batch not committed keeps every stored row;
                    # guarding PRE-gather/rotation keeps the E-KFAC
                    # moment transport on a finite basis too
                    with jax.named_scope('kfac.HealthGuard.decomp'):
                        decomp_local = engine.guard_decomposition(
                            decomp_local,
                            stored if tiled else
                            engine.local_decomposition(
                                plan, decomp, axis_name, self.comm_mode,
                                self.method),
                            self.method, done=tiled,
                            guard=self.health is not None, commit=commit)
                if self.comm_mode == 'inverse':
                    with jax.named_scope('kfac.CommunicateInverse'):
                        new_decomp = engine.gather_decomposition(
                            plan, decomp_local, axis_name,
                            communicate=not self.exclude_communicate_inverse,
                            comm_precision=self.comm_precision)
                    if self.ekfac:
                        # the EMA'd moments live in the OLD basis: carry
                        # them across the basis change by the squared-
                        # overlap transport (exact for sign flips /
                        # unmoved bases, mass-preserving otherwise)
                        with jax.named_scope('kfac.EkfacScales.rotate'):
                            scales_prev = engine.rotate_ekfac_scales(
                                plan, scales_prev, decomp, new_decomp)
                    decomp = new_decomp
                else:
                    if self.ekfac:
                        # comm_pred: rotate each local slot by its own
                        # old/new basis rows (owner-local transport)
                        with jax.named_scope('kfac.EkfacScales.rotate'):
                            scales_prev = engine.rotate_ekfac_scales_local(
                                plan, scales_prev,
                                engine.local_evecs(plan, decomp, axis_name,
                                                   'pred'),
                                decomp_local['evecs'], axis_name)
                    decomp = decomp_local
        if update_only:
            return None, state.replace(factors=factors, decomp=decomp,
                                       comm_err=comm_err)
        if self.ekfac:
            decomp = dict(decomp)
            decomp['scales'] = scales_prev
            if (update_factors and acts is not None
                    and not self.exclude_compute_factor):
                reduce = ('local' if self.exclude_communicate_factor
                          else self.stats_reduce)
                with jax.named_scope('kfac.EkfacScales'):
                    if self.comm_mode == 'pred':
                        # owner-local moments: zero scale communication
                        decomp['scales'] = engine.update_ekfac_scales_local(
                            plan, decomp, acts, gs, self.batch_averaged,
                            scales_prev, self.factor_decay, axis_name)
                    else:
                        decomp['scales'] = engine.update_ekfac_scales(
                            plan, decomp, acts, gs, self.batch_averaged,
                            scales_prev, self.factor_decay, reduce,
                            axis_name, comm_precision=self.comm_precision)
                if self.health is not None:
                    # non-finite moment rows keep the (rotated) previous
                    # moments; the pred path's zero-validity guard covers
                    # the cold case already
                    with jax.named_scope('kfac.HealthGuard.scales'):
                        decomp['scales'] = engine.where_finite_rows(
                            decomp['scales'], scales_prev)

        # double-buffer: staggered steps precondition with the PREVIOUS
        # stored table while the freshly decomposed cohort is merged into
        # the state for the next step — the cohort eigh/gather has no
        # same-step consumer, so XLA can overlap it with the pred einsums
        pred_decomp = decomp
        if prefetch and update_inverse:
            # comm_prefetch: the same trade for a FULL inverse update —
            # publish the freshly gathered table for the NEXT step,
            # precondition THIS step with the stored one (one step of
            # staleness; the gather overlaps the pred einsums)
            assert self.comm_prefetch, \
                'prefetch requires KFAC(comm_prefetch=True)'
            pred_decomp = state.decomp
        if stagger_update:
            cohorts = self._cohorts
            assert cohorts is not None, \
                'stagger_update requires KFAC(stagger=True) + setup()'
            cohort_idx = jnp.mod(jnp.asarray(state.step, jnp.int32),
                                 jnp.int32(cohorts.num_cohorts))
            if self.decomp_shard:
                # tentpole: the cohort's rows decompose balanced across
                # ALL devices (plan.build_decomp_shard) — the shard
                # exchange's two gathers carry the kfac.DecompComm
                # scope for the HLO byte ledger
                shard = self._shard_plan
                assert shard is not None, \
                    'decomp_shard requires setup() (rebase_cohorts)'
                with jax.named_scope('kfac.ComputeInverse.stagger'):
                    shard_new = engine.compute_shard_decomposition(
                        plan, cohorts, shard, factors, cohort_idx,
                        damping, self.method, self.eps, axis_name,
                        impl=self.resolved_decomp_impl,
                        decomp_prev=decomp, comm_mode=self.comm_mode,
                        warm_sweeps=self.warm_sweeps,
                        comm_precision=self.comm_precision)
                # chaos drill parity: blowups injected BEFORE the
                # merge's per-row screen, which is what heals them
                shard_new = faults.corrupt_decomposition(
                    self._faults, state.step, shard_new)
                with jax.named_scope('kfac.CommunicateInverse.stagger'):
                    decomp = engine.merge_shard_decomposition(
                        plan, shard, decomp, shard_new, cohort_idx,
                        axis_name, self.comm_mode, self.method,
                        guard=self.health is not None,
                        comm_precision=self.comm_precision)
            else:
                with jax.named_scope('kfac.ComputeInverse.stagger'):
                    cohort_new = engine.compute_cohort_decomposition(
                        plan, cohorts, factors, cohort_idx, damping,
                        self.method, self.eps, axis_name,
                        impl=self.resolved_decomp_impl,
                        decomp_prev=(decomp if self.warm_impl else None),
                        comm_mode=self.comm_mode,
                        warm_sweeps=self.warm_sweeps)
                # chaos drill parity with the full path: blowups
                # injected BEFORE the merge's per-row screen, which is
                # what heals them
                cohort_new = faults.corrupt_decomposition(
                    self._faults, state.step, cohort_new)
                with jax.named_scope('kfac.CommunicateInverse.stagger'):
                    decomp = engine.merge_cohort_decomposition(
                        plan, cohorts, decomp, cohort_new, cohort_idx,
                        axis_name, self.comm_mode, self.method,
                        communicate=not self.exclude_communicate_inverse,
                        guard=self.health is not None,
                        comm_precision=self.comm_precision)

        grad_mats = [engine.layer_grad_matrix(m, grads) for m in plan.metas]
        with jax.named_scope('kfac.Precondition'):
            if self.comm_mode == 'inverse':
                preds = engine.compute_pred_replicated(
                    plan, pred_decomp, grad_mats, damping, self.method,
                    scales=pred_decomp.get('scales') if self.ekfac else None)
            else:
                preds = engine.compute_pred_local(
                    plan, pred_decomp, grad_mats, damping, self.method,
                    axis_name,
                    communicate=not self.exclude_communicate_inverse,
                    scales=pred_decomp.get('scales') if self.ekfac else None,
                    comm_precision=self.comm_precision)

        new_grads = engine.preconditioned_grads(
            plan, grads, grad_mats, preds, lr, self.kl_clip,
            skip_clip=self.exclude_communicate_inverse)
        new_state = state.replace(step=state.step + 1, factors=factors,
                                  decomp=decomp, comm_err=comm_err)
        return new_grads, new_state
