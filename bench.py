"""Headline benchmark: ResNet-50 ImageNet-shape training with DP-KFAC on
one TPU chip — imgs/sec/chip and K-FAC step overhead vs SGD.

Mirrors the reference's SPEED mode (examples/pytorch_imagenet_resnet.py:21,
388-394: mean steady-state iteration time) and its efficiency config
(train_imagenet.sh: bs 32/chip, DP-KFAC, damping 0.002).

The flagship variant on TPU is ``inverse_dp`` (Cholesky): XLA's TPU
eigendecomposition is iteration-bound (~17x slower than the blocked
Cholesky inverse at ResNet-50 factor sizes, scripts/bench_ops.py), while
Cholesky+triangular-solve is matmul-bound and MXU-friendly. ``eigen_dp``
(the reference's default) is benchmarked at its deployed amortization
(update freq 10, pytorch_imagenet_resnet.py:94).

vs_baseline: reference 1-GPU K-FAC iteration 0.487 s at bs 32
(scripts/time_breakdown.py:26) = 65.7 imgs/s, factor+inverse every step —
compared against our inverse_dp at the same every-step setting.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}
when every leg ran; a leg that raises ends the run with a non-zero exit
code and no result line — there is no stand-in for a measurement. Runs
on whatever platform JAX selects (``JAX_PLATFORMS=cpu BENCH_MODEL=resnet20
BENCH_IMG=32 python bench.py`` is the CPU smoke of the harness; its
numbers are not device metrics and carry an ``overrides`` marker).
Extras include model-FLOPs MFU (achieved/peak, reference north star is
per-chip efficiency) and, with BENCH_BREAKDOWN=1, the exclude-parts
per-phase breakdown (scripts/time_breakdown.py parity).
"""

import json
import math
import os
import sys
import time

import jax

# Persistent compile cache: the measured programs cost many minutes of
# XLA compilation on first run; cached reruns start timing immediately.
from kfac_pytorch_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp
import numpy as np
import optax

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import models, training

# Size/model overrides exist for CPU smoke runs of the bench harness; the
# driver's official run uses the defaults (noted in extras when changed).
BATCH = int(os.environ.get('BENCH_BATCH', 32))
IMG = int(os.environ.get('BENCH_IMG', 224))
MODEL = os.environ.get('BENCH_MODEL', 'resnet50')
ITERS = int(os.environ.get('BENCH_ITERS', 20))
# optional legs start only while under this budget (seconds, counted
# from the end of the headline legs) — parsed here so a malformed value
# fails fast, before any chip work
TIME_BUDGET_S = float(os.environ.get('BENCH_TIME_BUDGET', 2400))
WARMUP = 3
BASELINE_KFAC_ITER_S = 0.487  # scripts/time_breakdown.py:26 (1 GPU, bs 32)
METRIC = 'resnet50_imagenet_dpkfac_imgs_per_sec_per_chip'

RESULT = {'metric': METRIC, 'value': None, 'unit': 'imgs/s',
          'vs_baseline': None, 'extra': {}}

# Public per-chip peak dense bf16 FLOP/s by device kind (scaling-book /
# cloud TPU docs figures). A TPU that is not in the table is an error,
# not a default.
_PEAK_FLOPS = (('v6', 918e12), ('v5p', 459e12), ('v5lite', 197e12),
               ('v5e', 197e12), ('v4', 275e12), ('v3', 123e12),
               ('v2', 45e12))


def _peak_flops(device):
    kind = getattr(device, 'device_kind', '').lower().replace(' ', '')
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    raise KeyError(f'no peak FLOP/s on record for device kind {kind!r}')


def _model_flops_per_iter(model, batch):
    """Model-FLOPs per training iteration: XLA cost analysis of the jitted
    forward × 3 (fwd + bwd ≈ 2×fwd, the standard MFU convention — K-FAC
    math is deliberately excluded: MFU counts useful model work)."""
    def fwd(variables, x):
        return model.apply(variables, x, train=False)

    from kfac_pytorch_tpu import capture
    variables = capture.init(model, jax.random.PRNGKey(0), batch['input'],
                             train=False)
    cost = (jax.jit(fwd).lower(variables, batch['input'])
            .compile().cost_analysis())
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    fwd_flops = float(cost.get('flops', 0.0)) if cost else 0.0
    return 3.0 * fwd_flops if fwd_flops > 0 else None


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def _time_steps(step, state, batch, iters, warmup=WARMUP, **kw):
    # each step consumes the previous step's state, so fencing the final
    # metrics fences the whole chain exactly
    from kfac_pytorch_tpu.utils.profiling import host_fence
    for _ in range(warmup):
        state, m = step(state, batch, **kw)
    host_fence(m)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step(state, batch, **kw)
    host_fence(m)
    return (time.perf_counter() - t0) / iters, state


def _measure_variant(model, tx, batch, variant, fac, kfac_freq, iters,
                     basis_freq=None, warm_start=False, eigh_impl=None):
    # the amortized/warm paths dispatch distinct compiled programs (the
    # eigenvalue-refresh / warm-full variants) first at step kfac_freq —
    # warm past it so their XLA compiles cannot land inside the timed
    # window (with warm_start, the steady state measured IS warm fulls)
    warmup = (WARMUP if basis_freq is None and not warm_start
              else kfac_freq + 2)
    prior_impl = os.environ.get('KFAC_EIGH_IMPL')
    if eigh_impl is not None:
        # trace-time knob: set before the step variants are first traced
        os.environ['KFAC_EIGH_IMPL'] = eigh_impl
    try:
        precond = kfac.KFAC(variant=variant, lr=0.0125, damping=0.002,
                            fac_update_freq=fac, kfac_update_freq=kfac_freq,
                            num_devices=1, axis_name=None,
                            assignment='balanced',
                            basis_update_freq=basis_freq,
                            warm_start_basis=warm_start)
        state = training.init_train_state(model, tx, precond,
                                          jax.random.PRNGKey(0),
                                          batch['input'])
        step = training.build_train_step(model, tx, precond, _ce,
                                         extra_mutable=('batch_stats',))
        s, _ = _time_steps(step, state, batch, iters, warmup=warmup,
                           lr=0.0125, damping=0.002)
    finally:
        if eigh_impl is not None:
            if prior_impl is None:
                os.environ.pop('KFAC_EIGH_IMPL', None)
            else:
                os.environ['KFAC_EIGH_IMPL'] = prior_impl
    return s


def _phase_breakdown(model, tx, batch, iters=10):
    """exclude-parts subtraction ladder on the flagship every-step config
    (reference scripts/time_breakdown.py semantics). 5 extra compiles —
    opt-in via BENCH_BREAKDOWN=1."""
    from kfac_pytorch_tpu.utils.profiling import exclude_parts_breakdown

    def make_step(exclude):
        precond = kfac.KFAC(variant='inverse_dp', lr=0.0125, damping=0.002,
                            fac_update_freq=1, kfac_update_freq=1,
                            num_devices=1, axis_name=None,
                            assignment='balanced', exclude_parts=exclude)
        state = training.init_train_state(
            model, tx, precond, jax.random.PRNGKey(0), batch['input'])
        step = training.build_train_step(model, tx, precond, _ce,
                                         extra_mutable=('batch_stats',))
        return step, state

    bd = exclude_parts_breakdown(make_step, batch, iters=iters,
                                 lr=0.0125, damping=0.002)
    return {k: round(v, 4) for k, v in bd.items()}


def _micro_model():
    """The micro-bench workload: a 6x192 MLP whose factor slots land in
    comparable buckets (so amortization schedules have something to
    balance), with a deterministic synthetic batch. Shared by the
    stagger micro-bench and the autotune leg."""
    import flax.linen as linen

    from kfac_pytorch_tpu import nn as knn

    B, D_IN, WIDTH, DEPTH = 16, 48, 192, 6

    class MicroMLP(linen.Module):
        @linen.compact
        def __call__(self, x, train=True):
            for i in range(DEPTH):
                x = linen.relu(knn.Dense(WIDTH, name=f'fc{i}')(x))
            return knn.Dense(10, name='head')(x)

    rng = np.random.RandomState(0)
    batch = {'input': jnp.asarray(rng.randn(B, D_IN), jnp.float32),
             'label': jnp.asarray(rng.randint(0, 10, B))}
    return MicroMLP(), batch, f'micro-mlp{DEPTH}x{WIDTH}', B


def _micro_bench():
    """CPU micro-benchmark of the stacked K-FAC step: steady-state vs
    refresh-step wall time, with and without the staggered cohort
    refresh, plus the eigh rows-per-step accounting.

    Runs wherever a backend exists (the fallback path forces a 1-device
    CPU via KFAC_PLATFORM); the model is a 6x192 MLP whose factor slots
    land in comparable buckets, so the staggered schedule can actually
    flatten the refresh spike (a single dominant factor would bound the
    flattening at its own D^3). Every step is fenced
    (utils/profiling.host_fence) so per-step walls are real.
    """
    from kfac_pytorch_tpu.utils.profiling import host_fence

    F = int(os.environ.get('BENCH_MICRO_FREQ', 4))
    windows = int(os.environ.get('BENCH_MICRO_WINDOWS', 5))
    model, batch, model_name, B = _micro_model()
    tx = training.sgd(0.05, momentum=0.9)

    def run(stagger):
        precond = kfac.KFAC(variant='eigen_dp', lr=0.05, damping=0.003,
                            fac_update_freq=1, kfac_update_freq=F,
                            num_devices=1, axis_name=None, stagger=stagger)
        state = training.init_train_state(model, tx, precond,
                                          jax.random.PRNGKey(0),
                                          batch['input'])
        step = training.build_train_step(model, tx, precond, _ce)
        # warm past one full window so every variant (cold full at step
        # 0, refresh/stagger afterwards) is compiled before timing
        warm = F + 2
        for _ in range(warm):
            state, m = step(state, batch, lr=0.05, damping=0.003)
        host_fence(m)
        walls = []  # (step index, seconds)
        for i in range(windows * F):
            t0 = time.perf_counter()
            state, m = step(state, batch, lr=0.05, damping=0.003)
            host_fence(m)
            walls.append((warm + i, time.perf_counter() - t0))
        return walls, precond

    # structural timings are per-(step-phase) MINIMA across windows: each
    # cohort/phase runs the identical program every window, so the min is
    # its true cost and anything above it is host noise (this container
    # shares cores) — a raw max would let one GC pause masquerade as an
    # imbalanced cohort. Raw medians/maxes ride along for honesty.
    med = lambda xs: float(np.median(xs)) * 1e3  # noqa: E731
    off, _ = run(False)
    refresh = [t for s, t in off if s % F == 0]
    steady = [t for s, t in off if s % F != 0]
    on, pre_on = run(True)
    stag = [t for _, t in on]
    by_cohort = [min(t for s, t in on if s % F == c) * 1e3
                 for c in range(F)]
    layout = pre_on.cohorts
    total_rows = layout.total_rows()
    budget = math.ceil(total_rows / F)
    steady_ms = min(steady) * 1e3
    refresh_ms = min(refresh) * 1e3
    stag_mean_ms = med(stag)
    stag_max_ms = float(np.max(stag)) * 1e3
    peak_ms = max(by_cohort)
    typ_ms = float(np.median(by_cohort))
    return {
        'platform': jax.default_backend(),
        'model': model_name, 'batch': B,
        'variant': 'eigen_dp', 'kfac_update_freq': F,
        'timed_steps_per_mode': windows * F,
        'samples_per_sec': round(B * F / (sum(by_cohort) / 1e3), 2),
        'unstaggered': {
            'steady_ms': round(steady_ms, 3),
            'refresh_ms': round(refresh_ms, 3),
            # the spike the tentpole removes: refresh steps cost a
            # multiple of steady steps when every bucket eigh-decomposes
            # at once
            'spike_over_steady': round(refresh_ms / steady_ms, 3),
        },
        'staggered': {
            'median_ms': round(stag_mean_ms, 3),
            'raw_max_ms': round(stag_max_ms, 3),
            # per-cohort minima across windows (noise-stripped): the
            # structurally heaviest step vs the typical step — the
            # flatness of the staggered schedule (acceptance: ~<=1.5)
            'cohort_ms': [round(c, 3) for c in by_cohort],
            'peak_ms': round(peak_ms, 3),
            'peak_over_typical': round(peak_ms / typ_ms, 3),
            'peak_over_unstaggered_refresh': round(
                peak_ms / refresh_ms, 3),
        },
        'eigh_rows': {
            'total': total_rows,
            'max_per_step': layout.max_rows_per_step(),
            'budget_ceil_total_over_freq': budget,
            'padded_static_per_step': layout.padded_rows_per_step(),
        },
        'window_ms': {
            # full-window totals (noise-stripped): the staggered total
            # carries the static-shape padding overhead
            # (padded_static_per_step vs max_per_step rows) in exchange
            # for the flattened per-step peak
            'unstaggered': round((F - 1) * steady_ms + refresh_ms, 3),
            'staggered': round(sum(by_cohort), 3),
        },
    }


def _micro_autotune():
    """Closed-loop autotune leg of the CPU micro-bench: start the
    eigen_dp micro config at the PESSIMAL cadence (kfac_update_freq=1 —
    a full eigh every step, the configuration a hand-tuner would never
    ship) and let the ``autotune.KnobController`` climb the bounded
    frequency ladder from measured step times. Reports the decision
    tail, the final knob state, and steady-state step time against the
    best hand-configured cadence of the same sweep — the acceptance
    comparison ``scripts/autotune_smoke.py`` gates on. Mirrors the
    ``drift`` block wiring: the block lands in the micro-mode extras,
    so the record shows what the tuner chose.
    """
    from kfac_pytorch_tpu import autotune
    from kfac_pytorch_tpu.utils.profiling import host_fence

    model, batch, name, _ = _micro_model()
    tx = training.sgd(0.05, momentum=0.9)
    f_max = int(os.environ.get('BENCH_AUTOTUNE_FMAX', 8))
    budget = int(os.environ.get('BENCH_AUTOTUNE_STEPS', 600))

    def make(freq):
        precond = kfac.KFAC(variant='eigen_dp', lr=0.05, damping=0.003,
                            fac_update_freq=1, kfac_update_freq=freq,
                            num_devices=1, axis_name=None)
        state = training.init_train_state(model, tx, precond,
                                          jax.random.PRNGKey(0),
                                          batch['input'])
        step = training.build_train_step(model, tx, precond, _ce)
        return precond, state, step

    def timed(step, state):
        t0 = time.perf_counter()
        state, m = step(state, batch, lr=0.05, damping=0.003)
        host_fence(m)
        return state, time.perf_counter() - t0

    def steady_mean(step, state, n):
        walls = []
        for _ in range(n):
            state, dt = timed(step, state)
            walls.append(dt)
        return state, sum(walls) / len(walls)

    # the hand-configured sweep the closed loop replaces: per-cadence
    # steady mean, warmed past every variant compile
    hand = {}
    ladder = []
    f = 1
    while f <= f_max:
        ladder.append(f)
        f *= 2
    for F in ladder:
        _, state, step = make(F)
        for _ in range(F + 3):
            state, _ = timed(step, state)
        _, hand[F] = steady_mean(step, state, 2 * f_max)
    best_f = min(hand, key=hand.get)

    precond, state, step = make(1)
    # window = 4 full refresh periods at the ladder top: enough samples
    # per phase set that one noisy host window (GC pause, CI neighbor)
    # cannot flip a probe verdict and strand the true optimum on
    # cooldown — CPU wall times are the noisiest feed the controller
    # sees, and the smoke gate rides this leg
    ctl = autotune.KnobController(
        precond, window=4 * f_max, settle=3, rel_improve=0.05,
        dwell_windows=1, cooldown=2, steady_every=0,
        tune=('kfac_update_freq',), freq_bounds=(1, f_max))
    state, _ = timed(step, state)  # cold full decomposition + compile
    steps_run = 0
    while steps_run < budget and ctl.state != 'steady':
        state, dt = timed(step, state)
        ctl.record(step.last_phases, dt)
        steps_run += 1
    state, steady = steady_mean(step, state, 2 * f_max)
    return {
        'enabled': True, 'model': name, 'platform': jax.default_backend(),
        'initial_kfac_update_freq': 1,
        'hand_sweep_mean_ms': {str(k): round(v * 1e3, 3)
                               for k, v in hand.items()},
        'hand_best': {'kfac_update_freq': best_f,
                      'mean_ms': round(hand[best_f] * 1e3, 3)},
        'final_kfac_update_freq': precond.kfac_update_freq,
        'converged_to_hand_best': precond.kfac_update_freq == best_f,
        'steady_mean_ms': round(steady * 1e3, 3),
        'steady_over_hand_best': round(steady / hand[best_f], 4),
        'steps_to_steady': steps_run,
        'windows': ctl.windows,
        'controller': ctl.report(),
    }


def _micro_decomp():
    """Decomposition-wall leg of the CPU micro-bench (ROADMAP item 5):

    (a) MEASURED steady-state step time of the ``decomp_impl`` ladder
    rungs at one refresh cadence — the cold XLA kernels (QDWH eigh for
    eigen_dp, batched Cholesky for inverse_dp) vs their warm iterative
    replacements (subspace tracking / Newton-Schulz), each timed over
    full refresh windows so the decomposition cost lands in the mean at
    its true cadence. The acceptance comparison: the iterative rungs'
    steady state beats the full-eigh rung's at the same
    ``kfac_update_freq``.

    (b) the sharded-vs-owner-local cohort CRITICAL PATH on an
    imbalanced plan (one device owns every large factor — the
    real-world trigger), computed from the static cohort/shard tables:
    the padded per-device Σ rows·D³ each compiled program actually
    executes per step. Deterministic host arithmetic — no mesh needed,
    so the number is exact on any platform (the wire price of
    the shard exchange is the separately-pinned DecompComm ledger,
    scripts/comm_count.py).
    """
    from kfac_pytorch_tpu.utils.profiling import host_fence

    F = int(os.environ.get('BENCH_DECOMP_FREQ', 4))
    windows = int(os.environ.get('BENCH_DECOMP_WINDOWS', 3))
    model, batch, model_name, B = _micro_model()
    tx = training.sgd(0.05, momentum=0.9)

    def steady_ms(variant, impl):
        precond = kfac.KFAC(variant=variant, lr=0.05, damping=0.003,
                            fac_update_freq=1, kfac_update_freq=F,
                            num_devices=1, axis_name=None,
                            decomp_impl=impl)
        state = training.init_train_state(model, tx, precond,
                                          jax.random.PRNGKey(0),
                                          batch['input'])
        step = training.build_train_step(model, tx, precond, _ce)
        # warm past TWO full windows: the cold full at step 0, the
        # refresh variants, and (for iterative impls) the first WARM
        # full must all be compiled before the timed windows
        for _ in range(2 * F + 2):
            state, m = step(state, batch, lr=0.05, damping=0.003)
        host_fence(m)
        # per-position minima across windows (the same noise-stripping
        # the stagger micro uses: each position reruns one program;
        # anything above its min is host noise), then the window mean —
        # refresh steps weighed at exactly 1/F
        walls = [[] for _ in range(F)]
        for i in range(windows * F):
            t0 = time.perf_counter()
            state, m = step(state, batch, lr=0.05, damping=0.003)
            host_fence(m)
            walls[i % F].append(time.perf_counter() - t0)
        return sum(min(w) for w in walls) / F * 1e3

    ladder = {
        'eigen_dp:xla': ('eigen_dp', 'xla'),
        'eigen_dp:subspace': ('eigen_dp', 'subspace'),
        'inverse_dp:xla': ('inverse_dp', 'xla'),
        'inverse_dp:newton_schulz': ('inverse_dp', 'newton_schulz'),
    }
    impl_ms = {k: round(steady_ms(v, i), 3) for k, (v, i) in ladder.items()}
    full_eigh = impl_ms['eigen_dp:xla']
    best_iter = min(impl_ms['eigen_dp:subspace'],
                    impl_ms['inverse_dp:newton_schulz'])

    # (b) static critical-path tables on the imbalanced plan: every
    # 512-factor layer sits at index i % 4 == 0, so round-robin
    # ownership puts ALL large rows on device 0 of a 4-device plan
    from kfac_pytorch_tpu.capture import LayerMeta
    from kfac_pytorch_tpu.plan import (build_cohorts, build_decomp_shard,
                                       build_plan)
    P = 4
    dims = [(512, 512) if i % P == 0 else (48, 48) for i in range(16)]
    metas = {}
    for i, (di, do) in enumerate(dims):
        m = LayerMeta(name=f'l{i}', path=(f'l{i}',), kind='dense',
                      use_bias=False, in_dim=di, out_dim=do,
                      kernel_shape=(di, do))
        metas[m.name] = m
    plan = build_plan(metas, num_devices=P, comm_mode='pred')
    cohorts = build_cohorts(plan, F)
    shard = build_decomp_shard(plan, cohorts)
    owner_cost = sum(t.shape[2] * d ** 3 for d, t in cohorts.rows.items())
    shard_cost = sum(t.shape[2] * d ** 3 for d, t in shard.src.items())
    counts = shard.shard_count
    mean_rows = float(counts.mean()) if counts.size else 0.0
    return {
        'platform': jax.default_backend(),
        'model': model_name, 'kfac_update_freq': F,
        'timed_steps_per_impl': windows * F,
        'impl_steady_ms': impl_ms,
        'full_eigh_ms': full_eigh,
        # the acceptance bit: the inverse-free ladder's best rung under
        # the full-eigh rung at the same refresh cadence. On THIS
        # platform that is Newton-Schulz — CPU LAPACK syevd is fast, so
        # the subspace tracker's GEMMs lose here, while on the modeled
        # chip the fenced QDWH constants (seconds per refresh,
        # perfmodel.FENCED_EIGH_POINTS) put BOTH iterative rungs orders
        # of magnitude under full eigh (the predicted block's
        # ComputeInverse_subspace/_ns vs ComputeInverse_eigh_full)
        'iterative_beats_full_eigh': bool(best_iter < full_eigh),
        'best_iterative_ms': best_iter,
        # regression guard on the NS rung ITSELF: full-eigh is an easy
        # yardstick (cold Cholesky already beats it), so also bound NS
        # against its own method's cold kernel — 1.5x slack absorbs the
        # CPU noise floor (NS ~= Cholesky here) while catching a 2x
        # kernel regression that the eigh comparison would mask
        'ns_within_1p5x_cholesky': bool(
            impl_ms['inverse_dp:newton_schulz']
            < 1.5 * impl_ms['inverse_dp:xla']),
        'note': ('off-chip: kernel ranking is platform-specific — '
                 'LAPACK eigh is fast on CPU; the iterative rungs are '
                 'shaped for the chip, where QDWH eigh is '
                 'iteration-bound (see predicted.scenarios.*.phases_s)'),
        'shard': {
            'devices': P, 'layers': len(dims),
            'imbalance': 'all 512-dim factors owned by device 0',
            'owner_cohort_cost_d3': int(owner_cost),
            'sharded_cohort_cost_d3': int(shard_cost),
            'critical_path_ratio': round(shard_cost / owner_cost, 4),
            'sharded_below_owner': bool(shard_cost < owner_cost),
            'rows_per_device': {
                'max': int(counts.max()) if counts.size else 0,
                'mean': round(mean_rows, 2),
                'within_2x_mean': bool(
                    counts.max() <= 2 * max(mean_rows, 1.0)),
            },
        },
    }


def _micro_capture():
    """Capture hot-path leg of the CPU micro-bench (ISSUE 19): the
    ``capture_impl`` ladder's kernels head-to-head at real factor
    shapes. Unifies the two retired offline scripts into the one
    emission contract every other leg already rides:

    - scripts/bench_extract_patches.py's im2col timing survives as
      ``patch_extract_ms`` — the HBM patch-matrix round trip the fused
      conv-A kernel deletes is priced right next to the kernels that
      delete it;
    - scripts/bench_ops.py's factor-GEMM leg survives as the
      ``xla_ms`` column (``ops.compute_a_conv`` / ``_dense`` at the
      same conv shapes it used).

    Off-chip the Pallas kernels run in INTERPRETER mode (the parity
    configuration tests/test_pallas_capture.py pins), so the ranking
    here is a correctness artifact, not the chip's: the fused win is
    skipped HBM traffic, which a CPU interpreter cannot exhibit. The
    block therefore always carries ``fused_beats_unfused`` AND a
    platform note — the CI capture gate accepts either the win or the
    note (scripts/ci_gate semantics mirror the decomp leg's).
    """
    import functools

    from kfac_pytorch_tpu.ops import factors, pallas_capture

    interpret = pallas_capture.interpret_default()
    iters = int(os.environ.get('BENCH_CAPTURE_ITERS', 3))

    def best_ms(fn, *args):
        fn(*args)  # compile
        walls = []
        for i in range(iters):
            varied = tuple(a + jnp.asarray(1e-3 * (i + 1), a.dtype)
                           for a in args)
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*varied))
            walls.append(time.perf_counter() - t0)
        return min(walls) * 1e3

    rng = np.random.RandomState(0)
    out = {'platform': jax.default_backend(), 'interpret': bool(interpret),
           'kernels': {}}
    parity = []

    # dense A at MLP-head shape (bench_ops' GEMM regime, sized for CPU)
    d_in = int(os.environ.get('BENCH_CAPTURE_DIM', 256))
    a_dense = jnp.asarray(rng.randn(32, d_in).astype(np.float32))
    x_ms = best_ms(jax.jit(lambda a: factors.compute_a_dense(a, True)),
                   a_dense)
    p_ms = best_ms(jax.jit(functools.partial(
        pallas_capture.compute_a_dense, use_bias=True,
        interpret=interpret)), a_dense)
    parity.append(bool(np.array_equal(
        np.asarray(factors.compute_a_dense(a_dense, True)),
        np.asarray(pallas_capture.compute_a_dense(
            a_dense, True, interpret=interpret)))))
    out['kernels']['a_dense'] = {'xla_ms': round(x_ms, 3),
                                 'pallas_ms': round(p_ms, 3)}

    # conv A: the patch-extract fusion target. The standalone im2col
    # cost is what the fused kernel never pays.
    a_conv = jnp.asarray(rng.randn(8, 14, 14, 64).astype(np.float32))
    ks, st, pad = (3, 3), (1, 1), (1, 1)
    patch_ms = best_ms(jax.jit(lambda a: factors.extract_patches(
        a, ks, st, pad)), a_conv)
    x_ms = best_ms(jax.jit(lambda a: factors.compute_a_conv(
        a, ks, st, pad, False)), a_conv)
    p_ms = best_ms(jax.jit(functools.partial(
        pallas_capture.compute_a_conv, kernel_size=ks, strides=st,
        padding=pad, use_bias=False, interpret=interpret)), a_conv)
    # this conv shape is MULTI-TILE (the per-image VMEM footprint splits
    # the batch across grid steps), so the contract is value-equal up to
    # fp32 summation order — bitwise holds only for single-tile runs
    # (tests/test_pallas_capture.py pins both regimes)
    parity.append(bool(np.allclose(
        np.asarray(pallas_capture.compute_a_conv(
            a_conv, ks, st, pad, False, interpret=interpret)),
        np.asarray(factors.compute_a_conv(a_conv, ks, st, pad, False)),
        rtol=1e-6, atol=1e-7)))
    out['kernels']['a_conv'] = {'xla_ms': round(x_ms, 3),
                                'pallas_ms': round(p_ms, 3),
                                'patch_extract_ms': round(patch_ms, 3)}

    # EMA epilogue: two-pass stat + update_running_avg vs the fused
    # accumulator epilogue (the per-step HBM read-modify-write saved)
    g = jnp.asarray(rng.randn(32, d_in).astype(np.float32))
    cur = jnp.asarray(rng.randn(d_in, d_in).astype(np.float32))
    x_ms = best_ms(jax.jit(lambda t, c: factors.update_running_avg(
        factors.compute_g_dense(t, True), c, 0.95)), g, cur)
    p_ms = best_ms(jax.jit(
        lambda t, c: pallas_capture.compute_g_dense(
            t, True, ema=(c, 0.95), interpret=interpret)), g, cur)
    out['kernels']['g_dense_ema'] = {'xla_ms': round(x_ms, 3),
                                     'pallas_ms': round(p_ms, 3)}

    # EF wire-quantize: the two-pass compress + residual vs one pass
    x = jnp.asarray(rng.randn(4, d_in, d_in).astype(np.float32))
    r = jnp.zeros_like(x)

    def two_pass(t, res):
        xc = t + res
        wire = xc.astype(jnp.bfloat16)
        return wire, xc - wire.astype(t.dtype)

    x_ms = best_ms(jax.jit(two_pass), x, r)
    p_ms = best_ms(jax.jit(functools.partial(
        pallas_capture.ef_quantize, interpret=interpret)), x, r)
    w0, r0 = two_pass(x, r)
    w1, r1 = pallas_capture.ef_quantize(x, r, interpret=interpret)
    parity.append(bool(np.array_equal(np.asarray(w0), np.asarray(w1))
                       and np.array_equal(np.asarray(r0),
                                          np.asarray(r1))))
    out['kernels']['ef_quantize'] = {'xla_ms': round(x_ms, 3),
                                     'pallas_ms': round(p_ms, 3)}

    fused_wins = all(k['pallas_ms'] < k['xla_ms']
                     for k in out['kernels'].values())
    out['parity_ok'] = all(parity)
    out['fused_beats_unfused'] = bool(fused_wins)
    out['note'] = (
        'off-chip, Pallas runs in interpreter mode (the parity '
        'configuration), so kernel ranking is a correctness artifact — '
        'the fused win is skipped HBM patch-matrix traffic and folded '
        'epilogues, which only the chip exhibits (see '
        'predicted.scenarios.*.phases_s.ComputeFactor_pallas); not '
        'measured on the chip')
    return out


def _attach_drift(extra, measured=None, variant='inverse_dp',
                  platform=None, source=None):
    """Attach the measured-vs-predicted ``drift`` block (obs.drift) to
    the bench extras — advisory (``comparable: false``) on every
    platform but the modeled chip."""
    from kfac_pytorch_tpu.obs import drift as obs_drift
    if measured is None:
        measured = obs_drift.measured_from_bench_extras(extra)
    extra['drift'] = obs_drift.drift_block(
        measured, extra.get('predicted'), platform=platform,
        variant=variant, source=source)


def _run_micro_mode():
    """BENCH_MICRO=1 entrypoint (the CI smoke job): the stacked K-FAC
    step micro-bench on whatever platform JAX selected, one JSON line.
    A leg that raises ends the run."""
    from kfac_pytorch_tpu import perfmodel
    from kfac_pytorch_tpu.obs import drift as obs_drift
    extra = RESULT['extra']
    # stable keys: a leg switched off reads as an explicit null
    extra.update(autotune=None, decomp=None, capture=None)
    micro = _micro_bench()
    RESULT['value'] = micro['samples_per_sec']
    RESULT['unit'] = 'samples/s'
    extra['platform'] = jax.default_backend()
    extra['micro'] = micro
    # the micro phases vs the analytic model (advisory off the chip)
    extra['predicted'] = perfmodel.predict_block()
    _attach_drift(extra, measured=obs_drift.micro_measured(micro),
                  variant='eigen_dp', platform=extra['platform'],
                  source='micro')
    # the closed-loop leg: what the tuner chooses for this workload
    if os.environ.get('BENCH_MICRO_AUTOTUNE', '1') != '0':
        extra['autotune'] = _micro_autotune()
    # the decomposition-wall leg: decomp_impl ladder steady-state + the
    # sharded-vs-owner cohort critical path on an imbalanced plan
    if os.environ.get('BENCH_MICRO_DECOMP', '1') != '0':
        extra['decomp'] = _micro_decomp()
    # the capture hot-path leg: capture_impl ladder kernels head-to-head
    # (fused Pallas vs unfused XLA + the standalone patch-extract cost)
    if os.environ.get('BENCH_MICRO_CAPTURE', '1') != '0':
        extra['capture'] = _micro_capture()
    print(json.dumps(RESULT), flush=True)


def _run(devices):
    n_classes = 1000 if MODEL in ('resnet18', 'resnet34', 'resnet50',
                                  'resnet101', 'resnet152', 'resnext50',
                                  'resnext101', 'inceptionv4',
                                  'inception-v4', 'densenet121',
                                  'densenet169', 'densenet201') else 10
    rng = np.random.RandomState(0)
    batch = {
        'input': jnp.asarray(rng.randn(BATCH, IMG, IMG, 3), jnp.bfloat16),
        'label': jnp.asarray(rng.randint(0, n_classes, BATCH)),
    }
    model = models.get_model(MODEL, num_classes=n_classes,
                             dtype=jnp.bfloat16)
    tx = training.sgd(0.0125, momentum=0.9, weight_decay=5e-5)
    extra = RESULT['extra']
    # pre-seed every leg's key with null so the output contract is stable:
    # a failed/skipped leg reads as an explicit null, not an absent key
    extra.update({k: None for k in (
        'sgd_iter_s', 'inverse_dp_iter_s_freq1', 'inverse_dp_iter_s_freq10',
        'inverse_dp_iter_s_freq1_warm_ns', 'eigen_dp_iter_s_freq10',
        'eigen_dp_iter_s_freq10_basis100',
        'eigen_dp_iter_s_freq10_warm_subspace',
        'ekfac_iter_s_freq10_basis100',
        'kfac_overhead_vs_sgd_freq1', 'kfac_overhead_vs_sgd_freq10',
        'model_flops_per_iter', 'mfu_inverse_dp_freq1', 'peak_flops',
        'phase_breakdown_s', 'autotune', 'decomp', 'capture')})
    extra['eigh_impl'] = os.environ.get('KFAC_EIGH_IMPL', 'xla')
    extra.update({'batch': BATCH, 'img': IMG, 'device': str(devices[0]),
                  'device_kind': getattr(devices[0], 'device_kind', None)})
    # a smoke-config run must never read as an official resnet50 number
    if (BATCH, IMG, MODEL, ITERS) != (32, 224, 'resnet50', 20):
        extra['overrides'] = {'batch': BATCH, 'img': IMG,
                              'model': MODEL, 'iters': ITERS}

    # headline: flagship inverse_dp with factor+inverse EVERY step — the
    # reference breakdown setting
    inv1_s = _measure_variant(model, tx, batch, 'inverse_dp', 1, 1, ITERS)
    imgs_per_sec = BATCH / inv1_s
    RESULT['value'] = round(imgs_per_sec, 2)
    RESULT['vs_baseline'] = round(
        imgs_per_sec / (BATCH / BASELINE_KFAC_ITER_S), 3)
    extra['inverse_dp_iter_s_freq1'] = round(inv1_s, 4)

    # the optional legs must not push the process into an outer timeout:
    # each starts only while under the budget (on a cold compile cache
    # the fresh programs cost minutes each) and reads null when skipped.
    # A leg that RAISES ends the run.
    t_start = time.perf_counter()

    def _optional(fn):
        if time.perf_counter() - t_start > TIME_BUDGET_S:
            print('BENCH_TIME_BUDGET exceeded — skipping remaining '
                  'optional leg', file=sys.stderr, flush=True)
            return None
        return fn()

    # SGD baseline (for the overhead ratios; the headline doesn't need it)
    def _sgd():
        state = training.init_train_state(model, tx, None,
                                          jax.random.PRNGKey(0),
                                          batch['input'])
        sgd_step = training.build_train_step(model, tx, None, _ce,
                                             extra_mutable=('batch_stats',))
        s, _ = _time_steps(sgd_step, state, batch, ITERS)
        return s

    def _leg(key, seconds, digits=4):
        # record a completed optional leg (None = skipped stays the
        # pre-seeded null)
        if seconds is not None:
            extra[key] = round(seconds, digits)
        return seconds

    sgd_s = _leg('sgd_iter_s', _optional(_sgd))
    if sgd_s is not None:
        extra['kfac_overhead_vs_sgd_freq1'] = round(inv1_s / sgd_s, 3)

    inv10_s = _leg('inverse_dp_iter_s_freq10', _optional(
        lambda: _measure_variant(model, tx, batch, 'inverse_dp', 10, 10,
                                 ITERS)))
    if inv10_s is not None and sgd_s is not None:
        extra['kfac_overhead_vs_sgd_freq10'] = round(inv10_s / sgd_s, 3)
    # warm Newton-Schulz inverse at freq 1: every step's inverse update is
    # ~4 batched matmuls seeded by the stored inverse (residual-gated
    # Cholesky fallback) — the headline-config candidate; reported
    # alongside the reference-parity cold number that stays the headline
    _leg('inverse_dp_iter_s_freq1_warm_ns', _optional(
        lambda: _measure_variant(model, tx, batch, 'inverse_dp', 1, 1,
                                 ITERS, warm_start=True)))
    # reference-default eigen_dp at deployed amortization: opt-in — its
    # eigh program is by far the slowest compile and the headline metric
    # doesn't use it (BENCH_FULL=1 to include)
    if os.environ.get('BENCH_FULL'):
        _leg('eigen_dp_iter_s_freq10', _optional(
            lambda: _measure_variant(model, tx, batch, 'eigen_dp', 10, 10,
                                     min(ITERS, 10))))
        # + eigenbasis amortization: full eigh every 100 steps, eigenvalue
        # refresh at the freq-10 inverse updates. The timed window
        # contains refreshes only — which IS the steady state at this
        # cadence (fulls are 1 in 10 inverse updates); warm-started fulls
        # never land in a 10-iter window, so warm_start is deliberately
        # NOT part of this measurement. Combine with KFAC_EIGH_IMPL to
        # switch the eigh kernel of the fulls outside the window.
        _leg('eigen_dp_iter_s_freq10_basis100', _optional(
            lambda: _measure_variant(model, tx, batch, 'eigen_dp', 10, 10,
                                     min(ITERS, 10), basis_freq=100)))
        # + warm subspace tracking: every freq-10 inverse update is a
        # FULL decomposition, but warm — perturbative tracking steps in
        # the stored basis (ops.subspace_eigh) instead of QDWH. The timed
        # window contains one warm full, so this measures the real
        # steady-state of the reference cadence with the MXU-shaped
        # kernel (the candidate fix for eigen_dp's TPU gap).
        _leg('eigen_dp_iter_s_freq10_warm_subspace', _optional(
            lambda: _measure_variant(model, tx, batch, 'eigen_dp', 10, 10,
                                     min(ITERS, 10), warm_start=True,
                                     eigh_impl='subspace')))
        # E-KFAC at the amortized cadence: full eigh every 100 steps,
        # per-example scale updates at the freq-10 factor steps (two
        # projections + one GEMM per layer — no eigh in the window).
        # The third candidate in the eigen-path decision:
        # unlike the refresh, the stale-basis steps carry the provably
        # optimal diagonal (tests/test_ekfac.py).
        _leg('ekfac_iter_s_freq10_basis100', _optional(
            lambda: _measure_variant(model, tx, batch, 'ekfac', 10, 10,
                                     min(ITERS, 10), basis_freq=100)))

    flops_iter = _optional(lambda: _model_flops_per_iter(model, batch))
    # MFU is a statement about a chip: off-TPU (the CPU smoke) it and
    # the peak stay null
    peak = (_peak_flops(devices[0]) if devices[0].platform == 'tpu'
            else None)
    extra['model_flops_per_iter'] = flops_iter
    extra['peak_flops'] = peak
    extra['mfu_inverse_dp_freq1'] = (round(flops_iter / inv1_s / peak, 4)
                                     if flops_iter and peak else None)
    if os.environ.get('BENCH_BREAKDOWN'):
        extra['phase_breakdown_s'] = _optional(
            lambda: _phase_breakdown(model, tx, batch))
    from kfac_pytorch_tpu import perfmodel
    extra['predicted'] = perfmodel.predict_block()
    _attach_drift(extra, measured=None, variant='inverse_dp',
                  platform=extra.get('device_kind'),
                  source='bench_legs' + ('+phase_breakdown'
                                         if extra.get('phase_breakdown_s')
                                         else ''))
    return RESULT


def main():
    if os.environ.get('BENCH_MICRO'):
        _run_micro_mode()
        return
    print(json.dumps(_run(jax.devices())), flush=True)


if __name__ == '__main__':
    main()
