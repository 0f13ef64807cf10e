"""Analytic performance model: a hypothesis to test on the chip, never a
stand-in for a measurement.

A per-phase cost model that PREDICTS steady-state s/iter and imgs/s/chip
for each K-FAC variant on the chip this project targets (TPU v5e /
"v5 lite"), against the reference's measured 1-GPU anchor of
0.487 s/iter at bs 32 (reference: scripts/time_breakdown.py:26). The
autotuner's drift gate and the bench's ``drift`` block compare measured
phases with it; ROADMAP S3/S7 hold what the chip has said so far.

Every prediction is clearly labeled ``predicted_not_measured`` and is
assembled from exactly three ingredient classes, each pinned and
auditable:

1. **Per-phase FLOPs / bytes from XLA cost analysis** — the compiled
   train-step programs of each variant are differenced along the same
   cumulative-ablation ladder the measured breakdown uses
   (utils/profiling.exclude_parts_breakdown; reference
   scripts/parse_logs.py:44-73). Derived once on the CPU backend by
   ``scripts/derive_perf_inputs.py`` (flop counts of dot/conv ops are
   backend-independent; LAPACK custom calls are NOT counted there, so
   the two decomposition phases below use ingredient 2/3 instead) and
   committed as ``data/perf_inputs_resnet50_bs32.json``.
2. **Chip constants** — two eigh timings carried over from an earlier
   round's notes as HYPOTHESES (ROADMAP S3; not re-measured on today's
   v5e): batched XLA QDWH eigh [4,2304] = 9.85 s and [8,512] = 1.64 s.
   The eigen variants' full-decomposition phase is extrapolated from
   these two points (power law, form stated on the function).
3. **Stated roofline assumptions** — phases with no measurement
   (conv fwd/bwd, factor GEMMs, Cholesky) get
   ``t = max(flops / (eff * peak), bytes / (hbm_eff * bw))`` under
   THREE efficiency scenarios (optimistic / central / conservative).
   The scenarios bracket the prediction; a chip measurement outside
   the [optimistic, conservative] band falsifies the model, one inside
   narrows it.

Single-chip only, matching the anchor (no collectives; the DP-vs-MPD
comm story is separately compiler-verified by scripts/comm_count.py).

The bench harness (bench.py) embeds ``predict_block()`` in its output
extras as the other half of the ``drift`` block. Pinned by
tests/test_perf_model.py.
"""

import json
import math
import os

#: reference 1-GPU K-FAC iteration at bs 32 (scripts/time_breakdown.py:26)
BASELINE_ITER_S = 0.487
BATCH = 32

#: TPU v5e ("v5 lite") public per-chip figures: dense bf16 peak FLOP/s
#: and HBM bandwidth (cloud TPU docs / scaling-book numbers).
PEAK_BF16 = 197e12
HBM_BW = 819e9

#: Two eigh timings from an earlier round's notes (f32; ROADMAP S3 holds
#: them as hypotheses, not re-measured on today's v5e): (rows, dim,
#: seconds).
FENCED_EIGH_POINTS = ((4, 2304, 9.8486), (8, 512, 1.6368))

#: Attention datapoint from the same notes (hypothesis, ROADMAP S7):
#: XLA fwd+bwd causal attention,
#: B=1 H=8 D=64 L=16384 in 103.64 ms -> ~8e12 FLOP/s achieved (~4% of
#: peak). Recorded as the measured lower anchor for SKINNY programs —
#: not used to set the conv scenarios (bs-32 convs are MXU-shaped), but
#: it bounds how wrong "conservative" can be for thin shapes.
FENCED_ATTN_NOTE = dict(program='xla_attention_fwd_bwd_causal',
                        config='B1_H8_D64_L16384', seconds=0.10364,
                        approx_flops=8.25e11, achieved_flops=8.0e12)

#: Roofline scenarios: (MXU efficiency for bf16-input matmul/conv work,
#: HBM efficiency). Central 0.4 is the scaling-book's "well-mapped
#: model" band midpoint; conservative 0.2 covers fusion/layout misses;
#: optimistic 0.6 is near the practical ceiling for conv nets.
SCENARIOS = {
    'optimistic': (0.60, 0.90),
    'central': (0.40, 0.70),
    'conservative': (0.20, 0.50),
}

#: f32-accumulating GEMMs on f32 inputs (precondition / refresh /
#: Cholesky phases) cannot use the bf16 MXU path directly; assumed rate
#: = bf16 rate / F32_PENALTY (stated assumption, v5e has no native f32
#: matmul unit).
F32_PENALTY = 4.0

#: analytic FLOPs of psd_inverse per dxd matrix: potrf d^3/3 + two
#: full-RHS triangular solves d^3 each (ops/linalg.py:30-41). The CPU
#: derivation counts these as 0 (LAPACK custom calls), so the Cholesky
#: phase is reconstructed analytically from the plan's bucket table.
CHOLESKY_FLOPS_PER_MATRIX = lambda d: (7.0 / 3.0) * d ** 3  # noqa: E731

#: analytic FLOPs of the ITERATIVE decomposition kernels per dxd matrix
#: (the inverse-free ladder rungs, ops/linalg.py) — pure batched GEMMs,
#: so unlike QDWH eigh they roofline honestly at the MXU rate:
#:
#: - subspace_eigh, per tracking step (default 2): X@Q + Q^T(XQ) +
#:   Q@K (3 GEMMs, 2d^3 each) and CholeskyQR2 = 2 x (Gram 2d^3 +
#:   cholesky d^3/3 + triangular solve d^3) ~= 6.7d^3 -> ~12.7d^3 per
#:   step; plus the final Rayleigh X@Q + diag contraction ~= 3d^3.
#: - newton_schulz_inverse, per iteration (default 2): A@X + X@(2I-AX)
#:   (2 GEMMs, 2d^3 each) -> 4d^3; plus the residual check A@X ~= 2d^3
#:   (the Cholesky fallback sits behind a lax.cond and costs nothing on
#:   the healthy path).
SUBSPACE_FLOPS_PER_MATRIX = \
    lambda d, steps=2: (12.7 * steps + 3.0) * d ** 3  # noqa: E731
NEWTON_SCHULZ_FLOPS_PER_MATRIX = \
    lambda d, iters=2: (4.0 * iters + 2.0) * d ** 3   # noqa: E731

#: HBM-byte multiplier of the FUSED capture path (ops/pallas_capture,
#: ISSUE 19) relative to the unfused ComputeFactor bytes: the fused
#: kernels never materialize the im2col patch matrix in HBM (conv-A's
#: dominant traffic — written once by extract_patches, read back by the
#: GEMM) and fold the EMA read-modify-write into the accumulator
#: epilogue instead of a separate elementwise pass. FLOPs are unchanged
#: (the same statistic GEMMs run either way), so the fused rung only
#: moves the memory-bound side of the roofline. 0.5 is a stated
#: assumption bracketing "patch matrix round trip gone, activations
#: still stream once"; not measured on the chip (ROADMAP S4).
CAPTURE_FUSION_BYTES_FACTOR = 0.5

#: TPU v5e ICI per-chip interconnect bandwidth, one direction
#: (~45 GB/s per link, public scaling-book figure) — the stated
#: assumption behind the per-axis comm scenarios. DCN (cross-slice)
#: rides a ~25 Gb/s-class NIC share per chip.
ICI_BW = 4.5e10
DCN_BW = 3.1e9

#: link-efficiency scenarios for the collective comm model (fraction of
#: the wire rate an all-reduce/reduce-scatter actually sustains at the
#: factor payload sizes; bracketed the same way SCENARIOS brackets the
#: MXU roofline).
COMM_SCENARIOS = {
    'optimistic': 0.85,
    'central': 0.70,
    'conservative': 0.45,
}

_INPUTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            'data', 'perf_inputs_resnet50_bs32.json')


def load_inputs(path=None):
    with open(path or _INPUTS_PATH) as f:
        return json.load(f)


def eigh_time_model():
    """Two-point power-law fit of the fenced batched-eigh times.

    Form: ``t = c * rows * dim**p`` — batch-linear (conservative: the
    MXU may overlap small batches) with the dim exponent solved from the
    two fenced points. QDWH is iteration-bound, not flop-bound, which is
    WHY this phase gets measured points instead of a roofline (the
    roofline predicts ~milliseconds; the chip says seconds). Returns
    ``(c, p, fn)`` with ``fn(rows, dim) -> seconds``. Extrapolation
    beyond [512, 2304] is labeled as such in the assumptions block.
    """
    (b1, d1, t1), (b2, d2, t2) = FENCED_EIGH_POINTS
    p = math.log((t1 / b1) / (t2 / b2)) / math.log(d1 / d2)
    c = (t1 / b1) / d1 ** p
    return c, p, lambda rows, dim: c * rows * dim ** p


def _phase_time(flops, bytes_, eff, hbm_eff, rate=PEAK_BF16):
    """Roofline: compute-bound vs memory-bound, whichever dominates."""
    t_c = flops / (eff * rate) if flops else 0.0
    t_m = bytes_ / (hbm_eff * HBM_BW) if bytes_ else 0.0
    return max(t_c, t_m)


def phase_costs(inputs):
    """Difference the per-program cost-analysis totals into the ledger
    phases (the measured breakdown's taxonomy, reference
    scripts/time_breakdown.py:24-27 names).

    Returns {phase: (flops, bytes)} plus the bucket table. 'inverse_chol'
    is analytic (see CHOLESKY_FLOPS_PER_MATRIX); 'inverse_eigh' carries
    the bucket table for the fenced time model instead of flops.
    """
    prog = inputs['programs']

    def diff(a, b):
        return (max(prog[a]['flops'] - prog[b]['flops'], 0.0),
                max(prog[a]['bytes'] - prog[b]['bytes'], 0.0))

    buckets = inputs['buckets']  # [[rows, dim], ...]
    chol_flops = sum(r * CHOLESKY_FLOPS_PER_MATRIX(d) for r, d in buckets)
    # bytes: read factors + write inverses, f32: 2 * rows * d^2 * 4 B
    chol_bytes = sum(2 * r * d * d * 4 for r, d in buckets)
    # iterative decomp_impl rungs: reads factor + seed, writes result
    sub_flops = sum(r * SUBSPACE_FLOPS_PER_MATRIX(d) for r, d in buckets)
    ns_flops = sum(r * NEWTON_SCHULZ_FLOPS_PER_MATRIX(d)
                   for r, d in buckets)
    iter_bytes = sum(3 * r * d * d * 4 for r, d in buckets)
    return {
        'model': (prog['sgd']['flops'], prog['sgd']['bytes']),
        'precondition': diff('inverse_dp_base', 'sgd'),
        'precondition_eigen': diff('eigen_dp_base', 'sgd'),
        'factor': diff('inverse_dp_factor', 'inverse_dp_base'),
        'refresh': diff('eigen_dp_refresh', 'eigen_dp_factor'),
        'ekfac_scales': diff('ekfac_factor', 'eigen_dp_factor'),
        'inverse_chol': (chol_flops, chol_bytes),
        'inverse_subspace': (sub_flops, iter_bytes),
        'inverse_ns': (ns_flops, iter_bytes),
    }


def decomp_impl_priors(block, method, anchor='central'):
    """{rung: predicted decomposition seconds} for the method's
    decomp_impl ladder, from a ``predict_block()`` dict — the
    autotuner's seeding input (``KnobController._seed_decomp_impl``).
    eigh: fenced QDWH full vs the subspace tracker; cholesky: analytic
    Cholesky vs Newton-Schulz. Returns {} when the block carries no
    usable phases (the tuner then probes from the configured rung)."""
    try:
        ph = block['scenarios'][anchor]['phases_s']
    except (KeyError, TypeError):
        return {}
    if method == 'eigh':
        out = {'xla': ph.get('ComputeInverse_eigh_full'),
               'subspace': ph.get('ComputeInverse_subspace')}
    elif method == 'cholesky':
        out = {'xla': ph.get('ComputeInverse_chol'),
               'newton_schulz': ph.get('ComputeInverse_ns')}
    else:
        return {}
    if any(v is None for v in out.values()):
        return {}
    return {k: float(v) for k, v in out.items()}


def capture_impl_priors(block, anchor='central'):
    """{rung: predicted ComputeFactor seconds} for the capture_impl
    ladder, from a ``predict_block()`` dict — the autotuner's seeding
    input (``KnobController._seed_capture_impl``). Unfused XLA capture
    vs the fused Pallas kernels (same GEMM FLOPs, HBM bytes scaled by
    CAPTURE_FUSION_BYTES_FACTOR). Returns {} when the block carries no
    usable phases (the tuner then probes from the configured rung)."""
    try:
        ph = block['scenarios'][anchor]['phases_s']
    except (KeyError, TypeError):
        return {}
    out = {'xla': ph.get('ComputeFactor'),
           'pallas': ph.get('ComputeFactor_pallas')}
    if any(v is None for v in out.values()):
        return {}
    return {k: float(v) for k, v in out.items()}


def predict(inputs=None):
    """Predicted steady-state s/iter + imgs/s per variant per scenario.

    Cadences modeled (matching bench.py's measured legs):
      sgd; inverse_dp freq 1 (the headline config: factor+inverse every
      step, the reference-breakdown setting); inverse_dp freq 10 (the
      deployed cadence, pytorch_imagenet_resnet.py:94); eigen_dp freq 10
      cold (the reference DEFAULT variant at its deployed cadence —
      predicted unusable on TPU, the quantified eigen-path gap);
      eigen_dp freq 10 + basis_update_freq 100 (amortized rescue);
      ekfac freq 10 + basis 100 (amortized + per-example corrected
      scales).
    """
    inputs = inputs or load_inputs()
    ph = phase_costs(inputs)
    _, _, eigh_t = eigh_time_model()
    eigh_full_s = sum(eigh_t(r, d) for r, d in inputs['buckets'])

    out = {}
    # the fourth entry is the COMPUTE-BOUND FLOOR: bytes ignored at the
    # central MXU efficiency. The CPU-derived 'bytes accessed' proxy
    # OVERSTATES TPU HBM traffic (pre-fusion buffer counting, f32-
    # emulated bf16), which makes the three roofline scenarios skew
    # SLOW — so together they bracket the truth from both sides: the
    # chip cannot beat the floor, and should beat the bytes-heavy
    # scenarios if XLA's TPU fusion behaves as designed.
    cases = dict(SCENARIOS)
    cases['central_flops_only'] = (SCENARIOS['central'][0], None)
    for name, (eff, hbm) in cases.items():

        def t(phase, rate=PEAK_BF16, _eff=eff, _hbm=hbm):
            f, b = ph[phase]
            if _hbm is None:
                b = 0.0
            return _phase_time(f, b, _eff, _hbm or 1.0, rate)

        f32 = PEAK_BF16 / F32_PENALTY
        model = t('model')
        prec = t('precondition', f32)
        prec_e = t('precondition_eigen', f32)
        fac = t('factor')
        # the fused capture rung: same GEMM FLOPs, the HBM side scaled
        # by the no-patch-matrix/folded-EMA factor (capture_impl prior)
        fac_f, fac_b = ph['factor']
        fac_pallas = _phase_time(
            fac_f, 0.0 if hbm is None
            else fac_b * CAPTURE_FUSION_BYTES_FACTOR, eff, hbm or 1.0)
        chol = t('inverse_chol', f32)
        refresh = t('refresh', f32)
        scales = t('ekfac_scales', f32)
        sub = t('inverse_subspace', f32)
        ns = t('inverse_ns', f32)

        variants = {
            'sgd': model,
            # factor + inverse every step (headline / anchor cadence)
            'inverse_dp_freq1': model + prec + fac + chol,
            # factor + inverse every 10th step, amortized steady state
            'inverse_dp_freq10': model + prec + (fac + chol) / 10.0,
            # the reference default on TPU: full QDWH eigh every 10th
            # step — the fenced-eigh term dominates everything else
            'eigen_dp_freq10_cold': (model + prec_e
                                     + (fac + eigh_full_s) / 10.0),
            # full eigh 1-in-100 steps, eigenvalue-only refresh at the
            # other 9-in-100 inverse updates
            'eigen_dp_freq10_basis100': (model + prec_e + fac / 10.0
                                         + eigh_full_s / 100.0
                                         + refresh * 9.0 / 100.0),
            # ekfac: scale update every factor step + amortized basis
            'ekfac_freq10_basis100': (model + prec_e
                                      + (fac + scales) / 10.0
                                      + eigh_full_s / 100.0
                                      + refresh * 9.0 / 100.0),
        }
        out[name] = {
            k: {'iter_s': round(v, 4), 'imgs_per_s': round(BATCH / v, 1),
                'vs_baseline': round((BATCH / v)
                                     / (BATCH / BASELINE_ITER_S), 2)}
            for k, v in variants.items()
        }
        out[name]['phases_s'] = {
            'Model': round(model, 4), 'Precondition': round(prec, 4),
            'ComputeFactor': round(fac, 4),
            # the fused capture rung (ops/pallas_capture, ISSUE 19):
            # what the capture_impl knob buys on the modeled chip
            'ComputeFactor_pallas': round(fac_pallas, 4),
            'ComputeInverse_chol': round(chol, 4),
            'ComputeInverse_eigh_full': round(eigh_full_s, 2),
            # the inverse-free ladder rungs (warm kernels, GEMM
            # roofline at the f32 rate — what the decomp_impl knob
            # buys on the modeled chip vs the fenced QDWH seconds)
            'ComputeInverse_subspace': round(sub, 6),
            'ComputeInverse_ns': round(ns, 6),
            'EigenRefresh': round(refresh, 4),
            'EkfacScales': round(scales, 4),
        }
    return out


def prior_phase_costs(block, variant='inverse_dp', anchor='central',
                      decomp_impl=None):
    """Per-phase prior seconds for the autotuner's pre-measurement
    seeding (``autotune.prior_best_freq``): pull the ``anchor``
    scenario's phase predictions out of a ``predict_block()`` dict and
    bind the decomposition phase to the variant's kernel (the fenced
    full eigh for eigen/ekfac, the analytic Cholesky otherwise —
    the same binding ``obs.drift._predicted_phase`` uses). An iterative
    ``decomp_impl`` rebinds to its GEMM-roofline rung, so the freq
    prior prices the kernel the run will actually execute. Returns
    ``{'model', 'precondition', 'factor', 'decomp'}`` seconds, or ``{}``
    when the block carries no usable phases (the tuner then starts from
    the configured cadence instead of a prior)."""
    try:
        ph = block['scenarios'][anchor]['phases_s']
    except (KeyError, TypeError):
        return {}
    eigen = str(variant).startswith(('eigen', 'ekfac'))
    decomp_key = ('ComputeInverse_eigh_full' if eigen
                  else 'ComputeInverse_chol')
    if decomp_impl in ('subspace', 'jacobi', 'auto') and eigen:
        decomp_key = 'ComputeInverse_subspace'
    elif decomp_impl in ('newton_schulz', 'auto') and not eigen:
        decomp_key = 'ComputeInverse_ns'
    out = {
        'model': ph.get('Model'),
        'precondition': ph.get('Precondition'),
        'factor': ph.get('ComputeFactor'),
        'decomp': ph.get(decomp_key),
    }
    if any(v is None for v in out.values()):
        return {}
    return {k: float(v) for k, v in out.items()}


def predict_block(inputs=None):
    """The self-describing block bench.py embeds in its JSON extras."""
    try:
        inputs = inputs or load_inputs()
        c, p, _ = eigh_time_model()
        return {
            'predicted_not_measured': True,
            'method': ('per-phase analytic model: XLA cost_analysis '
                       'FLOPs/bytes (CPU-derived, backend-independent '
                       'dot/conv counts) x roofline scenarios + two '
                       'carried-over eigh timings (hypotheses); see '
                       'kfac_pytorch_tpu/perfmodel.py'),
            'anchor': {'reference_kfac_iter_s': BASELINE_ITER_S,
                       'source': 'reference scripts/time_breakdown.py:26 '
                                 '(1 GPU, bs 32, factor+inverse every '
                                 'step)'},
            'chip': {'kind': 'TPU v5e (v5 lite)', 'peak_bf16': PEAK_BF16,
                     'hbm_bw': HBM_BW},
            'assumptions': {
                'scenarios_mxu_hbm_eff': {k: list(v) for k, v
                                          in SCENARIOS.items()},
                'f32_gemm_rate': f'peak_bf16 / {F32_PENALTY}',
                'eigh_fit': {'form': 't = c * rows * dim^p',
                             'c': c, 'p': round(p, 4),
                             'fenced_points': [list(x) for x
                                               in FENCED_EIGH_POINTS],
                             'note': 'extrapolated beyond dim 2304 '
                                     '(largest ResNet-50 bucket 4608)'},
                'cholesky_flops': '7/3 d^3 per matrix (analytic; LAPACK '
                                  'custom calls carry no XLA flop count)',
                'iterative_decomp_flops': (
                    'subspace ~(12.7*steps+3) d^3, newton_schulz '
                    '~(4*iters+2) d^3 per matrix at the defaults '
                    '(steps=iters=2) — pure GEMMs, rooflined at the '
                    'f32 rate; the decomp_impl ladder priors '
                    '(ops/linalg.py kernels, autotune seeding)'),
                'bytes_proxy_bias': (
                    'the CPU-derived bytes-accessed totals overstate TPU '
                    'HBM traffic (pre-fusion buffer counting, f32-'
                    'emulated bf16), so the roofline scenarios skew '
                    'SLOW; central_flops_only is the compute-bound '
                    'floor from the other side'),
                'skinny_floor_datapoint': FENCED_ATTN_NOTE,
            },
            'inputs_meta': inputs['meta'],
            'scenarios': (scen := predict(inputs)),
            'headline': {
                'metric': 'predicted_inverse_dp_freq1_imgs_per_s_central',
                'value': scen['central']['inverse_dp_freq1']['imgs_per_s'],
                'falsify': ('a fenced measured value outside the '
                            '[conservative, optimistic] band falsifies '
                            'the model'),
            },
        }
    except Exception as e:  # noqa: BLE001 — bench must still emit
        return {'predicted_not_measured': True,
                'error': f'{type(e).__name__}: {e}'}


def comm_scenarios(per_axis_volume, axis_bw=None, dcn_axes=()):
    """Per-axis K-FAC communication time scenarios for a composed mesh.

    ``per_axis_volume`` is the dict returned by
    ``meshplan.MeshFactorPlan.comm_volume()``: axis name -> phase-bytes
    dict ({'FactorComm': ..., 'InverseComm': ..., 'PredComm': ...}).
    Each axis is priced independently at ``bytes / (eff * bw)`` under
    the COMM_SCENARIOS link-efficiency ladder — the per-axis collectives
    are disjoint device groups, but XLA serialises them within one step,
    so the per-step total is the SUM over axes, not the max.

    ``axis_bw`` optionally overrides the wire rate per axis (B/s);
    axes listed in ``dcn_axes`` default to DCN_BW instead of ICI_BW
    (e.g. a cross-slice data axis). Zero-byte axes (expert, pipeline)
    stay in the output at 0.0 s — the zero-comm claim priced, not
    elided.

    Predicted, not measured: the byte counts are compiler-verified by
    scripts/comm_count.py; only the wire rates here are assumptions.
    """
    axis_bw = dict(axis_bw or {})
    out = {}
    for scen, eff in COMM_SCENARIOS.items():
        axes = {}
        total_s = 0.0
        for ax, phases in per_axis_volume.items():
            bw = axis_bw.get(ax, DCN_BW if ax in dcn_axes else ICI_BW)
            byts = int(sum(phases.values()))
            t = byts / (eff * bw)
            axes[ax] = {'bytes': byts,
                        'phase_bytes': dict(phases),
                        'bw_assumed': bw,
                        's': t}
            total_s += t
        out[scen] = {'axes': axes, 'total_s': total_s}
    return out


def comm_block(per_axis_volume, axis_bw=None, dcn_axes=()):
    """Self-describing wrapper around :func:`comm_scenarios`."""
    return {
        'predicted_not_measured': True,
        'method': ('per-axis serial sum of bytes/(eff*bw); bytes from '
                   'meshplan.MeshFactorPlan.comm_volume (pinned byte-'
                   'for-byte against compiled HLO by '
                   'scripts/comm_count.py composed-mesh specs)'),
        'assumptions': {
            'ici_bw_B_per_s': ICI_BW,
            'dcn_bw_B_per_s': DCN_BW,
            'link_eff_scenarios': dict(COMM_SCENARIOS),
            'serialisation': 'axes summed (XLA serialises same-step '
                             'collectives), intra-axis perfectly '
                             'overlapped within each phase',
        },
        'scenarios': comm_scenarios(per_axis_volume, axis_bw=axis_bw,
                                    dcn_axes=dcn_axes),
    }
