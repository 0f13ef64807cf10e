"""Perf-model drift: measured per-phase wall time vs ``perfmodel.py``.

The analytic perf model (VERDICT r4 #1) predicts per-phase seconds for
the flagship configs under three roofline scenarios; its own contract
says a fenced measurement outside the [optimistic, conservative] band
falsifies it. This module closes that loop mechanically: every
``bench.py`` emission carries a ``drift`` block pairing whatever WAS
measured this round — full chip legs, the exclude-parts breakdown, or
the CPU-fallback micro phases — against the matching ``predicted``
entries, as per-phase ratios with an explicit verdict.

Two honesty rules, enforced structurally:

- a measurement taken on a platform the model does not describe (CPU
  fallback, a different TPU generation) still produces ratios, but the
  gate verdict is ``advisory`` and ``comparable: false`` rides next to
  every number — a CPU round can never read as chip evidence;
- a phase with no prediction (the single-chip model predicts no comm
  phases) or no measurement reports ``null``, never a fabricated ratio.

Measured inputs arrive in the exclude-parts ledger taxonomy
(ComputeFactor / CommunicateFactor / ComputeInverse /
CommunicateInverse, plus Model / Precondition); adapters below convert
the two host-side sources (``PhaseTimers`` epoch dicts, ``bench.py``
extras). Pure stdlib arithmetic — importable anywhere, pinned by
``tests/test_obs.py`` on a synthetic predicted/measured pair.
"""

import math

#: PhaseTimers label -> ledger taxonomy: the single source of truth
#: lives next to the span emitter (both sides must speak it).
from kfac_pytorch_tpu.obs.trace import PHASE_TAXONOMY as _TIMER_LABELS

#: substrings of jax device_kind identifying the chip the model is fit
#: for (perfmodel targets TPU v5e / "v5 lite").
_MODEL_CHIP_KEYS = ('v5e', 'v5 lite', 'v5lite')

#: comm_precision -> per-phase multiplier on the COMM phase predictions:
#: the wire-dtype payload ratios of parallel/collectives.py, restated
#: here because this module must stay importable without jax (the
#: canonical constants live in collectives.WIRE_COMPRESSION /
#: reduce_wire_dtype; cross-module agreement is pinned by
#: tests/test_comm_precision.py). CommunicateFactor is the stats REDUCE
#: — it floors at bf16 under 'int8' (integer all-reduce overflow);
#: CommunicateInverse and PredComm (the comm_pred variants' gather of
#: preconditioned gradients, ledger taxonomy of scripts/comm_count.py)
#: are gathers and take the full wire factor. NOTE the 'Precondition'
#: phase is deliberately NOT scaled: in the host timer taxonomy it is
#: the joint compute+gather apply, and the single-chip perfmodel
#: predicts no comm share for it — scaling the whole phase by a wire
#: factor would shrink its COMPUTE prediction too. A future multi-chip
#: model should predict the gather as a separate PredComm phase, which
#: IS scaled here.
COMM_WIRE_FACTORS = {
    'fp32': {'CommunicateFactor': 1.0, 'CommunicateInverse': 1.0,
             'PredComm': 1.0},
    'bf16': {'CommunicateFactor': 0.5, 'CommunicateInverse': 0.5,
             'PredComm': 0.5},
    'int8': {'CommunicateFactor': 0.5, 'CommunicateInverse': 0.25,
             'PredComm': 0.25},
}

#: the comm phases the compression factor applies to (compute phases
#: and the gradient allreduce folded into Model are untouched by
#: comm_precision; 'Precondition' is excluded — see the note above).
_COMM_PHASES = ('CommunicateFactor', 'CommunicateInverse', 'PredComm')


def scale_comm_scenarios(predicted_block, comm_precision):
    """A drift scenario per wire dtype: return a deep-copied
    ``perfmodel.predict_block()``-shaped dict whose per-scenario
    CommunicateFactor/CommunicateInverse/PredComm phase predictions are
    scaled by the :data:`COMM_WIRE_FACTORS` of ``comm_precision`` — so the
    measured-vs-predicted gate covers compressed runs with an honest
    band instead of flagging every compressed run as drift. fp32 (or an
    unknown dtype) returns the block unchanged; blocks with no comm
    phases (the single-chip perfmodel) pass through untouched."""
    import copy
    factors = COMM_WIRE_FACTORS.get(comm_precision)
    if not factors or comm_precision == 'fp32' or not predicted_block:
        return predicted_block
    block = copy.deepcopy(predicted_block)
    for scen in (block.get('scenarios') or {}).values():
        if not isinstance(scen, dict):
            continue
        phases = scen.get('phases_s') or {}
        for name in _COMM_PHASES:
            if phases.get(name) is not None:
                phases[name] = float(phases[name]) * factors[name]
    block['comm_precision'] = comm_precision
    return block


def _timer_label_to_taxonomy(label):
    """'decomp+gather' -> 'ComputeInverse+CommunicateInverse' etc."""
    return '+'.join(_TIMER_LABELS.get(p, p) for p in label.split('+'))


def measured_from_phase_timers(phase_ms):
    """Convert a ``PhaseTimers.epoch_flush()`` dict (ms, host labels)
    into ledger-taxonomy seconds. ``step_mean``/``step_max`` ride along
    under their own names (no prediction maps to them — they stay
    informational)."""
    out = {}
    for label, ms in (phase_ms or {}).items():
        if label in ('step_mean', 'step_max'):
            out[label] = ms / 1e3
        else:
            out[_timer_label_to_taxonomy(label)] = ms / 1e3
    return out


def measured_from_bench_extras(extra):
    """Pull every phase-shaped measurement out of a ``bench.py`` extras
    dict: the exclude-parts breakdown (already ledger-taxonomy) when
    present, the SGD leg as the Model phase, and the freq-1 K-FAC
    overhead as a joint phase when only whole-iteration legs exist."""
    out = {}
    bd = extra.get('phase_breakdown_s')
    if bd:
        for k, v in bd.items():
            if k not in ('Total', 'Rest') and v is not None:
                out[k] = float(v)
    sgd = extra.get('sgd_iter_s')
    if sgd is not None:
        out.setdefault('Model', float(sgd))
        inv1 = extra.get('inverse_dp_iter_s_freq1')
        if inv1 is not None and not bd:
            # whole-iteration difference: everything K-FAC adds at the
            # every-step cadence, attributable no finer without the
            # breakdown ladder
            out['Precondition+ComputeFactor+ComputeInverse'] = max(
                float(inv1) - float(sgd), 0.0)
    return out


def _predicted_phase(phases_s, name, variant, decomp_impl=None,
                     capture_impl=None):
    """Predicted seconds for one (possibly joint) taxonomy name, or
    None when any component has no prediction. 'ComputeInverse' binds
    to the variant's decomposition kernel (Cholesky for inverse_*,
    the fenced full eigh for eigen_*); an iterative ``decomp_impl``
    rebinds to its GEMM-roofline rung ('ComputeInverse_subspace' /
    'ComputeInverse_ns') — without the rebind, a run on the iterative
    rung would land seconds under the fenced full-eigh band and the
    gate would read the speedup as drift. 'ComputeFactor' likewise
    rebinds to 'ComputeFactor_pallas' under the fused capture rung
    (``capture_impl`` 'pallas'/'auto', ISSUE 19) — its band sits under
    the unfused one by the skipped patch-matrix HBM traffic."""
    eigen = variant.startswith('eigen') or variant.startswith('ekfac')
    total = 0.0
    for part in name.split('+'):
        if part == 'ComputeInverse':
            if decomp_impl in ('subspace', 'jacobi', 'auto') and eigen:
                key = 'ComputeInverse_subspace'
            elif decomp_impl in ('newton_schulz', 'auto') and not eigen:
                key = 'ComputeInverse_ns'
            elif eigen:
                key = 'ComputeInverse_eigh_full'
            else:
                key = 'ComputeInverse_chol'
        elif (part == 'ComputeFactor'
                and capture_impl in ('pallas', 'auto')):
            key = 'ComputeFactor_pallas'
        else:
            key = part
        v = phases_s.get(key)
        if v is None:
            return None
        total += float(v)
    return total


def drift_block(measured_s, predicted_block, *, platform=None,
                variant='inverse_dp', anchor='central', tolerance=1.0,
                source=None, comm_precision='fp32', decomp_impl=None,
                capture_impl=None):
    """Assemble the ``drift`` block for a bench emission.

    Args:
      measured_s: {taxonomy phase: seconds} (see the adapters above).
      predicted_block: ``perfmodel.predict_block()``'s dict (or the
        ``extra['predicted']`` already embedded in a bench JSON).
      platform: the measured device kind (``device_kind`` string, or
        'cpu'); decides ``comparable``.
      variant: which decomposition kernel the measured config ran.
      anchor: scenario the headline ratio is taken against.
      tolerance: multiplicative slack on the scenario band before a
        phase counts as drifted (the gate's knob; 1.0 = the model's own
        falsification contract).
      source: free-form provenance string recorded in the block.
      comm_precision: wire dtype of the measured run's factor
        collectives — the comm-phase predictions are scaled by the
        :data:`COMM_WIRE_FACTORS` first
        (:func:`scale_comm_scenarios`), so a compressed run is judged
        against its own honest band.
      decomp_impl: the decomposition kernel the measured run selected
        (KFAC ``decomp_impl`` knob) — rebinds the ComputeInverse
        prediction to the matching rung (see
        :func:`_predicted_phase`), so an iterative-kernel run is
        judged against its own roofline, not the cold kernel's.
      capture_impl: the capture kernel the measured run selected (KFAC
        ``capture_impl`` knob, ISSUE 19) — rebinds ComputeFactor to
        the fused-Pallas band the same way, so a fused-capture run is
        not read as drift for being faster than the unfused roofline.

    Returns a dict; never raises on malformed inputs (a drift block
    must never take the bench down — errors are reported in-band).
    """
    try:
        predicted_block = scale_comm_scenarios(predicted_block,
                                               comm_precision)
        scenarios = (predicted_block or {}).get('scenarios') or {}
        per_scen = {name: scen.get('phases_s', {})
                    for name, scen in scenarios.items()
                    if isinstance(scen, dict)}
        comparable = bool(platform) and any(
            k in str(platform).lower() for k in _MODEL_CHIP_KEYS)
        phases = {}
        violations = []
        for name, meas in sorted((measured_s or {}).items()):
            if meas is None:
                continue
            pred = {scen: _predicted_phase(ph, name, variant, decomp_impl,
                                           capture_impl)
                    for scen, ph in per_scen.items()}
            pred = {k: v for k, v in pred.items() if v is not None}
            entry = {'measured_s': round(float(meas), 6),
                     'predicted_s': {k: round(v, 6)
                                     for k, v in sorted(pred.items())}}
            anchor_pred = pred.get(anchor)
            if anchor_pred and anchor_pred > 0 and meas >= 0:
                entry['ratio'] = round(meas / anchor_pred, 4)
            else:
                entry['ratio'] = None
            band_vals = [v for k, v in pred.items()
                         if k in ('optimistic', 'conservative', 'central')]
            if band_vals and entry['ratio'] is not None:
                lo, hi = min(band_vals), max(band_vals)
                entry['band_s'] = [round(lo, 6), round(hi, 6)]
                within = (lo / tolerance <= meas <= hi * tolerance)
                entry['within_band'] = within
                if not within:
                    violations.append(name)
            else:
                entry['within_band'] = None
            phases[name] = entry
        if not comparable:
            verdict = 'advisory'
        elif violations:
            verdict = 'drift'
        elif any(e['within_band'] for e in phases.values()):
            verdict = 'ok'
        else:
            verdict = 'no_overlap'  # nothing measured maps to a prediction
        return {
            'measured_vs_predicted': True,
            'source': source,
            'platform': platform,
            'variant': variant,
            'comparable': comparable,
            'comm_precision': comm_precision,
            'decomp_impl': decomp_impl,
            'capture_impl': capture_impl,
            'anchor_scenario': anchor,
            'tolerance': tolerance,
            'phases': phases,
            'gate': {
                'verdict': verdict,
                'violations': violations,
                'note': ('ratios are informational: the analytic model '
                         'describes TPU v5e, not this platform'
                         if not comparable else
                         'a phase outside the [optimistic, conservative]'
                         ' band (x tolerance) falsifies the model for '
                         'that phase'),
            },
        }
    except Exception as e:  # noqa: BLE001 — never break the bench
        return {'measured_vs_predicted': True,
                'error': f'{type(e).__name__}: {e}'}


def gate(measured_s, predicted_block, **kw):
    """``(verdict, violations)`` shortcut over :func:`drift_block` for
    callers that only consume the gate — the autotuner's commit veto:
    'drift' (reachable only on the modeled chip) rejects a knob change,
    'advisory'/'ok'/'no_overlap' let it through. Keyword args pass
    through to :func:`drift_block` (platform / variant / anchor /
    comm_precision / tolerance)."""
    block = drift_block(measured_s, predicted_block, **kw)
    g = block.get('gate') or {}
    return g.get('verdict'), g.get('violations') or []


def micro_measured(micro):
    """Adapter for bench.py's BENCH_MICRO=1 block: its steady step
    runs model+precondition+stats fused; the unstaggered refresh step
    adds the full decomposition, so the refresh-minus-steady marginal is
    the ComputeInverse phase. Returns ledger-taxonomy seconds (the
    micro model is an MLP — these numbers exercise the drift schema
    off the chip and are never chip-comparable)."""
    try:
        un = micro['unstaggered']
        steady = un['steady_ms'] / 1e3
        refresh = un['refresh_ms'] / 1e3
        out = {'Model+Precondition+ComputeFactor': steady}
        marg = refresh - steady
        if math.isfinite(marg) and marg >= 0:
            out['ComputeInverse'] = marg
        return out
    except (KeyError, TypeError):
        return {}
