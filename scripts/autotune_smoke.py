"""Closed-loop autotune smoke: the CI gate for the online KnobController.

Five legs, all jax-free and fully deterministic (a planted cost profile
drives the controller through ``record`` — no wall clock), each writing
its decision log as a JSONL artifact:

1. **synthetic**: a refresh spike that amortizes with frequency
   (optimum = the ladder top). Gate: the final ``kfac_update_freq``
   matches the planted optimum, steady state is reached within a
   bounded number of probe windows, and the run had ZERO vetoes
   (nothing to veto — a veto here would mean the gate fires
   spuriously).
2. **decomp-ladder**: the inverse-free rung (``decomp_impl``) under a
   planted optimum — the newton_schulz rung is genuinely cheaper, the
   controller must converge onto it with ZERO vetoes.
3. **capture-ladder**: the same for the fused-capture rung
   (``capture_impl``).
4. **quality-hold**: the numerical-health gate — the iterative rung is
   FASTER but raises the badness counter (``quality_gate``) during its
   probe window. Gate: zero commits (an accuracy-regressing rung never
   lands on speed alone), at least one quality veto, steady at the
   cold kernel.
5. **comm-mode**: a planted comm-bound profile; the analytic verdict
   orders the probe, the measured window decides, and the commit is
   applied through ``request_replan``.

Usage:
  KFAC_AUTOTUNE_ASSERT=1 python scripts/autotune_smoke.py

Env knobs:
  KFAC_AUTOTUNE_ASSERT    '1' = violations exit nonzero (the CI gate);
                          unset = report-only (summary still written)
  AUTOTUNE_SMOKE_DIR      artifact dir (default '.'): per-leg
                          autotune-decisions-<leg>.jsonl + summary
                          autotune-smoke.json
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

from kfac_pytorch_tpu import autotune


class _FakePrecond:
    """Knob attributes only — the synthetic legs never touch jax."""

    def __init__(self, fac=1, kfac=1):
        self.fac_update_freq = fac
        self.kfac_update_freq = kfac
        self.damping = 0.003
        self.comm_precision = None
        self.axis_name = None


def _feed(ctl, pre, model, steps):
    fed = 0
    while fed < steps and ctl.state != 'steady':
        F = pre.kfac_update_freq
        for i in range(F):
            phases, cost = model(F, i)
            ctl.record(phases, cost)
            fed += 1
            if fed >= steps:
                break
    return fed


def leg_synthetic(art_dir):
    """Planted optimum at the ladder top: refresh cost 0.5 amortizes,
    steady steps cost 0.01 — every doubling wins until the cap."""
    optimum = 8
    pre = _FakePrecond(kfac=1)
    ctl = autotune.KnobController(
        pre, window=16, settle=1, rel_improve=0.03, dwell_windows=1,
        cooldown=2, steady_every=0, tune=('kfac_update_freq',),
        freq_bounds=(1, optimum),
        decision_log=os.path.join(art_dir,
                                  'autotune-decisions-synthetic.jsonl'))

    def model(F, i):
        if i == 0:
            return ('pred', 'stats', 'decomp', 'gather'), 0.51
        return ('pred',), 0.01

    steps = _feed(ctl, pre, model, 2000)
    failures = []
    if pre.kfac_update_freq != optimum:
        failures.append(f'final kfac_update_freq={pre.kfac_update_freq} '
                        f'!= planted optimum {optimum}')
    if ctl.state != 'steady':
        failures.append(f'no steady state after {steps} steps')
    if ctl.windows > 30:
        failures.append(f'{ctl.windows} probe windows (bound: 30)')
    if ctl.vetoes:
        failures.append(f'{ctl.vetoes} spurious vetoes')
    return {'leg': 'synthetic', 'planted_optimum': optimum,
            'final_kfac_update_freq': pre.kfac_update_freq,
            'steps': steps, 'windows': ctl.windows,
            'commits': ctl.commits, 'reverts': ctl.reverts,
            'vetoes': ctl.vetoes, 'failures': failures}


class _FakeDecompPrecond(_FakePrecond):
    def __init__(self, method='cholesky', decomp_impl='xla', **kw):
        super().__init__(**kw)
        self.method = method
        self.decomp_impl = decomp_impl


def leg_decomp_ladder(art_dir):
    """Planted optimum on the inverse-free rung: newton_schulz's
    decomposition marginal is 4x cheaper — the controller must land on
    it with zero spurious vetoes."""
    pre = _FakeDecompPrecond(kfac=4)
    ctl = autotune.KnobController(
        pre, window=8, settle=1, rel_improve=0.03, dwell_windows=1,
        cooldown=2, steady_every=0, tune=('decomp_impl',),
        decision_log=os.path.join(art_dir,
                                  'autotune-decisions-decomp.jsonl'))

    def model(F, i):
        decomp = 0.4 if pre.decomp_impl == 'xla' else 0.1
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + decomp
        return ('pred',), 0.01

    steps = _feed(ctl, pre, model, 1000)
    failures = []
    if pre.decomp_impl != 'newton_schulz':
        failures.append(f'final decomp_impl={pre.decomp_impl} != planted '
                        'optimum newton_schulz')
    if ctl.state != 'steady':
        failures.append(f'no steady state after {steps} steps')
    if ctl.vetoes:
        failures.append(f'{ctl.vetoes} spurious vetoes')
    return {'leg': 'decomp_ladder', 'planted_optimum': 'newton_schulz',
            'final_decomp_impl': pre.decomp_impl, 'steps': steps,
            'commits': ctl.commits, 'vetoes': ctl.vetoes,
            'failures': failures}


def leg_quality_hold(art_dir):
    """The numerical-health acceptance criterion: a FASTER iterative
    rung whose probe window raises the badness counter never commits."""
    pre = _FakeDecompPrecond(kfac=4)
    events = {'n': 0}
    ctl = autotune.KnobController(
        pre, window=8, settle=1, rel_improve=0.03, dwell_windows=1,
        cooldown=50, steady_every=0, tune=('decomp_impl',),
        quality_gate=lambda: events['n'],
        decision_log=os.path.join(art_dir,
                                  'autotune-decisions-quality.jsonl'))

    def model(F, i):
        if pre.decomp_impl == 'newton_schulz':
            events['n'] += 1                  # accuracy regressing...
            decomp = 0.05                     # ...but much faster
        else:
            decomp = 0.4
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + decomp
        return ('pred',), 0.01

    steps = _feed(ctl, pre, model, 1000)
    failures = []
    if ctl.commits:
        failures.append(f'{ctl.commits} commits of an accuracy-'
                        'regressing rung')
    if not ctl.vetoes:
        failures.append('no quality veto fired')
    if pre.decomp_impl != 'xla':
        failures.append(f'knob moved to {pre.decomp_impl} despite the '
                        'quality veto')
    if ctl.state != 'steady':
        failures.append(f'no steady state after {steps} steps '
                        f'(state={ctl.state})')
    return {'leg': 'quality_hold', 'commits': ctl.commits,
            'vetoes': ctl.vetoes,
            'final_decomp_impl': pre.decomp_impl, 'steps': steps,
            'failures': failures}


class _FakeCapturePrecond(_FakePrecond):
    def __init__(self, capture_impl='xla', **kw):
        super().__init__(**kw)
        self.capture_impl = capture_impl


def leg_capture_ladder(art_dir):
    """Planted optimum on the fused-capture rung (ISSUE 19): the pallas
    kernels' per-window capture marginal is 4x cheaper — the controller
    must land on the fused rung with zero spurious vetoes."""
    pre = _FakeCapturePrecond(kfac=4)
    ctl = autotune.KnobController(
        pre, window=8, settle=1, rel_improve=0.03, dwell_windows=1,
        cooldown=2, steady_every=0, tune=('capture_impl',),
        decision_log=os.path.join(art_dir,
                                  'autotune-decisions-capture.jsonl'))

    def model(F, i):
        stats = 0.4 if pre.capture_impl == 'xla' else 0.1
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + stats
        return ('pred',), 0.01

    steps = _feed(ctl, pre, model, 1000)
    failures = []
    if pre.capture_impl != 'pallas':
        failures.append(f'final capture_impl={pre.capture_impl} != '
                        'planted optimum pallas')
    if ctl.state != 'steady':
        failures.append(f'no steady state after {steps} steps')
    if ctl.vetoes:
        failures.append(f'{ctl.vetoes} spurious vetoes')
    return {'leg': 'capture_ladder', 'planted_optimum': 'pallas',
            'final_capture_impl': pre.capture_impl, 'steps': steps,
            'commits': ctl.commits, 'vetoes': ctl.vetoes,
            'failures': failures}


class _FakeCommModePrecond(_FakePrecond):
    """comm-mode-switchable fake (ISSUE 14): a planted analytic byte
    model (pred ships 64 MiB every step, inverse 8 MiB per refresh) and
    a replan stub that records the applied switch — everything the
    controller's comm_mode rung needs, no jax anywhere."""

    def __init__(self, mode='pred', **kw):
        super().__init__(**kw)
        self.comm_mode = mode
        self.axis_name = 'batch'
        self.method = 'eigh'
        self.ekfac = False
        self.comm_prefetch = False
        self.replans = []
        outer = self

        class _Plan:
            def comm_volume(self, *, stats_reduce, method,
                            comm_precision='fp32', comm_mode=None,
                            decomp_shard=None):
                mode = comm_mode or outer.comm_mode
                return {'FactorComm': 0,
                        'InverseComm': (8 << 20) if mode == 'inverse'
                        else 0,
                        'PredComm': (64 << 20) if mode == 'pred' else 0,
                        'DecompComm': 0}

        self.plan = _Plan()

    def request_replan(self, _invalidate=True, **spec):
        self.replans.append(dict(spec))


def leg_comm_mode(art_dir):
    """The applied comm-mode switch (ISSUE 14 acceptance): a planted
    comm-bound profile where comm_pred costs 0.05 s every step and
    comm_inverse amortizes to ~0.015 s — the analytic verdict seeds the
    inverse candidate first, the measured probe wins, the controller
    COMMITS (decision log shows an *applied*, not advisory, commit via
    KFAC.replan) and steady state beats the starting mode."""
    pre = _FakeCommModePrecond(mode='pred', kfac=4)
    ctl = autotune.KnobController(
        pre, window=8, settle=1, rel_improve=0.03, dwell_windows=1,
        cooldown=2, steady_every=0, tune=('comm_mode',),
        decision_log=os.path.join(art_dir,
                                  'autotune-decisions-comm-mode.jsonl'))

    def model(F, i):
        if pre.comm_mode == 'pred':
            # the pred gather ships every step: comm-bound flat profile
            return ('pred',), 0.05
        if i == 0:
            return ('pred', 'stats', 'decomp', 'gather'), 0.03
        return ('pred',), 0.01

    steps = _feed(ctl, pre, model, 1000)
    failures = []
    if pre.comm_mode != 'inverse':
        failures.append(f'final comm_mode={pre.comm_mode} — the planted '
                        'comm-bound profile was not applied')
    commits = [d for d in ctl.decisions
               if d['kind'] == 'commit' and d.get('knob') == 'comm_mode']
    if not commits:
        failures.append('no comm_mode commit in the decision log')
    elif not commits[0].get('applied'):
        failures.append('comm_mode commit is not marked applied '
                        '(advisory-only regression)')
    if ctl.comm_mode_choice != 'inverse':
        failures.append(f'analytic prior chose {ctl.comm_mode_choice}, '
                        "expected 'inverse' (seeded-prior regression)")
    if not pre.replans:
        failures.append('no KFAC.request_replan recorded — the commit '
                        'did not route through the live replanning path')
    # the committed config's window time: the last window measured is
    # the reverted probe back to 'pred', not the steady config
    steady_t = ctl.baseline_t
    if steady_t is None or steady_t >= 0.05:
        failures.append(f'steady-state window {steady_t}s does not beat '
                        'the starting mode (0.05 s/step)')
    if ctl.state != 'steady':
        failures.append(f'no steady state after {steps} steps '
                        f'(state={ctl.state})')
    return {'leg': 'comm_mode', 'final_comm_mode': pre.comm_mode,
            'prior_choice': ctl.comm_mode_choice,
            'replans': list(pre.replans), 'steady_window_s': steady_t,
            'commits': ctl.commits, 'steps': steps, 'failures': failures}


def main():
    art_dir = os.environ.get('AUTOTUNE_SMOKE_DIR', '.')
    os.makedirs(art_dir, exist_ok=True)
    legs = [leg_synthetic(art_dir), leg_decomp_ladder(art_dir),
            leg_capture_ladder(art_dir), leg_quality_hold(art_dir),
            leg_comm_mode(art_dir)]
    failures = [f for leg in legs for f in leg['failures']]
    summary = {'ok': not failures, 'failures': failures, 'legs': legs}
    out = os.path.join(art_dir, 'autotune-smoke.json')
    with open(out, 'w') as f:
        json.dump(summary, f, indent=2)
    for leg in legs:
        status = 'ok' if not leg['failures'] else 'FAIL'
        print(f"autotune-smoke: {leg['leg']}: {status}"
              + (f" {leg['failures']}" if leg['failures'] else ''))
    print(f'autotune-smoke: summary -> {out}')
    if failures and os.environ.get('KFAC_AUTOTUNE_ASSERT') == '1':
        print('autotune-smoke: ASSERT FAILED', file=sys.stderr)
        for f in failures:
            print(f'  - {f}', file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
