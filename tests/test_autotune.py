"""Closed-loop autotuner (kfac_pytorch_tpu/autotune.py).

Pins the tentpole contracts:

1. The arbiter is the ONLY writer of the runtime knobs: the
   KFACParamScheduler and the StragglerGovernor propose factors /
   stretches and never assign ``fac_update_freq`` /
   ``kfac_update_freq`` / ``damping`` themselves (a ``__setattr__``
   guard proves every write happens inside ``arbiter._commit``), and
   the composed result is schedule x stretch x tuner over the
   construction-time base.
2. The scheduler x governor interplay that used to be last-writer-wins
   is now order-free: an epoch advance mid-stretch decays the BASE
   while the stretch stays in force; recovery removes only the stretch
   (ManualClock, fully deterministic).
3. The controller converges to a planted optimum on a deterministic
   synthetic phase-time feed (no wall clock anywhere), with hysteresis
   (no knob flap inside the dwell window, cooldown after a revert,
   bounded probing in steady state).
4. The controller compares measurements only: it starts from the
   knobs the preconditioner was built with, and an improving candidate
   commits unless the ``quality_gate`` counter rose in its probe window.
5. Knob changes reuse the compiled variant cache (frequency moves
   compile nothing new when revisited) while a ``comm_precision``
   change clears it through the registered invalidator — and the
   mid-run fp32 -> bf16 -> fp32 wire switch keeps the EF-residual
   state structure consistent and checkpoints restorable.
6. Decisions are artifacts: JSONL decision log, ``report()`` block,
   and log lines in the shared ``incident`` event grammar (kfac-obs
   renders tuning timelines for free).
"""

import json
import logging

import numpy as np
import pytest

from kfac_pytorch_tpu import autotune
from kfac_pytorch_tpu.resilience.retry import ManualClock
from kfac_pytorch_tpu.resilience.straggler import StragglerGovernor

pytestmark = pytest.mark.core


class _FakePrecond:
    """Knob-attribute-only stand-in (jax-free, like the governor's)."""

    def __init__(self, fac=1, kfac=10, damping=0.03,
                 comm_precision=None, axis_name=None):
        self.fac_update_freq = fac
        self.kfac_update_freq = kfac
        self.damping = damping
        self.comm_precision = comm_precision
        self.axis_name = axis_name


class _GuardedPrecond(_FakePrecond):
    """Asserts every knob write happens inside the arbiter's apply —
    the single-writer enforcement of the acceptance criteria."""

    def __init__(self, *a, **kw):
        object.__setattr__(self, '_armed', False)
        super().__init__(*a, **kw)
        object.__setattr__(self, '_armed', True)

    def __setattr__(self, name, value):
        if name in autotune.KNOB_ATTRS and getattr(self, '_armed', False):
            assert autotune.in_apply(), \
                f'direct (non-arbiter) write of {name}'
        object.__setattr__(self, name, value)


# ---------------------------------------------------------------------------
# the arbiter: composition, adoption, single-writer enforcement
# ---------------------------------------------------------------------------

def test_arbiter_composes_schedule_stretch_tuner():
    pre = _FakePrecond(fac=1, kfac=10, damping=0.04)
    arb = autotune.arbiter_for(pre)
    assert autotune.arbiter_for(pre) is arb  # one per precond
    arb.propose('schedule', freq_factor=2.0, damping_factor=0.5)
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (2, 20)
    assert abs(pre.damping - 0.02) < 1e-12
    arb.propose('straggler', stretch=4)
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (8, 80)
    assert abs(pre.damping - 0.02) < 1e-12  # stretch leaves damping alone
    # tuner absolute override replaces base x schedule, stretch still on
    arb.propose('tuner', kfac_update_freq=5)
    assert pre.kfac_update_freq == 20          # 5 x stretch 4
    arb.propose('straggler', stretch=1)
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (2, 5)
    # clearing the override returns to base x schedule
    arb.propose('tuner', kfac_update_freq=None)
    assert pre.kfac_update_freq == 20


def test_arbiter_freq_floor_and_int_truncation():
    # reference semantics: int() truncation then a floor of 1
    pre = _FakePrecond(fac=1, kfac=2)
    arb = autotune.arbiter_for(pre)
    arb.propose('schedule', freq_factor=0.1)
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (1, 1)


def test_arbiter_adopts_external_direct_write():
    pre = _FakePrecond(fac=1, kfac=10)
    arb = autotune.arbiter_for(pre)
    arb.propose('straggler', stretch=2)
    assert pre.kfac_update_freq == 20
    # a legacy caller writes the attrs directly: adopted as the new
    # base, stretch/schedule/tuner state reset (the old governor
    # collision rule, now in one place)
    pre.fac_update_freq, pre.kfac_update_freq = 4, 40
    arb.propose('straggler', stretch=1)
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (4, 40)
    assert arb.base['kfac_update_freq'] == 40


def test_adoption_keeps_stretch_and_schedule_incremental():
    """The adoption regressions: (a) an external write of ONE knob
    must not bake an in-force straggler stretch into the untouched
    frequency base — recovery still removes it; (b) a schedule advance
    after adoption decays INCREMENTALLY from the adopted value, never
    re-applying the whole cumulative factor to an already-decayed
    base."""
    # (a) damping written externally while the governor is stretched
    pre = _FakePrecond(fac=1, kfac=10, damping=0.04)
    arb = autotune.arbiter_for(pre)
    arb.propose('straggler', stretch=4)
    assert pre.kfac_update_freq == 40
    pre.damping = 0.01                       # external, damping only
    arb.propose('straggler', stretch=1)      # recovery
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (1, 10)
    assert abs(pre.damping - 0.01) < 1e-12   # external value survives
    # (b) epoch decay, external damping write, next epoch decay:
    # cumulative factor 0.25 at epoch 2 applies as one more halving of
    # the ADOPTED value (0.01 -> 0.005), not 0.01 * 0.25
    pre2 = _FakePrecond(fac=1, kfac=10, damping=0.04)
    arb2 = autotune.arbiter_for(pre2)
    arb2.propose('schedule', damping_factor=0.5)   # epoch 1: 0.02
    assert abs(pre2.damping - 0.02) < 1e-12
    pre2.damping = 0.01                            # external mid-run
    arb2.propose('schedule', damping_factor=0.25)  # epoch 2
    assert abs(pre2.damping - 0.005) < 1e-12
    # an external FREQ write supersedes the stretch (the old governor
    # rule): the written cadence is the new unstretched base
    pre3 = _FakePrecond(fac=1, kfac=10)
    arb3 = autotune.arbiter_for(pre3)
    arb3.propose('straggler', stretch=2)
    pre3.fac_update_freq, pre3.kfac_update_freq = 4, 40
    arb3.propose('straggler', stretch=2)     # still degraded
    assert (pre3.fac_update_freq, pre3.kfac_update_freq) == (8, 80)
    arb3.propose('straggler', stretch=1)
    assert (pre3.fac_update_freq, pre3.kfac_update_freq) == (4, 40)


def test_tuner_damping_override_applies_and_clears():
    pre = _FakePrecond(fac=1, kfac=10, damping=0.04)
    arb = autotune.arbiter_for(pre)
    arb.propose('schedule', damping_factor=0.5)
    assert abs(pre.damping - 0.02) < 1e-12
    arb.propose('tuner', damping=0.007)      # absolute override
    assert abs(pre.damping - 0.007) < 1e-12
    arb.propose('schedule', damping_factor=0.25)  # override still wins
    assert abs(pre.damping - 0.007) < 1e-12
    arb.propose('tuner', damping=None)       # cleared -> base x schedule
    assert abs(pre.damping - 0.01) < 1e-12


def test_tick_attributes_interval_to_previous_dispatch():
    """The trainer feed: build_train_step ticks BEFORE the dispatch
    updates last_phases, so the phases argument names the dispatch the
    just-ended interval covered — tick must attribute the interval to
    the phases passed NOW (an off-by-one here buckets every refresh
    spike under the preceding steady step's phase set, where the
    outlier screen discards it)."""
    pre = _FakePrecond(fac=1, kfac=4)
    t = {'now': 0.0}
    ctl = autotune.KnobController(pre, window=4, settle=0, tune=(),
                                  clock=lambda: t['now'])
    # dispatch sequence: refresh (10 s) then three steady (1 s) —
    # each tick happens before the NEXT dispatch, carrying the phase
    # set of the dispatch whose interval just ended
    seq = [(('pred', 'stats', 'decomp', 'gather'), 10.0),
           (('pred',), 1.0), (('pred',), 1.0), (('pred',), 1.0)]
    ctl.tick(0, ())                       # first tick: nothing recorded
    for i, (phases, dt) in enumerate(seq):
        t['now'] += dt
        ctl.tick(i + 1, phases)
    acc = ctl.last_window['measured']
    # the 10 s interval landed on the refresh phase set, not 'pred'
    assert ctl.last_window['time_s'] == pytest.approx(3.25)
    refresh_label = [k for k in acc if 'ComputeInverse' in k]
    assert refresh_label, acc


def test_arbiter_rejects_unknown_proposer_and_knob():
    pre = _FakePrecond()
    arb = autotune.arbiter_for(pre)
    with pytest.raises(KeyError):
        arb.propose('tuner', basis_update_freq=7)
    with pytest.raises(KeyError):
        arb.propose('cosmic_rays', stretch=2)


def test_arbiter_elastic_records_compose_nothing():
    pre = _FakePrecond(fac=2, kfac=20)
    arb = autotune.arbiter_for(pre)
    arb.propose('elastic', from_world=2, to_world=3, lr_factor=1.5)
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (2, 20)
    assert arb.records == [{'from_world': 2, 'to_world': 3,
                            'lr_factor': 1.5}]


def test_arbiter_rebases_cohorts_once_per_change():
    calls = []

    class _P(_FakePrecond):
        def rebase_cohorts(self):
            calls.append(1)

    pre = _P(fac=1, kfac=10)
    arb = autotune.arbiter_for(pre)
    arb.propose('straggler', stretch=2)       # freq change -> 1 rebase
    assert len(calls) == 1
    arb.propose('straggler', stretch=2)       # no-op -> no rebase
    assert len(calls) == 1
    arb.propose('schedule', damping_factor=0.5)   # damping only -> none
    assert len(calls) == 1
    arb.propose('tuner', kfac_update_freq=7)  # composed change -> 1 more
    assert len(calls) == 2


def test_arbiter_invalidator_fires_only_on_comm_precision():
    pre = _FakePrecond(comm_precision='fp32')
    arb = autotune.arbiter_for(pre)
    cleared = []
    arb.add_invalidator(lambda: cleared.append(1))
    arb.propose('straggler', stretch=2)
    assert not cleared                         # freq moves reuse cache
    arb.propose('tuner', comm_precision='bf16')
    assert len(cleared) == 1
    assert pre.comm_precision == 'bf16'
    arb.propose('tuner', comm_precision='bf16')
    assert len(cleared) == 1                   # unchanged -> no clear


def test_scheduler_and_governor_never_write_knobs_directly():
    """The acceptance-criteria pin: every fac/kfac_update_freq/damping
    mutation flows through the arbiter — asserted at the setattr level
    while the real scheduler and governor run their full paths."""
    from kfac_pytorch_tpu.scheduler import KFACParamScheduler
    pre = _GuardedPrecond(fac=1, kfac=10, damping=0.03)
    sched = KFACParamScheduler(pre, damping_alpha=0.5,
                               damping_schedule=[1],
                               update_freq_alpha=2,
                               update_freq_schedule=[1])
    clk = ManualClock()
    gov = StragglerGovernor(pre, budget=1.0, decay=0.5, warmup=0,
                            clock=clk.monotonic, sleep=clk.sleep)
    sched.step(1)
    for dt in (5.0, 5.0, 5.0):
        gov.observe(dt)
    assert gov.level >= 1
    for _ in range(10):
        gov.observe(0.01)
    assert gov.level == 0
    ctl = autotune.KnobController(pre, window=2, settle=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 80))
    for _ in range(8):
        ctl.record(('pred',), 0.01)
    # all three proposers ran full cycles; _GuardedPrecond asserted
    # in_apply() on every knob write along the way
    assert autotune.arbiter_for(pre).changes >= 3


def test_scheduler_epoch_mid_stretch_then_recover_ordering():
    """The satellite regression: stretch -> epoch decay -> recover on a
    ManualClock. The old direct writes lost one side's intent at each
    hand-off; through the arbiter both survive in either order."""
    from kfac_pytorch_tpu.scheduler import KFACParamScheduler
    pre = _FakePrecond(fac=1, kfac=10, damping=0.03)
    sched = KFACParamScheduler(pre, update_freq_alpha=2,
                               update_freq_schedule=[1])
    clk = ManualClock()
    gov = StragglerGovernor(pre, budget=1.0, decay=0.5, warmup=0,
                            stretch=2, clock=clk.monotonic,
                            sleep=clk.sleep)
    # 1) the governor stretches
    for dt in (5.0, 5.0, 5.0):
        gov.observe(dt)
    level = gov.level
    assert level >= 1
    stretch = 2 ** level
    assert pre.kfac_update_freq == 10 * stretch
    # 2) an epoch advance mid-stretch: the schedule decays the BASE
    #    while the stretch stays in force (neither clobbers the other)
    sched.step(1)
    assert pre.kfac_update_freq == 20 * stretch
    assert pre.fac_update_freq == 2 * stretch
    # 3) recovery removes ONLY the stretch: the epoch's cadence survives
    for _ in range(10):
        gov.observe(0.01)
    assert gov.level == 0
    assert (pre.fac_update_freq, pre.kfac_update_freq) == (2, 20)


# ---------------------------------------------------------------------------
# the controller: deterministic synthetic feeds (no wall clock)
# ---------------------------------------------------------------------------

def _feed(ctl, pre, model, steps):
    """Drive ``ctl`` with a synthetic per-step cost model
    ``model(kfac_update_freq, i_in_window) -> (phases, seconds)``;
    returns steps actually fed."""
    fed = 0
    while fed < steps:
        F = pre.kfac_update_freq
        for i in range(F):
            phases, cost = model(F, i)
            ctl.record(phases, cost)
            fed += 1
            if fed >= steps:
                break
    return fed


def _amortized(F, i):
    """Refresh cost 0.5 amortized over the window: optimum = max freq."""
    if i == 0:
        return ('pred', 'stats', 'decomp', 'gather'), 0.51
    return ('pred',), 0.01


def test_controller_converges_to_planted_optimum():
    pre = _FakePrecond(fac=1, kfac=1)
    ctl = autotune.KnobController(pre, window=16, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 8))
    _feed(ctl, pre, _amortized, 400)
    assert pre.kfac_update_freq == 8          # the planted optimum
    assert ctl.state == 'steady'
    assert ctl.commits == 3                   # 1 -> 2 -> 4 -> 8
    assert ctl.windows <= 30                  # bounded probe budget
    k = ctl.report()
    assert k['knobs']['kfac_update_freq'] == 8
    assert k['state'] == 'steady'


def test_controller_converges_down_from_pessimal_high_freq():
    """Stale-side optimum: when every step's cost GROWS with the
    cadence (a stand-in for staleness pricing), the controller must
    climb DOWN the ladder too."""
    pre = _FakePrecond(fac=1, kfac=8)

    def model(F, i):
        phases = ('pred', 'stats', 'decomp', 'gather') if i == 0 \
            else ('pred',)
        return phases, 0.01 + 0.002 * F + (0.001 if i == 0 else 0.0)

    ctl = autotune.KnobController(pre, window=16, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 8))
    _feed(ctl, pre, model, 600)
    assert pre.kfac_update_freq == 1
    assert ctl.state == 'steady'


def test_controller_hysteresis_no_flap_on_flat_profile():
    """A flat cost profile must settle, not oscillate: every probe
    reverts (no >rel_improve gain), candidates go on cooldown, and the
    controller reaches steady with the original knob intact."""
    pre = _FakePrecond(fac=1, kfac=4)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=2,
                                  cooldown=4, steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 8))
    _feed(ctl, pre, lambda F, i: (('pred',), 0.01), 600)
    assert ctl.state == 'steady'
    assert pre.kfac_update_freq == 4
    assert ctl.commits == 0
    assert ctl.reverts == 2                   # 8 and 2 each tried once


def test_controller_dwell_blocks_probes_after_commit():
    """Hysteresis: after a commit the controller holds the committed
    config for dwell_windows full windows before probing again."""
    pre = _FakePrecond(fac=1, kfac=1)
    ctl = autotune.KnobController(pre, window=16, settle=1,
                                  rel_improve=0.03, dwell_windows=3,
                                  cooldown=2, steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 8))
    # run until the first commit lands
    while ctl.commits == 0:
        _feed(ctl, pre, _amortized, 16)
    assert ctl.state == 'dwell'
    committed = pre.kfac_update_freq
    start = ctl.windows
    while ctl.state == 'dwell':
        # the knob may only change at the dwell->probe transition —
        # while still dwelling it must hold the committed value
        assert pre.kfac_update_freq == committed
        _feed(ctl, pre, _amortized, 1)
    assert ctl.windows - start >= 3


def test_controller_discards_windows_under_straggler_stretch():
    """A host emergency is not a tuning signal: while the governor's
    stretch is in force the controller accumulates nothing."""
    pre = _FakePrecond(fac=1, kfac=4)
    arb = autotune.arbiter_for(pre)
    ctl = autotune.KnobController(pre, window=4, settle=0,
                                  tune=('kfac_update_freq',))
    arb.propose('straggler', stretch=2)
    for _ in range(40):
        ctl.record(('pred',), 5.0)            # catastrophic step times
    assert ctl.windows == 0 and ctl.state == 'baseline'
    arb.propose('straggler', stretch=1)
    for _ in range(6):
        ctl.record(('pred',), 0.01)
    assert ctl.windows >= 1                   # measuring again


def test_first_window_starts_from_constructed_knobs(caplog):
    """No seeding step: the first record moves no knob, the baseline
    window measures the knobs the preconditioner was built with, and
    the first decision is a probe (no 'seed', no 'autotune: seeded'
    line)."""
    pre = _GuardedPrecond(fac=2, kfac=4)
    built = autotune._capture(pre)
    log = logging.getLogger('test_autotune_first_window')
    ctl = autotune.KnobController(pre, window=4, settle=0,
                                  steady_every=0, freq_bounds=(1, 8),
                                  log=log)
    with caplog.at_level(logging.INFO, logger=log.name):
        ctl.record(('pred',), 0.01)
        assert autotune._capture(pre) == built and not ctl.decisions
        for _ in range(3):
            ctl.record(('pred',), 0.01)
    assert ctl.last_window['window'] == 1
    assert ctl.last_window['knobs'] == built
    assert ctl.decisions[0]['kind'] == 'probe'
    assert not any(d['kind'] == 'seed' for d in ctl.decisions)
    assert 'seeded' not in caplog.text


@pytest.mark.parametrize('make', [
    lambda pre, **kw: autotune.KnobController(pre, **kw),
    lambda pre, **kw: autotune.controller_from_args(pre, enabled=True,
                                                    **kw),
], ids=['KnobController', 'controller_from_args'])
def test_model_arguments_are_rejected(make):
    """The analytic model and everything that indexed it are gone: a
    stale caller fails loudly."""
    pre = _FakePrecond()
    assert make(pre) is not None
    for name, value in (('predicted', {'scenarios': {}}),
                        ('platform', 'TPU v5e'),
                        ('variant', 'eigen_dp'), ('anchor', 'central')):
        with pytest.raises(TypeError, match=name):
            make(pre, **{name: value})


# ---------------------------------------------------------------------------
# the one gate: quality_gate
# ---------------------------------------------------------------------------

def _veto_harness(gate=None, log=None):
    """Baseline window at 0.6 s steps, then a probe window at 0.5 s:
    the candidate passes the objective. ``gate`` is 'rises' (the
    badness counter goes up in the probe window), 'flat' or None."""
    pre = _FakePrecond(fac=1, kfac=4)
    events = {'n': 0}
    ctl = autotune.KnobController(
        pre, window=4, settle=0, rel_improve=0.03, dwell_windows=1,
        cooldown=2, steady_every=0, tune=('kfac_update_freq',),
        freq_bounds=(1, 8), log=log,
        quality_gate=(lambda: events['n']) if gate else None)
    for _ in range(4):                        # baseline window
        ctl.record(('pred',), 0.6)
    assert ctl.state == 'probe'
    for _ in range(4):                        # probe window: improved
        events['n'] += gate == 'rises'
        ctl.record(('pred',), 0.5)
    return pre, ctl


@pytest.mark.parametrize('gate', [None, 'flat', 'rises'])
def test_improving_candidate_commits_unless_quality_gate_rises(gate):
    """rel_improve and the quality gate are all that stand between an
    improving candidate and its commit."""
    pre, ctl = _veto_harness(gate)
    if gate == 'rises':
        assert ctl.vetoes == 1 and ctl.commits == 0
        assert pre.kfac_update_freq != 8      # the vetoed value never stuck
        veto = next(d for d in ctl.decisions if d['kind'] == 'veto')
        assert veto['value'] == 8 and veto['reason'] == 'quality'
        assert veto['health_events'] == 4
    else:
        assert ctl.vetoes == 0 and ctl.commits == 1
        assert pre.kfac_update_freq != 4      # the probe value stuck
    assert set(ctl.counts()) == {'autotune_commits', 'autotune_reverts',
                                 'autotune_vetoes'}


# ---------------------------------------------------------------------------
# comm-mode decision (advisory, analytic)
# ---------------------------------------------------------------------------

def test_decide_comm_mode_amortization_crossover():
    vols = {'inverse': 1000.0, 'pred': 100.0}
    # at freq 1 the gather ships every step: pred is 10x cheaper
    mode, per_step = autotune.decide_comm_mode(vols, 1)
    assert mode == 'pred' and per_step['inverse'] == 1000.0
    # at freq 100 the gather amortizes to 10 B/step: inverse wins
    mode, per_step = autotune.decide_comm_mode(vols, 100)
    assert mode == 'inverse' and per_step['inverse'] == 10.0


def test_comm_mode_decision_recorded_once_from_plan():
    from kfac_pytorch_tpu import plan as plan_mod

    class _Bucket:
        n_rows, dim = 4, 16

    class _Pred:
        dg, da, k_per_dev = 8, 8, 2

    class _Plan:
        # the real byte model (the tuner must price both roads through
        # plan.comm_volume, never a restated formula)
        comm_volume = plan_mod.FactorPlan.comm_volume
        comm_mode = 'inverse'
        buckets = {16: _Bucket()}
        pred_groups = (_Pred(),)
        num_devices = 2

    pre = _FakePrecond(fac=1, kfac=8, comm_precision='fp32',
                       axis_name='batch')
    pre.plan = _Plan()
    pre.method = 'chol'
    pre.comm_mode = 'inverse'
    ctl = autotune.KnobController(pre, window=2, settle=0, tune=())
    for _ in range(4):
        ctl.record(('pred',), 0.01)
    assert ctl.comm_mode_choice in ('inverse', 'pred')
    assert len([d for d in ctl.decisions
                if d['kind'] == 'comm_mode']) == 1  # one-shot


# ---------------------------------------------------------------------------
# artifacts: decision log, counters, incident grammar
# ---------------------------------------------------------------------------

def test_decision_log_jsonl(tmp_path):
    log_path = tmp_path / 'sub' / 'autotune-decisions.jsonl'
    pre = _FakePrecond(fac=1, kfac=1)
    ctl = autotune.KnobController(pre, window=16, settle=1,
                                  dwell_windows=1, cooldown=2,
                                  steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 8),
                                  decision_log=str(log_path))
    _feed(ctl, pre, _amortized, 400)
    lines = [json.loads(ln) for ln in
             log_path.read_text().splitlines()]
    kinds = [d['kind'] for d in lines]
    assert 'probe' in kinds and 'commit' in kinds and 'steady' in kinds
    assert all('window' in d and 'step' in d for d in lines)


def test_counts_and_registry_collector():
    from kfac_pytorch_tpu.obs import metrics
    pre = _FakePrecond(fac=1, kfac=1)
    ctl = autotune.KnobController(pre, window=16, settle=1,
                                  dwell_windows=1, cooldown=2,
                                  steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 8))
    _feed(ctl, pre, _amortized, 400)
    c = ctl.counts()
    assert c['autotune_commits'] == ctl.commits > 0
    reg = metrics.Registry()
    ctl.collect(reg)
    snap = reg.snapshot()
    assert snap['autotune/kfac_update_freq'] == pre.kfac_update_freq
    assert snap['autotune/commits'] == ctl.commits


def test_autotune_log_lines_speak_the_incident_grammar():
    """The shared-grammar contract: the controller's run-log lines are
    parsed into typed events by incident.EVENT_PATTERNS — kfac-obs
    renders tuning timelines with zero new aggregate code."""
    from kfac_pytorch_tpu.resilience.incident import IncidentReport
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    log = logging.getLogger('test_autotune_grammar')
    log.setLevel(logging.INFO)
    log.addHandler(_Capture())
    try:
        pre = _FakePrecond(fac=1, kfac=1)
        ctl = autotune.KnobController(pre, window=16, settle=1,
                                      dwell_windows=1, cooldown=2,
                                      steady_every=0,
                                      tune=('kfac_update_freq',),
                                      freq_bounds=(1, 8), log=log)
        _feed(ctl, pre, _amortized, 400)
        # and one veto line (rig the gate through the harness)
        _veto_harness('rises', log=log)
    finally:
        log.handlers.clear()
    rep = IncidentReport(host_id=0).scrape_lines(records)
    kinds = [e['kind'] for e in rep.events]
    assert 'autotune_probe' in kinds
    assert 'autotune_commit' in kinds
    assert 'autotune_steady' in kinds
    commit = next(e for e in rep.events if e['kind'] == 'autotune_commit')
    assert commit['knob'] == 'kfac_update_freq'
    steady = next(e for e in rep.events if e['kind'] == 'autotune_steady')
    assert int(steady['kfac']) == pre.kfac_update_freq


def test_veto_log_line_speaks_the_grammar():
    from kfac_pytorch_tpu.resilience.incident import IncidentReport
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    log = logging.getLogger('test_autotune_veto_grammar')
    log.setLevel(logging.INFO)
    log.addHandler(_Capture())
    try:
        _veto_harness('rises', log=log)
    finally:
        log.handlers.clear()
    rep = IncidentReport(host_id=0).scrape_lines(records)
    veto = [e for e in rep.events if e['kind'] == 'autotune_veto']
    assert veto and veto[0]['knob'] == 'kfac_update_freq'
    assert veto[0]['value'] == 8 and veto[0]['health_events'] == 4


# ---------------------------------------------------------------------------
# jax integration: variant-cache reuse + the mid-run wire-dtype switch
# ---------------------------------------------------------------------------

def _jax_trainer(variant='eigen_dp', ndev=1, kfac_freq=2,
                 comm_precision='fp32'):
    import flax.linen as linen
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    import kfac_pytorch_tpu as kfac
    from kfac_pytorch_tpu import nn as knn
    from kfac_pytorch_tpu import training

    class MLP(linen.Module):
        @linen.compact
        def __call__(self, x, train=True):
            x = knn.Dense(8, name='fc1')(x)
            x = linen.relu(x)
            return knn.Dense(3, name='fc2')(x)

    def ce(outputs, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, batch['label']).mean()

    rng = np.random.RandomState(0)
    batch = {'input': jnp.asarray(rng.randn(8, 5), jnp.float32),
             'label': jnp.asarray(rng.randint(0, 3, 8))}
    mesh = (Mesh(np.array(jax.devices()[:ndev]), ('batch',))
            if ndev > 1 else None)
    axis = 'batch' if ndev > 1 else None
    model = MLP()
    pre = kfac.KFAC(variant=variant, lr=0.05, damping=0.003,
                    kfac_update_freq=kfac_freq, num_devices=ndev,
                    axis_name=axis, bucket_fn=lambda d: 16,
                    comm_precision=comm_precision)
    tx = training.sgd(0.05, momentum=0.9)
    state = training.init_train_state(model, tx, pre,
                                      jax.random.PRNGKey(0),
                                      batch['input'])
    step = training.build_train_step(model, tx, pre, ce, axis_name=axis,
                                     mesh=mesh)
    return step, state, pre, batch


def test_freq_knob_changes_reuse_variant_cache():
    """The compile-count guard of the acceptance criteria: a tuner /
    straggler / schedule frequency move through the arbiter compiles
    NOTHING new — the frequency is host-side dispatch gating over the
    same variant set — while a ``comm_precision`` change clears the
    cache (the registered invalidator) so no stale program can keep
    the old wire dtype."""
    step, state, pre, batch = _jax_trainer(kfac_freq=2)
    arb = autotune.arbiter_for(pre)
    for _ in range(5):
        state, _ = step(state, batch, lr=0.05, damping=0.003)
    baseline = set(step.variants)
    assert baseline                        # warmed past every variant
    # a pure kfac_update_freq move (the tuner's bread and butter)
    # re-times the SAME dispatch combos: zero new programs
    arb.propose('tuner', kfac_update_freq=4)
    for _ in range(9):
        state, _ = step(state, batch, lr=0.05, damping=0.003)
    assert set(step.variants) == baseline, (
        sorted(map(str, set(step.variants) - baseline)))

    # the full trajectory a controller run would drive: tuner overrides
    # up and down the ladder, a schedule decay stretching the stats
    # cadence, a straggler emergency + recovery. The FIRST pass may
    # fill in dispatch combos the warmup never hit (stats-off steps) —
    # that is the bounded variant set completing, not churn
    def play(s):
        moves = (('tuner', {'kfac_update_freq': 1}),
                 ('schedule', {'freq_factor': 2.0, 'damping_factor': 0.5}),
                 ('straggler', {'stretch': 2}),
                 ('straggler', {'stretch': 1}),
                 ('tuner', {'kfac_update_freq': 4}),
                 ('schedule', {'freq_factor': 1.0, 'damping_factor': 1.0}))
        for source, kw in moves:
            arb.propose(source, **kw)
            for _ in range(6):
                s, _ = step(s, batch, lr=0.05, damping=0.003)
        return s

    state = play(state)
    grown = set(step.variants)
    assert baseline <= grown           # never cleared by a cadence move
    # the compile-count guard proper: REPLAYING the whole trajectory —
    # every cadence revisited — compiles exactly nothing
    state = play(state)
    assert set(step.variants) == grown, (
        sorted(map(str, set(step.variants) - grown)))


def test_mid_run_comm_precision_switch_fp32_bf16_fp32(tmp_path):
    """The PR 8 follow-on satellite: the tuner switches the wire dtype
    mid-run through the arbiter. fp32 -> bf16 must clear the compiled
    variants and seed a zero EF residual host-side; bf16 -> fp32 must
    drop it again; a checkpoint written in the bf16 era restores into
    a bf16-era trainer byte-exactly; and the post-switch fp32 state
    checkpoints/restores cleanly (structure = a never-compressed run)."""
    import jax
    import numpy as onp

    from kfac_pytorch_tpu.utils.checkpoint import (restore_checkpoint,
                                                   save_checkpoint)
    step, state, pre, batch = _jax_trainer(variant='eigen', ndev=2,
                                           kfac_freq=1)
    arb = autotune.arbiter_for(pre)
    for _ in range(3):
        state, m = step(state, batch, lr=0.05, damping=0.003)
    assert state.kfac_state.comm_err is None          # fp32: no residual
    # -> bf16 (what a tuner commit of comm_precision does)
    arb.propose('tuner', comm_precision='bf16')
    assert not step.variants                          # cache cleared
    for _ in range(3):
        state, m = step(state, batch, lr=0.05, damping=0.003)
    assert np.isfinite(float(m['loss']))
    assert state.kfac_state.comm_err is not None      # EF residual live
    assert pre._tracks_comm_err
    save_checkpoint(str(tmp_path / 'bf16'), 0, state)
    # -> back to fp32: residual dropped host-side, run keeps training
    arb.propose('tuner', comm_precision='fp32')
    assert not step.variants
    for _ in range(3):
        state, m = step(state, batch, lr=0.05, damping=0.003)
    assert np.isfinite(float(m['loss']))
    assert state.kfac_state.comm_err is None
    # the post-switch state checkpoints like a never-compressed run
    save_checkpoint(str(tmp_path / 'fp32'), 0, state)
    f32_step, f32_fresh, _, _ = _jax_trainer(variant='eigen', ndev=2,
                                             kfac_freq=1)
    restored = restore_checkpoint(str(tmp_path / 'fp32'), 0, f32_fresh)
    assert restored.kfac_state.comm_err is None
    restored = jax.tree.map(onp.asarray, restored)
    restored, m = f32_step(restored, batch, lr=0.05, damping=0.003)
    assert np.isfinite(float(m['loss']))
    # and the bf16-era checkpoint restores byte-exactly into a
    # bf16-configured trainer (the switch stranded nothing)
    b16_step, b16_fresh, _, _ = _jax_trainer(variant='eigen', ndev=2,
                                             kfac_freq=1,
                                             comm_precision='bf16')
    restored16 = restore_checkpoint(str(tmp_path / 'bf16'), 0, b16_fresh)
    assert restored16.kfac_state.comm_err is not None
    restored16 = jax.tree.map(onp.asarray, restored16)
    restored16, m = b16_step(restored16, batch, lr=0.05, damping=0.003)
    assert np.isfinite(float(m['loss']))


def test_controller_live_on_jax_trainer_converges():
    """End-to-end: the controller rides a REAL jitted trainer through
    ``record`` with a synthetic cost model keyed off the actual
    dispatched phase set — the knob lands on the planted optimum and
    every dispatch ran against a consistent compiled variant."""
    step, state, pre, batch = _jax_trainer(kfac_freq=1)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('kfac_update_freq',),
                                  freq_bounds=(1, 4))
    for _ in range(250):
        state, _ = step(state, batch, lr=0.05, damping=0.003)
        phases = step.last_phases
        cost = 0.41 if 'decomp' in phases else 0.01   # planted: amortize
        ctl.record(phases, cost)
        if ctl.state == 'steady':
            break
    assert pre.kfac_update_freq == 4
    assert ctl.state == 'steady'


# ---------------------------------------------------------------------------
# knob-arbiter state across generations (elastic shrink -> relaunch)
# ---------------------------------------------------------------------------
# An elastic shrink kills the trainer and relaunches it at the new
# world size: a NEW process, a NEW preconditioner, a NEW arbiter — but
# the tuner's artifacts must survive the generation boundary. Two
# contracts, previously only asserted within one generation:
#
# - the decision log is APPEND-only across relaunches (same
#   KFAC_TRACE_DIR -> same autotune-decisions.jsonl), so generation
#   1's trajectory lands after generation 0's instead of clobbering it;
# - a relaunch that restores the adopted knob values (the pod
#   supervisor re-exports them; elastic_resume re-applies state) gets
#   an arbiter whose BASE is the adopted cadence — a later schedule
#   advance composes incrementally from it, and the tuner does not
#   regress to the cold-start default.


def test_decision_log_appends_across_generations(tmp_path):
    log_path = tmp_path / 'trace' / 'autotune-decisions.jsonl'

    def make_ctl(pre):
        return autotune.KnobController(
            pre, window=16, settle=1, dwell_windows=1, cooldown=2,
            steady_every=0, tune=('kfac_update_freq',),
            freq_bounds=(1, 8), decision_log=str(log_path))

    # generation 0: converge to the planted optimum, decisions logged
    pre0 = _FakePrecond(fac=1, kfac=1)
    _feed(make_ctl(pre0), pre0, _amortized, 400)
    assert pre0.kfac_update_freq == 8
    gen0 = log_path.read_text().splitlines()
    assert any(json.loads(ln)['kind'] == 'commit' for ln in gen0)

    # shrink -> relaunch: fresh precond restored to the adopted knobs,
    # fresh controller pointed at the SAME decision log
    adopted = autotune._capture(pre0)
    pre1 = _FakePrecond(fac=adopted['fac_update_freq'],
                        kfac=adopted['kfac_update_freq'],
                        damping=adopted['damping'])
    _feed(make_ctl(pre1), pre1, _amortized, 120)

    lines = log_path.read_text().splitlines()
    # generation 0's trajectory is intact (append, never truncate) and
    # generation 1 wrote after it
    assert lines[:len(gen0)] == gen0
    assert len(lines) > len(gen0)
    # the relaunched window counter restarting (a fresh controller)
    # marks the generation boundary in the artifact itself
    gen1 = [json.loads(ln) for ln in lines[len(gen0):]]
    assert gen1[0]['window'] <= 1
    # and the adopted cadence holds — no regression to the cold default
    assert pre1.kfac_update_freq == 8


def test_arbiter_adopted_base_survives_relaunch_composition():
    # generation 0: the tuner committed an absolute override
    pre0 = _FakePrecond(fac=1, kfac=2, damping=0.04)
    arb0 = autotune.arbiter_for(pre0)
    arb0.propose('tuner', kfac_update_freq=8)
    assert pre0.kfac_update_freq == 8

    # relaunch: the restored knob values are the new construction-time
    # base (single-writer enforcement stays on through the guard)
    adopted = autotune._capture(pre0)
    pre1 = _GuardedPrecond(fac=adopted['fac_update_freq'],
                           kfac=adopted['kfac_update_freq'],
                           damping=adopted['damping'])
    arb1 = autotune.arbiter_for(pre1)
    assert arb1.base['kfac_update_freq'] == 8
    assert arb1.base['damping'] == pytest.approx(0.04)

    # an epoch-schedule advance in the new generation composes
    # INCREMENTALLY from the adopted base, not the old generation's
    # pre-tuner default (2)
    arb1.propose('schedule', freq_factor=2.0)
    assert pre1.kfac_update_freq == 16
    # elastic provenance records compose nothing (record-only lane)
    arb1.propose('elastic', gen=1, world=2)
    assert pre1.kfac_update_freq == 16
    assert arb1.records and arb1.records[-1]['gen'] == 1
    # a straggler stretch then multiplies the adopted-base schedule,
    # and recovery restores exactly the composed value
    arb1.propose('straggler', stretch=2)
    assert pre1.kfac_update_freq == 32
    arb1.propose('straggler', stretch=1)
    assert pre1.kfac_update_freq == 16


# ---------------------------------------------------------------------------
# the decomp_impl ladder (the inverse-free lane of ROADMAP item 5)
# ---------------------------------------------------------------------------

class _DecompPrecond(_FakePrecond):
    """Fake preconditioner carrying the decomp_impl knob surface."""

    def __init__(self, method='cholesky', decomp_impl='xla', **kw):
        super().__init__(**kw)
        self.method = method
        self.decomp_impl = decomp_impl


def test_decomp_impls_restated_tuple_matches_preconditioner():
    # autotune must stay stdlib-importable, so it restates the canon
    from kfac_pytorch_tpu import preconditioner
    assert autotune.DECOMP_IMPLS == preconditioner.DECOMP_IMPLS


def test_controller_decomp_impl_commits_planted_optimum():
    """NS-ladder commit under a planted optimum: the newton_schulz rung
    is genuinely faster, the controller probes it, commits, and goes
    steady on it — the decomp_impl analog of the freq planted-optimum
    tests."""
    pre = _DecompPrecond(method='cholesky', decomp_impl='xla', kfac=4)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('decomp_impl',))

    def model(F, i):
        # cholesky refresh costs 0.4; the NS rung replaces it with 0.1
        decomp = 0.4 if pre.decomp_impl == 'xla' else 0.1
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + decomp
        return ('pred',), 0.01

    _feed(ctl, pre, model, 200)
    assert pre.decomp_impl == 'newton_schulz'
    assert ctl.state == 'steady'
    assert ctl.commits == 1
    assert ctl.vetoes == 0                    # zero spurious vetoes
    kinds = [d['kind'] for d in ctl.decisions]
    assert 'commit' in kinds


def test_controller_decomp_impl_reverts_when_slower():
    """The revert side of the ladder: an iterative rung that does NOT
    beat the cold kernel reverts and cools down — the knob never
    flaps."""
    pre = _DecompPrecond(method='eigh', decomp_impl='xla', kfac=4)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=3, steady_every=0,
                                  tune=('decomp_impl',))

    def model(F, i):
        # subspace is SLOWER here (the CPU-like regime)
        decomp = 0.2 if pre.decomp_impl == 'xla' else 0.35
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + decomp
        return ('pred',), 0.01

    _feed(ctl, pre, model, 200)
    assert pre.decomp_impl == 'xla'           # reverted, stays cold
    assert ctl.state == 'steady'
    assert ctl.commits == 0
    assert ctl.reverts >= 1


def test_quality_gate_vetoes_accuracy_regressing_rung():
    """The numerical-health gate: a rung that IS faster but raises the
    badness counter during its probe window never commits (counted as
    a veto, decision log says 'quality'), and the controller settles
    steady on the original knob."""
    pre = _DecompPrecond(method='cholesky', decomp_impl='xla', kfac=4)
    events = {'n': 0}
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('decomp_impl',),
                                  quality_gate=lambda: events['n'])

    def model(F, i):
        if pre.decomp_impl == 'newton_schulz':
            events['n'] += 1                  # health events every step
            decomp = 0.05                     # ...but much faster
        else:
            decomp = 0.4
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + decomp
        return ('pred',), 0.01

    _feed(ctl, pre, model, 300)
    assert pre.decomp_impl == 'xla'           # the fast-but-wrong rung
    assert ctl.commits == 0                   # never committed
    assert ctl.vetoes >= 1
    assert ctl.state == 'steady'
    vetoes = [d for d in ctl.decisions if d['kind'] == 'veto']
    assert vetoes and vetoes[0].get('reason') == 'quality'
    assert ctl.report()['vetoes'] == ctl.vetoes


def test_arbiter_decomp_impl_is_trace_affecting():
    """A decomp_impl change fires the variant-cache invalidators (the
    kernel is baked into the traced programs) and direct external
    writes are adopted as the new base, like comm_precision."""
    pre = _DecompPrecond(method='eigh', decomp_impl='xla')
    arb = autotune.arbiter_for(pre)
    cleared = []
    arb.add_invalidator(lambda: cleared.append(1))
    arb.propose('tuner', decomp_impl='subspace')
    assert pre.decomp_impl == 'subspace'
    assert cleared == [1]
    with pytest.raises(ValueError, match='decomp_impl'):
        arb.propose('tuner', decomp_impl='bogus')
    # external write adopted as base, tuner override dropped
    pre.decomp_impl = 'xla'
    arb.adopt_external()
    assert arb.base['decomp_impl'] == 'xla'
    assert 'decomp_impl' not in arb.tuner


# ---------------------------------------------------------------------------
# the capture_impl ladder (fused Pallas capture kernels, ISSUE 19)
# ---------------------------------------------------------------------------

class _CapturePrecond(_FakePrecond):
    """Fake preconditioner carrying the capture_impl knob surface."""

    def __init__(self, capture_impl='xla', **kw):
        super().__init__(**kw)
        self.capture_impl = capture_impl


def test_capture_impls_restated_tuple_matches_preconditioner():
    # autotune must stay stdlib-importable, so it restates the canon
    from kfac_pytorch_tpu import preconditioner
    assert autotune.CAPTURE_IMPLS == preconditioner.CAPTURE_IMPLS
    # the ladder probes concrete rungs only ('auto' is a policy, not a
    # program) and every rung is a valid knob value
    assert 'auto' not in autotune.CAPTURE_LADDER
    assert set(autotune.CAPTURE_LADDER) < set(autotune.CAPTURE_IMPLS)


def test_controller_capture_impl_commits_planted_optimum():
    """Fused-capture commit under a planted optimum: the pallas rung is
    genuinely faster, the controller probes it, commits, and goes
    steady on it — the capture analog of the decomp ladder tests."""
    pre = _CapturePrecond(capture_impl='xla', kfac=4)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('capture_impl',))

    def model(F, i):
        # unfused capture costs 0.4/window; the fused kernels cost 0.1
        stats = 0.4 if pre.capture_impl == 'xla' else 0.1
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + stats
        return ('pred',), 0.01

    _feed(ctl, pre, model, 200)
    assert pre.capture_impl == 'pallas'
    assert ctl.state == 'steady'
    assert ctl.commits == 1
    assert ctl.vetoes == 0                    # zero spurious vetoes
    kinds = [d['kind'] for d in ctl.decisions]
    assert 'commit' in kinds


def test_controller_capture_impl_reverts_when_slower():
    """The revert side: a fused rung that does NOT beat the unfused
    capture reverts and cools down — the knob never flaps."""
    pre = _CapturePrecond(capture_impl='xla', kfac=4)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=3, steady_every=0,
                                  tune=('capture_impl',))

    def model(F, i):
        # fused is SLOWER here (tiny F: fusion overhead dominates)
        stats = 0.2 if pre.capture_impl == 'xla' else 0.35
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + stats
        return ('pred',), 0.01

    _feed(ctl, pre, model, 200)
    assert pre.capture_impl == 'xla'          # reverted, stays unfused
    assert ctl.state == 'steady'
    assert ctl.commits == 0
    assert ctl.reverts >= 1


def test_quality_gate_vetoes_regressing_capture_rung():
    """A capture rung that IS faster but raises the badness counter
    during its probe window never commits (quality veto) — the same
    numerical-health gate the decomp ladder gets."""
    pre = _CapturePrecond(capture_impl='xla', kfac=4)
    events = {'n': 0}
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('capture_impl',),
                                  quality_gate=lambda: events['n'])

    def model(F, i):
        if pre.capture_impl == 'pallas':
            events['n'] += 1                  # health events every step
            stats = 0.05                      # ...but much faster
        else:
            stats = 0.4
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + stats
        return ('pred',), 0.01

    _feed(ctl, pre, model, 300)
    assert pre.capture_impl == 'xla'          # the fast-but-wrong rung
    assert ctl.commits == 0
    assert ctl.vetoes >= 1
    assert ctl.state == 'steady'
    vetoes = [d for d in ctl.decisions if d['kind'] == 'veto']
    assert vetoes and vetoes[0].get('reason') == 'quality'


def test_arbiter_capture_impl_is_trace_affecting():
    """A capture_impl change fires the variant-cache invalidators (the
    capture kernels are baked into the traced programs) and direct
    external writes are adopted as the new base."""
    pre = _CapturePrecond(capture_impl='xla')
    arb = autotune.arbiter_for(pre)
    cleared = []
    arb.add_invalidator(lambda: cleared.append(1))
    arb.propose('tuner', capture_impl='pallas')
    assert pre.capture_impl == 'pallas'
    assert cleared == [1]
    with pytest.raises(ValueError, match='capture_impl'):
        arb.propose('tuner', capture_impl='bogus')
    # external write adopted as base, tuner override dropped
    pre.capture_impl = 'xla'
    arb.adopt_external()
    assert arb.base['capture_impl'] == 'xla'
    assert 'capture_impl' not in arb.tuner


def test_capture_impl_hidden_when_legacy_none():
    """capture_impl=None is the legacy capture path: the rung is
    invisible to the tuner — no seed, no candidates, no knob writes —
    so pre-ISSUE-19 configs tune exactly as before."""
    pre = _FakePrecond(kfac=4)                # no capture_impl attr
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('capture_impl',))
    _feed(ctl, pre, _amortized, 200)
    assert getattr(pre, 'capture_impl', None) is None
    assert ctl.commits == 0
    assert not any(d.get('knob') == 'capture_impl' for d in ctl.decisions)


def test_controller_capture_auto_probes_the_other_rung():
    """'auto' resolves to the fused rung as the effective program, so
    the only candidate is 'xla' — and when unfused is genuinely faster
    the controller commits the concrete rung."""
    pre = _CapturePrecond(capture_impl='auto', kfac=4)
    ctl = autotune.KnobController(pre, window=8, settle=1,
                                  rel_improve=0.03, dwell_windows=1,
                                  cooldown=2, steady_every=0,
                                  tune=('capture_impl',))

    def model(F, i):
        eff = ('pallas' if pre.capture_impl == 'auto'
               else pre.capture_impl)
        stats = 0.4 if eff == 'pallas' else 0.1
        if i == 0:
            return ('pred', 'stats', 'decomp'), 0.01 + stats
        return ('pred',), 0.01

    _feed(ctl, pre, model, 200)
    assert pre.capture_impl == 'xla'
    assert ctl.commits == 1
