"""``step_reads_in_trace`` (PR 44): how many ``kfac.step.read_step`` spans
the traced segment holds, through the metric's own file and the reducer
the benchmark already had (``reducers/span_count.py``). A program that
reads the step counter back on every call writes one a step; one that
keeps it on the host writes none once the counter is there."""

import pytest

from harness import files, tracefile
from test_reduce import ctx_of
from test_spans import BEFORE, RECORDED, STEPS, hand_built, metric

CELLS = ['resnet50-freq10', 'bert-base-freq10', 'resnet50-freq1',
         'kanana2-ep16-freq10', 'trinity-mini-ep16-freq10']


def run_ahead():
    """The hand-built trace as a program that counts on the host leaves
    it: ``kfac.step`` spans, none called ``read_step``."""
    trace = hand_built()
    host = trace['planes'][1]['lines'][0]['events']
    host[:] = [e for e in host if e[0] != 'kfac.step.read_step']
    return ctx_of(trace, steps=2)


@pytest.mark.parametrize('ctx, want', [
    pytest.param(lambda: ctx_of(hand_built(), steps=2), 2.0,
                 id='a_read_a_step'),
    pytest.param(run_ahead, 0.0, id='counted_on_the_host'),
    pytest.param(lambda: ctx_of(tracefile.load(RECORDED), steps=STEPS),
                 float(STEPS), id='recorded_on_the_chip_a_read_a_step'),
    pytest.param(lambda: ctx_of(tracefile.load(BEFORE), steps=STEPS), None,
                 id='a_program_without_spans'),
    pytest.param(lambda: {'trace': None}, None, id='no_trace'),
])
def test_step_reads_in_trace(ctx, want):
    assert metric('step_reads_in_trace', ctx()) == want


def test_a_trace_without_read_spans_reads_through_every_span_metric():
    """Nothing waits under ``read_step`` and no reader of the step's spans
    raises or falls silent for want of one."""
    ctx = run_ahead()
    assert metric('idle_read_step_ms_per_step', ctx) == 0.0
    # the whole span is the host's own work now
    assert metric('host_step_busy_ms', ctx) == pytest.approx(
        (600 + 1900) / 2 / 1e6)
    for name in ('idle_dispatch_ms_per_step', 'idle_hooks_select_ms_per_step',
                 'idle_outside_step_ms_per_step', 'launch_latency_ms',
                 'device_clock_shift_ms', 'step_builds_in_trace'):
        assert metric(name, ctx) is not None


def test_the_metric_is_declared_for_every_cell():
    spec, rehearsal = files.load_json('metrics', 'step_reads_in_trace')
    assert not rehearsal
    assert spec == {'name': 'step_reads_in_trace', 'reducer': 'span_count',
                    'args': {'name': 'kfac.step.read_step'}, 'unit': 'count'}
    entry, = [m for m in files.benchmark_json()['per_layer']
              if m['name'] == 'step_reads_in_trace']
    assert entry == {'name': 'step_reads_in_trace', 'unit': 'count',
                     'better': 'lower', 'source': 'program_span',
                     'layer': 'step dispatch', 'moves': 'samples_per_s',
                     'workloads': CELLS}
    for cell in CELLS:
        resolved, _ = files.resolve_workload(cell)
        assert 'step_reads_in_trace' in [m['name']
                                         for m in resolved['per_layer']]
