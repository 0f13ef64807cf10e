"""The reduction from a trace to metrics (``harness/tracefile.py`` and the
reducers), on a synthetic trace for the interval arithmetic and on the
trace recorded on the chip under ``tests/data/``."""

import glob
import os

import pytest

from harness import files, tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data')


def synthetic():
    ops = [
        ['fusion.1', 0, 100, 'tf_op=jit(step)/kfac.ComputeFactor/dot'],
        ['fusion.2', 50, 100, 'tf_op=jit(step)/conv'],          # overlaps
        ['cholesky.3', 300, 200, 'tf_op=jit(step)/kfac.ComputeInverse/x'],
        ['fusion.4', 600, 100, 'tf_op=jit(step)/kfac.Precondition/einsum'],
        ['fusion.2', 900, 100, 'tf_op=jit(step)/conv'],
        # a while loop encloses its body: its own time is 100 - 60
        ['while.9', 1100, 100, 'tf_op=jit(step)/kfac.ComputeInverse/while'],
        ['body.1', 1110, 60, 'tf_op=jit(step)/kfac.ComputeInverse/while/b'],
    ]
    host = [['PjitFunction(step)', 140, 170, ''], ['device_get', 700, 250, ''],
            ['inner', 710, 100, '']]
    return {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Modules', 'events': [['jit_step', 0, 1000, '']]},
            {'name': 'XLA Ops', 'events': ops}]},
        {'name': '/host:CPU', 'lines': [{'name': 'main', 'events': host}]},
    ]}


def ctx_of(trace, steps):
    events = tracefile.device_ops(trace)['/device:TPU:0']
    lo, hi = events[0][1], max(s + d for _, s, d, _ in events)
    return {'trace': {'data': trace, 'steps': steps,
                      'busy_s': tracefile.busy_ns(events) / 1e9,
                      'window_s': (hi - lo) / 1e9}}


def reduce(name, ctx, **args):
    return files.load_module('reducers', name).reduce(ctx, **args)


def test_interval_arithmetic():
    trace = synthetic()
    events = tracefile.device_ops(trace)['/device:TPU:0']
    assert [e[0] for e in events][:2] == ['fusion.1', 'fusion.2']
    assert tracefile.union([(0, 100), (50, 150), (300, 500)]) == [
        [0, 150], [300, 500]]
    assert tracefile.busy_ns(events) == 150 + 200 + 100 + 100 + 100
    assert tracefile.self_ns(events)[-2:] == [40.0, 60.0]
    ctx = ctx_of(trace, steps=2)
    assert reduce('idle_pct', ctx) == pytest.approx(100 * (1 - 650 / 1200))
    assert reduce('scope_device_ms', ctx, scopes=['kfac.ComputeInverse']
                  ) == pytest.approx(300 / 2 / 1e6)
    assert reduce('scope_device_ms', ctx, scopes=[
        'kfac.ComputeFactor', 'kfac.UpdateFactors']) == pytest.approx(
            100 / 2 / 1e6)
    # everything outside kfac.*: the two conv fusions
    assert reduce('scope_device_ms', ctx, outside=['kfac.']
                  ) == pytest.approx(200 / 2 / 1e6)
    # a scope no event names: nothing to read, not zero
    assert reduce('scope_device_ms', ctx, scopes=['kfac.Communicate']) is None
    assert reduce('scope_device_ms', {'trace': None}, scopes=['x']) is None
    assert tracefile.top_ops(events, 2) == [['fusion.2', 2e-7],
                                            ['cholesky.3', 2e-7]]
    gaps = tracefile.idle_gaps(trace, events, 0, 1200, n=2)
    # the longest gaps, each named after the host event covering most of it
    assert gaps[0] == ['device_get', 2e-7] and gaps[1][1] == 1.5e-7
    assert gaps[1][0] == 'PjitFunction(step)'


def test_no_scope_names_means_no_model_time():
    trace = synthetic()
    for e in trace['planes'][0]['lines'][1]['events']:
        e[3] = ''
    assert reduce('scope_device_ms', ctx_of(trace, 1),
                  outside=['kfac.']) is None


@pytest.mark.parametrize('path', sorted(glob.glob(
    os.path.join(DATA, '*.json.gz'))) or [None])
def test_recorded_chip_trace(path):
    if path is None:
        pytest.skip('no recorded trace under tests/data/')
    trace = tracefile.load(path)
    per_device = tracefile.device_ops(trace)
    assert per_device, 'the recorded trace has a device plane with XLA Ops'
    events = next(iter(per_device.values()))
    lo, hi = events[0][1], max(s + d for _, s, d, _ in events)
    busy = tracefile.busy_ns(events)
    assert 0 < busy <= hi - lo
    ctx = ctx_of(trace, steps=30)
    idle = reduce('idle_pct', ctx)
    assert 0 <= idle < 100
    parts = {k: reduce('scope_device_ms', ctx, **a) for k, a in {
        'factor': dict(scopes=['kfac.ComputeFactor', 'kfac.UpdateFactors']),
        'inverse': dict(scopes=['kfac.ComputeInverse']),
        'apply': dict(scopes=['kfac.Precondition']),
        'model': dict(outside=['kfac.'])}.items()}
    assert all(v is not None and v > 0 for v in parts.values()), parts
    # the four metrics cover the operations but for the few under other
    # kfac.* scopes (HealthGuard): they add up to nearly the sum of all
    # operations' own times, which is the busy time when no two run at once
    own = sum(tracefile.self_ns(events)) / 30 / 1e6
    assert 0.95 * own <= sum(parts.values()) <= own * (1 + 1e-9)
    assert own == pytest.approx(busy / 30 / 1e6, rel=0.02)
