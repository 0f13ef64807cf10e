"""The readers of the program's own spans, step-program names, ``train.*``
scopes and kernel names (``harness/spans.py`` and the reducers beside
it): on hand-built intervals, and on the trace recorded on the chip with
the spans in it (``tests/data/tiny-bert-pallas-freq10.v5e.json.gz``: the
rehearsal cell ``tiny-bert-pallas-freq10``, 30 steps, my chip run, PR 24).
"""

import json
import os

import pytest

from harness import files, spans, tracefile
from test_reduce import ctx_of, reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, 'data', 'tiny-bert-pallas-freq10.v5e.json.gz')
#: a trace of the program before it wrote spans (PR 23's parent)
BEFORE = os.path.join(HERE, 'data', 'tiny-bert-freq10.v5e.json.gz')
STEPS = 30
NEW = ['idle_read_step_ms_per_step', 'idle_dispatch_ms_per_step',
       'idle_hooks_select_ms_per_step', 'idle_outside_step_ms_per_step',
       'host_step_busy_ms', 'launch_latency_ms', 'device_clock_shift_ms',
       'plain_step_device_ms', 'update_step_device_ms',
       'forward_ms_per_step', 'backward_ms_per_step',
       'optimizer_ms_per_step', 'guard_ms_per_step', 'kernel_ms_per_step',
       'step_builds_in_trace', 'unscoped_ms_per_step']


def metric(name, ctx):
    """A metric of ``BENCHMARK.json`` through its own file and reducer."""
    spec, _ = files.load_json('metrics', name)
    return files.load_module('reducers', spec['reducer']).reduce(
        ctx, **spec.get('args', {}))


def hand_built(lead=0):
    """Two steps. The device's timeline runs ``lead`` ns early against the
    host's (as the profiler's alignment does on the v5e)."""
    ops = [
        ['fusion.1', 1000, 300, 'tf_op=jit(kfac_step_pred)/train.grad/'
                                'jvp(Net)/conv'],
        ['fusion.2', 1300, 500, 'tf_op=jit(kfac_step_pred)/train.grad/'
                                'transpose(jvp(Net))/conv'],
        ['fusion.3', 1800, 100, 'tf_op=jit(kfac_step_pred)/cond/'
                                'train.optimizer/add'],
        ['fusion.4', 1900, 100, 'tf_op=jit(kfac_step_pred)/'
                                'kfac.Precondition/einsum'],
        # second step: the update program, with a kernel in it
        ['fusion.5', 3000, 400, 'tf_op=jit(kfac_step_pred_stats_decomp)/'
                                'train.grad/jvp(Net)/conv'],
        ['kfac_stat_rows.7', 3400, 200,
         'tf_op=jit(kfac_step_pred_stats_decomp)/kfac.ComputeFactor/'
         'kfac_stat_rows/pallas_call hlo_category=custom-call'],
        ['fusion.6', 3600, 50, 'tf_op=jit(kfac_step_pred_stats_decomp)/'
                               'train.health_screen/reduce_and'],
        ['fusion.7', 3650, 50, 'tf_op=jit(kfac_step_pred_stats_decomp)/'
                               'kfac.HealthGuard.factors/is_finite'],
    ]
    modules = [['jit_kfac_step_pred(123)', 1000, 1000, ''],
               ['jit_convert_element_type(9)', 2600, 1, ''],
               ['jit_kfac_step_pred_stats_decomp(456)', 3000, 700, '']]
    for e in ops + modules:
        e[1] -= lead
    host = [
        ['$loop.py:1 run', 0, 5000, ''],
        ['kfac.step', 500, 600, ''],
        ['kfac.step.read_step', 510, 90, ''],
        ['kfac.step.select', 650, 100, ''],         # hooks: dropped, < 20 us
        ['kfac.step.dispatch/pred', 800, 290, ''],
        ['DoEnqueueProgram', 950, 30, ''],
        ['kfac.step', 1200, 1900, ''],
        ['kfac.step.read_step', 1210, 1000, ''],    # waits for step 1
        ['kfac.step.select', 2300, 100, ''],
        ['kfac.step.build/kfac_step_pred_stats_decomp', 2450, 640, ''],
        ['kfac.step.dispatch/pred+stats+decomp', 2500, 580, ''],
        ['DoEnqueueProgram', 2900, 30, ''],
    ]
    return {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Modules', 'events': modules},
            {'name': 'XLA Ops', 'events': ops}]},
        {'name': '/host:CPU', 'lines': [{'name': 'python3', 'events': host}]},
    ]}


def test_interval_helpers():
    assert spans.overlap_ns([[0, 10], [20, 30]], [[5, 25]]) == 10
    assert spans.overlap_ns([[0, 10]], []) == 0
    ops = [['a', 0, 10, ''], ['b', 5, 10, ''], ['c', 40, 5, '']]
    assert spans.idle_intervals(ops) == [[15, 40]]
    host = spans.host_events(hand_built())
    assert [e[0] for e in spans.named(host, spans.DISPATCH)] == [
        'kfac.step.dispatch/pred', 'kfac.step.dispatch/pred+stats+decomp']
    assert len(spans.named(host, spans.STEP)) == 2      # not its children
    assert spans.shifted(ops, 7)[2] == ['c', 47, 5, '']


@pytest.mark.parametrize('lead', [0, 400, 1400])
def test_hand_built_trace(lead):
    """Whatever the device's lead, the readers give the same numbers: the
    shift puts the step programs back behind their hand-over."""
    trace = hand_built(lead)
    ctx = ctx_of(trace, steps=2)
    host = spans.host_events(trace)
    modules = spans.step_modules(trace)['/device:TPU:0']
    assert [m[0].partition('(')[0] for m in modules] == [
        'jit_kfac_step_pred', 'jit_kfac_step_pred_stats_decomp']
    # hand-over 950 -> start 1000, 2900 -> 3000: the tighter pair sets it
    assert spans.device_shift_ns(host, modules) == lead - 50
    assert metric('device_clock_shift_ms', ctx) == pytest.approx(
        (lead - 50) / 1e6)
    # the one gap, [2000, 3000] on the device (the 1 ns convert apart),
    # lies at [1950, 2950] after the shift: read_step until 2210, the rest
    # of kfac.step until its dispatch span opens at 2500, then that
    parts = {p: reduce('idle_under_span_ms', ctx, part=p) * 2 * 1e6
             for p in ('read_step', 'dispatch', 'hooks_select', 'outside')}
    assert parts == pytest.approx({'read_step': 260, 'dispatch': 450,
                                   'hooks_select': 290, 'outside': 0},
                                  abs=1.5)
    idle_ns = (ctx['trace']['window_s'] - ctx['trace']['busy_s']) * 1e9
    assert sum(parts.values()) == pytest.approx(idle_ns)
    # device start - dispatch start: (950 - 800), (2950 - 2500)
    assert metric('launch_latency_ms', ctx) == pytest.approx(300 / 1e6)
    assert metric('host_step_busy_ms', ctx) == pytest.approx(
        ((600 - 90) + (1900 - 1000)) / 2 / 1e6)
    assert metric('step_builds_in_trace', ctx) == 1.0
    assert metric('plain_step_device_ms', ctx) == pytest.approx(1000 / 1e6)
    assert metric('update_step_device_ms', ctx) == pytest.approx(700 / 1e6)
    assert metric('forward_ms_per_step', ctx) == pytest.approx(700 / 2 / 1e6)
    assert metric('backward_ms_per_step', ctx) == pytest.approx(
        500 / 2 / 1e6)
    assert metric('optimizer_ms_per_step', ctx) == pytest.approx(
        100 / 2 / 1e6)
    assert metric('guard_ms_per_step', ctx) == pytest.approx(100 / 2 / 1e6)
    assert metric('kernel_ms_per_step', ctx) == pytest.approx(200 / 2 / 1e6)
    assert metric('unscoped_ms_per_step', ctx) == 0.0
    # the new scopes and names take nothing from what "outside kfac." reads
    assert reduce('scope_device_ms', ctx, outside=['kfac.']) == pytest.approx(
        (300 + 500 + 100 + 400 + 50) / 2 / 1e6)


def test_gap_outside_every_step_span_is_the_callers():
    trace = hand_built()
    host = trace['planes'][1]['lines'][0]['events']
    # the second step is entered late: the caller's loop held the host
    host[:] = [e for e in host if not (e[0].startswith('kfac.step')
                                       and 1200 <= e[1] < 2400)]
    host.append(['kfac.step', 2400, 700, ''])
    ctx = ctx_of(trace, steps=2)
    outside = reduce('idle_under_span_ms', ctx, part='outside') * 2 * 1e6
    assert outside == pytest.approx(2400 - 1950, abs=1.5)


def test_a_program_without_spans_reads_nothing():
    """The parent of the PR that added the spans, under this benchmark:
    every new reader returns None (or what it can read) and none raises."""
    trace = tracefile.load(BEFORE)
    ctx = ctx_of(trace, STEPS)
    got = {name: metric(name, ctx) for name in NEW}
    # kfac.HealthGuard is an older scope, a trace with device operations
    # and no named kernel reads 0 kernel time, and with no train.* scope
    # everything outside kfac.* is unscoped
    assert got.pop('guard_ms_per_step') > 0
    assert got.pop('kernel_ms_per_step') == 0.0
    assert got.pop('unscoped_ms_per_step') == pytest.approx(
        reduce('scope_device_ms', ctx, outside=['kfac.']))
    assert all(v is None for v in got.values()), got
    assert all(metric(name, {'trace': None}) is None for name in NEW)


def test_every_new_metric_is_declared_with_its_cells():
    bench = files.benchmark_json()
    declared = {m['name']: m for m in bench['per_layer']}
    cells = [w['name'] for w in bench['workloads']]
    for name in NEW:
        assert set(declared[name]['workloads']) <= set(cells), name
        spec, _ = files.load_json('metrics', name)
        assert spec['unit'] == declared[name]['unit']
    rehearsal = json.load(open(os.path.join(
        HERE, 'workloads', 'tiny-bert-pallas-freq10.json')))
    assert set(NEW) <= set(rehearsal['per_layer'])


# -- the recorded chip trace ----------------------------------------------------


@pytest.fixture(scope='module')
def recorded():
    trace = tracefile.load(RECORDED)
    return trace, ctx_of(trace, STEPS)


def test_recorded_spans_and_programs(recorded):
    trace, _ = recorded
    host = spans.host_events(trace)
    steps = spans.named(host, spans.STEP)
    assert len(steps) == len(spans.named(host, spans.READ)) == STEPS
    dispatches = spans.named(host, spans.DISPATCH)
    assert [e[0] for e in dispatches] == (
        ['kfac.step.dispatch/pred+stats+decomp']
        + ['kfac.step.dispatch/pred'] * 9) * 3
    # every child lies inside its step, and the steps do not overlap
    for (_, s, d, _), nxt in zip(steps, steps[1:] + [None]):
        assert nxt is None or s + d <= nxt[1]
    for child in spans.named(host, spans.READ) + dispatches:
        assert any(s <= child[1] and child[1] + child[2] <= s + d
                   for _, s, d, _ in steps)
    # no step program is anonymous, and they pair with the dispatches
    modules = spans.step_modules(trace)['/device:TPU:0']
    assert [m[0].partition('(')[0] for m in modules] == [
        'jit_kfac_step_' + e[0].partition('/')[2].replace('+', '_')
        for e in dispatches]
    every = [e[0] for p in trace['planes'] for line in p['lines']
             if line['name'] == 'XLA Modules' for e in line['events']]
    assert not any('unknown' in name for name in every)


def test_recorded_clocks_agree_only_after_the_shift(recorded):
    trace, ctx = recorded
    host = spans.host_events(trace)
    modules = spans.step_modules(trace)['/device:TPU:0']
    dispatches = spans.named(host, spans.DISPATCH)
    # as the profiler placed them, every step program starts BEFORE the
    # call that dispatched it: its alignment of the two clocks is out
    assert all(m[1] < d[1] for d, m in zip(dispatches, modules))
    shift = spans.device_shift_ns(host, modules)
    assert 1.3e6 < shift < 1.6e6            # 1.446 ms in this trace
    launch = files.load_module('reducers', 'launch_latency_ms')
    waits, shift_ms = launch.waits_ms(trace)
    assert shift_ms == pytest.approx(shift / 1e6)
    # after it: sign and order hold for every step, and no step program
    # ends after the host has read its result (the next read_step's end)
    assert len(waits) == STEPS and min(waits) >= 0
    assert max(waits) - min(waits) < 0.5    # ms: an idle device starts at once
    reads = spans.named(host, spans.READ)
    for module, read in zip(modules, reads[1:]):
        assert module[1] + module[2] + shift <= read[1] + read[2]
    assert metric('launch_latency_ms', ctx) == pytest.approx(
        sorted(waits)[STEPS // 2], rel=0.05)


def test_recorded_idle_split_adds_up(recorded):
    _, ctx = recorded
    parts = [metric(name, ctx) for name in NEW[:4]]
    assert all(p is not None and p >= 0 for p in parts), parts
    idle_ms = (reduce('idle_pct', ctx) / 100 * ctx['trace']['window_s']
               / STEPS * 1e3)
    assert sum(parts) == pytest.approx(idle_ms, rel=1e-6)
    # a tiny model: the device idles through every part of the host's step
    assert all(p > 0.05 for p in parts)


def test_recorded_device_time_by_pass_program_and_kernel(recorded):
    trace, ctx = recorded
    got = {name: metric(name, ctx) for name in NEW}
    assert all(v is not None for v in got.values()), got
    # forward + backward = all of train.grad
    grad = reduce('scope_device_ms', ctx, scopes=['train.grad'])
    assert got['forward_ms_per_step'] + got['backward_ms_per_step'] == (
        pytest.approx(grad))
    assert got['forward_ms_per_step'] > 0 and got['backward_ms_per_step'] > 0
    assert got['optimizer_ms_per_step'] > 0 and got['guard_ms_per_step'] > 0
    # the named kernels ran (26 kfac_stat_rows a factor update) and are
    # part of the statistics' time
    assert 0 < got['kernel_ms_per_step'] < reduce(
        'scope_device_ms', ctx, scopes=['kfac.ComputeFactor',
                                        'kfac.UpdateFactors'])
    events = tracefile.device_ops(trace)['/device:TPU:0']
    assert sum(e[0].startswith('kfac_stat_rows') for e in events) == 26 * 3
    # a program's own time is under its host-clock step time, and the
    # update program is the longer one
    assert 0 < got['plain_step_device_ms'] < got['update_step_device_ms']
    assert got['step_builds_in_trace'] == 0.0
    assert got['host_step_busy_ms'] > 0
    # what "outside kfac." reads is the train.* scopes plus the operations
    # the compiler added without any source path, and nothing else
    model = reduce('scope_device_ms', ctx, outside=['kfac.'])
    screen = reduce('scope_device_ms', ctx, scopes=['train.health_screen'])
    assert (got['forward_ms_per_step'] + got['backward_ms_per_step']
            + got['optimizer_ms_per_step'] + screen
            + got['unscoped_ms_per_step']) == pytest.approx(model)


def test_pallas_rehearsal_cell_runs_on_the_cpu():
    """The cell the recorded trace came from, end to end on the CPU with
    the kernels interpreted: ``correct`` holds, and with no device plane
    every trace reader returns nothing and raises nothing."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(HERE))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(root, 'benchmarks', 'run.py'),
         '--workload', 'tiny-bert-pallas-freq10', '--seed', '3000000001',
         '--seconds', '2', '--trace', '1'],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last['correct'] is True and last['failed'] == 0
    assert last['device']['platform'] == 'cpu'
    assert not set(NEW) & set(last['metrics'])
