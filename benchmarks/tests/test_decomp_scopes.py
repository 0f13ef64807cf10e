"""The readers of the decomposition's inner scopes (PR 45):
``decomp.b<D>x<n>`` round ``decomp.cholesky`` / ``.solve_lower`` /
``.solve_upper`` / ``.damp`` / ``.settle`` / ``.write`` inside
``kfac.ComputeInverse``, through the metrics' own files: on a hand-made
trace, on the traces recorded before the scopes were there (every new
metric reads nothing and raises nothing) and on one recorded on the chip
with them (``tests/data/tiny-bert-decomp-freq10.v5e.json.gz``: the
rehearsal cell ``tiny-bert-decomp-freq10``, ``--seconds 2 --trace 1``, seed
4545000003, 30 traced steps, my chip run, PR 45; at ``--seconds 30`` the
cell's constant rate blows up after 8,671 of the chip's 1.4 ms steps and the
guard then refuses every batch, so that no decomposition is left to trace).
"""

import math
import os

import pytest

from harness import files, spans, tracefile
from test_reduce import ctx_of, reduce
from test_spans import BEFORE, HERE, RECORDED, STEPS, metric

WITH_SCOPES = os.path.join(HERE, 'data', 'tiny-bert-decomp-freq10.v5e.json.gz')
CELLS = ['resnet50-freq10', 'bert-base-freq10', 'resnet50-freq1',
         'kanana2-ep16-freq10', 'trinity-mini-ep16-freq10']
STAGES = ['inverse_cholesky_ms_per_step', 'inverse_solve_lower_ms_per_step',
          'inverse_solve_upper_ms_per_step', 'inverse_rest_ms_per_step']
NEW = STAGES + ['inverse_block_calls_ms_per_step', 'inverse_ops_per_update',
                'inverse_top_bucket_ms_per_step', 'inverse_top_bucket_dim',
                'inverse_behind_ms_per_step', 'inverse_task_tflops']
UPDATE = 'tf_op=jit(kfac_step_pred_stats_decomp)/cond/branch_1_fun/'
INVERSE = UPDATE + 'kfac.ComputeInverse/'
B256, B128 = INVERSE + 'decomp.b256x3/', INVERSE + 'decomp.b128x5/while/body/'


def hand_made(second_device=False):
    """Three steps: a plain program, the update program, a plain one. The
    update inverts a bucket of 3 x 256 whole and one of 5 x 128 in a loop
    of groups; the compiler's pathless operations stand behind the
    backward pass, behind the decomposition (one of them inside the
    loop), behind the apply and at the start of the next program."""
    call = ' hlo_category=custom-call'
    ops = [
        ['fusion.1', 1000, 500, 'tf_op=jit(kfac_step_pred)/'
                                'kfac.Precondition/einsum'],
        ['fusion.2', 3000, 100, 'tf_op=jit(kfac_step_pred_stats_decomp)/'
                                'train.grad/transpose(jvp(Net))/conv'],
        ['copy.1', 3100, 50, 'hlo_category=data formatting'],
        ['fusion.3', 3150, 100, INVERSE + 'reduce_sum:'],
        ['fusion.4', 3250, 200, B256 + 'decomp.damp/add:'],
        ['custom-call.1', 3450, 400, B256 + 'decomp.cholesky/jit(cholesky)/'
                                            'cholesky:' + call],
        ['fusion.5', 3850, 100, B256 + 'decomp.cholesky/jit(cholesky)/'
                                       'cholesky: hlo_category=loop fusion'],
        ['custom-call.2', 3950, 300, B256 + 'decomp.solve_lower/'
                                            'triangular_solve:' + call],
        ['fusion.6', 4250, 200, B256 + 'decomp.solve_upper/triangular_solve:'
                                       ' hlo_category=convolution fusion'],
        ['copy.2', 4450, 60, 'hlo_category=data formatting'],
        ['copy-done.1', 4510, 40, 'hlo_category=copy-done'],
        # a loop of groups encloses its body: 50 ns of its own
        ['while.1', 4550, 600, INVERSE + 'decomp.b128x5/while:'],
        ['custom-call.3', 4560, 100, B128 + 'decomp.cholesky/cholesky:'
                                            + call],
        ['fusion.7', 4660, 100, B128 + 'decomp.solve_lower/triangular_solve:'],
        ['fusion.8', 4760, 100, B128 + 'decomp.solve_upper/triangular_solve:'],
        ['copy.3', 4860, 50, 'hlo_category=data formatting'],
        ['fusion.9', 4910, 100, B128 + 'decomp.settle/jit(settle_inverse_rows)'
                                       '/while:'],
        ['fusion.10', 5010, 100, B128 + 'decomp.write/dynamic_update_slice:'],
        ['fusion.11', 5150, 100, UPDATE + 'kfac.Precondition/einsum:'],
        ['copy.4', 5250, 30, 'hlo_category=data formatting'],
        ['fusion.12', 5300, 100, INVERSE + 'sqrt:'],
        # the next program starts with a pathless operation: behind nothing
        ['copy-start.1', 7000, 70, 'hlo_category=copy-start'],
        ['fusion.13', 7070, 300, 'tf_op=jit(kfac_step_pred)/'
                                 'kfac.Precondition/einsum'],
    ]
    modules = [['jit_kfac_step_pred(123)', 1000, 1000, ''],
               ['jit_kfac_step_pred_stats_decomp(456)', 3000, 3000, ''],
               ['jit_kfac_step_pred(123)', 7000, 1000, '']]
    planes = [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': modules},
        {'name': 'XLA Ops', 'events': ops}]}]
    if second_device:
        planes.append({'name': '/device:TPU:1', 'lines': [
            {'name': 'XLA Modules', 'events': [list(m) for m in modules]},
            {'name': 'XLA Ops', 'events': [list(e) for e in ops]}]})
    return {'planes': planes + [
        {'name': '/host:CPU', 'lines': [{'name': 'python3', 'events': []}]}]}


@pytest.mark.parametrize('second_device', [False, True],
                         ids=['one_device', 'two_devices_averaged'])
def test_hand_made_trace_through_every_new_metric(second_device):
    ctx = ctx_of(hand_made(second_device), steps=3)
    got = {name: metric(name, ctx) for name in NEW}
    per_step = 3 * 1e6
    assert got == pytest.approx({
        'inverse_cholesky_ms_per_step': (400 + 100 + 100) / per_step,
        'inverse_solve_lower_ms_per_step': (300 + 100) / per_step,
        'inverse_solve_upper_ms_per_step': (200 + 100) / per_step,
        # trace averages, damping, the loop's own time, settle, write
        'inverse_rest_ms_per_step': (100 + 200 + 50 + 100 + 100 + 100)
        / per_step,
        'inverse_block_calls_ms_per_step': (400 + 300 + 100) / per_step,
        'inverse_ops_per_update': 13.0,
        # two buckets, the larger picked: 1,200 ns against 550
        'inverse_top_bucket_ms_per_step': 1200 / per_step,
        'inverse_top_bucket_dim': 256.0,
        # behind the decomposition, the loop's body included; not the
        # copies behind the backward pass and the apply, and not the one
        # the next program starts with
        'inverse_behind_ms_per_step': (60 + 40 + 50) / per_step,
        'inverse_task_tflops': (3 * 256 ** 3 + 5 * 128 ** 3) / 1950e-9
        / 1e12})
    # one event, one stage: the four add up to the scope's own metric
    assert sum(got[name] for name in STAGES) == pytest.approx(
        metric('inverse_ms_per_step', ctx))
    assert got['inverse_behind_ms_per_step'] <= metric(
        'unscoped_ms_per_step', ctx)


def test_behind_stops_at_the_program_border_and_at_the_next_path():
    trace = hand_made()
    ops = trace['planes'][0]['lines'][1]['events']
    # the update program now ENDS under the decomposition: what the next
    # program starts with is still behind nothing
    ops[:] = [e for e in ops if e[0] not in ('fusion.11', 'copy.4')]
    assert metric('inverse_behind_ms_per_step', ctx_of(trace, steps=3)
                  ) == pytest.approx(150 / 3e6)
    # an operation with another path between the scope and a copy takes it
    ops.insert(9, ['fusion.20', 4445, 5, 'tf_op=jit(f)/other/add:'])
    assert metric('inverse_behind_ms_per_step', ctx_of(trace, steps=3)
                  ) == pytest.approx(50 / 3e6)


@pytest.mark.parametrize('ctx', [
    pytest.param(lambda: ctx_of(tracefile.load(RECORDED), steps=STEPS),
                 id='recorded_before_the_scopes'),
    pytest.param(lambda: ctx_of(tracefile.load(BEFORE), steps=STEPS),
                 id='recorded_before_the_spans'),
    pytest.param(lambda: {'trace': None}, id='no_trace'),
])
def test_a_program_without_the_scopes_reads_nothing(ctx):
    """The parent's programs: ``kfac.ComputeInverse`` is there, its inside
    is not named. Nine of the ten read nothing; the tenth, what is under
    the scope and under none of the three stages, is then the whole scope
    (the benchmark's own ``scope_split_device_ms``, which knows no other
    answer). The two new reducers read such a program when not told what
    they need."""
    ctx = ctx()
    rest = 'inverse_rest_ms_per_step'
    assert [metric(n, ctx) for n in NEW if n != rest] == [None] * 9
    if not ctx['trace']:
        assert metric(rest, ctx) is None
        return
    assert metric(rest, ctx) == pytest.approx(
        metric('inverse_ms_per_step', ctx))
    if not spans.step_modules(ctx['trace']['data']):
        return      # before PR 24 the step programs had no names to find
    scope = 'kfac.ComputeInverse'
    assert reduce('scope_op_count', ctx, scope=scope) > 0
    assert 0 <= reduce('unscoped_after_scope_ms', ctx, scope=scope) <= metric(
        'unscoped_ms_per_step', ctx)


@pytest.mark.parametrize('name', NEW)
def test_new_metric_is_a_file_with_a_reducer_declared_for_every_cell(name):
    spec, rehearsal = files.load_json('metrics', name)
    assert not rehearsal and spec['name'] == name
    assert callable(files.load_module('reducers', spec['reducer']).reduce)
    entry, = [m for m in files.benchmark_json()['per_layer']
              if m['name'] == name]
    assert entry == {
        'name': name, 'unit': spec['unit'],
        'better': 'higher' if name == 'inverse_task_tflops' else 'lower',
        'source': 'device_trace', 'layer': 'preconditioner decomposition',
        'moves': 'samples_per_s', 'workloads': CELLS}
    for cell in CELLS:
        resolved, _ = files.resolve_workload(cell)
        assert name in [m['name'] for m in resolved['per_layer']]


@pytest.fixture(scope='module')
def scoped():
    return ctx_of(tracefile.load(WITH_SCOPES), steps=STEPS)


def test_recorded_chip_trace_with_the_scopes(scoped):
    """The rehearsal cell on the v5e: every new metric reads, the stages
    add up to the scope, and the task the names state is the one the
    program's ``kfac.precond.setup`` record states (tiny-bert: 24 rows of
    128 and 2 of 256, ``decomp_task_flop`` 83,886,080)."""
    got = {name: metric(name, scoped) for name in NEW}
    assert all(v is not None and math.isfinite(v) and v >= 0
               for v in got.values()), got
    # two tiny buckets leave next to nothing behind them
    assert got['inverse_behind_ms_per_step'] < 1e-3
    whole = metric('inverse_ms_per_step', scoped)
    assert sum(got[name] for name in STAGES) == pytest.approx(
        whole, abs=1e-9)
    assert got['inverse_rest_ms_per_step'] < whole
    assert got['inverse_block_calls_ms_per_step'] < whole
    assert got['inverse_top_bucket_ms_per_step'] < whole
    assert got['inverse_top_bucket_dim'] in (128.0, 256.0)
    assert got['inverse_behind_ms_per_step'] <= metric(
        'unscoped_ms_per_step', scoped)
    # three update programs in 30 steps at cadence 10
    task = 24 * 128 ** 3 + 2 * 256 ** 3
    assert task == 83886080
    assert got['inverse_task_tflops'] * (whole * STEPS / 1e3) * 1e12 == (
        pytest.approx(3 * task, rel=1e-9))
    # the chain: hundreds of operations for two tiny buckets
    assert got['inverse_ops_per_update'] == reduce(
        'scope_op_count', scoped, scope='kfac.ComputeInverse')


def test_decomp_table_of_the_recorded_trace(scoped, capsys):
    """``tools/decomp_table.py``: a row a bucket, the stages across; the
    rows and what runs outside them make up the scope."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'decomp_table', os.path.join(files.BENCH, 'tools', 'decomp_table.py'))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    result = tool.table(scoped['trace']['data'])
    (name, prog), = result.items()
    assert name.endswith('jit_kfac_step_pred_stats_decomp')
    assert prog['runs'] == 3
    assert sorted(prog['buckets']) == [(128, 24), (256, 2)]
    stages = ('cholesky', 'solve_lower', 'solve_upper', 'damp', 'other')
    under = sum(row.get(s, 0.0) for row in prog['buckets'].values()
                for s in stages)
    assert all(set(row) <= set(stages) | {'ops', 'block_calls',
                                          'block_calls_ns'}
               for row in prog['buckets'].values())
    whole = metric('inverse_ms_per_step', scoped) * STEPS / 3
    assert under / 1e6 + prog['outside_ms'] == pytest.approx(whole)
    assert sum(prog['behind'].values()) == pytest.approx(
        metric('inverse_behind_ms_per_step', scoped) * STEPS / 3)
    tool.show(result)
    out = capsys.readouterr().out
    assert 'b128x24' in out and 'b256x2' in out and 'ms/chain step' in out
    # a trace without the scopes: said, not raised
    assert tool.table(tracefile.load(RECORDED)) == {}
    tool.show({})
    assert 'no operation under' in capsys.readouterr().out
