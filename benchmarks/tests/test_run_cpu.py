"""``run.py`` end to end on the CPU with the rehearsal cells under
``benchmarks/tests/`` (tiny sizes: nothing here is a device number).
Run with ``python3 -m pytest benchmarks/tests -q``; not part of tier-1."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device', 'check'}
TIME_PARTS = ('setup_s', 'window_s', 'traced_s', 'memory_plan_s',
              'first_order_warm_s', 'first_order_s', 'reference_s',
              'reduce_s')
#: what `failed` is made of, each beside its limit in `check`
FAULTS = ('bad_steps_window', 'bad_steps_setup', 'health/skipped',
          'health/rung', 'health/fallbacks', 'compiles_in_window',
          'first_bad_step')


def run_cell(workload, trace=0, devices=1, extra=(), cwd=ROOT, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env['XLA_FLAGS'] = f'--xla_force_host_platform_device_count={devices}'
    env['JAX_COMPILATION_CACHE_DIR'] = '/nonexistent/overridden-by-run.py'
    proc = subprocess.run(
        [sys.executable, os.path.join(cwd, 'benchmarks', 'run.py'),
         '--workload', workload, '--seed', '2147483659', '--seconds',
         str(seconds), '--trace', str(trace), *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith('{')]
    return proc, rows


def by_phase(rows, phase):
    return [r for r in rows if r.get('phase') == phase]


@pytest.mark.parametrize('workload,seconds', [('tiny-bert-freq10', 2),
                                              # seconds a step on the CPU:
                                              # room for both window parts
                                              ('tiny-resnet-freq10', 40)])
def test_untraced_run(workload, seconds):
    proc, rows = run_cell(workload, seconds=seconds)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert set(last) == KEYS
    assert last['correct'] is True and last['failed'] == 0
    assert set(last['metrics']) == {'samples_per_s', 'step_ms_p95',
                                    'setup_s'}
    assert last['device']['platform'] == 'cpu'
    win, = by_phase(rows, 'window')
    assert win['compiles_in_window'] == 0
    assert win['steps'] == last['attempted']
    assert win['first_bad_step'] is None and not any(win['health'].values())
    # whole periods in both parts of the window
    assert win['chunks'] > 0 and win['fenced_steps'] > 0
    assert (win['steps'] - win['fenced_steps']) % 10 == 0
    assert win['fenced_steps'] % 10 == 0
    # every number compared is printed beside its limit
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert set(checks) == {'loss_gap', 'first_update_norm_gap',
                           'param_change_norm_gap', 'factor_gap'}
    assert all(r['value'] <= r['limit'] for r in checks.values())
    # ... in the result line too, as its last key, and at the end of stderr
    assert list(last)[-1] == 'check'
    assert list(last['check']) == [*checks, 'failed', *FAULTS]
    assert all(last['check'][k] == {'value': 0, 'limit': 0}
               for k in ('failed', *FAULTS[:-1]))
    assert last['check']['first_bad_step'] == {'value': -1, 'limit': -1}
    assert all(last['check'][k] == {'value': r['value'], 'limit': r['limit']}
               for k, r in checks.items())
    tail = proc.stderr.splitlines()[-len(last['check']):]
    assert [line.split()[1] for line in tail] == list(last['check'])
    # the K-FAC state is stored in the dtype the configuration states
    summary, = [r for r in by_phase(rows, 'check') if 'reference_s' in r]
    assert summary['kfac_state_dtypes'] == ['float32']
    assert summary['kfac_state_dtype_stated'] == 'float32'
    # the cache sits in the checkout whatever the environment names
    setup, = by_phase(rows, 'setup')
    assert setup['cache_dir'] == os.path.join(ROOT, '.jax_cache')


def test_tiny_bert_trains_for_twice_its_window():
    """What ``bert-base-freq10`` has to do on the chip for three windows
    (the rule of ``tools/lr_ladder.py``), rehearsed: no step refused, no
    loss above 1.05 x the first, the last period's mean below the first's.
    ``tiny-bert-trains`` starts every batch from ln 32 (a small span
    head) as the cell starts every batch from ln 384; ``tiny-bert``'s
    batches of 4 start 10 % apart, and at its 0.04 it does not train."""
    proc, rows = run_cell('tiny-bert-trains-freq10', seconds=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert last['correct'] is True and last['failed'] == 0
    win, = by_phase(rows, 'window')
    assert not any(win['health'].values())
    assert win['loss_max'] <= 1.05 * win['loss_first']
    assert win['loss_mean_last_period'] < win['loss_mean_first_period']
    # the rule as the ladder applies it, on these rows and on broken ones
    from harness import files
    ladder = files.load_module('tools', 'lr_ladder')
    ok, seen = ladder.trains(rows)
    assert ok and seen['steps'] == last['attempted']
    spiked = [dict(r, loss_max=2 * r['loss_first'])
              if r.get('phase') == 'window' else r for r in rows]
    assert not ladder.trains(spiked)[0]
    refused = rows[:-1] + [dict(last, failed=1)]
    assert not ladder.trains(refused)[0]
    assert not ladder.trains(rows[:-1])[0]     # a run that printed no result


@pytest.mark.parametrize('trace', [0, 1])
def test_time_row_adds_up(trace):
    proc, rows = run_cell('tiny-bert-freq1', trace=trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row, = by_phase(rows, 'time')
    assert set(row) == {'phase', 'total_s', *TIME_PARTS}
    assert sum(row[p] for p in TIME_PARTS) == pytest.approx(row['total_s'])
    setup, = by_phase(rows, 'setup')
    assert row['setup_s'] == pytest.approx(setup['setup_s'], abs=0.05)
    traced_only = ('traced_s', 'memory_plan_s', 'first_order_s')
    assert all((row[p] > 0) == bool(trace) for p in traced_only)
    # the row is the last before the result line
    assert rows[-2] is row


def test_traced_run_and_pieces_that_exist_only_under_tests():
    proc, rows = run_cell('tiny-bert-freq1', trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert last['correct'] is True
    # cadence 1: all steps alike, no fenced part, no tail
    win, = by_phase(rows, 'window')
    assert win['fenced_steps'] == 0 and win['steps'] % 10 == 0
    m = last['metrics']
    assert m['compiles_in_window']['value'] == 0
    assert {'sgd_step_ms', 'kfac_over_sgd', 'hbm_plan_gb'} <= set(m)
    # metric, reducer and workload found only under benchmarks/tests/
    assert m['steps_in_window']['value'] == win['steps']
    # a CPU trace has no device plane: no device metric carries a number
    assert not {'device_idle_pct', 'inverse_ms_per_step',
                'model_ms_per_step'} & set(m)
    assert 'busy_s' not in last['device']


def test_four_virtual_devices_rehearse_the_mesh_only():
    """The mesh path builds and steps on four devices. The reference
    cannot follow a sharded K-FAC step yet, so this rehearsal compares the
    first loss alone -- which is why ``run.py`` refuses such a cell when it
    comes from ``BENCHMARK.json`` (next test)."""
    proc, rows = run_cell('tiny-bert-dp4-freq10', devices=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rows[-1]['device']['count'] == 4
    assert rows[-1]['failed'] == 0
    checks = [r['check'] for r in by_phase(rows, 'check') if 'check' in r]
    assert checks == ['loss_gap']
    win, = by_phase(rows, 'window')
    assert win['compiles_in_window'] == 0


def test_multi_chip_cell_of_benchmark_json_is_refused(tmp_path):
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    bench['workloads'][0]['chips'] = 4
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(bench))
    shutil.copytree(BENCH, tmp_path / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__'))
    proc, rows = run_cell(bench['workloads'][0]['name'], devices=4,
                          cwd=str(tmp_path))
    assert proc.returncode != 0
    assert not any(KEYS <= set(r) for r in rows)
    assert 'asks for 4 chips' in proc.stderr


@pytest.mark.parametrize('mode', ['kfac', 'all'])
def test_lower_precision_control_fails(mode):
    """The reference one precision step down, in the program's place:
    the K-FAC state and arithmetic alone, and the activations too."""
    proc, rows = run_cell('tiny-bert-freq1', extra=['--lower', mode])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rows[-1]['correct'] is False
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert not checks['first_update_norm_gap']['ok']


def test_every_step_refused_is_a_well_formed_not_correct():
    """A loss that is never finite, from the first step of set-up on: the
    sum that `failed` used to be (bad steps of set-up and window + the
    counters) passes `attempted`, and the driver could not read the line
    (PR 30, 36, 37). Counted over the window's steps it is `attempted`."""
    proc, rows = run_cell('tiny-bert-refused-freq10')
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.splitlines()[-1],
                      parse_constant=pytest.fail)     # no NaN in the line
    assert set(last) == KEYS and list(last)[-1] == 'check'
    assert last['correct'] is False
    assert last['failed'] == last['attempted'] > 0
    chk = last['check']
    assert chk['failed'] == {'value': last['attempted'], 'limit': 0}
    assert chk['bad_steps_window'] == {'value': last['attempted'],
                                       'limit': 0}
    assert chk['bad_steps_setup'] == {'value': 10, 'limit': 0}
    assert chk['first_bad_step'] == {'value': 0, 'limit': -1}
    assert chk['health/skipped']['value'] == last['attempted'] + 10
    assert chk['loss_gap']['value'] is None         # the loss was NaN
    tail = proc.stderr.splitlines()[-len(chk):]
    assert [line.split()[1] for line in tail] == list(chk)


def test_first_order_program_is_built_by_a_checkouts_first_run():
    """The first run of a cell in a checkout builds and runs the
    first-order program, traced or not, and leaves a note in the compile
    cache; a later untraced run skips it, and a traced run then reads its
    program from the cache. A note that names another checkout (a copied
    cache: the path is part of JAX's cache key) does not count."""
    note = os.path.join(ROOT, '.jax_cache', 'first_order_warm',
                        'tiny-bert-freq1.json')
    if os.path.exists(note):
        os.remove(note)

    def run(trace):
        proc, rows = run_cell('tiny-bert-freq1', trace=trace)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert rows[-1]['correct'] is True
        return by_phase(rows, 'time')[0], by_phase(rows, 'first_order')

    time_row, legs = run(0)
    assert time_row['first_order_warm_s'] > 0 and len(legs) == 1
    assert legs[0]['traced'] is False
    with open(note) as f:
        assert json.load(f)['checkout'] == ROOT
    time_row, legs = run(0)
    assert time_row['first_order_warm_s'] == 0 and not legs
    time_row, legs = run(1)
    assert time_row['first_order_warm_s'] == 0
    assert time_row['first_order_s'] > 0
    assert legs[0]['traced'] is True and legs[0]['compiled'] == 0
    with open(note) as f:
        text = f.read()
    with open(note, 'w') as f:
        f.write(text.replace(ROOT, '/another/checkout'))
    time_row, legs = run(0)
    assert time_row['first_order_warm_s'] > 0 and len(legs) == 1


def test_stuck_step_is_not_correct():
    """The timed path broken underneath: parameters never change."""
    proc, rows = run_cell('tiny-bert-stuck-freq1')
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rows[-1]['correct'] is False
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert not checks['param_change_norm_gap']['ok']


@pytest.mark.parametrize('bad_at,bad', [(None, 0), (352, 8), (5, 355)])
def test_step_health_counts_refused_and_non_finite_steps(bad_at, bad):
    """A step is bad where the guard refused it or its loss is not finite;
    the ``window`` row says where the run's first was (``bert-base-squad``
    at lr 0.04 blew up 274-356 steps in). How ``failed`` is tallied from
    them: ``test_reference.py``."""
    import numpy as np
    from harness import window
    mets = [{'loss': np.float32(5.9), 'health/ok': np.bool_(True)}
            for _ in range(360)]
    for i in range(bad_at or 360, 360):
        mets[i] = ({'loss': np.float32(np.nan), 'health/ok': np.bool_(True)}
                   if i % 2 else
                   {'loss': np.float32(4.0), 'health/ok': np.bool_(False)})
    losses, n_bad, first = window.step_health(mets)
    assert len(losses) == 360 and first == bad_at and n_bad == bad


def test_kfac_state_stored_in_a_lower_dtype_shows():
    import types
    import jax.numpy as jnp
    from harness import program
    f32, bf16 = jnp.zeros((2, 2)), jnp.zeros((2, 2), jnp.bfloat16)
    state = types.SimpleNamespace(kfac_state=types.SimpleNamespace(
        factors={'0': f32}, decomp={'0': bf16}))
    assert program.kfac_state_dtypes(state) == ['bfloat16', 'float32']


def test_no_tpu_no_result():
    proc, rows = run_cell('resnet50-freq10')
    assert proc.returncode != 0
    assert not any(KEYS <= set(r) for r in rows)
    assert 'needs a TPU' in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    shutil.copytree(BENCH, tmp_path / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__'))
    proc, rows = run_cell('tiny-bert-freq1', cwd=str(tmp_path))
    assert proc.returncode != 0
    assert not any(KEYS <= set(r) for r in rows)
