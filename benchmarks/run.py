#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's K-FAC train step exactly as the trainers do, from the
seed (weights and a device-resident batch pool made by the benchmark's own
generators), warms up every executable of the cadence, measures for
``--seconds``, then checks the first steps against the plain reference.
The last line of stdout is one JSON object: ``correct, attempted, failed,
metrics, device`` (and ``breakdown`` with ``--trace 1``). No TPU, or fewer
chips than the cell asks for: non-zero exit, no result line -- except for
the rehearsal cells under ``benchmarks/tests/`` with ``JAX_PLATFORMS=cpu``.
See ``benchmarks/README.md``.
"""

import time
_T0 = time.time()

import argparse
import hashlib
import json
import math
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

#: steps driven through the window's own call before the window opens:
#: at least the reference's three and one whole cadence period
MIN_WARM_STEPS = 3
#: whole cadence periods in the traced segment
TRACE_PERIODS = 3
TRACE_MIN_STEPS = 10


def say(**row):
    print(json.dumps(row, default=float), flush=True)


class Laps:
    """Where the process's seconds went: each ``lap(part)`` gives the time
    since the last one (the first: since the process started) to ``part``,
    so the parts add up to the time from the start to the last lap."""

    PARTS = ('setup_s', 'window_s', 'traced_s', 'memory_plan_s',
             'first_order_warm_s', 'first_order_s', 'reference_s',
             'reduce_s')

    def __init__(self, t0):
        self.last, self.parts = t0, dict.fromkeys(self.PARTS, 0.0)

    def lap(self, part):
        now = time.time()
        self.parts[part] += now - self.last
        self.last = now


def die(msg, code=2):
    print(f'benchmarks/run.py: {msg}', file=sys.stderr, flush=True)
    sys.exit(code)


def place_cache():
    """The persistent compile cache sits at a fixed path inside the
    checkout, whatever the environment names: only the checkout is both
    private to one side of a comparison and there for the next run."""
    import jax
    path = os.path.join(ROOT, '.jax_cache')
    os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)
    jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    # the chip machine's environment caps the cache at 192 MiB
    # (JAX_COMPILATION_CACHE_MAX_SIZE): a 667 MB step executable is then
    # never kept and every run compiles (PERF.md, PR 23)
    jax.config.update('jax_compilation_cache_max_size', -1)
    return path


class CompileCounter:
    """Counts backend compilations and persistent-cache hits as JAX
    reports them."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == '/jax/core/compile/backend_compile_duration':
            self.compiles += 1

    def _event(self, name, **kw):
        if name == '/jax/compilation_cache/cache_hits':
            self.hits += 1


def dir_mb(path):
    total = 0
    for base, _, names in os.walk(path):
        total += sum(os.path.getsize(os.path.join(base, n)) for n in names)
    return total / 1e6


def memory_plan_gb(prog):
    """The largest of the compiler's plans for the step executables the
    cell dispatched: arguments + outputs + temporaries - aliased, GB."""
    import jax.numpy as jnp
    from kfac_pytorch_tpu.preconditioner import KFACHyperParams
    hyper = KFACHyperParams(lr=jnp.float32(0), damping=jnp.float32(0))
    plans = []
    for fn in prog.step_fn.variants.values():
        m = fn.lower(prog.state, prog.pool[0], hyper).compile(
        ).memory_analysis()
        plans.append((m.argument_size_in_bytes + m.output_size_in_bytes
                      + m.temp_size_in_bytes - m.alias_size_in_bytes) / 1e9)
    return max(plans)


def traced_segment(stepper, traffic, out_dir, keep=False):
    """Trace whole cadence periods; -> the trace context of the reducers."""
    import jax
    from harness import tracefile, window
    per = window.period(traffic)
    steps = per * max(TRACE_PERIODS, -(-TRACE_MIN_STEPS // per))
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    t0 = time.perf_counter()
    stepper.run(steps)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    data = tracefile.load(tracefile.find_trace(out_dir))
    if not keep:
        shutil.rmtree(out_dir, ignore_errors=True)
    per_device = tracefile.device_ops(data)
    if not per_device:
        if jax.devices()[0].platform == 'tpu':
            die('the trace holds no device operations', 3)
        return None     # a CPU rehearsal: its trace has no device plane
    busy, span, ops, gaps = [], [], None, None
    for events in per_device.values():
        lo = events[0][1]
        hi = max(s + d for _, s, d, _ in events)
        busy.append(tracefile.busy_ns(events) / 1e9)
        span.append((hi - lo) / 1e9)
        if ops is None:
            ops = tracefile.top_ops(events)
            gaps = tracefile.idle_gaps(data, events, lo, hi)
    return {'data': data, 'steps': steps, 'wall_s': wall,
            'busy_s': sum(busy) / len(busy),
            'window_s': sum(span) / len(span),
            'breakdown': {'device_ops': ops, 'idle_gaps': gaps}}


def first_order_leg(builder, plain, config, traffic, seed, fence, n):
    """Mean step time of the same model, batch and optimizer without
    K-FAC, in this process."""
    from harness import program, window
    prog = program.build(builder, plain, config, traffic, seed, kfac=False)
    stepper = window.Stepper(prog, fence)
    stepper.run(3)
    (secs, steps), = stepper.run(n)
    return {'sgd_step_ms': secs / steps * 1e3}


def first_order_marker(cache_dir, cell, config, traffic):
    """-> (path, text) of the note, inside the compile cache, that this
    checkout's cache holds the cell's first-order program. The text holds
    the checkout's absolute path, which is part of JAX's cache key (a
    copied cache never hits, so its note must not count), and a digest of
    what the program is built from."""
    built_from = json.dumps([config, traffic], sort_keys=True).encode()
    text = json.dumps({'checkout': ROOT,
                       'built_from': hashlib.sha256(built_from).hexdigest()})
    return os.path.join(cache_dir, 'first_order_warm',
                        cell['name'] + '.json'), text


def finite_or_none(obj):
    """``obj`` with every float that is not finite replaced by None: the
    result line is JSON, which has no NaN."""
    if isinstance(obj, dict):
        return {k: finite_or_none(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite_or_none(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, default=0)
    ap.add_argument('--keep-trace', action='store_true',
                    help='leave the profiler\'s files under .bench_trace/')
    ap.add_argument('--lower', choices=('kfac', 'all'), default=False,
                    help='diagnostic: put the lower-precision control in '
                         'the reference\'s place (K-FAC arithmetic alone, '
                         'or activations too) and print its numbers')
    args = ap.parse_args()

    from harness import files
    try:
        cell, rehearsal = files.resolve_workload(args.workload)
        config, r1 = files.load_json('configs', cell['config'])
        traffic, r2 = files.load_json('traffic', cell['traffic'])
    except (KeyError, OSError) as e:
        die(str(e))
    rehearsal = rehearsal or r1 or r2
    if cell['chips'] > 1 and not rehearsal:
        # inverse_dp takes each layer's statistics from its owner's shard
        # of the batch, the reference from the whole batch: until it learns
        # owner-local statistics nothing but the first loss can be compared
        die(f'cell {cell["name"]} asks for {cell["chips"]} chips: the '
            'reference cannot follow a sharded K-FAC step yet, so such a '
            'cell has no `correct` (PERF.md, Open questions); only the '
            'rehearsal cells under benchmarks/tests/ build the mesh')
    traffic = dict(traffic, chips=cell['chips'])
    seconds = args.seconds
    if seconds is None:
        seconds = files.benchmark_json()['run_seconds']

    import jax
    cache_dir = place_cache()
    counter = CompileCounter()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        die(f'JAX found no device: {e}')
    platform = devices[0].platform
    if platform != 'tpu' and not (
            rehearsal and os.environ.get('JAX_PLATFORMS') == 'cpu'):
        die(f'needs a TPU, JAX found {platform} x{len(devices)} (only the '
            'rehearsal cells under benchmarks/tests/ run on the CPU)')
    if len(devices) < cell['chips']:
        die(f'cell {cell["name"]} needs {cell["chips"]} chips, JAX found '
            f'{len(devices)}')

    import numpy as np
    from harness import check, program, weights, window
    from kfac_pytorch_tpu.utils.profiling import host_fence

    builder = files.load_module('builders', config['builder'])
    plain = files.load_module('reference', config['plain'])
    cache_mb0 = dir_mb(cache_dir)

    # ---- set-up: the one object the window will drive -----------------
    laps = Laps(_T0)
    prog = program.build(builder, plain, config, traffic, args.seed)
    stepper = window.Stepper(prog, host_fence)
    chk = config['check']
    per = window.period(traffic)
    warm = per * -(-max(MIN_WARM_STEPS, chk['steps']) // per)
    first_calls, mine = {}, {'losses': []}
    for i in range(warm):
        t = time.perf_counter()
        mets = stepper.step()
        host_fence(mets['loss'])
        key = str(stepper.phases[-1])
        first_calls.setdefault(key, round(time.perf_counter() - t, 3))
        if i == 0:
            mine['first_update'] = program.momentum_norms(prog.state)
            mine['factors'] = program.sampled_factors(
                prog.state, prog.precond, chk['sampled_layers'])
        if i == chk['steps'] - 1:
            mine['param_change'] = program.param_change_norms(
                prog.state, config, args.seed)
    populated = program.decomposition_populated(prog.state)
    stored = program.kfac_state_dtypes(prog.state)
    setup_s = time.time() - _T0
    laps.lap('setup_s')
    say(phase='setup', setup_s=setup_s, warm_steps=warm,
        first_call_s=first_calls, compiles=counter.compiles,
        cache_hits=counter.hits, cache_dir=cache_dir,
        cache_mb_before=cache_mb0, cache_mb_after=dir_mb(cache_dir),
        variants=[str(k) for k in prog.step_fn.variants])

    # ---- the window -----------------------------------------------------
    compiles0 = counter.compiles
    traced0 = sum(fn._cache_size() for fn in prog.step_fn.variants.values())
    win = window.measure(stepper, traffic, seconds)
    win['compiles_in_window'] = max(
        counter.compiles - compiles0,
        sum(fn._cache_size() for fn in prog.step_fn.variants.values())
        - traced0)
    win['setup_s'] = setup_s
    mets = jax.device_get(stepper.metrics)
    losses, _, first_bad = window.step_health(mets)
    mine['losses'] = losses[:chk['steps']]
    counters = program.health_counters(mets[-1], chk.get('counters'))
    attempted, failed, faults = window.tally(
        mets, win['first_step'], win['steps'], counters,
        win['compiles_in_window'])
    # how the losses went over set-up and window together, and where the
    # run's first refused or non-finite step was
    say(phase='window', **{k: v for k, v in win.items()
                           if np.isscalar(v)},
        loss_first=losses[0], loss_last=losses[-1], loss_max=max(losses),
        loss_mean_first_period=float(np.mean(losses[:per])),
        loss_mean_last_period=float(np.mean(losses[-per:])),
        first_bad_step=first_bad, health=counters)
    laps.lap('window_s')

    ctx = {'window': win, 'cell': cell, 'config': config,
           'traffic': traffic, 'trace': None}
    if args.trace:
        ctx['trace'] = traced_segment(
            stepper, traffic,
            os.path.join(ROOT, '.bench_trace', cell['name']),
            keep=args.keep_trace)
        laps.lap('traced_s')
        ctx['plans'] = {'hbm_plan_gb': memory_plan_gb(prog)}
        laps.lap('memory_plan_s')
    # on this backend the allocator counts a running program's
    # temporaries under "reserved", not "in use" (2.7 GB in use beside
    # 11.0 GB reserved for a 12.6 GB plan, PERF.md PR 23): the peak is both
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    memory_peak = max((s.get('peak_bytes_in_use', 0)
                       + s.get('peak_bytes_reserved', 0) for s in stats),
                      default=0)
    say(phase='memory', allocator=stats[0], memory_peak_bytes=memory_peak)

    # ---- the program's state goes; first-order leg and reference ------
    kfac_step_ms = win['window_s'] / win['steps'] * 1e3
    del stepper, prog
    # the first run of a cell in a checkout, traced or not, builds the
    # first-order program: the driver allows that run 1,200 s, and the
    # first traced run, which has 360 s, then reads it from the cache
    marker, note = first_order_marker(cache_dir, cell, config, traffic)
    warmed = False
    if os.path.isfile(marker):
        with open(marker) as f:
            warmed = f.read() == note
    if args.trace or not warmed:
        c0, h0 = counter.compiles, counter.hits
        sgd = first_order_leg(builder, plain, config, traffic, args.seed,
                              host_fence, 2 * window.CHUNK)
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        with open(marker, 'w') as f:
            f.write(note)
        say(phase='first_order', traced=bool(args.trace), **sgd,
            compiled=(counter.compiles - c0) - (counter.hits - h0))
        if args.trace:
            sgd['kfac_over_sgd'] = kfac_step_ms / sgd['sgd_step_ms']
            ctx['sgd'] = sgd
        laps.lap('first_order_s' if args.trace else 'first_order_warm_s')

    t = time.perf_counter()
    key = weights.seed_key(args.seed)
    ref_mod = files.load_kfac_reference(config)
    ref = ref_mod.run(plain, config, traffic,
                      weights.params_fn(config['init']), key,
                      program.data_key(key), chk['steps'],
                      lower=args.lower, keep_factors=chk['sampled_layers'])
    nums = check.numbers(mine, ref)
    if cell['chips'] > 1:
        # a mesh rehearsal (refused above for a cell of BENCHMARK.json):
        # the first loss alone is comparable
        nums = {'loss_gap': (abs(mine['losses'][0] - ref['losses'][0])
                             / abs(ref['losses'][0]), 'step 0')}
    ok, rows = check.judge(nums, chk['limits'])
    for row in rows:
        say(phase='check', **row)
    say(phase='check', reference_s=time.perf_counter() - t,
        lower=args.lower, losses_program=mine['losses'],
        losses_reference=ref['losses'], kl_scale=ref.get('kl_scale'),
        decomposition_populated=populated, kfac_state_dtypes=stored,
        kfac_state_dtype_stated=config['dtype']['factors'])
    correct = bool(ok and populated and failed == 0
                   and stored == [config['dtype']['factors']])
    laps.lap('reference_s')

    # ---- the result -----------------------------------------------------
    metrics = {}
    for entry in cell['per_layer' if args.trace else 'end_to_end']:
        spec, _ = files.load_json('metrics', entry['name'])
        reducer = files.load_module('reducers', spec['reducer'])
        value = reducer.reduce(ctx, **spec.get('args', {}))
        if value is not None:
            metrics[entry['name']] = {'value': value, 'unit': spec['unit']}
    device = {'platform': platform, 'kind': devices[0].device_kind,
              'count': len(devices), 'memory_peak_bytes': memory_peak}
    result = {'correct': correct, 'attempted': attempted, 'failed': failed,
              'metrics': metrics, 'device': device}
    if ctx['trace']:
        device['busy_s'] = ctx['trace']['busy_s']
        device['window_s'] = ctx['trace']['window_s']
        result['breakdown'] = ctx['trace']['breakdown']
    # every number compared beside its limit: last in the result line and
    # as the last lines of stderr (what the driver keeps of a run that is
    # not correct)
    result['check'] = {r['check']: {'value': float(r['value']),
                                    'limit': r['limit']} for r in rows}
    result['check']['failed'] = {'value': failed, 'limit': 0}
    for name, value in faults.items():
        result['check'][name] = {
            'value': value, 'limit': -1 if name == 'first_bad_step' else 0}
    result = finite_or_none(result)
    laps.lap('reduce_s')
    say(phase='time', **laps.parts, total_s=sum(laps.parts.values()))
    for name, pair in result['check'].items():
        print(f'check {name} {pair["value"]!r} limit {pair["limit"]!r}',
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == '__main__':
    main()
