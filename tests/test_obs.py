"""Observability subsystem (kfac_pytorch_tpu/obs/).

Pins, per ISSUE 5's acceptance list:
- span nesting, the bounded ring, and flush-on-SIGTERM through the
  runlog chain;
- Perfetto/Chrome-trace schema validity of every emitted JSONL line;
- registry -> epoch-line suffix BYTE-compatibility with the legacy
  hand-plumbed path (health / resilience / kfac_phase);
- kfac-obs merging a pod drill's artifact classes (runlog + incident
  JSON + trace JSONL) into one ordered, clock-aligned timeline;
- exporters: JSONL, Prometheus textfile, native TensorBoard roundtrip,
  and rank gating.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

from kfac_pytorch_tpu.obs import aggregate, metrics, trace

pytestmark = pytest.mark.core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- trace ---------------------------------------------------------------------


def test_span_nesting_and_taxonomy():
    rec = trace.TraceRecorder(None)
    with rec.span('outer', cat='kfac'):
        with rec.span('kfac.ComputeFactor', cat='kfac'):
            pass
    spans = [e for e in rec.events() if e['ph'] == 'X']
    # completion order: inner closes first
    assert [s['name'] for s in spans] == ['kfac.ComputeFactor', 'outer']
    inner, outer = spans
    # nesting: inner lies within outer on the wall axis
    assert outer['ts'] <= inner['ts']
    assert inner['ts'] + inner['dur'] <= outer['ts'] + outer['dur'] + 1e3
    assert trace.taxonomy_phases(('stats', 'pred', 'decomp', 'gather')) == [
        'CommunicateInverse', 'ComputeFactor', 'ComputeInverse',
        'Precondition']


def test_ring_buffer_bounded():
    rec = trace.TraceRecorder(None, maxlen=16)
    for i in range(50):
        rec.instant(f'e{i}')
    assert len(rec.events()) == 16
    assert rec.dropped == 50 + 2 - 16  # + metadata & clock_sync events
    # newest events survive
    assert rec.events()[-1]['name'] == 'e49'


def test_flush_appends_and_clears(tmp_path):
    path = str(tmp_path / 't.jsonl')
    rec = trace.TraceRecorder(path)
    rec.instant('a')
    n = rec.flush()
    assert n == 3  # metadata + clock_sync + a
    rec.instant('b')
    rec.flush()
    names = [json.loads(l)['name'] for l in open(path)]
    assert names == ['process_name', 'clock_sync', 'a', 'b']
    assert rec.events() == []


def test_trace_jsonl_is_valid_perfetto_schema(tmp_path):
    path = str(tmp_path / 't.jsonl')
    rec = trace.TraceRecorder(path, process_id=3)
    with rec.span('kfac.step', cat='kfac.step',
                  phases=['ComputeFactor', 'Precondition']):
        pass
    rec.instant('watchdog_trip', deadline_s=1.5)
    rec.complete('bench.iter', 0.01, cat='bench', i=0)
    rec.flush()
    lines = [l for l in open(path).read().splitlines() if l]
    assert lines
    for line in lines:
        evt = json.loads(line)  # every line independently parseable
        assert isinstance(evt['name'], str) and evt['name']
        assert evt['ph'] in ('X', 'i', 'M')
        assert isinstance(evt['pid'], int) and evt['pid'] == 3
        assert isinstance(evt['tid'], int)
        assert isinstance(evt['ts'], (int, float)) and evt['ts'] >= 0
        if evt['ph'] == 'X':
            assert evt['dur'] >= 0
            assert isinstance(evt.get('cat'), str)
        if evt['ph'] == 'i':
            assert evt['s'] in ('g', 'p', 't')
        if 'args' in evt:
            assert isinstance(evt['args'], dict)
    # and the merged form loads as one Perfetto trace object
    merged = aggregate.merged_chrome_trace(
        {'events': [], 'sources': [],
         '_trace_events': [json.loads(l) for l in lines]})
    assert isinstance(merged['traceEvents'], list)


def test_flush_on_sigterm_runlog_chain(tmp_path):
    """A SIGTERM with NO manual flush must still land the buffered
    events in the JSONL — the recorder rides the runlog flush chain."""
    path = tmp_path / 'sig.jsonl'
    script = textwrap.dedent(f"""
        import os, signal, sys
        sys.path.insert(0, {REPO!r})
        from kfac_pytorch_tpu.obs import trace
        rec = trace.install({str(path)!r})
        rec.instant('before_sigterm', step=7)
        os.kill(os.getpid(), signal.SIGTERM)
        print('UNREACHABLE')  # the chained handler re-delivers SIGTERM
    """)
    p = subprocess.run([sys.executable, '-c', script],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == -signal.SIGTERM, (p.returncode, p.stderr)
    assert 'UNREACHABLE' not in p.stdout
    names = [json.loads(l)['name'] for l in open(path)]
    assert 'before_sigterm' in names


def test_module_level_noops_without_recorder():
    assert trace.get() is None or trace.uninstall() is not None
    trace.uninstall()
    assert trace.instant('nobody_home') is None
    with trace.span('nobody_home'):
        pass
    assert trace.flush() == 0


def test_install_from_env_role_naming(tmp_path):
    env = {trace.ENV_TRACE_DIR: str(tmp_path), 'JAX_PROCESS_ID': '2'}
    rec = trace.install_from_env(env=env, role='sup')
    try:
        assert rec.path.endswith('trace-host2-sup.jsonl')
        assert rec.process_id == 2
    finally:
        trace.uninstall()
    # role applies to an exact-path target too (two co-hosted processes
    # must never append into one file)
    exact = {trace.ENV_TRACE_DIR: str(tmp_path / 'run.jsonl')}
    rec = trace.install_from_env(env=exact, role='sup')
    try:
        assert rec.path.endswith('run-sup.jsonl')
    finally:
        trace.uninstall()
    assert trace.install_from_env(env={}) is None


# -- registry / suffix byte-compatibility -------------------------------------


def _old_suffixes(health_epoch, res_delta, phase_ms):
    from kfac_pytorch_tpu.utils.runlog import (health_suffix,
                                               kfac_phase_suffix,
                                               resilience_suffix)
    return (health_suffix(health_epoch) + resilience_suffix(res_delta)
            + kfac_phase_suffix(phase_ms))


def test_registry_suffixes_byte_identical_to_legacy(tmp_path):
    """Drive the SAME event stream through the legacy plumbing and the
    registry; the epoch-line suffix strings must match byte-for-byte —
    including the all-clean epoch rendering to ''."""
    from kfac_pytorch_tpu import resilience
    from kfac_pytorch_tpu.utils.metrics import HealthMonitor, PhaseTimers
    from kfac_pytorch_tpu.utils.runlog import counter_deltas
    resilience.counters.reset()
    try:
        gov_counts = {'straggler_level': 0, 'straggler_degrades': 0}

        # legacy side
        import logging
        quiet = logging.getLogger('test_obs.quiet')
        quiet.setLevel(logging.CRITICAL)
        old_mon = HealthMonitor(quiet)
        old_timers = PhaseTimers()
        # registry side
        reg = metrics.Registry(process_id=0)
        new_mon = HealthMonitor(quiet, registry=reg)
        new_timers = PhaseTimers(registry=reg)
        reg.add_collector(metrics.resilience_collector(lambda: gov_counts))
        res_prev = {}

        def epoch(mets_seq, phase_seq, res_bumps, gov):
            nonlocal res_prev
            for name, by in res_bumps:
                resilience.counters.bump(name, by)
            gov_counts.update(gov)
            for m in mets_seq:
                old_mon.update(m)
                new_mon.update(m)
            for phases, secs in phase_seq:
                old_timers.record(phases, secs)
                new_timers.record(phases, secs)
            res_now = resilience.counters.snapshot()
            res_now.update(gov_counts)
            res_delta, res_prev = counter_deltas(res_now, res_prev), res_now
            legacy = _old_suffixes(old_mon.epoch_flush(), res_delta,
                                   old_timers.epoch_flush())
            via_registry = reg.epoch_suffixes()
            new_mon.epoch_flush()
            assert via_registry == legacy, (via_registry, legacy)
            return legacy

        # epoch 0: clean — both must render ''
        s0 = epoch([{'health/skipped': 0, 'health/fallbacks': 0,
                     'health/rung': 0}],
                   [(('pred',), 0.010), (('pred',), 0.012)],
                   [], {'straggler_level': 0})
        assert s0.startswith(' kfac_phase_ms=')  # phases always render
        # epoch 1: health events + resilience counters + phase marginals
        s1 = epoch([{'health/skipped': 1, 'health/fallbacks': 0,
                     'health/rung': 1},
                    {'health/skipped': 2, 'health/fallbacks': 1,
                     'health/rung': 2}],
                   [(('pred',), 0.010),
                    (('pred', 'stats', 'decomp', 'gather'), 0.050)],
                   [('io_retries', 2), ('watchdog_trips', 1)],
                   {'straggler_level': 1, 'straggler_degrades': 1})
        assert '[health: skipped=2 sgd_fallbacks=1 max_rung=2]' in s1
        assert 'io_retries=2' in s1 and 'straggler_level=1' in s1
        assert 'decomp+gather+stats' in s1
        # epoch 2: quiet again — deltas reset, stale phase gauges gone,
        # gauge-typed level passes through
        s2 = epoch([{'health/skipped': 2, 'health/fallbacks': 1,
                     'health/rung': 0}], [], [],
                   {'straggler_level': 1})
        assert '[health:' not in s2
        assert 'kfac_phase_ms' not in s2
        assert 'io_retries' not in s2
        assert 'straggler_level=1' in s2
    finally:
        resilience.counters.reset()


def test_registry_counter_monotonic_and_types():
    reg = metrics.Registry(process_id=0)
    c = reg.counter('a')
    with pytest.raises(ValueError):
        c.inc(-1)
    c.inc(3)
    c.set_total(2)       # ignored: monotonic
    assert c.value == 3
    with pytest.raises(TypeError):
        reg.gauge('a')   # type collision
    w = reg.watermark('w')
    w.set(5)
    w.set(2)
    assert reg.epoch_flush()['w'] == 5
    assert reg.epoch_flush()['w'] == 0  # watermark reset per epoch


def test_health_monitor_resume_baseline_not_reannounced():
    """A restored cumulative baseline must not appear in the first
    epoch's registry deltas (mirrors the legacy monitor semantics)."""
    import logging

    class FakeHealth:
        skipped, fallbacks, rung = 4, 1, 0

    class FakeState:
        health = FakeHealth()

    reg = metrics.Registry(process_id=0)
    quiet = logging.getLogger('test_obs.quiet2')
    quiet.setLevel(logging.CRITICAL)
    from kfac_pytorch_tpu.utils.metrics import HealthMonitor
    HealthMonitor(quiet, state=FakeState(), registry=reg)
    assert reg.epoch_suffixes() == ''


# -- exporters -----------------------------------------------------------------


def _populated_registry(process_id=0):
    reg = metrics.Registry(process_id=process_id)
    reg.counter('resilience/io_retries').inc(2)
    reg.gauge('kfac_phase/pred').set(1.5)
    h = reg.histogram('step_seconds', buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    return reg


def test_jsonl_exporter(tmp_path):
    reg = _populated_registry()
    reg.add_exporter(metrics.JsonlExporter(str(tmp_path / 'm.jsonl')))
    assert reg.export(step=0) == 1
    assert reg.export(step=1) == 1
    lines = [json.loads(l) for l in open(tmp_path / 'm.jsonl')]
    assert [l['step'] for l in lines] == [0, 1]
    m = lines[1]['metrics']
    assert m['resilience/io_retries'] == 2
    assert m['step_seconds']['count'] == 4
    assert m['step_seconds']['buckets'] == {
        '0.01': 1, '0.1': 2, '1.0': 3, '+Inf': 4}  # cumulative


def test_prometheus_textfile_exporter(tmp_path):
    path = str(tmp_path / 'kfac.prom')
    reg = _populated_registry()
    reg.add_exporter(metrics.PrometheusTextfileExporter(path))
    reg.export(step=0)
    text = open(path).read()
    # the registry's real kinds drive the TYPE lines (no name heuristics)
    assert '# TYPE kfac_resilience_io_retries counter' in text
    assert 'kfac_resilience_io_retries 2' in text
    assert '# TYPE kfac_kfac_phase_pred gauge' in text
    assert 'kfac_step_seconds_bucket{le="+Inf"} 4' in text
    assert 'kfac_step_seconds_count 4' in text
    assert 'kfac_step_seconds_sum' in text
    # atomic write: no tmp debris
    assert not os.path.exists(path + '.tmp')


def test_tensorboard_exporter_roundtrip(tmp_path):
    from kfac_pytorch_tpu.utils.summary import read_scalars
    reg = _populated_registry()
    reg.add_exporter(metrics.TensorBoardExporter(str(tmp_path)))
    reg.export(step=3)
    series = read_scalars(str(tmp_path))
    assert series['resilience/io_retries'] == [(3, 2.0)]
    assert series['kfac_phase/pred'] == [(3, 1.5)]
    (step, mean), = series['step_seconds/mean']
    assert step == 3 and abs(mean - 5.555 / 4) < 1e-4


def test_epoch_gauges_survive_flush_for_exporters(tmp_path):
    """The trainers render the epoch line (flushing the per-epoch
    gauges) BEFORE exporting; the exporters must still see the phase
    timings — staleness hides them from the NEXT epoch line only."""
    from kfac_pytorch_tpu.utils.metrics import PhaseTimers
    reg = metrics.Registry(process_id=0)
    timers = PhaseTimers(registry=reg)
    reg.add_exporter(metrics.JsonlExporter(str(tmp_path / 'm.jsonl')))
    timers.record(('pred',), 0.010)
    s = reg.epoch_suffixes()
    assert 'kfac_phase_ms=' in s
    reg.export(step=0)
    snap = json.loads(open(tmp_path / 'm.jsonl').read())['metrics']
    assert snap['kfac_phase/pred'] == 10.0
    assert 'kfac_phase/step_mean' in snap
    # but an idle next epoch renders no stale phase suffix
    assert 'kfac_phase_ms=' not in reg.epoch_suffixes()


def test_setup_trainer_helper(tmp_path):
    from kfac_pytorch_tpu import obs
    try:
        tracer, reg = obs.setup_trainer(trace_dir=str(tmp_path),
                                        prom_file=str(tmp_path / 'p'))
        assert tracer is trace.get()
        assert tracer.path.endswith('trace-host0.jsonl')
        assert len(reg._exporters) == 2 and len(reg._collectors) == 1
    finally:
        trace.uninstall()
    # no trace dir, no env: tracing off, registry still built
    tracer, reg = obs.setup_trainer()
    assert (tracer is None) == (trace.ENV_TRACE_DIR not in os.environ)
    trace.uninstall()


def test_export_rank_gated(tmp_path):
    reg = _populated_registry(process_id=1)
    reg.add_exporter(metrics.JsonlExporter(str(tmp_path / 'm.jsonl')))
    assert reg.export(step=0) == 0
    assert not os.path.exists(tmp_path / 'm.jsonl')


# -- aggregation (kfac-obs) ----------------------------------------------------


def _write_drill_artifacts(tmp_path):
    """Synthesize the 2-host SIGKILL drill's artifact classes with the
    EXACT line forms the modules emit (the regexes are shared with
    resilience.incident, so a drifted form fails here AND there)."""
    # host0.out: timestamped pod-supervisor lines interleaved with the
    # trainer's clockless protocol/heartbeat lines, in causal order
    host0 = tmp_path / 'host0.out'
    host0.write_text('\n'.join([
        '2026-08-03 10:00:00,100 pod-supervisor: launching gen 0',
        'EPOCH 0 step=2 loss=2.1000',
        'heartbeat: peer 1 declared dead — no heartbeat advance for '
        '4.52s (deadline 4.00s, last step 3) [resilience: peer_dead=1 '
        'peer=1 detect_s=4.52]',
        '2026-08-03 10:00:08,000 elastic: shrinking world 2 -> 1 '
        'survivors=[0] gen=1',
        'RESHARDED from_world=2 to_world=1 step=4',
        'RESUMED from=checkpoint-0 step=4',
        'EPOCH 1 step=6 loss=1.9000',
        'DONE final_step=8 epochs=3',
    ]) + '\n')
    host1 = tmp_path / 'host1.out'
    host1.write_text(
        '2026-08-03 10:00:01,000 pod-supervisor: launching gen 0\n'
        'EPOCH 0 step=2 loss=2.1000\n')
    # incident-host0.json via the real producer; live walls sit on the
    # same clock the log asctimes parse to (one machine, like the drill)
    base = aggregate._parse_asctime('2026-08-03 10:00:00,100 x')
    from kfac_pytorch_tpu.resilience.incident import IncidentReport
    rep = IncidentReport(host_id=0)
    rep.add_event('peer_dead', peer=1, detect_s=4.52, wall=base + 5.0)
    rep.add_event('trainer_exit', rc=115, reason='peer dead',
                  wall=base + 5.5)
    rep.add_event('shrink', wall=base + 7.9,
                  **{'from': 2, 'to': 1, 'survivors': [0], 'gen': 1})
    rep.write(str(tmp_path / 'incident-host0.json'))
    # per-host trace JSONL via the real recorder, on the same synthetic
    # clock (injectable clock — the drill's files all share one machine)
    rec = trace.TraceRecorder(str(tmp_path / 'trace-host0.jsonl'),
                              process_id=0, clock=lambda: base + 4.6)
    with rec.span('kfac.dispatch', cat='kfac.step', step=3,
                  phases=['ComputeFactor']):
        pass
    rec.instant('peer_dead', peer=1, detect_s=4.52)
    rec.flush()
    # the registry's metrics.jsonl lives in the same --trace dir in real
    # runs: it must be ignored by the trace loader, not leak junk rows
    (tmp_path / 'metrics.jsonl').write_text(json.dumps(
        {'wall': base, 'step': 0, 'metrics': {'health/skipped': 0}}) + '\n')
    return host0, host1


def test_aggregate_merges_artifacts_into_ordered_timeline(tmp_path):
    host0, host1 = _write_drill_artifacts(tmp_path)
    timeline = aggregate.build_timeline([str(tmp_path)])
    events = timeline['events']
    kinds = [e['kind'] for e in events]
    for needed in ('peer_dead', 'shrink', 'resharded', 'resumed',
                   'trainer_exit', 'run_done'):
        assert needed in kinds, (needed, sorted(set(kinds)))

    def first(kind):
        return next(i for i, e in enumerate(events) if e['kind'] == kind)

    # causal order on the merged clock
    assert first('peer_dead') < first('shrink') < first('resharded')
    assert first('resharded') < first('resumed') < first('run_done')
    # clock alignment: the clockless RESHARDED line inherited the
    # preceding timestamped shrink line's wall (carry-forward)
    resh = events[first('resharded')]
    assert resh['wall'] is None
    shrink_wall = aggregate._parse_asctime('2026-08-03 10:00:08,000 x')
    assert resh['wall_aligned'] is not None
    assert 0 <= resh['wall_aligned'] - shrink_wall < 1.0
    # host attribution from filenames / payloads
    assert events[first('resharded')]['host'] == 0
    assert {s['kind'] for s in timeline['sources']} == {
        'trace', 'incident', 'log'}
    # detail fields parsed and coerced
    d = events[first('shrink')]['detail']
    assert (d['from'], d['to']) == (2, 1)


def test_aggregate_cli_writes_timeline_and_merged_trace(tmp_path, capsys):
    _write_drill_artifacts(tmp_path)
    out = tmp_path / 'timeline.json'
    tout = tmp_path / 'pod_trace.json'
    rc = aggregate.main([str(tmp_path), '-o', str(out),
                         '--trace-out', str(tout)])
    assert rc == 0
    printed = capsys.readouterr().out
    assert 'pod timeline' in printed and 'peer_dead' in printed
    doc = json.loads(out.read_text())
    assert doc['events'] and doc['sources']
    assert '_trace_events' not in doc
    merged = json.loads(tout.read_text())
    names = [e['name'] for e in merged['traceEvents']]
    # raw spans AND injected log/incident instants share the canvas
    assert 'kfac.dispatch' in names
    assert 'shrink' in names
    # every merged event is trace-shaped: the co-located metrics.jsonl
    # (not Chrome-trace events) must not have leaked junk rows
    assert all('ph' in e and 'name' in e for e in merged['traceEvents'])


def test_aggregate_offset_applies(tmp_path):
    _write_drill_artifacts(tmp_path)
    base = aggregate.build_timeline([str(tmp_path / 'host0.out')])
    moved = aggregate.build_timeline([str(tmp_path / 'host0.out')],
                                     offsets={0: 100.0})
    w0 = [e['wall_aligned'] for e in base['events']
          if e['wall_aligned'] is not None]
    w1 = [e['wall_aligned'] for e in moved['events']
          if e['wall_aligned'] is not None]
    assert all(abs(b - a - 100.0) < 1e-6 for a, b in zip(w0, w1))


def test_incident_scrapes_trace_jsonl(tmp_path):
    path = str(tmp_path / 't.jsonl')
    rec = trace.TraceRecorder(path, process_id=0)
    rec.instant('watchdog_trip', deadline_s=2.0, rc=114)
    rec.instant('clock_sync_is_meta_not_resilience')  # cat=resilience!
    with rec.span('kfac.step'):
        pass
    rec.flush()
    from kfac_pytorch_tpu.resilience.incident import IncidentReport
    rep = IncidentReport(host_id=0).scrape_path(path)
    kinds = [e['kind'] for e in rep.events]
    assert 'watchdog_trip' in kinds
    assert 'kfac.step' not in kinds  # spans are not incident events
    trip = next(e for e in rep.events if e['kind'] == 'watchdog_trip')
    assert trip['rc'] == 114 and trip['wall'] is not None


# -- training integration ------------------------------------------------------


def test_training_dispatch_and_step_spans():
    """build_train_step(tracer=) emits kfac.dispatch spans with the
    taxonomy phase set; PhaseTimers(tracer=) emits the kfac.step span."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kfac_pytorch_tpu as kfac
    from kfac_pytorch_tpu import training
    from kfac_pytorch_tpu.models.tiny import TinyCNN
    from kfac_pytorch_tpu.utils.metrics import PhaseTimers

    rec = trace.TraceRecorder(None)
    timers = PhaseTimers(tracer=rec)
    rng = np.random.RandomState(0)
    batch = {'input': jnp.asarray(rng.randn(4, 8, 8, 3), jnp.float32),
             'label': jnp.asarray(rng.randint(0, 10, 4))}
    model = TinyCNN()
    tx = training.sgd(0.05)
    precond = kfac.KFAC(variant='eigen_dp', lr=0.05, damping=0.003,
                        fac_update_freq=1, kfac_update_freq=2,
                        num_devices=1, axis_name=None)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0),
                                      batch['input'])

    def loss_fn(outputs, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, batch['label']).mean()

    step = training.build_train_step(model, tx, precond, loss_fn,
                                     tracer=rec)
    import time as _time
    for _ in range(3):
        t0 = _time.perf_counter()
        state, m = step(state, batch, lr=0.05, damping=0.003)
        float(m['loss'])
        timers.record(step.last_phases, _time.perf_counter() - t0)
    spans = [e for e in rec.events() if e['ph'] == 'X']
    dispatches = [s for s in spans if s['name'] == 'kfac.dispatch']
    steps = [s for s in spans if s['name'] == 'kfac.step']
    assert len(dispatches) == 3 and len(steps) == 3
    # step 0 is the first full decomposition; its phase args carry the
    # ledger taxonomy
    assert dispatches[0]['args']['step'] == 0
    all_phases = {p for s in steps for p in s['args']['phases']}
    assert 'ComputeFactor' in all_phases
    assert all_phases <= {'ComputeFactor', 'ComputeInverse',
                          'CommunicateInverse', 'Precondition'}


# -- automatic clock-offset solving (ISSUE 7 satellite) ------------------------


def _sync_trace(path, pid, rows):
    """Write a minimal trace JSONL of clock_sync instants.
    rows: [(receiver_wall, peer, peer_wall)]"""
    with open(path, 'w') as f:
        f.write(json.dumps({'ph': 'M', 'name': 'process_name',
                            'pid': pid, 'tid': 0, 'ts': 0,
                            'args': {'name': f'host{pid}'}}) + '\n')
        for wall, peer, peer_wall in rows:
            f.write(json.dumps({'name': 'clock_sync', 'ph': 'i',
                                'cat': 'meta', 's': 'p',
                                'ts': wall * 1e6, 'pid': pid, 'tid': 0,
                                'args': {'peer': peer,
                                         'peer_wall': peer_wall}})
                    + '\n')


def test_solve_offsets_recovers_injected_skew(tmp_path):
    """Host 1's clock runs 3.5s AHEAD. The cross-host clock_sync pairs
    (sender wall vs receiver wall at delivery, latency-biased upward)
    must solve host 1's correction to ~-3.5s, anchored at host 0."""
    T0, skew = 1_000_000.0, 3.5
    rows0, rows1 = [], []
    for i in range(6):
        t = T0 + 10 * i
        lat = 0.02 * (i + 1)        # varying latency; min ~0.02
        # host 0 receives host 1's payload: stamped on 1's fast clock
        rows0.append((t + lat, 1, t + skew))
        # host 1 receives host 0's payload: its local clock reads fast
        rows1.append((t + lat + skew, 0, t))
    _sync_trace(tmp_path / 'trace-host0.jsonl', 0, rows0)
    _sync_trace(tmp_path / 'trace-host1.jsonl', 1, rows1)
    offsets = aggregate.solve_offsets([str(tmp_path / 'trace-host0.jsonl'),
                                       str(tmp_path / 'trace-host1.jsonl')])
    assert set(offsets) == {1}
    assert offsets[1] == pytest.approx(-skew, abs=0.05)


def test_solve_offsets_bfs_propagates_through_indirect_links(tmp_path):
    """Host 2 only ever exchanged beats with host 1 (never with the
    anchor host 0): its offset must still solve through the 0<->1<->2
    link chain."""
    T0 = 5_000.0
    # host 1 runs +2.0s fast, host 2 +1.0s fast (both vs host 0)
    _sync_trace(tmp_path / 't0.jsonl', 0, [(T0, 1, T0 + 2.0)])
    _sync_trace(tmp_path / 't1.jsonl', 1,
                [(T0 + 2.0, 0, T0), (T0 + 2.0, 2, T0 + 1.0)])
    offsets = aggregate.solve_offsets([str(tmp_path / 't0.jsonl'),
                                       str(tmp_path / 't1.jsonl')])
    # e1 = +2.0 -> offset -2.0; e2 = e1 - (ts1 - peer_wall2) = 2 - 1 = 1
    assert offsets[1] == pytest.approx(-2.0, abs=1e-6)
    assert offsets[2] == pytest.approx(-1.0, abs=1e-6)


def test_solve_offsets_falls_back_to_empty_without_pairs(tmp_path):
    """No clock_sync pairs (tracing off, single host): the solver
    returns {} and the timeline keeps its carry-forward alignment."""
    rec = trace.TraceRecorder(str(tmp_path / 'plain.jsonl'), process_id=0)
    with rec.span('kfac.step'):
        pass
    rec.flush()
    assert aggregate.solve_offsets([str(tmp_path / 'plain.jsonl')]) == {}
    log = tmp_path / 'host0.out'
    log.write_text('EPOCH 0 step=5 loss=1.0\n')
    assert aggregate.solve_offsets([str(log)]) == {}


def test_heartbeat_emits_cross_host_clock_sync_pairs(tmp_path):
    """The solver's inputs come from the heartbeat monitors: every 8th
    publish with a fresh peer advance records a clock_sync instant
    carrying (peer, peer_wall)."""
    from kfac_pytorch_tpu.resilience.heartbeat import (
        FileLeaseTransport, PeerHeartbeat)
    from kfac_pytorch_tpu.resilience.retry import ManualClock
    rec = trace.install(None)
    try:
        clock = ManualClock()
        h0 = PeerHeartbeat(FileLeaseTransport(tmp_path, 0), 0, 2,
                           interval=1.0, deadline=50.0,
                           startup_grace=60.0, clock=clock.monotonic,
                           on_dead=lambda p, i: None)
        t1 = FileLeaseTransport(tmp_path, 1)
        for seq in range(1, 20):
            t1.publish({'host': 1, 'seq': seq, 'pid': 9, 'gen': 0,
                        'wall': 123456.0 + seq})
            h0.poll_once()
            clock.sleep(1.0)
        syncs = [e for e in rec.events()
                 if e.get('name') == 'clock_sync'
                 and (e.get('args') or {}).get('peer') == 1]
        assert syncs, 'no cross-host clock_sync emitted'
        assert all(isinstance(s['args']['peer_wall'], float)
                   for s in syncs)
        # throttled: every 8th publish, not every poll
        assert len(syncs) <= 4
    finally:
        trace.uninstall()


def test_aggregate_cli_solves_offsets_by_default(tmp_path, capsys):
    _sync_trace(tmp_path / 'trace-host0.jsonl', 0,
                [(1000.0, 1, 998.0)])
    _sync_trace(tmp_path / 'trace-host1.jsonl', 1,
                [(1002.0, 0, 1000.0)])
    aggregate.main([str(tmp_path / 'trace-host0.jsonl'),
                    str(tmp_path / 'trace-host1.jsonl')])
    out = capsys.readouterr().out
    assert 'clock offsets solved' in out and 'host1=' in out
    aggregate.main(['--no-solve-offsets',
                    str(tmp_path / 'trace-host0.jsonl')])
    out = capsys.readouterr().out
    assert 'clock offsets solved' not in out
