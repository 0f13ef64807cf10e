"""Structured per-run incident reports from resilience runlogs.

Every resilience piece already narrates what it did in a greppable form:
epoch lines and supervisor events carry ``[resilience: k=v ...]``
suffixes (``utils.runlog.resilience_suffix``), the watchdog logs its
deadline trip, the heartbeat its peer-death declaration, the supervisor
its restarts and its machine-greppable give-up (``gave_up=1``). What was
missing is the OTHER half of the loop: after a bad night, "what died,
when, how many restarts, how many steps lost, which windows ran
degraded" should be one artifact, not an hour of grepping.

:class:`IncidentReport` is that artifact: events are either scraped
from runlog lines (:meth:`scrape_lines` — regexes over exactly the
forms the modules emit) or recorded live (:meth:`add_event` — the pod
supervisor does this, it IS the event source for peer death / shrink /
relaunch). ``to_dict()`` is the JSON report; ``summary()`` the human
one. CLI::

    python -m kfac_pytorch_tpu.resilience.incident run1.log run2.log \\
        -o incident.json
"""

import argparse
import json
import re
import sys
import time

# cumulative gauges/counters (supervisor totals, ladder positions):
# aggregate by MAX. Everything else in a [resilience: ...] suffix is a
# per-epoch delta: aggregate by SUM.
_CUMULATIVE = frozenset({
    'restarts', 'crashes', 'hangs', 'gave_up', 'fenced', 'suspended',
    'shrinks', 'grows', 'joins', 'straggler_level',
    'partition_suspected', 'quorum_lost', 'coord_lost',
    'coord_retries', 'coord_gave_ups', 'poll_wait_s',
    'store_lost', 'store_retries', 'store_gave_ups',
})
# (the replicated backend's replica_down/replica_repair/quorum_degraded
# suffixes are per-event deltas — =1 each emission — so they take the
# default SUM aggregation, not the cumulative MAX above)

# suffix keys that are event FIELDS riding along in a [resilience: ...]
# line (heartbeat's peer=/detect_s=, the join announcement's host=), not
# counters — the event regexes capture them; aggregating them as counts
# would be nonsense
_NON_COUNTERS = frozenset({'peer', 'detect_s', 'host'})

# one regex per event-emitting module, matching the exact log forms
_PATTERNS = (
    ('watchdog_trip', re.compile(
        r'watchdog: step deadline exceeded \((?P<deadline_s>[\d.]+)s'
        r'(?:, (?P<tag>[^)]+))?\)')),
    ('peer_dead', re.compile(
        r'heartbeat: peer (?P<peer>\d+) declared dead — no heartbeat '
        r'advance for (?P<detect_s>[\d.]+)s')),
    ('restart', re.compile(
        r'supervisor: trainer exited rc=(?P<rc>-?\d+) \((?P<why>[^)]+)\) '
        r'— restart (?P<n>\d+)/(?P<max>\d+) in (?P<delay_s>[\d.]+)s')),
    ('gave_up', re.compile(
        r'supervisor: trainer exited rc=(?P<rc>-?\d+) .*giving up')),
    # the supervisor's OTHER two terminal verdicts (found by the
    # kfac-lint event-grammar rule: these emit sites carried k=v event
    # payloads the grammar could not see, so a preemption or
    # configured-stop shutdown was invisible on the kfac-obs timeline
    # while the give-up verdict was not)
    ('preempt_stop', re.compile(
        r'supervisor: trainer exited rc=(?P<rc>-?\d+) after forwarded '
        r'signal — preemption shutdown, not restarting')),
    ('stop_rc', re.compile(
        r'supervisor: trainer exited rc=(?P<rc>-?\d+) \(configured '
        r'stop code\) — not restarting')),
    ('shrink', re.compile(
        r'elastic: shrinking world (?P<from>\d+) -> (?P<to>\d+) '
        r'survivors=(?P<survivors>\[[^\]]*\]) gen=(?P<gen>\d+)')),
    # the partition story (quorum-gated membership): suspicion when
    # half or more of the membership goes unreachable at once, the
    # quorum verdict on the shrink barrier, and the losing side's
    # self-fence — three stages so a partition timeline reads
    # partition_suspected -> quorum_lost -> fenced alongside the
    # majority's shrink
    ('partition_suspected', re.compile(
        r'elastic: partition suspected — (?P<unreachable>\d+) of '
        r'(?P<world>\d+) members unreachable')),
    ('quorum_lost', re.compile(
        r'elastic: quorum lost at gen (?P<gen>\d+) — claimants '
        r'(?P<claimants>\[[^\]]*\]) are a minority of membership '
        r'(?P<membership>\[[^\]]*\])')),
    ('fenced', re.compile(
        r'Fencing this host \(killing the trainer')),
    # the checkpoint-suspend verdict (ISSUE 17 preemption): the
    # scheduler asked, the supervisor stopped the trainer at a
    # checkpoint boundary and exits RC_SUSPENDED with no further
    # commits — the pod half of the job_preempt -> job_suspend story
    # (head starts mid-line, like 'fenced' above: the many
    # 'pod-supervisor: %s ...' narration sites must not claim it)
    ('suspended', re.compile(
        r'suspending on request — trainer stopped '
        r'\(grace checkpoint banked, trainer rc was '
        r'(?P<trainer_rc>\S+)\), exiting rc=(?P<rc>\d+)')),
    # the coordination backend (kfac_pytorch_tpu/coord): per-op retries
    # surface as coord_retries= counters in the [resilience: ...]
    # suffixes; a spent budget is its own event — the give-up on ONE op
    # (coord.base.RetryingBackend) and the supervisor/scheduler-level
    # verdict that follows (rc=118, check the backend not the pod)
    ('coord_gave_up', re.compile(
        r'coord: giving up op=(?P<op>[\w_]+) key=(?P<key>\S*) after '
        r'(?P<attempts>\d+) attempts')),
    ('coord_lost', re.compile(
        r'coordination backend lost — .*exiting rc=(?P<rc>\d+)')),
    # the durable checkpoint plane (kfac_pytorch_tpu/store): per-op
    # retries surface as store_retries= counters; a spent budget is the
    # give-up on ONE op (store.base.RetryingStore) and the trainer/
    # verifier-level verdict that follows (rc=120, check the OBJECT
    # STORE, not the pod and not the coord backend). The manifest
    # lifecycle narrates alongside: the commit point of every save, the
    # scrub's clean verdict, each corrupt blob it (or a restore's hash
    # check) caught, and each repair — so a durability timeline reads
    # ckpt_commit -> ckpt_corrupt -> ckpt_repair -> ckpt_verify with
    # zero new aggregation code
    ('store_gave_up', re.compile(
        r'store: giving up op=(?P<op>[\w_]+) key=(?P<key>\S*) after '
        r'(?P<attempts>\d+) attempts')),
    ('store_lost', re.compile(
        r'checkpoint store lost — .*exiting rc=(?P<rc>\d+)')),
    ('ckpt_commit', re.compile(
        r'ckpt: committed manifest epoch=(?P<epoch>\d+) '
        r'blobs=(?P<blobs>\d+) kind=(?P<kind>\w+)')),
    ('ckpt_verify', re.compile(
        r'ckpt: verified epoch=(?P<epoch>\d+) blobs=(?P<blobs>\d+)')),
    ('ckpt_corrupt', re.compile(
        r'ckpt: corrupt blob key=(?P<key>\S+) epoch=(?P<epoch>\d+) '
        r'reason=(?P<reason>\w+)')),
    ('ckpt_repair', re.compile(
        r'ckpt: repaired blob key=(?P<key>\S+) epoch=(?P<epoch>\d+) '
        r'source=(?P<source>\S+)')),
    # the replicated quorum (coord.replicated): one replica's loss,
    # its read-through catch-up after a restart, and the degraded-
    # but-answering state between them — so an operator's timeline
    # reads replica_down -> quorum_degraded -> replica_repair without
    # any trainer-visible coord_lost in between (that one only appears
    # on TRUE quorum loss)
    ('replica_down', re.compile(
        r'coord-replicated: replica (?P<replica>\S+) down — '
        r'.*\((?P<up>\d+)/(?P<total>\d+) replicas reachable\)')),
    ('replica_repair', re.compile(
        r'coord-replicated: replica (?P<replica>\S+) repaired '
        r'key=(?P<key>\S+) rrev=(?P<rrev>\d+)')),
    ('quorum_degraded', re.compile(
        r'coord-replicated: quorum degraded — (?P<up>\d+) of '
        r'(?P<total>\d+) replicas answering \(quorum '
        r'(?P<quorum>\d+)\)')),
    # the grow cycle (elastic GROW / train-through-churn): a repaired
    # host's announcement, each supervisor's claim into the grow
    # barrier, the agreed enlargement, and the trainer-side upward
    # factor transport — one event per protocol stage so a churn
    # timeline can pin death -> shrink -> join -> grow causally
    ('join_announce', re.compile(
        r'join: host (?P<host>\d+) announcing to pod')),
    ('grow_claim', re.compile(
        r'elastic: grow claim written host=(?P<host>\d+) '
        r'gen=(?P<gen>\d+)')),
    ('grow', re.compile(
        r'elastic: growing world (?P<from>\d+) -> (?P<to>\d+) '
        r'members=(?P<members>\[[^\]]*\]) gen=(?P<gen>\d+) '
        r'joiners=(?P<joiners>\[[^\]]*\])')),
    ('grow_resharded', re.compile(
        r'elastic: grow reshard from_world=(?P<from>\d+) '
        r'to_world=(?P<to>\d+) step=(?P<step>\d+)')),
    # trainer-side world-change hook (training.world_change_rescale):
    # what the batch/lr actually became after a shrink/grow
    ('world_rescale', re.compile(
        r'WORLD_RESCALE from_world=(?P<from>\d+) to_world=(?P<to>\d+) '
        r'global_batch=(?P<global_batch>\d+) '
        r'lr=(?P<lr>[\d.eE+-]+) lr_factor=(?P<lr_factor>[\d.eE+-]+)')),
    # the closed-loop autotuner (kfac_pytorch_tpu/autotune.py): one
    # event per controller decision — probe/commit/revert of one knob
    # candidate, the quality-gate veto, steady-state arrival, and the
    # analytic comm-mode verdict — so a kfac-obs timeline renders the
    # whole tuning trajectory from the run logs with zero new aggregate
    # code (the same shared-grammar contract the grow/partition stories
    # use)
    ('autotune_probe', re.compile(
        r'autotune: probing (?P<knob>[\w_]+) (?P<from>\S+) -> '
        r'(?P<to>\S+) at step (?P<step>\d+) \(window (?P<window>\d+)\)')),
    ('autotune_commit', re.compile(
        r'autotune: committed (?P<knob>[\w_]+) (?P<from>\S+) -> '
        r'(?P<to>\S+) \(step time (?P<before_s>[\d.]+)s -> '
        r'(?P<after_s>[\d.]+)s, -(?P<gain_pct>[\d.]+)%\) at step '
        r'(?P<step>\d+)')),
    ('autotune_revert', re.compile(
        r'autotune: reverted (?P<knob>[\w_]+) (?P<from>\S+) -> '
        r'(?P<to>\S+) \(no improvement: (?P<baseline_s>[\d.]+)s -> '
        r'(?P<probe_s>[\d.]+)s\) at step (?P<step>\d+)')),
    ('autotune_veto', re.compile(
        r'autotune: quality veto — knob (?P<knob>[\w_]+) (?P<value>\S+) '
        r'rejected \(\+(?P<health_events>[\d.eE+-]+) health events in '
        r'the probe window\) at step (?P<step>\d+)')),
    ('autotune_steady', re.compile(
        r'autotune: steady state — knobs fac=(?P<fac>\d+) '
        r'kfac=(?P<kfac>\d+) comm_precision=(?P<comm_precision>\w+) '
        r'after (?P<windows>\d+) windows at step (?P<step>\d+)')),
    ('autotune_comm_mode', re.compile(
        r'autotune: comm_mode decision (?P<mode>\w+) \(inverse '
        r'(?P<inverse_kib>[\d.]+) KiB/step vs pred '
        r'(?P<pred_kib>[\d.]+) KiB/step\) at step (?P<step>\d+)')),
    # the multi-tenant training service (kfac_pytorch_tpu/service/):
    # one event per job-lifecycle edge — admission onto pod capacity,
    # a requeue after a classified failure, the terminal done/lost
    # verdicts, and live capacity-pool changes — so a tenant's whole
    # story (admit -> failure -> requeue -> done) renders on the
    # kfac-obs timeline from the service log alone, same shared-
    # grammar contract the grow/partition/autotune stories use
    ('job_admit', re.compile(
        r'service: job_admit job=(?P<job>\d+) tenant=(?P<tenant>[\w-]+) '
        r'trainer=(?P<trainer>[\w-]+) host=(?P<on>[\w,-]+) '
        r'attempt=(?P<attempt>\d+) port=(?P<port>\d+)')),
    ('job_requeue', re.compile(
        r'service: job_requeue job=(?P<job>\d+) '
        r'tenant=(?P<tenant>[\w-]+) rc=(?P<rc>-?\d+) '
        r'class=(?P<why>[\w-]+) attempt=(?P<attempt>\d+) '
        r'backoff_s=(?P<backoff_s>[\d.]+)')),
    ('job_done', re.compile(
        r'service: job_done job=(?P<job>\d+) tenant=(?P<tenant>[\w-]+) '
        r'attempts=(?P<attempts>\d+)')),
    ('job_lost', re.compile(
        r'service: job_lost job=(?P<job>\d+) tenant=(?P<tenant>[\w-]+) '
        r'rc=(?P<rc>-?\d+) class=(?P<why>[\w-]+) '
        r'attempts=(?P<attempts>\d+)')),
    ('pool_shrink', re.compile(
        r'service: pool_shrink slots=(?P<from>\d+) -> (?P<to>\d+) '
        r'lost=(?P<lost>\[[^\]]*\])')),
    ('pool_grow', re.compile(
        r'service: pool_grow slots=(?P<from>\d+) -> (?P<to>\d+) '
        r'added=(?P<added>\[[^\]]*\])')),
    # the multi-tenant policy lanes (ISSUE 17): a preemption names its
    # victim and the job it made room for, the landed checkpoint-
    # suspend parks the victim, a resume on different hosts is the
    # migration edge, and the fair-share accounting + autoscale
    # requests narrate WHY — so kfac-obs renders a per-tenant
    # preemption timeline (preempt -> suspend -> migrate -> done)
    # with zero new aggregation code
    ('job_preempt', re.compile(
        r'service: job_preempt job=(?P<job>\d+) '
        r'tenant=(?P<tenant>[\w-]+) victim_of=(?P<victim_of>\d+) '
        r'priority=(?P<priority>-?\d+) '
        r'by_priority=(?P<by_priority>-?\d+) '
        r'grace_s=(?P<grace_s>[\d.]+)')),
    ('job_suspend', re.compile(
        r'service: job_suspend job=(?P<job>\d+) '
        r'tenant=(?P<tenant>[\w-]+) rc=(?P<rc>-?\d+) '
        r'reason=(?P<why>[\w-]+) hosts=(?P<on>[\w,-]+) '
        r'attempt=(?P<attempt>\d+)')),
    ('job_migrate', re.compile(
        r'service: job_migrate job=(?P<job>\d+) '
        r'tenant=(?P<tenant>[\w-]+) from=(?P<from>[\w,-]+) '
        r'to=(?P<to>[\w,-]+) attempt=(?P<attempt>\d+)')),
    ('tenant_share', re.compile(
        r'service: tenant_share tenant=(?P<tenant>[\w-]+) '
        r'used=(?P<used>\d+) of=(?P<of>\d+) '
        r'weight=(?P<weight>[\d.]+) share=(?P<share>[\d.]+)')),
    ('scale_request', re.compile(
        r'service: scale_request desired=(?P<desired>\d+) '
        r'capacity=(?P<capacity>\d+) queued=(?P<queued>\d+) '
        r'suspended=(?P<suspended>\d+)')),
    ('straggler_degrade', re.compile(
        r'straggler: step-time EMA (?P<ema_s>[\d.]+)s over budget '
        r'(?P<budget_s>[\d.]+)s(?: at step (?P<step>\d+))? — stretching '
        r'update freqs to fac=(?P<fac>\d+) kfac=(?P<kfac>\d+) '
        r'\(level (?P<level>\d+)/(?P<max_level>\d+)\)')),
    ('straggler_recover', re.compile(
        r'straggler: recovered \(EMA (?P<ema_s>[\d.]+)s\)')),
    ('preempted', re.compile(
        r'preempted (?:in|after) epoch (?P<epoch>\d+)')),
    ('resumed', re.compile(
        r'(?:RESUMED from=checkpoint-(?P<epoch>\d+) step=(?P<step>\d+)'
        r'|resumed from checkpoint-(?P<epoch2>\d+) \(step '
        r'(?P<step2>\d+)\))')),
    ('resharded', re.compile(
        r'RESHARDED from_world=(?P<from>\d+) to_world=(?P<to>\d+) '
        r'step=(?P<step>\d+)')),
)

#: public name for the event grammar — ``obs.aggregate`` (the pod
#: timeline) reuses exactly these regexes so the two consumers of the
#: log forms can never drift apart.
EVENT_PATTERNS = _PATTERNS

_INT = re.compile(r'^-?\d+$')
_FLOAT = re.compile(r'^-?\d+\.\d+$')


def _coerce(v):
    if isinstance(v, str):
        if _INT.match(v):
            return int(v)
        if _FLOAT.match(v):
            return float(v)
    return v


class IncidentReport:
    """Accumulate events + counters; render JSON and a human summary."""

    def __init__(self, host_id=None):
        self.host_id = host_id
        self.events = []
        self.counters = {}
        self.sources = []

    # -- live recording (the pod supervisor's path) -----------------------

    def add_event(self, kind, **fields):
        evt = {'kind': kind, 'wall': fields.pop('wall', time.time())}
        evt.update(fields)
        self.events.append(evt)
        return evt

    def bump(self, counts):
        """Fold a ``[resilience: ...]``-shaped dict into the aggregate
        (MAX for cumulative supervisor counters, SUM for epoch deltas).
        """
        for k, v in counts.items():
            if k in _NON_COUNTERS or not isinstance(v, (int, float)):
                continue
            if k in _CUMULATIVE:
                self.counters[k] = max(self.counters.get(k, 0), v)
            else:
                self.counters[k] = self.counters.get(k, 0) + v

    # -- scraping ---------------------------------------------------------

    def scrape_lines(self, lines, source=None):
        """Scrape runlog ``lines`` for resilience events and counter
        suffixes. Returns self (chainable)."""
        # lazy: utils.runlog sits under the jax-heavy utils package, and
        # incident must stay importable from the lightweight supervisor
        from kfac_pytorch_tpu.utils.runlog import parse_resilience_suffix
        if source is not None:
            self.sources.append(str(source))
        for line in lines:
            counts = parse_resilience_suffix(line)
            if counts:
                self.bump(counts)
            for kind, pat in _PATTERNS:
                m = pat.search(line)
                if not m:
                    continue
                fields = {k: _coerce(v) for k, v in
                          m.groupdict().items() if v is not None}
                # the two 'resumed' spellings share one event shape
                for alias, canon in (('epoch2', 'epoch'), ('step2', 'step')):
                    if alias in fields:
                        fields[canon] = fields.pop(alias)
                if source is not None:
                    fields['source'] = str(source)
                self.add_event(kind, wall=None, **fields)
        return self

    def scrape_path(self, path):
        if str(path).endswith('.jsonl'):
            return self.scrape_trace(path)
        with open(path, errors='replace') as f:
            return self.scrape_lines(f, source=path)

    def scrape_trace(self, path):
        """Scrape an ``obs.trace`` JSONL file: every resilience-category
        instant becomes an event (same kinds the modules log — the trace
        stream is the structured twin of the log lines, with wall
        timestamps the log scrape lacks). Malformed lines are skipped:
        a ring buffer cut off mid-write must still report."""
        self.sources.append(str(path))
        with open(path, errors='replace') as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    evt = json.loads(line)
                except ValueError:
                    continue
                if evt.get('ph') != 'i' or evt.get('cat') != 'resilience':
                    continue
                fields = dict(evt.get('args') or {})
                fields['source'] = str(path)
                ts = evt.get('ts')
                wall = (ts / 1e6 if isinstance(ts, (int, float)) and ts > 0
                        else None)
                self.add_event(evt.get('name', 'event'), wall=wall,
                               **fields)
        return self

    # -- rendering --------------------------------------------------------

    def to_dict(self):
        deaths = [e for e in self.events if e['kind'] == 'peer_dead']
        restarts = [e for e in self.events if e['kind'] in
                    ('restart', 'relaunch')]
        shrinks = [e for e in self.events if e['kind'] == 'shrink']
        grows = [e for e in self.events if e['kind'] == 'grow']
        degrades = [e for e in self.events if e['kind'] ==
                    'straggler_degrade']
        steps_lost = sum(e.get('steps_lost', 0) for e in self.events
                         if isinstance(e.get('steps_lost'), int))
        return {
            'host_id': self.host_id,
            'sources': self.sources,
            'what_died': [{'peer': e.get('peer'),
                           'detect_s': e.get('detect_s'),
                           'wall': e.get('wall')} for e in deaths],
            'restarts_taken': max(len(restarts),
                                  self.counters.get('restarts', 0)),
            'shrinks': [{'from': e.get('from'), 'to': e.get('to'),
                         'survivors': e.get('survivors'),
                         'gen': e.get('gen')} for e in shrinks],
            'grows': [{'from': e.get('from'), 'to': e.get('to'),
                       'members': e.get('members'),
                       'joiners': e.get('joiners'),
                       'gen': e.get('gen')} for e in grows],
            'degrade_windows': len(degrades),
            'steps_lost': steps_lost or None,
            'gave_up': bool(self.counters.get('gave_up')
                            or any(e['kind'] == 'gave_up'
                                   for e in self.events)),
            'fenced': bool(self.counters.get('fenced')
                           or any(e['kind'] == 'fenced'
                                  for e in self.events)),
            'counters': dict(sorted(self.counters.items())),
            'events': self.events,
        }

    def summary(self):
        d = self.to_dict()
        lines = ['incident report'
                 + (f' (host {self.host_id})' if self.host_id is not None
                    else '')
                 + (f' — {len(self.sources)} log(s)' if self.sources
                    else '')]
        if not self.events and not self.counters:
            lines.append('  clean run: no resilience events recorded')
            return '\n'.join(lines)
        for e in d['what_died']:
            lines.append(f"  peer {e['peer']} died — detected in "
                         f"{e['detect_s']}s")
        if d['restarts_taken']:
            lines.append(f"  restarts taken: {d['restarts_taken']}")
        for s in d['shrinks']:
            lines.append(f"  pod shrank {s['from']} -> {s['to']} hosts "
                         f"(gen {s['gen']}, survivors {s['survivors']})")
        for g in d['grows']:
            lines.append(f"  pod grew {g['from']} -> {g['to']} hosts "
                         f"(gen {g['gen']}, joiners {g['joiners']})")
        if d['degrade_windows']:
            lines.append(f"  straggler degrade windows: "
                         f"{d['degrade_windows']}")
        if d['steps_lost']:
            lines.append(f"  steps lost to restarts: {d['steps_lost']}")
        if d['fenced']:
            lines.append('  HOST FENCED (rc 117) — quorum lost or '
                         'uncorroborated shrink; rejoin via --join')
        if d['gave_up']:
            lines.append('  SUPERVISOR GAVE UP — run did not complete')
        if d['counters']:
            body = ' '.join(f'{k}={v}' for k, v in d['counters'].items())
            lines.append(f'  counters: {body}')
        return '\n'.join(lines)

    def write(self, path):
        """Atomic JSON dump (tmp + rename — the report must never be a
        torn artifact, it is what gets read AFTER things went wrong)."""
        from kfac_pytorch_tpu.resilience import atomic_write_json
        return atomic_write_json(path, self.to_dict(), indent=2,
                                 default=str)


def scrape_paths(paths, host_id=None):
    report = IncidentReport(host_id=host_id)
    for p in paths:
        report.scrape_path(p)
    return report


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m kfac_pytorch_tpu.resilience.incident',
        description='Scrape run logs into a structured incident report '
                    '(JSON + human summary).')
    p.add_argument('logs', nargs='+', help='run log file(s) to scrape')
    p.add_argument('-o', '--out', default=None,
                   help='write the JSON report here (default: stdout '
                        'summary only)')
    args = p.parse_args(argv)
    report = scrape_paths(args.logs)
    print(report.summary())
    if args.out:
        report.write(args.out)
        print(f'wrote {args.out}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
