"""The deterministic fleet simulator (see package docstring).

Design rules that keep the sweep honest AND byte-reproducible:

- **Real protocol code in the loop.** Shrink barriers run
  ``PodSupervisor._shrink`` (the quorum gate, lineage bump, claim
  scrub); death detection runs ``PeerHeartbeat.poll_once`` over
  ``BackendLeaseTransport`` watches; the job lane runs the real
  ``JobQueue`` epoch-CAS transitions under the real
  ``AdmissionController.step``; every key goes through a real
  :class:`ReplicatedKvBackend` quorum over three real
  :class:`TcpKvServer` stores. The sim only *drives* — it never
  re-implements a protocol decision.
- **One ManualClock.** Every seam that tells time (supervisor pacing,
  heartbeat deadlines, lease TTLs via the servers' ``wall``, queue
  ``not_before`` backoffs) is injected with the same simulated clock,
  so a 10,000-host hour runs in wall seconds and two runs with one
  seed see identical timelines.
- **All randomness is planned up front** from ``random.Random(seed)``
  before the event loop starts, and per-actor jitter streams are
  seeded per (seed, pod, host). Nothing in the trace depends on wall
  time, pids, ports or CAS nonces.
- **The trace records semantic events only** (kills, detections,
  commits, fences, replica faults, job transitions) stamped with sim
  time — never revisions, sockets or wall clocks — which is what makes
  ``same seed -> identical JSONL`` a testable contract.

Two coordination lanes share the three replica stores:

- the *pod lane* reaches them in-process (:class:`_LocalKvBackend`,
  ``server.op`` with a JSON round-trip for wire fidelity) so 1,000+
  hosts of heartbeat/barrier traffic cost microseconds per op;
- the *service lane* is built by the production ``backend_from_env``
  (``KFAC_COORD_BACKEND=replicated`` + ``KFAC_COORD_ADDRS``) and
  speaks real TCP to the same stores — the scheduler's quorum stack is
  exactly the one a deployment gets.

A replica outage marks the in-process endpoint down AND closes the
TCP listener; a restore brings up an EMPTY store on the same port, so
surviving traffic must prove both quorum absorption (zero
``coord_lost``) and read-through repair (the restarted replica is
caught back up).
"""

import dataclasses
import functools
import heapq
import json
import logging
import os
import random
import threading

from kfac_pytorch_tpu.coord import (
    CoordGiveUp, CoordTimeout, ReplicatedKvBackend, RetryingBackend,
    TcpKvBackend, TcpKvServer)
from kfac_pytorch_tpu.resilience.chaos_net import (
    NetFaultConfig, PartitionWindow)
from kfac_pytorch_tpu.resilience.elastic import (
    RC_SUSPENDED, PodSupervisor)
from kfac_pytorch_tpu.resilience.heartbeat import (
    BackendLeaseTransport, PeerHeartbeat)
from kfac_pytorch_tpu.resilience.retry import ManualClock, RetryPolicy
from kfac_pytorch_tpu.service import AdmissionController

#: sim wall epoch: the servers' TTL sweeps and the queue's submit
#: stamps ride ``WALL0 + clock.now`` — an arbitrary fixed origin, so
#: wall-shaped values are simulated too (never ``time.time()``)
WALL0 = 1_700_000_000.0

#: sim seconds per simulated training step: the simulator's step length,
#: from which job durations are laid out — not a timing of anything
ITER_S = 0.1311


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """One fleet sweep. Defaults are the CI profile: 1,000 hosts,
    every fault family armed, seconds of wall time."""
    hosts: int = 1000
    pod_size: int = 8
    seed: int = 0
    kill_pods: int = 12             # pods that lose one host (SIGKILL)
    partition_pods: int = 4         # pods split minority | majority
    jobs: int = 10
    fail_jobs: int = 3              # jobs that die once (rc 115) first
    hb_interval: float = 2.0        # sim seconds between hb rounds
    hb_deadline: float = 5.0
    hb_grace: float = 10.0
    service_period: float = 1.0     # sim seconds between ctrl.step()s
    #: service-lane capacity pool (the scheduler's hosts.json):
    #: ``service_hosts`` controller-exec hosts of ``service_slots``
    #: slots each — small next to ``hosts`` because the POD lane is
    #: where the fleet scale lives; this pool is the POLICY surface
    service_hosts: int = 2
    service_slots: int = 4
    #: multi-tenant policy drills (ISSUE 17). ``preempt_jobs`` late
    #: high-priority non-preemptible jobs, each wide enough that the
    #: scheduler must checkpoint-suspend victims; ``autoscale`` arms
    #: the sim's capacity responder (reads ``scale-request.json``,
    #: rewrites ``hosts.json``); ``drain_at`` > 0 marks the last
    #: service host draining at that sim time (zero-loss drain drill).
    preempt_jobs: int = 0
    autoscale: bool = False
    autoscale_period: float = 2.0
    drain_at: float = 0.0
    suspend_latency: float = 0.4    # request -> RC_SUSPENDED exits
    suspend_grace: float = 8.0      # scheduler SIGKILL escalation
    #: replica outages: (replica index, down at, back at) in sim
    #: seconds. Non-overlapping by construction — one replica down is
    #: the absorb drill; overlapping windows would be the loud
    #: RC_COORD_LOST drill, which the unit suite owns.
    replica_outages: tuple = ((1, 6.0, 22.0), (2, 24.0, 30.0))
    max_sim_seconds: float = 600.0


class EventLoop:
    """Discrete-event loop over a shared :class:`ManualClock`.

    Events fire in (time, insertion) order; firing an event advances
    the clock to its timestamp (never backwards — protocol code that
    sleeps on the shared clock mid-event, e.g. a barrier settle, moves
    time forward and later events simply fire 'late', exactly like a
    busy host)."""

    def __init__(self, clock):
        self.clock = clock
        self._heap = []
        self._seq = 0

    def at(self, when, fn):
        heapq.heappush(self._heap, (float(when), self._seq, fn))
        self._seq += 1

    def after(self, delay, fn):
        self.at(self.clock.now + float(delay), fn)

    def run(self, deadline):
        """Drain the heap; returns False if ``deadline`` cut it short
        (a stuck recurring event — the runaway guard, not a mode)."""
        while self._heap:
            when, _, fn = heapq.heappop(self._heap)
            if when > deadline:
                return False
            if when > self.clock.now:
                self.clock.now = float(when)
            fn()
        return True


class SimProcess:
    """Popen-shaped stand-in the scheduler reaps: ``poll``/``wait``
    report the rc the event loop (or a kill) assigned."""

    def __init__(self, pid):
        self.pid = int(pid)
        self._rc = None

    def poll(self):
        return self._rc

    def wait(self, timeout=None):
        return self._rc

    def finish(self, rc):
        if self._rc is None:
            self._rc = int(rc)

    def kill(self):
        self.finish(-9)


class _LocalKvBackend(TcpKvBackend):
    """In-process replica endpoint: the server's ``op`` dict protocol
    with a JSON round-trip both ways (wire fidelity — no shared
    mutable values), no socket. A replica marked down raises
    :class:`CoordTimeout` exactly like a refused connection; the
    server object is resolved through the fleet PER CALL so a restore
    (new empty store, same index) is picked up transparently."""

    def __init__(self, fleet, idx, namespace):
        super().__init__((f'sim-kv{idx}', 0), namespace)
        self._fleet = fleet
        self._idx = idx

    def _request(self, req):
        if self._fleet.replica_down[self._idx]:
            raise CoordTimeout(f'sim: replica kv{self._idx} is down')
        server = self._fleet.servers[self._idx]
        resp = json.loads(json.dumps(
            server.op(json.loads(json.dumps(req)))))
        if not resp.get('ok'):
            raise CoordTimeout(f'coord kv error: {resp.get("error")}')
        return resp


class _Pod:
    """One simulated pod: its coordination namespace, live member
    set, heartbeat actors and (lazily built) supervisors."""

    def __init__(self, fleet, idx):
        self.idx = idx
        self.lease_dir = os.path.join(fleet.root, 'pods',
                                      f'pod{idx:04d}', 'lease')
        self.merged = ReplicatedKvBackend(
            [_LocalKvBackend(fleet, i, self.lease_dir)
             for i in range(len(fleet.servers))],
            names=[f'kv{i}' for i in range(len(fleet.servers))],
            clock=fleet.clock.monotonic, log=fleet.log)
        self.coord = RetryingBackend(
            self.merged,
            policy=RetryPolicy(attempts=4, base_delay=0.05,
                               max_delay=0.4,
                               retry_on=(CoordTimeout,)),
            clock=fleet.clock,
            rng=random.Random(fleet.cfg.seed * 1_000_003 + idx),
            log=fleet.log)
        self.live = list(range(fleet.cfg.pod_size))
        self.gen = 0
        self.lineages = [0]           # observed committed epochs
        self.hbs = {}                 # host -> PeerHeartbeat actor
        self.sups = {}                # witness host -> PodSupervisor
        self.barrier_pending = False


class FleetSim:
    """Build with a :class:`SimConfig` and a scratch ``root`` dir,
    :meth:`run` once; the returned trace is the artifact."""

    def __init__(self, cfg, root):
        self.cfg = cfg
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self.log = logging.getLogger('kfac_pytorch_tpu.sim')
        if not self.log.handlers:
            # quiet by default: the TRACE is the output. A CLI that
            # wants the raw protocol chatter attaches its own handler.
            self.log.addHandler(logging.NullHandler())
            self.log.propagate = False
        self.clock = ManualClock()
        self.loop = EventLoop(self.clock)
        self.trace = []
        self.replica_down = [False, False, False]
        self._replica_port = {}
        self.servers = [TcpKvServer('127.0.0.1', 0, wall=self.wall)
                        for _ in range(3)]
        self._pid_ctr = 100_000
        self._launches = {}           # queue id -> launch count
        self._procs = {}              # queue id -> live SimProcess
        self._job_seen = {}           # queue id -> (state, requeues,
        #                               attempt)
        self._suspend_driven = set()  # (queue id, attempt) already acting
        # the queue assigns ids in INGEST order, which diverges from
        # the plan's ids once a late preemptor submits between base
        # jobs: map spool origin -> plan id so the trace (and the plan
        # lookup driving durations/fail_rc) speaks ONE id space
        self._origin_plan = {}        # spool name -> plan id
        self._qid_plan = {}           # queue id -> plan id
        self._jobs_done = False
        self.kill_barriers_pending = 0
        self._plan()
        n_pods = cfg.hosts // cfg.pod_size
        self.pods = [_Pod(self, i) for i in range(n_pods)]
        for pod in self.pods:
            for h in range(cfg.pod_size):
                self._add_actor(pod, h)
        self._make_controller()

    # -- time --------------------------------------------------------------

    def wall(self):
        return WALL0 + self.clock.now

    def _trace(self, kind, **fields):
        ev = {'t': round(self.clock.now, 3), 'kind': kind}
        ev.update(fields)
        self.trace.append(ev)

    # -- the seeded fault + workload plan ----------------------------------

    def _plan(self):
        cfg = self.cfg
        rng = random.Random(cfg.seed)
        n_pods = cfg.hosts // cfg.pod_size
        if n_pods < cfg.kill_pods + cfg.partition_pods:
            raise ValueError(
                f'{n_pods} pods cannot host {cfg.kill_pods} kills + '
                f'{cfg.partition_pods} partitions')
        chosen = rng.sample(range(n_pods),
                            cfg.kill_pods + cfg.partition_pods)
        self.pod_plan = {}
        # half the kills land INSIDE the first replica outage window
        # (quorum shrink during replica failover — the acceptance
        # property), half after every replica is back
        for j, pod in enumerate(chosen[:cfg.kill_pods]):
            when = (round(rng.uniform(7.0, 12.0), 3) if j % 2 == 0
                    else round(rng.uniform(31.0, 35.0), 3))
            self.pod_plan[pod] = {'kill': when,
                                  'victim': rng.randrange(cfg.pod_size)}
        for pod in chosen[cfg.kill_pods:]:
            minority = sorted(rng.sample(range(cfg.pod_size),
                                         max(1, cfg.pod_size // 2 - 1)))
            self.pod_plan[pod] = {
                'partition': round(rng.uniform(8.0, 16.0), 3),
                'minority': minority,
                'first': rng.choice(['minority', 'majority'])}
        # unequal tenant weights make the fair-share property visible:
        # with mixed demand the scheduler's weighted-dominant-share
        # ordering must converge usage toward 1:2:4, and no nonzero-
        # weight tenant may starve (the sweep test pins both)
        self.tenant_weights = {'tenant0': 1.0, 'tenant1': 2.0,
                               'tenant2': 4.0}
        self.job_plan = {}
        for j in range(1, cfg.jobs + 1):
            steps = rng.randrange(30, 90)
            self.job_plan[j] = {
                'submit': round(0.5 + 0.8 * (j - 1), 3),
                'steps': steps,
                'duration': round(steps * ITER_S, 3),
                'fail_rc': 115 if j <= cfg.fail_jobs else 0}
        # the preemption drill: late, wide, high-priority and NOT
        # preemptible — the pool is already packed when these land, so
        # the scheduler must checkpoint-suspend victims to place them
        for k in range(1, cfg.preempt_jobs + 1):
            jid = cfg.jobs + k
            steps = rng.randrange(20, 40)
            self.job_plan[jid] = {
                'submit': round(1.8 + 0.9 * (k - 1), 3),
                'steps': steps,
                'duration': round(steps * ITER_S, 3),
                'fail_rc': 0, 'priority': 10,
                # full-pool width: placing it REQUIRES suspending
                # every running preemptible job
                'hosts': cfg.service_hosts * cfg.service_slots,
                'preemptible': False}

    # -- pod lane: heartbeat actors + barriers -----------------------------

    def _add_actor(self, pod, host):
        transport = BackendLeaseTransport(pod.merged, host, prefix='sup')
        pod.hbs[host] = PeerHeartbeat(
            transport, host,
            peers=[p for p in pod.live if p != host],
            interval=self.cfg.hb_interval,
            deadline=self.cfg.hb_deadline,
            startup_grace=self.cfg.hb_grace,
            on_dead=functools.partial(self._on_peer_dead, pod, host),
            gen=pod.gen, clock=self.clock.monotonic, log=self.log)

    def _hb_round(self):
        for pod in self.pods:
            for host in sorted(pod.hbs):
                hb = pod.hbs.get(host)
                if hb is not None:
                    hb.poll_once()
        if (self.kill_barriers_pending > 0
                and self.clock.now < self.cfg.max_sim_seconds):
            self.loop.after(self.cfg.hb_interval, self._hb_round)

    def _on_peer_dead(self, pod, watcher, peer, info):
        self._trace('peer_dead', pod=pod.idx, watcher=watcher,
                    peer=peer, detect_s=info.get('detect_s'))
        plan = self.pod_plan.get(pod.idx) or {}
        victim = plan.get('victim')
        if (victim is None or peer != victim or pod.barrier_pending
                or pod.gen > 0):
            return
        # every survivor detects; the LOWEST live one drives the sim's
        # single real barrier (its peers' symmetric claims are injected
        # at barrier time, the _kv_sup test idiom)
        if watcher != min(h for h in pod.live if h != victim):
            return
        pod.barrier_pending = True
        self.loop.after(0.25,
                        functools.partial(self._run_shrink, pod,
                                          frozenset([victim])))

    def _sup(self, pod, witness, net=None):
        if witness not in pod.sups:
            pod.sups[witness] = PodSupervisor(
                ['sim-trainer'], host_id=witness,
                num_hosts=self.cfg.pod_size, lease_dir=pod.lease_dir,
                coord=pod.coord, settle=0.0, shrink_timeout=3.0,
                poll_period=0.05, hb_interval=self.cfg.hb_interval,
                hb_deadline=self.cfg.hb_deadline,
                hb_grace=self.cfg.hb_grace, clock=self.clock,
                rng=random.Random(self.cfg.seed * 7_919
                                  + pod.idx * 64 + witness),
                net_chaos=net, log=self.log)
        return pod.sups[witness]

    def _barrier(self, pod, witness, side, dead, net=None):
        """Claims for ``side``'s other members, then the REAL survivor
        barrier from ``witness``. Returns (sup, committed)."""
        sup = self._sup(pod, witness, net=net)
        gen1 = pod.gen + 1
        for h in side:
            if h != witness:
                pod.merged.put(
                    f'shrink-gen{gen1}/survivor-{h}.json',
                    {'host': h, 'addr': None, 'wall': self.wall()})
        try:
            committed = sup._shrink({d: {} for d in sorted(dead)})
        finally:
            if sup._hb is not None:
                sup._hb.stop()
        return sup, committed

    def _commit(self, pod, sup):
        pod.live = list(sup.members)
        pod.gen = sup.gen
        lineage = sup._current_lineage()
        pod.lineages.append(lineage)
        self._trace('shrink_commit', pod=pod.idx, gen=pod.gen,
                    survivors=list(sup.members), lineage=lineage)

    def _rebase_pod(self, pod):
        """Post-barrier actor bookkeeping: dead/fenced hosts' monitors
        exit; survivors rebase to the committed generation (the same
        rebase the supervisor applies to its own monitor)."""
        for host in list(pod.hbs):
            if host not in pod.live:
                pod.hbs.pop(host)
                continue
            pod.hbs[host].rebase(
                peers=[p for p in pod.live if p != host], gen=pod.gen)

    def _run_shrink(self, pod, dead):
        side = [h for h in pod.live if h not in dead]
        witness = min(side)
        try:
            sup, committed = self._barrier(pod, witness, side, dead)
        except CoordGiveUp as e:
            self._trace('coord_lost', pod=pod.idx, detail=str(e))
            self.kill_barriers_pending -= 1
            return
        if committed:
            self._commit(pod, sup)
            self._rebase_pod(pod)
        else:
            self._trace('fenced', pod=pod.idx, host=witness,
                        gen=sup.gen + 1)
        self.kill_barriers_pending -= 1

    def _run_partition(self, pod, minority, first):
        members = list(pod.live)
        majority = [h for h in members if h not in minority]
        self._trace('partition', pod=pod.idx, minority=list(minority),
                    majority=majority, first=first)
        net = NetFaultConfig(windows=(
            PartitionWindow(0.0, 1e18, (frozenset(minority),
                                        frozenset(majority))),))
        sides = [(minority, majority), (majority, minority)]
        if first == 'majority':
            sides.reverse()
        for side, other in sides:
            witness = min(side)
            try:
                sup, committed = self._barrier(pod, witness, list(side),
                                               set(other), net=net)
            except CoordGiveUp as e:
                self._trace('coord_lost', pod=pod.idx, detail=str(e))
                return
            if committed:
                self._commit(pod, sup)
            else:
                self._trace('fenced', pod=pod.idx, host=witness,
                            gen=sup.gen + 1)
        self._rebase_pod(pod)

    def _kill_host(self, pod, victim):
        self._trace('host_kill', pod=pod.idx, host=victim)
        pod.hbs.pop(victim, None)   # the process is gone: no more beats

    # -- replica faults ----------------------------------------------------

    def _kill_replica(self, idx):
        self.replica_down[idx] = True
        srv = self.servers[idx]
        self._replica_port[idx] = srv.port
        srv.close()
        self._trace('replica_down', replica=idx)

    def _restore_replica(self, idx):
        # an EMPTY store on the old port: everything it knew is gone,
        # read-through repair must rebuild it from the quorum
        self.servers[idx] = TcpKvServer(
            '127.0.0.1', self._replica_port[idx], wall=self.wall)
        self.replica_down[idx] = False
        self._trace('replica_up', replica=idx)

    # -- service lane ------------------------------------------------------

    def _make_controller(self):
        self.service_dir = os.path.join(self.root, 'service')
        overlay = {
            'KFAC_COORD_BACKEND': 'replicated',
            'KFAC_COORD_ADDRS': ','.join(
                f'127.0.0.1:{s.port}' for s in self.servers)}
        saved = {k: os.environ.get(k) for k in overlay}
        os.environ.update(overlay)
        hosts = {f'h{i}': self.cfg.service_slots
                 for i in range(self.cfg.service_hosts)}
        try:
            self.ctrl = AdmissionController(
                self.service_dir, hosts=hosts,
                popen=self._popen, killer=lambda p: p.kill(),
                clock=self.clock, wall=self.wall, backoff_base=1.0,
                backoff_max=4.0, env={}, preempt=True,
                suspend_grace=self.cfg.suspend_grace,
                autoscale=self.cfg.autoscale, log=self.log)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def _next_pid(self):
        self._pid_ctr += 1
        return self._pid_ctr

    def _plan_for(self, qid):
        """queue id -> plan id, resolved once through the record's
        spool ``origin`` (the only stable join between the two id
        spaces); a record the queue cannot read right now falls back
        to the queue id (retried next sighting)."""
        plan_id = self._qid_plan.get(qid)
        if plan_id is None:
            rec = self.ctrl.queue.read(qid)
            origin = (rec or {}).get('origin')
            plan_id = self._origin_plan.get(origin)
            if plan_id is None:
                return qid
            self._qid_plan[qid] = plan_id
        return plan_id

    def _popen(self, argv, env=None, **kw):
        qid = int(str((env or {}).get('KFAC_JOB_ID',
                                      'job-0')).split('-')[-1])
        self._launches[qid] = self._launches.get(qid, 0) + 1
        plan = self.job_plan.get(self._plan_for(qid)) \
            or {'duration': 1.0, 'fail_rc': 0}
        rc = plan['fail_rc'] if self._launches[qid] == 1 else 0
        proc = SimProcess(self._next_pid())
        self._procs[qid] = proc
        self.loop.after(max(plan['duration'], 0.001),
                        functools.partial(proc.finish, rc))
        return proc

    def _submit_job(self, jid):
        plan = self.job_plan[jid]
        tenant = f'tenant{(jid - 1) % 3}'
        name = self.ctrl.queue.submit({
            'tenant': tenant,
            'trainer': 'cifar10_resnet', 'args': [],
            'hosts': plan.get('hosts', 1),
            'priority': plan.get('priority', 0), 'retry_budget': 2,
            'weight': self.tenant_weights[tenant],
            'preemptible': plan.get('preemptible', True)})
        self._origin_plan[name] = jid
        self._trace('job_submit', job=jid, tenant=tenant,
                    priority=plan.get('priority', 0),
                    steps=plan['steps'])

    def _service_step(self):
        try:
            self.ctrl.step()
        except CoordGiveUp as e:
            self._trace('coord_lost', pod=None, detail=str(e))
            return
        self._diff_job_states()
        self._drive_suspends()
        counts = self.ctrl.queue.counts()
        total = sum(counts.values())
        finished = (total >= len(self.job_plan)
                    and counts.get('done', 0) + counts.get('lost', 0)
                    >= len(self.job_plan))
        if finished:
            self._jobs_done = True
        elif self.clock.now < self.cfg.max_sim_seconds:
            self.loop.after(self.cfg.service_period, self._service_step)

    def _diff_job_states(self):
        for rec in self.ctrl.queue.jobs():
            qid = rec.get('id')
            now = (rec.get('state'), rec.get('requeues', 0),
                   rec.get('attempt', 0))
            before = self._job_seen.get(qid)
            if now == before:
                continue
            self._job_seen[qid] = now
            jid = self._plan_for(qid)    # trace in the plan's id space
            state, requeues, attempt = now
            if state == 'running':
                run = self.ctrl.running.get(qid)
                hosts = ','.join(run.hosts()) if run is not None else ''
                if (rec.get('last_reason') == 'resume'
                        and before is not None and before[0] == 'running'
                        and attempt > before[2]):
                    # the park + resume + re-admit completed inside ONE
                    # scheduler cycle (capacity was already free, e.g.
                    # autoscale had grown the pool): the SUSPENDED state
                    # was never observable between diffs, so surface the
                    # suspend edge from the record's history — the trace
                    # must still tell the whole story
                    susp = next((h for h in
                                 reversed(rec.get('history', []))
                                 if h.get('to') == 'suspended'), {})
                    self._trace('job_suspend', job=jid,
                                rc=susp.get('last_rc'),
                                reason=susp.get('last_reason'))
                self._trace('job_admit', job=jid,
                            attempt=attempt,
                            hosts=hosts)
                # a resumed suspension on different hosts IS the
                # migration (the scheduler logs the same edge)
                prev = rec.get('last_hosts')
                if (rec.get('last_reason') == 'resume' and prev
                        and hosts and prev != hosts):
                    self._trace('job_migrate', job=jid, src=prev,
                                dst=hosts)
            elif state == 'suspended':
                self._trace('job_suspend', job=jid,
                            rc=rec.get('last_rc'),
                            reason=rec.get('last_reason'))
            elif state == 'queued' and before is not None \
                    and before[0] == 'suspended':
                # resume normally lands + re-admits inside one cycle
                # (then job_suspend + job_admit show); this edge appears
                # when placement fell through between resume and claim
                self._trace('job_resume', job=jid)
            elif state == 'queued' and before is not None \
                    and requeues > before[1]:
                self._trace('job_requeue', job=jid, requeues=requeues,
                            rc=rec.get('last_rc'))
            elif state == 'done':
                self._trace('job_done', job=jid,
                            requeues=requeues)
            elif state == 'lost':
                self._trace('job_lost', job=jid, requeues=requeues)

    def _drive_suspends(self):
        """The pod side of a checkpoint-suspend, simulated: once the
        scheduler has requested a suspend (``run.suspend`` armed, the
        ``suspend.json`` key written into the job's lease namespace),
        every rank of that attempt exits :data:`RC_SUSPENDED` after
        ``suspend_latency`` sim seconds — the time a real
        PodSupervisor takes to stop its trainer at a checkpoint
        boundary. The scheduler's reap then runs the REAL suspended
        verdict (epoch-CAS park, port release, adopted-knobs carry)."""
        for jid in sorted(self.ctrl.running):
            run = self.ctrl.running[jid]
            if run.suspend is None:
                continue
            key = (jid, run.record.get('attempt', 0))
            if key in self._suspend_driven:
                continue
            self._suspend_driven.add(key)
            self._trace('pod_suspend', job=self._plan_for(jid),
                        reason=run.suspend.get('reason'))
            procs = list(run.procs.values())

            def _land(procs=procs):
                for p in procs:
                    p.finish(RC_SUSPENDED)
            self.loop.after(self.cfg.suspend_latency, _land)

    # -- capacity responder + drain (the operator side) --------------------

    def _autoscale_step(self):
        """The external capacity responder the scheduler's
        ``scale_request`` lane is written for: read the latest
        ``scale-request.json``, grow the pool with ``aN`` hosts until
        capacity covers the desired slots, shrink by removing IDLE
        ``aN`` hosts when demand falls — all through the same quorum
        backend ``hosts.json`` rides on, so the scheduler adopts the
        answer via its ordinary capacity refresh."""
        try:
            self._autoscale_respond()
        except CoordGiveUp as e:
            self._trace('coord_lost', pod=None, detail=str(e))
            return
        if (not self._jobs_done
                and self.clock.now < self.cfg.max_sim_seconds):
            self.loop.after(self.cfg.autoscale_period,
                            self._autoscale_step)

    def _autoscale_respond(self):
        got = self.ctrl.coord.get('scale-request.json')
        doc = None if got is None else got.value
        if not isinstance(doc, dict):
            return
        desired = int(doc.get('desired_slots', 0))
        got = self.ctrl.coord.get('hosts.json')
        hosts_doc = None if got is None else got.value
        if not (isinstance(hosts_doc, dict)
                and isinstance(hosts_doc.get('hosts'), dict)):
            return
        raw = dict(hosts_doc['hosts'])
        unit = self.cfg.service_slots

        def _slots(e):
            return e.get('slots', 0) if isinstance(e, dict) else e

        cap = sum(_slots(e) for e in raw.values()
                  if not (isinstance(e, dict) and e.get('draining')))
        if desired > cap:
            i, grown = 0, 0
            while cap < desired and grown < 64:
                name = f'a{i}'
                i += 1
                if name in raw:
                    continue
                raw[name] = unit
                cap += unit
                grown += 1
            if grown:
                self.ctrl.coord.put('hosts.json', {'hosts': raw},
                                    indent=2)
                self._trace('autoscale', action='grow',
                            desired=desired, capacity=cap)
        elif desired < cap:
            busy = set()
            for run in self.ctrl.running.values():
                busy.update(run.hosts())
            shrunk = 0
            for name in sorted((n for n in raw
                                if n.startswith('a')), reverse=True):
                if cap - unit < desired or name in busy:
                    continue
                del raw[name]
                cap -= unit
                shrunk += 1
            if shrunk:
                self.ctrl.coord.put('hosts.json', {'hosts': raw},
                                    indent=2)
                self._trace('autoscale', action='shrink',
                            desired=desired, capacity=cap)

    def _drain_host(self, name):
        """Mark one service host draining in ``hosts.json`` (the
        operator's zero-loss drain gesture): the scheduler stops
        placing on it and checkpoint-suspends its preemptible jobs
        off; they resume — migrate — onto the remaining pool."""
        try:
            got = self.ctrl.coord.get('hosts.json')
            doc = None if got is None else got.value
            if not (isinstance(doc, dict)
                    and isinstance(doc.get('hosts'), dict)):
                return
            raw = dict(doc['hosts'])
            entry = raw.get(name)
            if entry is None:
                return
            slots = entry.get('slots') if isinstance(entry, dict) \
                else entry
            raw[name] = {'slots': slots, 'draining': True}
            self.ctrl.coord.put('hosts.json', {'hosts': raw}, indent=2)
        except CoordGiveUp as e:
            self._trace('coord_lost', pod=None, detail=str(e))
            return
        self._trace('host_drain', host=name)

    # -- run ---------------------------------------------------------------

    def run(self):
        cfg = self.cfg
        # planned draws only below this line: the global random module
        # is reseeded purely to pin incidental library draws (spool
        # name suffixes) that never reach the trace anyway
        random.seed(cfg.seed)
        self._trace('sim_start', hosts=cfg.hosts,
                    pods=len(self.pods), pod_size=cfg.pod_size,
                    seed=cfg.seed, iter_s=ITER_S)
        for idx, t0, t1 in cfg.replica_outages:
            self.loop.at(t0, functools.partial(self._kill_replica, idx))
            self.loop.at(t1, functools.partial(self._restore_replica,
                                               idx))
        for pod_idx in sorted(self.pod_plan):
            plan = self.pod_plan[pod_idx]
            pod = self.pods[pod_idx]
            if 'kill' in plan:
                self.kill_barriers_pending += 1
                self.loop.at(plan['kill'],
                             functools.partial(self._kill_host, pod,
                                               plan['victim']))
            else:
                self.loop.at(plan['partition'],
                             functools.partial(self._run_partition, pod,
                                               plan['minority'],
                                               plan['first']))
        for jid in sorted(self.job_plan):
            self.loop.at(self.job_plan[jid]['submit'],
                         functools.partial(self._submit_job, jid))
        self.loop.at(1.0, self._hb_round)
        self.loop.at(0.6, self._service_step)
        if cfg.autoscale:
            self.loop.at(1.4, self._autoscale_step)
        if cfg.drain_at > 0:
            self.loop.at(cfg.drain_at, functools.partial(
                self._drain_host, f'h{cfg.service_hosts - 1}'))
        drained = self.loop.run(cfg.max_sim_seconds)
        repaired = sum(p.merged.counts.get('replica_repair', 0)
                       for p in self.pods)
        degraded = sum(p.merged.counts.get('quorum_degraded', 0)
                       for p in self.pods)
        kinds = [e['kind'] for e in self.trace]
        self._trace(
            'sim_end', drained=bool(drained),
            commits=kinds.count('shrink_commit'),
            fenced=kinds.count('fenced'),
            jobs_done=kinds.count('job_done'),
            jobs_requeued=kinds.count('job_requeue'),
            jobs_finished=bool(self._jobs_done),
            jobs_suspended=kinds.count('job_suspend'),
            jobs_migrated=kinds.count('job_migrate'),
            autoscaled=kinds.count('autoscale'),
            repaired=bool(repaired), degraded=bool(degraded),
            coord_lost=kinds.count('coord_lost'))
        return self.trace

    def close(self):
        for pod in self.pods:
            for sup in pod.sups.values():
                if sup._hb is not None:
                    sup._hb.stop()
        for srv in self.servers:
            srv.close()


def run_fleet_sim(cfg, root):
    """Build, run, tear down; returns the trace."""
    sim = FleetSim(cfg, root)
    try:
        return sim.run()
    finally:
        sim.close()


def write_trace(trace, path):
    """Canonical JSONL: one event per line, sorted keys — the
    determinism contract is byte-equality of this file."""
    with open(path, 'w') as f:
        for ev in trace:
            f.write(json.dumps(ev, sort_keys=True) + '\n')
    return path


# the threading import is load-bearing for subclasses constructing
# TcpKvBackend state; keep linters honest
_ = threading
