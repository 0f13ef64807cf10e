"""Central registry of every ``KFAC_*`` / ``JAX_*`` environment knob.

One declaration per variable — name, kind, consumer, one-line doc,
optional value choices/default — as **pure literal data**, because four
consumers read this registry and one of them never imports it:

- ``faults.from_env`` STRICT validation derives its known-set from
  :func:`declared` (a typo'd ``KFAC_FAULT_*`` drill var fails at build
  time);
- ``launch_tpu.sh`` runs ``python kfac_pytorch_tpu/envspec.py
  --validate`` before launching (an undeclared or malformed ``KFAC_*``
  export kills the launch, not the night);
- the README "Environment contract" table is generated by ``python
  kfac_pytorch_tpu/envspec.py --table`` (a test pins README == table);
- ``kfac-lint``'s ``env-contract`` rule reads the declarations
  *statically* (stdlib ``ast``, zero imports) and flags any
  env-name-shaped literal in the tree that is not declared here.

Keep this module stdlib-pure and runnable as a bare file (no package
imports): that is what lets the lint CI job and launch_tpu.sh use it
on a Python with no jax installed.
"""

import dataclasses
import os
import sys
from typing import Optional, Tuple

#: declaration kinds — documentation + what --validate checks
#:   flag     '0'/'1'
#:   int      base-10 integer
#:   float    decimal number
#:   steps    fault step spec: '7', '3,5,9', '4:8'
#:   choice   one of ``choices``
#:   str      free-form (paths, addresses, specs validated downstream)
KINDS = ('flag', 'int', 'float', 'steps', 'choice', 'str')


@dataclasses.dataclass(frozen=True)
class EnvVar:
    name: str
    kind: str
    consumer: str        # module (or tool) that reads it
    doc: str
    choices: Tuple[str, ...] = ()
    default: Optional[str] = None


def E(name, kind, consumer, doc, choices=(), default=None):
    assert kind in KINDS, (name, kind)
    return EnvVar(name, kind, consumer, doc, tuple(choices), default)


ENV: Tuple[EnvVar, ...] = (
    # -- trainer/preconditioner knobs (launch_tpu.sh exports these) ----
    E('KFAC_AUTOTUNE', 'flag', 'autotune.py',
      'enable the closed-loop knob controller in every trainer '
      '(`--kfac-autotune`)'),
    E('KFAC_CAPTURE_IMPL', 'choice', 'preconditioner.py',
      'capture-kernel ladder rung: unfused XLA capture vs the fused '
      'Pallas patch-extract+GEMM+epilogue kernels (auto = tuner '
      'decides)', choices=('xla', 'pallas', 'auto')),
    E('KFAC_CAPTURE_MAX_F', 'int', 'ops/pallas_capture.py',
      'factor-dim ceiling above which the fused capture kernels fall '
      'back to the reference path (VMEM accumulator bound)'),
    E('KFAC_CAPTURE_TR', 'int', 'ops/pallas_capture.py',
      'row-tile override of the fused capture kernels (must divide '
      'the row count; perf debugging only)'),
    E('KFAC_COMM_MODE', 'choice', 'preconditioner.py',
      'factor comm road: gather decompositions per refresh vs gather '
      'preconditioned grads per step (live-replan knob)',
      choices=('inverse', 'pred')),
    E('KFAC_COMM_PRECISION', 'choice', 'parallel/collectives.py',
      'wire dtype of the factor collectives (error-feedback residuals '
      'make bf16/int8 safe)', choices=('fp32', 'bf16', 'int8')),
    E('KFAC_DECOMP_IMPL', 'choice', 'preconditioner.py',
      'decomposition kernel ladder rung (auto = tuner decides)',
      choices=('xla', 'auto', 'jacobi', 'subspace', 'newton_schulz')),
    E('KFAC_DECOMP_SHARD', 'flag', 'preconditioner.py',
      'mesh-sharded decomposition: repartition the refresh cohort\'s '
      'rows across all devices'),
    E('KFAC_MESH', 'str', 'examples/longcontext_lm.py',
      "composed-mesh spec in the meshplan grammar ('dp2xsp4', "
      "'dp4xtp2', ...) — the --kfac-mesh default; the axis-aware "
      'mesh plan derives the K-FAC world from its data/sequence axes'),
    E('KFAC_MESH_TP_REDUCE', 'flag', 'meshplan/plan.py',
      'tensor-axis factor reduce on composed meshes (trace-time; '
      '0 = leave slice-replicated stats un-averaged, the A/B lever '
      'for the tp psum)', default='1'),
    E('KFAC_EIGH_IMPL', 'choice', 'ops/linalg.py',
      'eigh kernel (trace-time; auto resolves to subspace)',
      choices=('xla', 'auto', 'jacobi', 'subspace')),
    E('KFAC_JACOBI_ROT', 'choice', 'ops/linalg.py',
      'Jacobi sweep rotation application form',
      choices=('dense', 'paired')),
    E('KFAC_ATTN_IMPL', 'choice', 'parallel/ring_attention.py',
      'attention block kernel (trace-time)',
      choices=('auto', 'xla', 'pallas', 'pallas_interpret')),
    E('KFAC_ATTN_BWD_IMPL', 'choice', 'ops/pallas_attention.py',
      'flash-attention backward kernel (trace-time; auto gates on '
      'block key length)', choices=('auto', 'pallas', 'recompute')),
    # -- platform / mesh ----------------------------------------------
    E('KFAC_TPU_MULTIHOST', 'flag', 'parallel/mesh.py',
      'this process is one host of a real multi-host pod '
      '(launch_tpu.sh sets it; gates jax.distributed.initialize)'),
    E('KFAC_NUM_DEVICES', 'int', 'launch_tpu.sh',
      'forwarded to the trainers as `--num-devices`'),
    E('KFAC_CHIPS_PER_HOST', 'int', 'configs/pod*',
      'chips per host of the mesh preset (tests derive the simulated '
      'device count from it)', default='4'),
    # -- launcher wrapper knobs (launch_tpu.sh only) -------------------
    E('KFAC_SUPERVISE', 'flag', 'launch_tpu.sh',
      'run the trainer under the kfac-supervise restart loop'),
    E('KFAC_POD_SUPERVISE', 'flag', 'launch_tpu.sh',
      'run the trainer under the per-host kfac-pod-supervise elastic '
      'loop'),
    E('KFAC_POD_LEASE_DIR', 'str', 'launch_tpu.sh',
      'shared lease directory of the pod protocols (required with '
      'KFAC_POD_SUPERVISE)'),
    E('KFAC_POD_JOIN', 'flag', 'launch_tpu.sh',
      'announce this repaired host to the incumbent pod (grow lane) '
      'instead of cold-launching'),
    E('KFAC_JOIN_TIMEOUT', 'float', 'launch_tpu.sh',
      'seconds to wait for the pod to admit a joiner before rc=116'),
    E('KFAC_MAX_RESTARTS', 'int', 'launch_tpu.sh',
      'restart budget of the supervise wrappers', default='3'),
    E('KFAC_RESTART_BACKOFF', 'float', 'launch_tpu.sh',
      'exponential backoff base between restarts, seconds',
      default='2'),
    E('KFAC_STOP_RCS', 'str', 'launch_tpu.sh',
      'exit codes (names or numbers) the supervisor propagates '
      'instead of restarting'),
    E('KFAC_HOST_ADDR', 'str', 'launch_tpu.sh',
      'this host\'s address handed to kfac-pod-supervise '
      '(--host-addr)'),
    E('KFAC_HB_WORKERS', 'str', 'launch_tpu.sh',
      'pod worker addresses in rank order ("ip0 ip1 ..."); the '
      'launcher derives KFAC_HB_PEERS from it'),
    # -- resilience / pod protocol (supervisor -> trainer contract) ----
    E('KFAC_POD_GEN', 'int', 'resilience/elastic.py',
      'pod membership generation of this trainer incarnation (bumped '
      'on every committed shrink/grow)'),
    E('KFAC_LINEAGE', 'int', 'resilience/elastic.py',
      'monotonic lineage epoch: elastic_resume refuses checkpoints '
      'stamped newer (fencing forked incarnations)'),
    E('KFAC_HB_DIR', 'str', 'resilience/heartbeat.py',
      'heartbeat lease directory (file transport)'),
    E('KFAC_HB_HOST', 'int', 'resilience/heartbeat.py',
      'this host\'s rank in the heartbeat ring'),
    E('KFAC_HB_HOSTS', 'int', 'resilience/heartbeat.py',
      'number of hosts publishing heartbeats'),
    E('KFAC_HB_INTERVAL', 'float', 'resilience/heartbeat.py',
      'publish interval, seconds'),
    E('KFAC_HB_DEADLINE', 'float', 'resilience/heartbeat.py',
      'no-advance deadline after which a peer is declared dead, '
      'seconds'),
    E('KFAC_HB_GRACE', 'float', 'resilience/heartbeat.py',
      'startup grace before the deadline clock arms, seconds'),
    E('KFAC_HB_TRANSPORT', 'choice', 'resilience/heartbeat.py',
      'heartbeat side channel (tcp needs no shared filesystem)',
      choices=('file', 'tcp')),
    E('KFAC_HB_PORT', 'int', 'resilience/heartbeat.py',
      'port this host\'s tcp heartbeat responder binds'),
    E('KFAC_HB_PEERS', 'str', 'resilience/heartbeat.py',
      'rank->addr map of every peer responder '
      '("0=ip0:8478,1=ip1:8478")'),
    E('KFAC_HB_GEN', 'int', 'resilience/heartbeat.py',
      'pod generation stamped into heartbeat payloads (rejoin vs '
      'stale disambiguation)'),
    # -- coordination backend -----------------------------------------
    E('KFAC_COORD_BACKEND', 'choice', 'coord/__init__.py',
      'coordination plane: shared lease dir, the kfac-coord-serve KV '
      'service, or a 3-replica quorum of them',
      choices=('posix', 'tcp', 'replicated')),
    E('KFAC_COORD_ADDR', 'str', 'coord/tcpkv.py',
      'host:port of the KV server (required for tcp)'),
    E('KFAC_COORD_ADDRS', 'str', 'coord/replicated.py',
      'comma-separated host:port of the KV replicas — normally 3 '
      '(required for replicated)'),
    # -- object store (durable checkpoint plane) -----------------------
    E('KFAC_STORE_BACKEND', 'choice', 'store/__init__.py',
      'checkpoint durability plane: the run directory, or the '
      'kfac-store-serve GCS-style object server',
      choices=('posix', 'http')),
    E('KFAC_STORE_ADDR', 'str', 'store/httpstore.py',
      'host:port of the object server (required for http)'),
    # -- observability -------------------------------------------------
    E('KFAC_TRACE_DIR', 'str', 'obs/trace.py',
      'shared directory for structured Perfetto/Chrome trace JSONL '
      '(enables the recorder)'),
    E('KFAC_PROM_FILE', 'str', 'obs/metrics.py',
      'Prometheus textfile path the metrics exporter rewrites '
      '(node-exporter textfile collector)'),
    # -- multi-tenant service (kfac-serve -> job environment) ----------
    E('KFAC_TENANT', 'str', 'service/scheduler.py',
      'tenant namespace kfac-serve exports into an admitted job'),
    E('KFAC_JOB_ID', 'int', 'service/scheduler.py',
      'job id kfac-serve exports into an admitted job'),
    E('KFAC_PREEMPT', 'flag', 'service/scheduler.py',
      'priority preemption: checkpoint-suspend lower-priority jobs '
      'to place higher-priority ones (default on; 0 disables)',
      default='1'),
    E('KFAC_SUSPEND_GRACE', 'float', 'service/scheduler.py',
      'seconds a preempted pod gets to bank its checkpoint and exit '
      'RC_SUSPENDED before the scheduler escalates to SIGKILL',
      default='30'),
    E('KFAC_AUTOSCALE', 'flag', 'service/scheduler.py',
      'emit scale-request.json capacity requests (desired slots from '
      'queue depth) for an external responder to answer via '
      'hosts.json'),
    # -- chaos drills: in-jit + host-side faults (faults.py) -----------
    E('KFAC_FAULT_NAN_GRAD_STEP', 'steps', 'faults.py',
      'NaN gradients at the given step(s)'),
    E('KFAC_FAULT_INF_GRAD_STEP', 'steps', 'faults.py',
      'Inf gradients at the given step(s)'),
    E('KFAC_FAULT_STATS_STEP', 'steps', 'faults.py',
      'NaN captured (a, g) statistics at the given step(s)'),
    E('KFAC_FAULT_FACTOR_STEP', 'steps', 'faults.py',
      'corrupt the leading stored factor block (silent-data-'
      'corruption drill)'),
    E('KFAC_FAULT_EIGH_STEP', 'steps', 'faults.py',
      'non-finite decomposition output ("eigh blowup")'),
    E('KFAC_FAULT_SIGTERM_STEP', 'int', 'faults.py',
      'deliver SIGTERM to this process at the given step'),
    E('KFAC_FAULT_CKPT', 'choice', 'faults.py',
      'checkpoint-write fault', choices=('truncate', 'fail',
                                         'eio_once')),
    E('KFAC_FAULT_HANG_STEP', 'int', 'faults.py',
      'block the host forever at this step (watchdog drill)'),
    E('KFAC_FAULT_SLOW_STEP', 'steps', 'faults.py',
      'sleep KFAC_FAULT_SLOW_SECS per listed step (straggler drill)'),
    E('KFAC_FAULT_SLOW_SECS', 'float', 'faults.py',
      'seconds the slow fault sleeps', default='1.0'),
    E('KFAC_FAULT_CRASH_STEP', 'int', 'faults.py',
      'die at this step (supervisor restart / peer-death drill)'),
    E('KFAC_FAULT_CRASH_MODE', 'choice', 'faults.py',
      'how the crash fault dies', choices=('exit', 'sigkill'),
      default='exit'),
    E('KFAC_FAULT_DATA_STEP', 'int', 'faults.py',
      'transient EIO from the data loader at this batch index, once'),
    E('KFAC_FAULT_ONCE_DIR', 'str', 'faults.py',
      'cross-restart one-shot token dir: hang/crash faults fire only '
      'in the first process reaching them'),
    E('KFAC_FAULT_HB_STOP_STEP', 'int', 'resilience/heartbeat.py',
      'stop publishing heartbeats at this step while training on '
      '(silent-death drill)'),
    # -- chaos drills: deterministic network chaos (chaos_net.py) ------
    E('KFAC_FAULT_NET_SEED', 'int', 'resilience/chaos_net.py',
      'seed of the drop/delay/dup/reorder schedule', default='0'),
    E('KFAC_FAULT_NET_DROP', 'float', 'resilience/chaos_net.py',
      'message drop probability in [0, 1]'),
    E('KFAC_FAULT_NET_DELAY', 'float', 'resilience/chaos_net.py',
      'added delivery delay, seconds'),
    E('KFAC_FAULT_NET_DUP', 'float', 'resilience/chaos_net.py',
      'message duplication probability in [0, 1]'),
    E('KFAC_FAULT_NET_REORDER', 'float', 'resilience/chaos_net.py',
      'message reorder probability in [0, 1]'),
    E('KFAC_FAULT_NET_PARTITION', 'str', 'resilience/chaos_net.py',
      'time-windowed (src, dst) partition matrix spec'),
    E('KFAC_FAULT_NET_PARTITION_FILE', 'str',
      'resilience/chaos_net.py',
      'file the partition spec is read from (live-editable drills)'),
    E('KFAC_FAULT_NET_T0', 'float', 'resilience/chaos_net.py',
      'wall-clock origin of the partition windows'),
    E('KFAC_FAULT_NET_IDMAP', 'str', 'resilience/chaos_net.py',
      'host-id remap spec for the partition matrix'),
    # -- chaos drills: coordination-backend chaos (coord/chaos.py) -----
    E('KFAC_FAULT_COORD_SEED', 'int', 'coord/chaos.py',
      'seed of the backend fault schedule', default='0'),
    E('KFAC_FAULT_COORD_FAIL', 'float', 'coord/chaos.py',
      'per-op failure probability in [0, 1]'),
    E('KFAC_FAULT_COORD_TORN', 'float', 'coord/chaos.py',
      'torn-read probability in [0, 1]'),
    E('KFAC_FAULT_COORD_STALE', 'float', 'coord/chaos.py',
      'stale-read probability in [0, 1]'),
    E('KFAC_FAULT_COORD_CAS', 'float', 'coord/chaos.py',
      'spurious CAS-conflict probability in [0, 1]'),
    E('KFAC_FAULT_COORD_LEASE_EXPIRE', 'float', 'coord/chaos.py',
      'premature lease-expiry probability in [0, 1]'),
    E('KFAC_FAULT_COORD_WINDOWS', 'str', 'coord/chaos.py',
      'outage window spec (start:end[,start:end...], seconds)'),
    E('KFAC_FAULT_COORD_T0', 'float', 'coord/chaos.py',
      'wall-clock origin of the outage windows'),
    # -- chaos drills: object-store chaos (store/chaos.py) -------------
    E('KFAC_FAULT_STORE_SEED', 'int', 'store/chaos.py',
      'seed of the object-store fault schedule', default='0'),
    E('KFAC_FAULT_STORE_FAIL', 'float', 'store/chaos.py',
      'per-op failure probability in [0, 1]'),
    E('KFAC_FAULT_STORE_TORN', 'float', 'store/chaos.py',
      'torn-upload probability in [0, 1] (put dies mid-stream, '
      'nothing committed)'),
    E('KFAC_FAULT_STORE_PARTIAL', 'float', 'store/chaos.py',
      'partial-read probability in [0, 1] (get returns truncated '
      'bytes — the corruption the manifest hash check catches)'),
    E('KFAC_FAULT_STORE_STALE', 'float', 'store/chaos.py',
      'stale-read probability in [0, 1]'),
    E('KFAC_FAULT_STORE_ACK_LOST', 'float', 'store/chaos.py',
      'lost-put-ack probability in [0, 1] (commit lands, ack lost — '
      'the idempotent-replay drill)'),
    E('KFAC_FAULT_STORE_WINDOWS', 'str', 'store/chaos.py',
      '503-outage window spec (start:end[,start:end...], seconds)'),
    E('KFAC_FAULT_STORE_T0', 'float', 'store/chaos.py',
      'wall-clock origin of the outage windows'),
    # -- bench / scripts -----------------------------------------------
    E('KFAC_AUTOTUNE_ASSERT', 'flag', 'scripts/autotune_smoke.py',
      'autotune smoke: fail (not just report) when a leg misses its '
      'planted optimum'),
    E('KFAC_FLASH_TQ', 'int', 'scripts/bench_flash.py',
      'flash-attention bench: query tile override'),
    E('KFAC_FLASH_TK', 'int', 'scripts/bench_flash.py',
      'flash-attention bench: key tile override'),
    # -- test tiers / drill plumbing ----------------------------------
    E('KFAC_NIGHTLY', 'flag', 'tests/conftest.py',
      'opt the run into the nightly test tier'),
    E('KFAC_DRILL_ARTIFACTS', 'str', 'tests (chaos drills)',
      'directory the CI drills export timelines/incident reports to'),
    E('KFAC_TEST_CKPT_DIR', 'str', 'tests/test_multihost.py',
      'scratch checkpoint dir handed to multihost test subprocesses'),
    # -- jax's own knobs this repo sets/reads (documented for the
    #    launch surface; values are jax's contract, not ours) ----------
    E('JAX_PLATFORMS', 'str', 'jax',
      'jax platform selection (cpu for the simulated tier-1 mesh)'),
    E('JAX_PROCESS_ID', 'int', 'jax / parallel/mesh.py',
      'this host\'s process index in a multi-process run'),
    E('JAX_NUM_PROCESSES', 'int', 'jax / parallel/mesh.py',
      'total process count of a multi-process run'),
    E('JAX_COORDINATOR_ADDRESS', 'str', 'jax / parallel/mesh.py',
      'host:port of the jax.distributed coordinator'),
    E('JAX_COMPILATION_CACHE_DIR', 'str', 'jax / utils/platform.py',
      'persistent XLA compilation cache directory (unset: the '
      'checkout\'s .jax_cache/)'),
)

DECLARED = frozenset(v.name for v in ENV)
BY_NAME = {v.name: v for v in ENV}


def declared(prefix: str = '') -> frozenset:
    """Declared names, optionally filtered to a prefix."""
    return frozenset(n for n in DECLARED if n.startswith(prefix))


def _check_value(var: EnvVar, raw: str) -> Optional[str]:
    """Error string if ``raw`` is malformed for ``var``, else None."""
    if var.kind == 'flag' and raw not in ('0', '1'):
        return f'{var.name} must be 0 or 1, got {raw!r}'
    if var.kind == 'int':
        try:
            int(raw)
        except ValueError:
            return f'{var.name} must be an integer, got {raw!r}'
    if var.kind == 'float':
        try:
            float(raw)
        except ValueError:
            return f'{var.name} must be a number, got {raw!r}'
    if var.kind == 'steps':
        for part in raw.split(','):
            part = part.strip()
            if not part:
                continue
            bits = part.split(':')
            if len(bits) > 2 or not all(
                    b.lstrip('-').isdigit() for b in bits):
                return (f'{var.name}: malformed step spec {raw!r} '
                        f'(accepted: "7", "3,5,9", "4:8")')
    if var.kind == 'choice' and raw not in var.choices:
        return (f'{var.name} must be one of '
                f'{"|".join(var.choices)}, got {raw!r}')
    return None


def validate_environ(environ=None, prefixes=('KFAC_',)) -> list:
    """Return a list of problem strings for ``environ``: any variable
    under ``prefixes`` that is undeclared, plus any declared variable
    (any prefix) with a malformed value. Empty list = clean."""
    environ = os.environ if environ is None else environ
    problems = []
    for key in sorted(environ):
        if any(key.startswith(p) for p in prefixes) \
                and key not in DECLARED:
            problems.append(
                f'{key} is not declared in kfac_pytorch_tpu/envspec.py '
                f'— typo, or an undocumented knob (declare it)')
    for key, raw in sorted(environ.items()):
        var = BY_NAME.get(key)
        if var is not None and raw != '':
            err = _check_value(var, raw)
            if err:
                problems.append(err)
    return problems


def markdown_table() -> str:
    """The README "Environment contract" table (generated; a test pins
    the README copy to this output)."""
    rows = ['| Variable | Kind | Consumer | Description |',
            '|---|---|---|---|']
    for v in sorted(ENV, key=lambda v: v.name):
        kind = v.kind if not v.choices else ' / '.join(v.choices)
        rows.append(f'| `{v.name}` | {kind} | `{v.consumer}` | {v.doc} |')
    return '\n'.join(rows)


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog='envspec', description=__doc__.splitlines()[0])
    p.add_argument('--validate', action='store_true',
                   help='check os.environ against the registry')
    p.add_argument('--table', action='store_true',
                   help='print the markdown env table')
    args = p.parse_args(argv)
    if args.table:
        print(markdown_table())
        return 0
    if args.validate:
        problems = validate_environ()
        for prob in problems:
            print(f'envspec: {prob}', file=sys.stderr)
        return 1 if problems else 0
    p.print_help()
    return 2


if __name__ == '__main__':
    sys.exit(main())
