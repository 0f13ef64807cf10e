"""CLI for the fleet simulator: run one seeded sweep, write the
JSONL trace, print the summary line, exit non-zero when a pinned
property failed (any ``coord_lost``, jobs not finished, heap not
drained). This is what the jax-less ``fleet-sim`` CI job runs and
archives.

::

    python -m kfac_pytorch_tpu.sim --hosts 1000 --seed 0 --out trace.jsonl
"""

import argparse
import json
import logging
import shutil
import sys
import tempfile

from kfac_pytorch_tpu.sim.fleet import SimConfig, run_fleet_sim, write_trace


def main(argv=None):
    p = argparse.ArgumentParser(
        prog='python -m kfac_pytorch_tpu.sim',
        description='deterministic fleet simulator over the real '
                    'supervisor/heartbeat/queue/quorum code')
    p.add_argument('--hosts', type=int, default=1000)
    p.add_argument('--pod-size', type=int, default=8)
    p.add_argument('--seed', type=int, default=0)
    p.add_argument('--kill-pods', type=int, default=12)
    p.add_argument('--partition-pods', type=int, default=4)
    p.add_argument('--jobs', type=int, default=10)
    p.add_argument('--fail-jobs', type=int, default=3)
    p.add_argument('--service-hosts', type=int, default=2)
    p.add_argument('--service-slots', type=int, default=4)
    p.add_argument('--preempt-jobs', type=int, default=0,
                   help='late high-priority jobs that force '
                        'checkpoint-suspend preemption')
    p.add_argument('--autoscale', action='store_true',
                   help='arm the capacity responder answering '
                        'scale-request.json with hosts.json rewrites')
    p.add_argument('--drain-at', type=float, default=0.0,
                   help='sim time to mark the last service host '
                        'draining (0 = never)')
    p.add_argument('--out', default=None,
                   help='JSONL trace path (default: stdout summary only)')
    p.add_argument('--root', default=None,
                   help='scratch dir (default: a fresh temp dir, removed '
                        'after the run)')
    p.add_argument('--verbose', action='store_true',
                   help='stream the raw protocol logs to stderr')
    args = p.parse_args(argv)

    if args.verbose:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter('%(levelname)s %(message)s'))
        log = logging.getLogger('kfac_pytorch_tpu.sim')
        log.addHandler(h)
        log.setLevel(logging.INFO)

    cfg = SimConfig(hosts=args.hosts, pod_size=args.pod_size,
                    seed=args.seed,
                    kill_pods=args.kill_pods,
                    partition_pods=args.partition_pods,
                    jobs=args.jobs, fail_jobs=args.fail_jobs,
                    service_hosts=args.service_hosts,
                    service_slots=args.service_slots,
                    preempt_jobs=args.preempt_jobs,
                    autoscale=args.autoscale,
                    drain_at=args.drain_at)
    root = args.root or tempfile.mkdtemp(prefix='kfac-fleet-sim-')
    try:
        trace = run_fleet_sim(cfg, root)
    finally:
        if args.root is None:
            shutil.rmtree(root, ignore_errors=True)
    if args.out:
        write_trace(trace, args.out)
    end = trace[-1]
    print('fleet-sim:', json.dumps(end, sort_keys=True))
    ok = (end['kind'] == 'sim_end' and end['coord_lost'] == 0
          and end['jobs_finished'] and end['drained'])
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
