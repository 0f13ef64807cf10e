"""Deterministic fleet simulator (jax-free, stdlib-only).

Composes the repo's OWN protocol code — the :class:`PodSupervisor`
shrink barrier and lineage fencing (``resilience.elastic``), the
:class:`PeerHeartbeat` monitors (``resilience.heartbeat``), the durable
:class:`JobQueue` + :class:`AdmissionController` (``service/``) and the
3-replica quorum coordination plane (``coord.replicated`` over
:class:`TcpKvServer` stores) — into one discrete-event loop at
1,000-10,000 simulated hosts, with every clock, rng and process seam
injected. A simulated step has one fixed length (``fleet.ITER_S``);
replica and host faults come from a seeded schedule; the
output is a semantic event trace (JSONL) that is byte-identical across
runs with the same seed.

The point is NOT a model of the protocols — the barriers, quorum
gates, epoch CAS transitions and read-through repair in the loop are
the production code paths, driven at a fleet scale no real CI pod can
reach. What the sweep pins, in seconds on a laptop CPU:

- quorum shrink never splits brain (at most one side of a partition
  commits a generation; the minority fences),
- fencing never loses a committed lineage (per-pod lineage epochs are
  strictly monotonic, and a fenced side never bumps one),
- exactly-once requeue (a failed job re-enters the queue once per
  observed failure, through a replica failover),
- one KV replica down mid-everything is invisible to every actor
  (zero ``coord_lost``), and a restarted empty replica is caught back
  up by read-through repair.

CLI::

    python -m kfac_pytorch_tpu.sim --hosts 1000 --seed 0 --out trace.jsonl
"""

from kfac_pytorch_tpu.sim.fleet import (
    EventLoop, FleetSim, SimConfig, SimProcess, run_fleet_sim,
    write_trace)

__all__ = ['EventLoop', 'FleetSim', 'SimConfig', 'SimProcess',
           'run_fleet_sim', 'write_trace']
