#!/bin/bash
# TPU launcher — replaces the reference's mpirun/hostfile and ssh/torchrun
# launchers (launch_horovod.sh:32, launch_torch.sh:26-45).
#
# On TPU there is ONE python process per host; intra-host chips are just
# devices in the jax mesh, and multi-host pods coordinate through
# jax.distributed.initialize (driven by TPU runtime env vars — no ssh
# loops, no hostfiles). Single host:
#
#   bash launch_tpu.sh examples/cifar10_resnet.py --num-devices 8 ...
#
# Multi-host (run the same command on every worker of the pod slice, e.g.
# via `gcloud compute tpus tpu-vm ssh --worker=all --command=...`):
#
#   JAX_COORDINATOR_ADDRESS=<worker0-ip>:8476 \
#   JAX_NUM_PROCESSES=<n_hosts> JAX_PROCESS_ID=<this host> \
#   bash launch_tpu.sh examples/imagenet_resnet.py ...
#
# kfac_pytorch_tpu initializes jax.distributed automatically when these
# variables are present (see kfac_pytorch_tpu/parallel/mesh.py).

set -e
cd "$(dirname "$0")"
script="$1"; shift

# env defaults + optional mesh preset (pod=N -> configs/podN), the
# reference's `source configs/envs.conf` + hostfile selection
# (launch_horovod.sh:7,32).
[ -f configs/envs.conf ] && . configs/envs.conf
if [ -n "$pod" ]; then
  if [ -f "configs/pod$pod" ]; then
    set -a                 # export everything the preset defines
    . "configs/pod$pod"
    set +a
    # append so the preset wins over any earlier --num-devices default
    # from the train_*.sh param string (argparse last-occurrence-wins)
    set -- "$@" --num-devices "$KFAC_NUM_DEVICES"
  else
    echo "launch_tpu.sh: no such mesh preset configs/pod$pod" >&2
    exit 1
  fi
fi
export XLA_PYTHON_CLIENT_PREALLOCATE

# Observability: KFAC_TRACE_DIR=<shared dir> turns on structured trace
# spans in every process of the run (trainers AND supervisors each
# write trace-host<i>[-sup].jsonl there — obs/trace.py install_from_env).
# After a run (or an incident), merge the pod's artifacts into one
# clock-aligned timeline:
#   kfac-obs "$KFAC_TRACE_DIR" logs/*.log -o timeline.json
[ -n "$KFAC_TRACE_DIR" ] && export KFAC_TRACE_DIR

# Communication compression: KFAC_COMM_PRECISION=fp32|bf16|int8 sets the
# wire dtype of the K-FAC factor collectives on every trainer of the run
# (the trainers read it as the --kfac-comm-precision default; an explicit
# flag on the command line still wins). bf16 halves, int8 quarters the
# gather payloads; the stats reduce carries an error-feedback residual
# (KFACState.comm_err); the gradient allreduce is NEVER compressed. See
# README "Communication compression" for when int8 is safe.
if [ -n "$KFAC_COMM_PRECISION" ]; then
  case "$KFAC_COMM_PRECISION" in
    fp32|bf16|int8) export KFAC_COMM_PRECISION ;;
    *) echo "launch_tpu.sh: KFAC_COMM_PRECISION must be fp32|bf16|int8," \
            "got '$KFAC_COMM_PRECISION'" >&2; exit 1 ;;
  esac
fi

# Live replanning (README "Live replanning"): KFAC_COMM_MODE=inverse|pred
# overrides the variant's comm mode for every trainer of the run (the
# trainers read it as the --kfac-comm-mode default; an explicit flag
# still wins). 'inverse' gathers decompositions once per refresh,
# 'pred' gathers preconditioned gradients every step; with the
# autotuner on, the other mode is a real probe/commit rung applied
# mid-run via KFAC.replan — this env sets only the STARTING mode.
if [ -n "$KFAC_COMM_MODE" ]; then
  case "$KFAC_COMM_MODE" in
    inverse|pred) export KFAC_COMM_MODE ;;
    *) echo "launch_tpu.sh: KFAC_COMM_MODE must be inverse|pred," \
            "got '$KFAC_COMM_MODE'" >&2; exit 1 ;;
  esac
fi

# Composed meshes (README "K-FAC on composed meshes"): KFAC_MESH is a
# meshplan spec ('dp2xsp4', 'dp4xtp2', ...) the trainers read as the
# --kfac-mesh default — the axis-aware mesh plan derives the K-FAC
# world from its data/sequence axes. Grammar-checked here so a typo
# fails at launch, not after the pod spins up.
if [ -n "$KFAC_MESH" ]; then
  if echo "$KFAC_MESH" | grep -Eq \
      '^(dp|sp|tp|ep|pp)[0-9]+(=[A-Za-z_][A-Za-z0-9_]*)?(x(dp|sp|tp|ep|pp)[0-9]+(=[A-Za-z_][A-Za-z0-9_]*)?)*$'; then
    export KFAC_MESH
  else
    echo "launch_tpu.sh: KFAC_MESH must be an 'x'-separated list of" \
         "dp/sp/tp/ep/pp axis tokens ('dp2xsp4'), got '$KFAC_MESH'" >&2
    exit 1
  fi
fi

# Closed-loop autotuning: KFAC_AUTOTUNE=1 enables the online knob
# controller in every trainer of the run (the trainers read it as the
# --kfac-autotune default; an explicit flag still wins). The controller
# hill-climbs kfac/fac_update_freq and the comm wire dtype from
# measured step times through the single knob arbiter; decisions land
# in the run log (kfac-obs renders them) and, under KFAC_TRACE_DIR, in
# <dir>/autotune-decisions.jsonl. See README "Closed-loop autotuning".
if [ -n "$KFAC_AUTOTUNE" ]; then
  case "$KFAC_AUTOTUNE" in
    0|1) export KFAC_AUTOTUNE ;;
    *) echo "launch_tpu.sh: KFAC_AUTOTUNE must be 0 or 1," \
            "got '$KFAC_AUTOTUNE'" >&2; exit 1 ;;
  esac
fi

# Decomposition wall (README "Attacking the decomposition wall"):
# KFAC_DECOMP_IMPL selects the decomposition kernel for every trainer of
# the run (the trainers read it as the --kfac-decomp-impl default; an
# explicit flag still wins): xla = cold QDWH eigh / Cholesky;
# subspace|jacobi (eigh variants) / newton_schulz (Cholesky variants)
# are warm iterative GEMM kernels; auto picks the warm kernel per
# variant. An explicit value is also a live autotuner ladder rung.
if [ -n "$KFAC_DECOMP_IMPL" ]; then
  case "$KFAC_DECOMP_IMPL" in
    xla|auto|jacobi|subspace|newton_schulz) export KFAC_DECOMP_IMPL ;;
    *) echo "launch_tpu.sh: KFAC_DECOMP_IMPL must be" \
            "xla|auto|jacobi|subspace|newton_schulz," \
            "got '$KFAC_DECOMP_IMPL'" >&2; exit 1 ;;
  esac
fi

# Capture hot path (README "Capture hot path", ISSUE 19):
# KFAC_CAPTURE_IMPL selects the capture kernels for every trainer of
# the run (the trainers read it as the --kfac-capture-impl default; an
# explicit flag still wins): xla = the reference patch-extract + GEMM
# + EMA chain; pallas = the fused Pallas kernels (no HBM patch matrix,
# EMA / wire-quantize folded into the epilogues); auto = the fused
# rung, tuner decides. An explicit value is also a live autotuner
# ladder rung.
if [ -n "$KFAC_CAPTURE_IMPL" ]; then
  case "$KFAC_CAPTURE_IMPL" in
    xla|pallas|auto) export KFAC_CAPTURE_IMPL ;;
    *) echo "launch_tpu.sh: KFAC_CAPTURE_IMPL must be" \
            "xla|pallas|auto," \
            "got '$KFAC_CAPTURE_IMPL'" >&2; exit 1 ;;
  esac
fi

# KFAC_DECOMP_SHARD=1 turns on mesh-sharded decomposition (the
# --kfac-decomp-shard default): each refresh cohort's eigh/inverse rows
# are repartitioned cost-balanced across ALL devices instead of
# owner-local — ~P x shorter decomposition critical path for two
# bounded DecompComm gathers per step (scripts/comm_count.py pins the
# wire bytes against FactorPlan.comm_volume). Implies the staggered
# schedule.
if [ -n "$KFAC_DECOMP_SHARD" ]; then
  case "$KFAC_DECOMP_SHARD" in
    0|1) export KFAC_DECOMP_SHARD ;;
    *) echo "launch_tpu.sh: KFAC_DECOMP_SHARD must be 0 or 1," \
            "got '$KFAC_DECOMP_SHARD'" >&2; exit 1 ;;
  esac
fi

if [ -n "$JAX_COORDINATOR_ADDRESS" ]; then
  export KFAC_TPU_MULTIHOST=1
fi

# Coordination backend (kfac_pytorch_tpu/coord/, README "Coordination
# backends"): where the pod protocols — shrink/grow barrier claims,
# lineage fencing, heartbeat file-leases, join/done markers, the
# kfac-serve queue — keep their state.
#   KFAC_COORD_BACKEND  posix (default: the shared lease DIRECTORY,
#                       byte-compatible protocol files) | tcp (an
#                       etcd-style KV server, no shared filesystem —
#                       run one with `kfac-coord-serve --port 8479`)
#   KFAC_COORD_ADDR     host:port of the KV server (required for tcp)
#   KFAC_COORD_ADDRS    comma-separated host:port of the KV replicas —
#                       normally 3 (required for replicated; one
#                       replica down is invisible, quorum loss exits
#                       RC_COORD_LOST=118)
# Backend fault drills: KFAC_FAULT_COORD_* (seed/fail/torn/stale/cas/
# lease_expire/windows — faults.py STRICT from_env; on replicated they
# arm PER REPLICA with decorrelated seeds).
if [ -n "$KFAC_COORD_BACKEND" ]; then
  case "$KFAC_COORD_BACKEND" in
    posix) export KFAC_COORD_BACKEND ;;
    tcp)
      : "${KFAC_COORD_ADDR:?KFAC_COORD_BACKEND=tcp needs KFAC_COORD_ADDR (host:port of a kfac-coord-serve KV server)}"
      export KFAC_COORD_BACKEND KFAC_COORD_ADDR ;;
    replicated)
      : "${KFAC_COORD_ADDRS:?KFAC_COORD_BACKEND=replicated needs KFAC_COORD_ADDRS (comma-separated host:port of the kfac-coord-serve replicas, normally 3)}"
      case "$KFAC_COORD_ADDRS" in
        *[,\;]*) ;;
        *) echo "launch_tpu.sh: KFAC_COORD_ADDRS needs at least 2" \
                "comma-separated replicas, got '$KFAC_COORD_ADDRS'" \
                >&2; exit 1 ;;
      esac
      export KFAC_COORD_BACKEND KFAC_COORD_ADDRS ;;
    *) echo "launch_tpu.sh: KFAC_COORD_BACKEND must be" \
            "posix|tcp|replicated, got '$KFAC_COORD_BACKEND'" >&2
       exit 1 ;;
  esac
fi

# Training service (kfac-serve, kfac_pytorch_tpu/service/): when this
# launch is one tenant job of the multi-tenant service, the scheduler
# exports the per-job namespace env — pass it through so every child
# (supervisor + trainer) logs, traces and exports metrics into the
# job's own tenant directory instead of a shared path:
#   KFAC_TENANT     tenant name (metrics/prom paths are namespaced by it)
#   KFAC_JOB_ID     job-NNNNNN (ditto)
#   KFAC_PROM_FILE  the job's Prometheus textfile (trainers default
#                   --prom-file to it)
# KFAC_HB_PORT is also service-assigned per job (disjoint blocks), so
# jobs sharing a host never fight over heartbeat responder ports — the
# ${KFAC_HB_PORT:-8478} default below only applies outside the service.
[ -n "$KFAC_TENANT" ] && export KFAC_TENANT
[ -n "$KFAC_JOB_ID" ] && export KFAC_JOB_ID
[ -n "$KFAC_PROM_FILE" ] && export KFAC_PROM_FILE

# Peer-heartbeat transport (KFAC_HB_*, resilience/heartbeat.py).
# Contract consumed by heartbeat_from_env in every trainer:
#   KFAC_HB_TRANSPORT  file | tcp  (default: tcp when the pod has >1
#                      worker, file otherwise — file leases need a
#                      shared POSIX filesystem, which real multi-host
#                      pods don't have; single-host smoke runs keep the
#                      zero-config lease dir)
#   KFAC_HB_PORT       port each host's TCP responder binds (8478)
#   KFAC_HB_PEERS      "rank=host:port,..." for every rank; derived
#                      below from KFAC_HB_WORKERS="ip0 ip1 ..." (the
#                      pod's worker addresses in rank order) when unset
#   KFAC_HB_HOST/HOSTS this rank / world size (default: the jax pod
#                      coordination env)
#   KFAC_HB_INTERVAL/DEADLINE/GRACE  beat cadence / silence-to-death /
#                      startup grace, seconds
#   KFAC_HB_GEN        pod generation (the pod supervisor re-exports it
#                      per shrink/grow so a rejoined host's restarted
#                      sequence counter is never misread as stale)
nworkers="${JAX_NUM_PROCESSES:-1}"
if [ -z "$KFAC_HB_TRANSPORT" ] && [ "$nworkers" -gt 1 ] \
    && { [ -n "$KFAC_HB_PEERS" ] || [ -n "$KFAC_HB_WORKERS" ]; }; then
  # multi-host with a derivable peer map: tcp is the default transport
  export KFAC_HB_TRANSPORT=tcp
fi
if [ "$KFAC_HB_TRANSPORT" = tcp ]; then
  export KFAC_HB_PORT="${KFAC_HB_PORT:-8478}"
  if [ -z "$KFAC_HB_PEERS" ]; then
    if [ -n "$KFAC_HB_WORKERS" ]; then
      i=0; peers=""
      for w in $KFAC_HB_WORKERS; do
        peers="${peers:+$peers,}$i=$w:$KFAC_HB_PORT"
        i=$((i+1))
      done
      export KFAC_HB_PEERS="$peers"
    else
      # tcp was asked for EXPLICITLY but the peer map is underivable —
      # fail loudly rather than run a pod whose hosts can't see each
      # other die
      echo "launch_tpu.sh: KFAC_HB_TRANSPORT=tcp needs KFAC_HB_PEERS" \
           "(rank=host:port,...) or KFAC_HB_WORKERS (\"ip0 ip1 ...\")" >&2
      exit 1
    fi
  fi
  export KFAC_HB_HOST="${KFAC_HB_HOST:-${JAX_PROCESS_ID:-0}}"
  export KFAC_HB_HOSTS="${KFAC_HB_HOSTS:-$nworkers}"
fi

# Central env contract (kfac_pytorch_tpu/envspec.py; README "Static
# analysis"): every exported KFAC_* name must be declared in the
# registry and carry a well-formed value. A typo'd knob
# (KFAC_COMM_PRECISON=bf16) kills the launch here, in milliseconds,
# instead of silently never arming on an allocated pod. envspec.py is
# stdlib-pure and run as a bare file, so this works on hosts where jax
# itself is broken — the value checks above stay as the launcher's own
# fast path; the registry is the completeness net (undeclared names,
# malformed values of everything else).
if ! "${PY:-python}" kfac_pytorch_tpu/envspec.py --validate; then
  echo "launch_tpu.sh: environment failed the envspec contract (above)" >&2
  exit 1
fi

# Pod-resilience wrapper: KFAC_POD_SUPERVISE=1 runs the trainer under
# the per-host kfac-pod-supervise loop (resilience/elastic.py) — on top
# of the crash/hang restarts below, the supervisors heartbeat each other
# through KFAC_POD_LEASE_DIR (a shared directory every host can see);
# a host that dies for good (trainer rc 115 RC_PEER_DEAD, or this
# supervisor's own monitor) triggers the shrink protocol: the survivors
# agree on the surviving set, relaunch at the reduced world size, and
# the trainers reshard their K-FAC factor state through elastic_resume.
# An incident report JSON lands in the lease dir on every exit path.
# Requires JAX_PROCESS_ID / JAX_NUM_PROCESSES (the pod coordination env
# above) and a checkpoint dir, like KFAC_SUPERVISE.
# Rejoin after repair: KFAC_POD_JOIN=1 on the REPAIRED host announces
# it on the heartbeat channel instead of cold-launching; the incumbent
# pod runs the grow barrier, every trainer relaunches at the enlarged
# world, and factor state reshards UP through elastic_resume. Exit 116
# (join_failed) means the pod never answered within KFAC_JOIN_TIMEOUT.
# Partitions: membership changes are QUORUM-GATED — the minority side
# of a network partition exits 117 (fenced) instead of relaunching a
# rival generation, stops finalizing checkpoints, and rejoins via
# KFAC_POD_JOIN=1 once the network heals; the supervisor exports the
# lineage epoch as KFAC_LINEAGE so a fenced fork's state is refused at
# resume. Drill it deterministically with the KFAC_FAULT_NET_* network
# chaos env (seeded drop/delay/dup/reorder + a time-windowed partition
# matrix; see resilience/chaos_net.py and README "Network partitions")
# — inherited by the supervisors and trainers like every KFAC_FAULT_*.
if [ -n "$KFAC_POD_SUPERVISE" ]; then
  : "${KFAC_POD_LEASE_DIR:?KFAC_POD_SUPERVISE=1 needs KFAC_POD_LEASE_DIR (shared across hosts)}"
  exec "${PY:-python}" -m kfac_pytorch_tpu.resilience.elastic \
    --host-id "${JAX_PROCESS_ID:-0}" \
    --num-hosts "${JAX_NUM_PROCESSES:-1}" \
    --lease-dir "$KFAC_POD_LEASE_DIR" \
    ${KFAC_HOST_ADDR:+--host-addr "$KFAC_HOST_ADDR"} \
    ${KFAC_POD_JOIN:+--join} \
    ${KFAC_JOIN_TIMEOUT:+--join-timeout "$KFAC_JOIN_TIMEOUT"} \
    --max-restarts "${KFAC_MAX_RESTARTS:-3}" \
    --backoff-base "${KFAC_RESTART_BACKOFF:-2}" \
    --hb-interval "${KFAC_HB_INTERVAL:-2}" \
    --hb-deadline "${KFAC_HB_DEADLINE:-10}" \
    -- "${PY:-python}" "$script" "$@"
fi

# Resilient-runtime wrapper: KFAC_SUPERVISE=1 runs the trainer under the
# kfac-supervise restart loop (kfac_pytorch_tpu/resilience/supervisor.py)
# — a crash (nonzero rc / signal death) or a step-watchdog hang abort
# (rc 114) relaunches the trainer up to KFAC_MAX_RESTARTS times with
# exponential backoff; the trainer resumes via its auto_resume
# checkpoint path. Give the trainer a --checkpoint-dir/--resume (cifar)
# or --checkpoint-format (imagenet, always on) or restarts start over.
# KFAC_STOP_RCS ("peer_dead 7 ...") propagates those exit codes instead
# of restarting — names from the protocol table (README) or numbers.
if [ -n "$KFAC_SUPERVISE" ]; then
  stop_rc_flags=""
  for rc in ${KFAC_STOP_RCS:-}; do
    stop_rc_flags="$stop_rc_flags --stop-rc $rc"
  done
  exec "${PY:-python}" -m kfac_pytorch_tpu.resilience.supervisor \
    --max-restarts "${KFAC_MAX_RESTARTS:-3}" \
    --backoff-base "${KFAC_RESTART_BACKOFF:-2}" \
    $stop_rc_flags \
    -- "${PY:-python}" "$script" "$@"
fi

exec "${PY:-python}" "$script" "$@"
