"""Observability: one schema for what the run DID and how long it took.

Before this package the repo narrated itself in five ad-hoc formats:
``[health: ...]`` / ``[resilience: ...]`` / ``kfac_phase_ms=`` epoch-line
suffixes (utils/runlog.py), the hand-rolled TensorBoard writer
(utils/summary.py), ``incident-host*.json`` (resilience/incident.py),
protocol prints (chaos_trainer), and the XLA profiler trace
(utils/profiling.trace). Each answers one question for one consumer;
none compose. This package is the common layer they all report through:

- :mod:`trace` — structured host-side spans and instants in the Chrome
  trace-event format (Perfetto/``chrome://tracing`` loadable), bounded
  ring buffer, flushed on the same SIGTERM/atexit chain as the run log.
  Per-step spans carry the same phase taxonomy the engine's
  ``jax.named_scope`` annotations use (ComputeFactor / CommunicateFactor
  / ComputeInverse / CommunicateInverse — the ``exclude_parts`` ledger
  names), and every resilience event (watchdog trip, peer death,
  supervisor restart, straggler degrade) lands as a trace instant.
- :mod:`metrics` — a typed registry (counter / gauge / watermark /
  histogram) with rank-0-gated pluggable exporters (JSONL, the native
  TensorBoard writer, a Prometheus textfile) that ALSO renders the
  exact legacy epoch-line suffixes, so one registry replaces the
  scattered suffix plumbing without changing a byte of the log format.
- :mod:`aggregate` — the ``kfac-obs`` console entry: merge per-host
  trace JSONL, run logs and incident reports into one clock-aligned
  pod timeline (the ROADMAP "pod-level timeline" open item).

Everything here is dependency-free stdlib (jax is touched only where the
process has already loaded it: ``trace.annotation``, the span's second
sink in the profiler's own trace), so the supervisor/aggregator side
stays importable on machines with no accelerator stack at all.
"""

import os as _os

from kfac_pytorch_tpu.obs import metrics, trace

__all__ = ['trace', 'metrics', 'setup_trainer']


def setup_trainer(trace_dir=None, prom_file=None, governor=None,
                  tuner=None):
    """The example trainers' shared observability bootstrap.

    Installs the process-default trace recorder (``trace_dir`` wins
    over ``KFAC_TRACE_DIR``; None + no env = tracing off), builds the
    metrics registry with the resilience-counter collector (plus a
    ``StragglerGovernor``'s and an ``autotune.KnobController``'s counts
    when given — the tuner also publishes its current knob gauges), and
    attaches the JSONL/Prometheus exporters the flags ask for. The
    TensorBoard exporter is NOT attached here — the trainers construct
    their writer later and add it themselves. Returns
    ``(tracer_or_None, registry)``.
    """
    if trace_dir:
        pid = int(_os.environ.get('JAX_PROCESS_ID', '0'))
        tracer = trace.install(
            _os.path.join(trace_dir, f'trace-host{pid}.jsonl'))
    else:
        tracer = trace.install_from_env()
    reg = metrics.Registry()
    extra_counts = [c.counts for c in (governor, tuner) if c is not None]
    reg.add_collector(metrics.resilience_collector(*extra_counts))
    if tuner is not None:
        reg.add_collector(tuner.collect)
    if trace_dir:
        reg.add_exporter(metrics.JsonlExporter(
            _os.path.join(trace_dir, 'metrics.jsonl')))
    if prom_file:
        # service namespacing: two tenant jobs handed the same textfile
        # path (a shared default) must not clobber each other's
        # exports — under KFAC_TENANT/KFAC_JOB_ID the path gains a
        # per-job suffix; outside the service this is the identity
        reg.add_exporter(metrics.PrometheusTextfileExporter(
            metrics.namespaced_prom_path(prom_file)))
    return tracer, reg
