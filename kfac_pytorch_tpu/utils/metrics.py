"""Training metrics.

Parity: the distributed ``Metric`` accumulator and ``accuracy``
(reference: examples/utils.py:6-9, 39-52). The reference allreduce-averages
each update across ranks; here values produced by a jitted/shard_map step
are already replicated, so the accumulator is a plain weighted host
average — the collective happened on-device.
"""

import logging

import jax.numpy as jnp
import numpy as np


class HealthMonitor:
    """Host-side consumer of the step metrics' ``health/*`` counters
    (beyond reference — the in-jit guard lives in health.py).

    The jitted step returns CUMULATIVE on-device counters (total skipped
    batches, total raw-SGD fallbacks, current ladder rung); the monitor
    diffs them between ``update`` calls and logs a WARNING the moment
    something happens — a skipped batch, a ladder escalation, the
    degraded-SGD mode engaging, recovery — so run logs carry the event at
    the step it occurred, not just the end-of-run totals. ``epoch_flush``
    returns (and resets) per-epoch deltas for the epoch summary line
    (runlog.health_suffix formats them).

    Reading the counters costs no extra device sync in practice: the
    trainers already block on ``float(metrics['loss'])`` every step, so
    the health scalars ride along with an already-materialized result.
    """

    def __init__(self, log=None, state=None, registry=None):
        """``state``: pass the (possibly restored) TrainState so the
        baseline starts from ITS cumulative counters — without it, a
        resumed run's first update would re-announce every pre-resume
        skip as if it just happened.

        ``registry``: an ``obs.metrics.Registry`` — the monitor then
        publishes ``health/skipped``, ``health/fallbacks`` (counters)
        and ``health/max_rung`` (per-epoch watermark) so the registry's
        ``epoch_suffixes()`` renders the same ``[health: ...]`` suffix
        this class used to feed by hand (and exporters see the
        cumulative counts). The restored baseline is rebased so a
        resume's first epoch line reports only post-resume events —
        identical to the legacy ``epoch_flush`` semantics."""
        self.log = log if log is not None else logging.getLogger(__name__)
        self.skipped = 0      # cumulative, mirrors the device counter
        self.fallbacks = 0
        self.rung = 0
        h = getattr(state, 'health', None)
        if h is not None:
            self.skipped = int(h.skipped)
            self.fallbacks = int(h.fallbacks)
            self.rung = int(h.rung)
        self._epoch = {'skipped': 0, 'fallbacks': 0, 'max_rung': 0}
        self.registry = registry
        if registry is not None:
            registry.counter('health/skipped').rebase(self.skipped)
            registry.counter('health/fallbacks').rebase(self.fallbacks)
            registry.watermark('health/max_rung')

    def update(self, metrics, step=None):
        """Consume one step's metrics dict; no-op without health/*."""
        if 'health/skipped' not in metrics:
            return
        at = '' if step is None else f' at step {step}'
        skipped = int(metrics['health/skipped'])
        fallbacks = int(metrics['health/fallbacks'])
        rung = int(metrics['health/rung'])
        if skipped > self.skipped:
            self._epoch['skipped'] += skipped - self.skipped
            self.log.warning(
                'health: non-finite batch skipped%s (total %d) — params '
                'and factor EMAs untouched', at, skipped)
        if fallbacks > self.fallbacks:
            self._epoch['fallbacks'] += fallbacks - self.fallbacks
            self.log.warning(
                'health: non-finite preconditioner output%s — raw-SGD '
                'gradients used for this step (total %d)', at, fallbacks)
        if rung > self.rung:
            self.log.warning(
                'health: damping-escalation ladder climbed to rung %d%s',
                rung, at)
        elif rung < self.rung:
            self.log.info(
                'health: recovered%s — damping ladder reset to rung %d',
                at, rung)
        self._epoch['max_rung'] = max(self._epoch['max_rung'], rung)
        if self.registry is not None:
            self.registry.counter('health/skipped').set_total(skipped)
            self.registry.counter('health/fallbacks').set_total(fallbacks)
            self.registry.watermark('health/max_rung').set(rung)
        self.skipped, self.fallbacks, self.rung = skipped, fallbacks, rung

    def quality_signal(self):
        """Monotone badness counter for the autotuner's numerical-
        health gate (``KnobController(quality_gate=...)``): total
        skipped batches + raw-SGD fallbacks. A knob probe window that
        raised this number regressed accuracy and never commits,
        whatever its step time said."""
        return self.skipped + self.fallbacks

    def epoch_flush(self):
        """Per-epoch deltas ``{skipped, fallbacks, max_rung}``; resets the
        epoch accumulators (cumulative totals keep running)."""
        out, self._epoch = self._epoch, {'skipped': 0, 'fallbacks': 0,
                                         'max_rung': 0}
        return out


class PhaseTimers:
    """Host-side per-step wall-time attribution by K-FAC phase set
    (beyond reference — the staggered-refresh observability companion).

    Under jit every K-FAC phase fuses into one program, so per-phase
    time cannot be read off the device per step; what the host CAN see
    is which phases each dispatched variant ran
    (``step_fn.last_phases``: 'pred'/'stats'/'decomp'/'gather') and the
    step's wall time. The timers bucket wall times by phase set and at
    ``epoch_flush`` derive marginal per-phase costs by subtraction
    between observed sets — the passive, in-run form of the
    reference's exclude-parts ablation method. A set with no observed
    strict subset
    reports its joint mean under a '+'-joined label (e.g. a staggered
    fac-freq-1 run, where every step runs everything, honestly reports
    one ``decomp+gather+pred+stats`` figure).

    ``step_max``/``step_mean`` always ride along: the refresh spike —
    and its removal under ``stagger=True`` — is visible as
    ``step_max/step_mean`` collapsing toward 1 in the epoch lines
    (runlog.kfac_phase_suffix formats the dict).
    """

    def __init__(self, tracer=None, registry=None, histogram=False):
        """``tracer``: an ``obs.trace.TraceRecorder`` — every recorded
        step then ALSO lands as a Chrome-trace span named
        ``kfac.step``, carrying the step's phase set in the
        exclude-parts ledger taxonomy (``obs.trace.PHASE_TAXONOMY``), so
        the same host-side attribution this class aggregates is
        inspectable step-by-step in Perfetto.

        ``registry``: an ``obs.metrics.Registry`` — ``collect`` (or a
        direct ``epoch_flush``-then-set) publishes the per-epoch phase
        marginals as ``kfac_phase/*`` epoch gauges, which the registry
        renders into the exact legacy ``kfac_phase_ms=`` suffix.
        ``histogram=True`` additionally feeds a ``step_seconds``
        histogram (Prometheus-shaped step-time distribution)."""
        self._acc = {}
        self._max = 0.0
        self._total = 0.0
        self._n = 0
        self.tracer = tracer
        self.registry = registry
        self._histogram = histogram
        if registry is not None:
            registry.add_collector(self.collect)
            if histogram:
                registry.histogram('step_seconds')

    def record(self, phases, seconds):
        """One step's wall time, attributed to its phase set. Call with
        the COMPLETED step's duration (time around the dispatch plus the
        blocking metric read that materializes it)."""
        key = frozenset(phases)
        tot, n = self._acc.get(key, (0.0, 0))
        self._acc[key] = (tot + seconds, n + 1)
        self._total += seconds
        self._n += 1
        self._max = max(self._max, seconds)
        if self.tracer is not None:
            from kfac_pytorch_tpu.obs.trace import taxonomy_phases
            self.tracer.complete('kfac.step', seconds, cat='kfac.step',
                                 phases=taxonomy_phases(phases))
        if self.registry is not None and self._histogram:
            self.registry.histogram('step_seconds').observe(seconds)

    def collect(self, registry):
        """Registry collector: flush the epoch's marginals into
        ``kfac_phase/<label>`` epoch gauges (reset after each flush so a
        phase set that disappears — a variant change, an idle epoch —
        cannot leak a stale number into the next epoch line)."""
        for label, ms in self.epoch_flush().items():
            registry.gauge('kfac_phase/' + label,
                           reset_on_flush=True).set(ms)

    def epoch_flush(self):
        """Per-epoch ``{label: ms}`` (resets the accumulators): marginal
        per-phase costs where a baseline set was observed, joint means
        otherwise, plus ``step_mean``/``step_max``. Empty dict when
        nothing was recorded."""
        means = {k: t / n for k, (t, n) in self._acc.items()}
        out = {}
        for s in sorted(means, key=lambda k: (len(k), sorted(k))):
            bases = [b for b in means if b < s]
            if bases:
                # deterministic base pick; and the FIRST derivation of a
                # label wins — smaller sets are flushed first and their
                # baselines are the better-sampled ones (a refresh step's
                # 'stats' marginal would be the noisiest estimate)
                base = max(bases, key=lambda b: (len(b), tuple(sorted(b))))
                label = '+'.join(sorted(s - base))
                val = max(means[s] - means[base], 0.0)
            else:
                label = '+'.join(sorted(s)) if s else 'step'
                val = means[s]
            if label and label not in out:
                out[label] = val
        if self._n:
            out['step_mean'] = self._total / self._n
            out['step_max'] = self._max
        self._acc, self._max, self._total, self._n = {}, 0.0, 0.0, 0
        return {k: v * 1000.0 for k, v in out.items()}


class Metric:
    """Weighted running average of scalars (loss, accuracy)."""

    def __init__(self, name):
        self.name = name
        self.total = 0.0
        self.n = 0.0

    def update(self, val, n=1):
        self.total += float(val) * n
        self.n += n

    @property
    def avg(self):
        return self.total / max(self.n, 1e-12)

    def sync(self):
        """Cross-process allreduce of (total, n) — the reference's
        allreduce-averaged Metric semantics on a multi-host pod
        (examples/utils.py:39-52). No-op on one process."""
        import jax
        if jax.process_count() == 1:
            return self
        from jax.experimental import multihost_utils
        agg = multihost_utils.process_allgather(
            np.asarray([self.total, self.n], np.float64))
        self.total = float(agg[:, 0].sum())
        self.n = float(agg[:, 1].sum())
        return self


def accuracy(outputs, labels):
    """Top-1 accuracy from logits (reference: examples/utils.py:6-9)."""
    pred = jnp.argmax(outputs, axis=-1)
    return jnp.mean((pred == labels).astype(jnp.float32))


def topk_accuracy(outputs, labels, k=5):
    topk = jnp.argsort(outputs, axis=-1)[:, -k:]
    hit = (topk == labels[:, None]).any(axis=-1)
    return jnp.mean(hit.astype(jnp.float32))
