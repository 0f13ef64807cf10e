"""How long a dispatched step waits for the device: over the traced steps,
(start of the step's program on the device's ``XLA Modules`` line - start
of its ``kfac.step.dispatch/*`` span on the host), the k-th span paired
with the k-th train-step program, in ms. The device's timeline is first
shifted to agree with the host's (``spans.device_shift_ns``), so no wait
is negative and the shortest is the host's own work from the call to the
hand-over. ``stat``: ``median`` of the waits, or ``shift``: the shift
itself (what the profiler's alignment of the two clocks was out by, at
least). Read from the first device. Nothing to pair (no spans, no named
step programs, or counts that differ): None."""

import numpy as np

from harness import spans


def waits_ms(trace):
    """-> (per traced step: device start minus dispatch start, ms; the
    shift applied, ms), or None."""
    host = spans.host_events(trace)
    modules = next(iter(spans.step_modules(trace).values()), [])
    shift = spans.device_shift_ns(host, modules)
    if shift is None:
        return None
    dispatches = spans.named(host, spans.DISPATCH)
    return ([(m[1] + shift - d[1]) / 1e6
             for d, m in zip(dispatches, modules)], shift / 1e6)


def reduce(ctx, stat='median'):
    trace = ctx.get('trace')
    read = waits_ms(trace['data']) if trace else None
    if read is None:
        return None
    waits, shift = read
    return float(np.median(waits)) if stat == 'median' else shift
