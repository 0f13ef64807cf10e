"""What the host spends on a step when it does not wait: the median
``kfac.step`` span minus the ``kfac.step.read_step`` inside it (the
blocking device read), in ms. No such span in the trace: None."""

import numpy as np

from harness import spans


def reduce(ctx):
    trace = ctx.get('trace')
    if not trace:
        return None
    host = spans.host_events(trace['data'])
    reads = spans.named(host, spans.READ)
    busy = []
    for _, start, dur, _ in spans.named(host, spans.STEP):
        waited = sum(d for _, s, d, _ in reads
                     if start <= s and s + d <= start + dur)
        busy.append((dur - waited) / 1e6)
    return float(np.median(busy)) if busy else None
