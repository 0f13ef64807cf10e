"""The device's own time for one step program: the median duration of the
``XLA Modules`` events of the train-step program called ``name`` exactly
(``jit_kfac_step_pred``), or of those with ``has`` in their name, in ms;
over all devices' events. The hash the device appends (``name(1234)``) is
not part of the name. No such program in the trace: None."""

import numpy as np

from harness import spans


def reduce(ctx, name=None, has=None):
    trace = ctx.get('trace')
    if not trace:
        return None
    durs = [e[2] / 1e6
            for events in spans.step_modules(trace['data']).values()
            for e in events
            if (name is None or e[0].partition('(')[0] == name)
            and (has is None or has in e[0])]
    return float(np.median(durs)) if durs else None
