"""How many device operations one step program runs under ``scope``: the
count of the traced operations whose name or ``tf_op`` holds ``scope`` and
that start inside one train-step program (``XLA Modules``), the median over
the programs of the segment that hold any (at cadence 10 the three update
programs of 30 steps), over all devices. Every event counts, a ``while``
or ``cond`` and each operation of its body alike: the length of the chain
the device walks, not a time. No trace, no step program or no operation
under the scope (the plain steps of a cell, a CPU rehearsal): None; and
with ``needs`` given, None too where no operation holds that string (the
chain of a program that does not name the scope's inside stands beside no
stage to compare it with).
"""

import bisect

import numpy as np

from harness import spans, tracefile


def counts(trace, scope):
    """Per step program that holds any, the operations under ``scope``."""
    per_device = tracefile.device_ops(trace)
    out = []
    for plane, modules in spans.step_modules(trace).items():
        starts = [e[1] for e in tracefile.matching(per_device.get(plane, []),
                                                   [scope])]
        for _, start, dur, _ in modules:
            n = (bisect.bisect_left(starts, start + dur)
                 - bisect.bisect_left(starts, start))
            if n:
                out.append(n)
    return out


def named(trace, needs):
    """Does any device operation of the trace hold ``needs``."""
    return needs is None or any(
        tracefile.matching(events, [needs])
        for events in tracefile.device_ops(trace).values())


def reduce(ctx, scope, needs=None):
    trace = ctx.get('trace')
    if not trace or not named(trace['data'], needs):
        return None
    found = counts(trace['data'], scope)
    return float(np.median(found)) if found else None
