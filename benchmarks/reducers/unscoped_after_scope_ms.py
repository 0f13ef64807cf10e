"""Device time per step of the compiler's own operations that run BEHIND
``scope``: the operations that carry no source path at all (no ``tf_op``:
layout copies, ``dynamic-update-slice``, ``copy-done``) and run, inside a
train-step program (``XLA Modules``), after an operation under ``scope``
and before the next operation that has a path. That is where such an
operation most likely belongs (the rule ``tools/scope_classes.py`` applies
by hand), not a proof: the compiler may have moved it. At the start of a
program nothing is behind anything. A part of what
``unscoped_ms_per_step`` counts, never more. Time of their own
(``tracefile.self_ns``), averaged over the devices, divided by the steps.
No trace, no step program, or no operation under ``scope``: None; with
``needs`` given, None too on a device none of whose operations holds that
string.
"""

import bisect

from harness import spans, tracefile


def has_path(event):
    return 'tf_op=' in event[3]


def behind(events, own, modules, scope):
    """``(event, own ns)`` of each pathless operation behind ``scope`` in
    the step programs ``modules`` of one device (``events`` sorted by
    start, ``own`` their ``tracefile.self_ns``)."""
    starts = [e[1] for e in events]
    for _, start, dur, _ in modules:
        after = False
        for i in range(bisect.bisect_left(starts, start),
                       bisect.bisect_left(starts, start + dur)):
            event = events[i]
            if has_path(event):
                after = scope in event[3] or scope in event[0]
            elif after:
                yield event, own[i]


def reduce(ctx, scope, needs=None):
    trace = ctx.get('trace')
    if not trace:
        return None
    per_device = tracefile.device_ops(trace['data'])
    modules = spans.step_modules(trace['data'])
    if not per_device or not modules:
        return None
    total = 0.0
    for plane, events in per_device.items():
        if not (tracefile.matching(events, [scope]) and (
                needs is None or tracefile.matching(events, [needs]))):
            return None     # no event names such a scope: nothing to read
        total += sum(ns for _, ns in behind(
            events, tracefile.self_ns(events), modules.get(plane, []),
            scope))
    return total / len(per_device) / trace['steps'] / 1e6
