"""Device idle time per step, by what the program was doing in it.

Every instant in which no operation ran on the device (the gaps
``device_idle_pct`` counts) goes to the innermost program span open at
that instant (``harness/spans.py``), not a whole gap to one span:

- ``read_step``: under ``kfac.step.read_step`` (the result travelling back);
- ``dispatch``: under ``kfac.step.dispatch/*`` (enqueue until the device starts);
- ``hooks_select``: the rest of ``kfac.step`` (itself, ``hooks``, ``select``,
  ``build/*``: a child under 20 us is dropped by the reader and its time
  falls to the parent, so the three are read together);
- ``outside``: under no ``kfac.step`` at all (the caller's loop).

The four add up to the idle time of the segment. The device's timeline is
first shifted to agree with the host's (``spans.device_shift_ns``: the
profiler's own alignment is out by more than a millisecond); the total
does not depend on the shift, the split between ``read_step`` and
``dispatch`` does. Averaged over the devices, divided by the steps, in ms.
No ``kfac.step`` span in the trace, no named step program to align by, or
no device plane: None.
"""

from harness import spans, tracefile


def reduce(ctx, part):
    trace = ctx.get('trace')
    if not trace:
        return None
    per_device = tracefile.device_ops(trace['data'])
    modules = spans.step_modules(trace['data'])
    host = spans.host_events(trace['data'])
    step = spans.intervals(spans.named(host, spans.STEP))
    if not per_device or not step:
        return None
    read = spans.intervals(spans.named(host, spans.READ))
    dispatch = spans.intervals(spans.named(host, spans.DISPATCH))
    total = 0.0
    for plane, ops in per_device.items():
        shift = spans.device_shift_ns(host, modules.get(plane, []))
        if shift is None:
            return None
        idle = spans.idle_intervals(spans.shifted(ops, shift))
        under = {'read_step': spans.overlap_ns(idle, read),
                 'dispatch': spans.overlap_ns(idle, dispatch)}
        in_step = spans.overlap_ns(idle, step)
        under['hooks_select'] = (in_step - under['read_step']
                                 - under['dispatch'])
        under['outside'] = sum(b - a for a, b in idle) - in_step
        total += under[part]
    return total / len(per_device) / trace['steps'] / 1e6
