"""Device time per step of the operations under ``jax.named_scope``s.

Sums the device time of their own (``tracefile.self_ns``: a ``cond`` or
``while`` does not count its body twice) of the traced operations whose
``tf_op`` contains one of ``scopes`` (or, with ``outside`` set, none of
them), averaged over the devices, divided by the steps in the segment.
Nothing to read (no trace, or no operation names a scope at all): None.
"""

from harness import tracefile


def reduce(ctx, scopes=(), outside=()):
    trace = ctx.get('trace')
    if not trace:
        return None
    per_device = tracefile.device_ops(trace['data'])
    if not per_device:
        return None
    total = 0.0
    for events in per_device.values():
        hit = set(map(id, tracefile.matching(events, outside or scopes)))
        if not hit:
            return None     # no event names such a scope: nothing to read
        total += sum(own for e, own in zip(events, tracefile.self_ns(events))
                     if (id(e) in hit) != bool(outside))
    return total / len(per_device) / trace['steps'] / 1e6
