"""Device time per step of the operations whose name or ``tf_op`` holds
every string of ``all_of`` and none of ``none_of``.

Inside ``train.grad`` JAX's own path tells the passes apart: a backward
operation's ``tf_op`` carries ``transpose(jvp(``, a forward one ``jvp(``
without it. A fusion carries one ``tf_op`` (its root's), so one that mixes
both passes counts whole for the pass of its root. Time of their own
(``tracefile.self_ns``), averaged over the devices, divided by the steps.
No operation holds all of ``all_of``: None.
"""

from harness import spans, tracefile


def reduce(ctx, all_of, none_of=()):
    trace = ctx.get('trace')
    if not trace:
        return None

    def pick(events):
        for text in all_of:
            events = tracefile.matching(events, [text])
        if not events:
            return None
        drop = set(map(id, tracefile.matching(events, none_of)))
        return [e for e in events if id(e) not in drop]

    return spans.own_device_ms_per_step(trace, pick)
