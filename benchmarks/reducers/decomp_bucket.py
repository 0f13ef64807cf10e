"""What the decomposition's bucket scopes say: ``engine.bucket_scope``
puts each bucket's work under ``decomp.b<D>x<n>`` inside
``kfac.ComputeInverse*``, ``D`` the bucket dim and ``n`` the matrices the
bucket hands back an update on the device. Both are static in a compiled
program, so the name is the counter and nothing but the trace is read.

``what``:

- ``top_ms``: device time of their own (``tracefile.self_ns``) a step of
  the operations under the bucket scope that has the most of it;
- ``top_dim``: that bucket's ``D``;
- ``task_tflops``: the task's work over the time of ``scope``:
  ``sum n * D^3`` over the distinct bucket names in the trace (one
  Cholesky, one triangular inverse and one triangular product at
  ``D^3 / 3`` flop each: the least a Cholesky-route inverse does; the
  ``decomp_task_flop`` of the program's ``kfac.precond.setup`` record)
  times the step programs of the segment that hold a bucket scope, over
  the device time of their own of everything under ``scope``, in TF/s. A
  rate, not a share of a peak: the work is the task's, so a faster route
  reads higher whatever it computes.

Times are averaged over the devices. No trace, or no operation under a
bucket scope (the parent of the PR that added them, a CPU rehearsal):
None.
"""

import bisect
import re

from harness import spans, tracefile

BUCKET = re.compile(r'decomp\.b(\d+)x(\d+)')


def bucket_of(event):
    """``(D, n)`` of the bucket scope in the event's path, or None."""
    found = BUCKET.search(event[3]) or BUCKET.search(event[0])
    return (int(found.group(1)), int(found.group(2))) if found else None


def own_ns_by_bucket(events, own):
    """{(D, n): own device ns} of one device's operations (``own``: their
    ``tracefile.self_ns``)."""
    out = {}
    for event, ns in zip(events, own):
        bucket = bucket_of(event)
        if bucket is not None:
            out[bucket] = out.get(bucket, 0.0) + ns
    return out


def programs_with_buckets(trace, plane, events):
    """How many of the device's step programs hold a bucket scope."""
    starts = [e[1] for e in events if bucket_of(e) is not None]
    return sum(bisect.bisect_left(starts, start + dur)
               > bisect.bisect_left(starts, start)
               for _, start, dur, _ in
               spans.step_modules(trace).get(plane, []))


def reduce(ctx, what, scope='kfac.ComputeInverse'):
    trace = ctx.get('trace')
    if not trace:
        return None
    per_device = tracefile.device_ops(trace['data'])
    if not per_device:
        return None
    by_bucket, flop, scope_ns = {}, 0.0, 0.0
    for plane, events in per_device.items():
        own = tracefile.self_ns(events)
        mine = own_ns_by_bucket(events, own)
        if not mine:
            return None     # the program names no bucket: nothing to read
        for bucket, ns in mine.items():
            by_bucket[bucket] = by_bucket.get(bucket, 0.0) + ns
        flop += (sum(n * d ** 3 for d, n in mine)
                 * programs_with_buckets(trace['data'], plane, events))
        under = set(map(id, tracefile.matching(events, [scope])))
        scope_ns += sum(ns for e, ns in zip(events, own) if id(e) in under)
    (dim, _), top_ns = max(by_bucket.items(), key=lambda kv: kv[1])
    if what == 'top_ms':
        return top_ns / len(per_device) / trace['steps'] / 1e6
    if what == 'top_dim':
        return float(dim)
    if what == 'task_tflops':
        return flop / (scope_ns / 1e9) / 1e12 if flop and scope_ns else None
    raise ValueError(f'what={what!r}: top_ms, top_dim or task_tflops')
