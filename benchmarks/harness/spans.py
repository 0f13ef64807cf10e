"""The program's own spans in a trace, and interval arithmetic on them.

``training.step_fn`` writes one host span per part of itself into the
profiler's trace (``jax.profiler.TraceAnnotation``, so on the clock of the
device events beside them)::

    kfac.step                       the whole of step_fn
      kfac.step.read_step           int(state.step): the device read
      kfac.step.hooks               straggler / autotune / heartbeat / faults / upgrades
      kfac.step.select              choosing the variant, building hyper
      kfac.step.build/<variant>     a cache miss; stays open over the first call
      kfac.step.dispatch/<phases>   the jitted call

(``/`` and not ``:`` before the variable part: the profiler's converter
reads ``name:word`` as a TensorFlow ``op:type`` and keeps ``word`` alone) and
names each step program (``jit_kfac_step_<phases>`` / ``jit_sgd_step``
on the device's ``XLA Modules`` line). ``tracefile.read`` drops host events
under 20 us, so a reducer sums gap time *under* spans, or takes spans that
are longer (``kfac.step``, ``read_step``, ``dispatch``); a dropped child's
time falls to its parent. A program without these spans (the parent of the
PR that added them) gives empty lists, and every reducer then reads nothing.

**The device's timeline is not the host's.** The profiler puts device events
on the host's clock by an alignment of its own, and on the v5e machine that
alignment is out by more than a millisecond: in the recorded trace
(``tests/data/tiny-bert-pallas-freq10.v5e.json.gz``) every one of 90
programs *starts* 1.34-1.46 ms *before* the runtime's ``DoEnqueueProgram``
hands it to the device (looked at by hand, PR 24). Durations and everything
within one timeline are unaffected; what compares the two (which span a gap
falls under, how long a dispatched program waits) first shifts the device's
timeline by ``device_shift_ns``: the smallest shift that lets no step
program start before it was handed over.
"""

from harness import tracefile

STEP = 'kfac.step'
READ = 'kfac.step.read_step'
DISPATCH = 'kfac.step.dispatch/'
BUILD = 'kfac.step.build/'
#: the train step's programs on the device's ``XLA Modules`` line
STEP_MODULES = ('jit_kfac_step', 'jit_sgd_step')
#: the runtime's hand-over of a program to the device (a host thread's event)
ENQUEUE = 'DoEnqueueProgram'


def host_events(trace):
    """Every host event ``[name, start, dur, text]``, sorted by start."""
    return sorted((e for plane in trace['planes']
                   if not tracefile.is_device_plane(plane['name'])
                   for line in plane['lines'] for e in line['events']),
                  key=lambda e: e[1])


def named(events, name):
    """Events called ``name``, or ``name...`` when it ends in ``/``."""
    if name.endswith('/'):
        return [e for e in events if e[0].startswith(name)]
    return [e for e in events if e[0] == name]


def intervals(events):
    """Merged ``[[start, end], ...]`` of events."""
    return tracefile.union([(e[1], e[1] + e[2]) for e in events])


def overlap_ns(a, b):
    """Nanoseconds in both of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(ops):
    """The gaps between a device's operations: merged intervals in which
    none ran, from the first operation's start to the last one's end (the
    segment ``run.py`` takes ``device_idle_pct`` over)."""
    busy = intervals(ops)
    return [[a[1], b[0]] for a, b in zip(busy, busy[1:])]


def step_modules(trace):
    """{device plane: the train-step programs' events on ``XLA Modules``,
    sorted by start}."""
    out = {}
    for plane in trace['planes']:
        if not tracefile.is_device_plane(plane['name']):
            continue
        events = [e for line in plane['lines']
                  if line['name'] == 'XLA Modules' for e in line['events']
                  if e[0].startswith(STEP_MODULES)]
        if events:
            out[plane['name']] = sorted(events, key=lambda e: e[1])
    return out


def device_shift_ns(host, modules):
    """Nanoseconds to add to a device's timeline so that it agrees with
    the host's in order: the largest, over the traced steps, of (the
    hand-over of the step's program - its start on the device as the
    profiler placed it). The hand-over is the last ``DoEnqueueProgram``
    inside the step's ``kfac.step.dispatch/*`` span, or, where the reader
    dropped it (under 20 us), the span's own start: every such difference
    is a lower bound of the true offset, so the largest is the tightest.
    It leaves out the time from hand-over to start, which on an idle
    device is short (the 90 differences of the recorded trace lie within
    0.13 ms). ``modules``: one device's train-step programs
    (``step_modules``), paired in order with the dispatch spans; counts
    that differ: None."""
    dispatches = named(host, DISPATCH)
    if not dispatches or len(dispatches) != len(modules):
        return None
    enqueues = named(host, ENQUEUE)
    bounds = []
    for (_, start, dur, _), module in zip(dispatches, modules):
        handed = [e[1] for e in enqueues if start <= e[1] <= start + dur]
        bounds.append((handed[-1] if handed else start) - module[1])
    return max(bounds)


def shifted(events, ns):
    """Events moved by ``ns`` on the time axis."""
    return [[name, start + ns, dur, text] for name, start, dur, text in events]


def own_device_ms_per_step(trace_ctx, pick):
    """Device time of their own (``tracefile.self_ns``) of the operations
    ``pick(events)`` selects on each device, averaged over the devices,
    divided by the traced steps, in ms. ``pick`` returns the selected
    events, or None where the device has nothing of the kind to read;
    then, as with no device plane at all, the result is None."""
    per_device = tracefile.device_ops(trace_ctx['data'])
    if not per_device:
        return None
    total = 0.0
    for events in per_device.values():
        picked = pick(events)
        if picked is None:
            return None
        hit = set(map(id, picked))
        total += sum(own for e, own in zip(events, tracefile.self_ns(events))
                     if id(e) in hit)
    return total / len(per_device) / trace_ctx['steps'] / 1e6
