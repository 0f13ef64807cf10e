"""From a profiler trace to intervals: the reduction every reducer shares.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb`` and,
beside it, ``*.trace.json.gz`` (Chrome trace format). On the v5e only the
second carries what names an operation's ``jax.named_scope`` path: each
device event's ``args.tf_op`` (``jit(..)/kfac.ComputeFactor/dot_general:``);
``jax.profiler.ProfileData`` shows the xplane's events with their start and
duration but without that metadata (looked at by hand, PR 23). ``read``
turns the JSON into a plain structure, which is also the format of the
recorded traces under ``tests/data/`` (``.json.gz``)::

    {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': [[name, start_ns, dur_ns, text], ...]}
    ]}]}

``name`` is the operation's short HLO name (``fusion.3145``), ``text`` its
``tf_op`` and ``hlo_category``.
"""

import glob
import gzip
import json
import os

#: device-plane lines whose events are single operations on the device
OP_LINES = ('XLA Ops',)
#: lines kept when reading (the rest -- overlays, per-unit lines -- is bulk)
DEVICE_LINES = ('Steps', 'XLA Modules', 'XLA Ops')
#: host events shorter than this are dropped when reading (size)
HOST_MIN_NS = 20_000


def find_trace(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.trace.json.gz')))
    if not paths:
        raise FileNotFoundError(f'no .trace.json.gz under {trace_dir}')
    return paths[-1]


def is_device_plane(name):
    return name.startswith('/device:') and 'CUSTOM' not in name.upper()


def read(path):
    """The profiler's Chrome trace -> the plain structure above."""
    with gzip.open(path, 'rt') as f:
        raw = json.load(f)['traceEvents']
    planes, lines = {}, {}
    for e in raw:
        if e.get('ph') == 'M' and e.get('name') == 'process_name':
            planes[e['pid']] = e['args']['name']
        elif e.get('ph') == 'M' and e.get('name') == 'thread_name':
            lines[(e['pid'], e['tid'])] = e['args']['name']
    events = {}
    for e in raw:
        if e.get('ph') != 'X':
            continue
        plane = planes.get(e['pid'], str(e['pid']))
        line = lines.get((e['pid'], e.get('tid')), str(e.get('tid')))
        args = e.get('args', {})
        if is_device_plane(plane):
            if line not in DEVICE_LINES:
                continue
            start = int(args.get('device_offset_ps', e['ts'] * 1e6)) / 1e3
            dur = int(args.get('device_duration_ps', e['dur'] * 1e6)) / 1e3
            text = ' '.join(f'{k}={args[k]}' for k in
                            ('tf_op', 'hlo_category') if k in args)
        else:
            start, dur, text = e['ts'] * 1e3, e.get('dur', 0) * 1e3, ''
            if dur < HOST_MIN_NS:
                continue
        events.setdefault((plane, line), []).append(
            [e['name'], start, dur, text])
    out = {}
    for (plane, line), evs in events.items():
        out.setdefault(plane, []).append({'name': line, 'events': evs})
    return {'planes': [{'name': p, 'lines': ls} for p, ls in out.items()]}


def load(path):
    """A profiler's ``*.trace.json.gz`` or a recorded ``*.json.gz``."""
    if path.endswith('.trace.json.gz'):
        return read(path)
    with gzip.open(path, 'rt') as f:
        return json.load(f)


def save(trace, path):
    with gzip.open(path, 'wt') as f:
        json.dump(trace, f, separators=(',', ':'))


def device_ops(trace):
    """{device plane name: [[name, start, dur, text], ...]} sorted by
    start: the single operations that ran on each device."""
    out = {}
    for plane in trace['planes']:
        if not is_device_plane(plane['name']):
            continue
        events = [e for line in plane['lines'] if line['name'] in OP_LINES
                  for e in line['events']]
        if events:
            out[plane['name']] = sorted(events, key=lambda e: e[1])
    return out


def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events, lo=None, hi=None):
    """Nanoseconds in which at least one of ``events`` ran (clipped to
    [lo, hi] when given)."""
    spans = []
    for _, s, d, _ in events:
        a, b = s, s + d
        if lo is not None:
            a, b = max(a, lo), min(b, hi)
        if b > a:
            spans.append((a, b))
    return sum(e - s for s, e in union(spans))


def self_ns(events):
    """Per event, its duration minus what its direct children cover: a
    ``cond``, ``while`` or ``call`` is an event that encloses the events
    of its body, so plain durations would count that time twice. Events
    must be sorted by start (``device_ops`` does)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [float(e[2]) for e in events]
    stack = []                          # (end, index) of open ancestors
    for i in order:
        start, dur = events[i][1], events[i][2]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0]:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, i))
    return [max(v, 0.0) for v in own]


def matching(events, scopes):
    """Events whose name or stats text contains one of ``scopes``."""
    return [e for e in events
            if any(sc in e[0] or sc in e[3] for sc in scopes)]


def top_ops(events, n=10):
    """[[name, seconds], ...] of the operations with most device time of
    their own (children of a ``cond`` or ``while`` count for themselves)."""
    total = {}
    for (name, _, _, text), d in zip(events, self_ns(events)):
        cat = text.partition('hlo_category=')[2]
        if cat:
            name = f'{name} ({cat})'
        total[name] = total.get(name, 0) + d
    return [[k, v / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace, events, lo, hi, n=10):
    """[[what the host was doing, seconds], ...]: the longest gaps
    between device operations inside [lo, hi], each named after the host
    event that covers most of it."""
    busy = union([(max(s, lo), min(s + d, hi)) for _, s, d, _ in events
                  if min(s + d, hi) > max(s, lo)])
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [e for plane in trace['planes']
            if not is_device_plane(plane['name'])
            for line in plane['lines'] for e in line['events']]
    out = []
    for a, b in gaps:
        best, cover, best_d = 'host: nothing recorded', 0, float('inf')
        for name, s, d, _ in host:
            c = min(b, s + d) - max(a, s)
            # the innermost (shortest) event that covers the most
            if c > cover or (c == cover and c > 0 and d < best_d):
                best, cover, best_d = name, c, d
        out.append([best, (b - a) / 1e9])
    return out
