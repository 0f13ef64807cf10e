#!/usr/bin/env python3
"""Where a trace's device time sits, by scope: ``python3
benchmarks/tools/scope_classes.py <trace dir or file>``.

Per step program (``XLA Modules``), the median device time and, of the
operations inside it, the time of their own by scope (``kfac.*``,
``train.grad`` forward and backward, the other ``train.*``). The compiler
adds operations that carry no source path at all (layout copies,
``dynamic-update-slice``, ``copy-done``): they are listed by category and
by the scope of the scoped operation that ran before them, which is where
they most likely belong. A look by hand, not a metric (PERF.md section 5).
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import spans, tracefile  # noqa: E402


def scope_of(event):
    text = event[0] + ' ' + event[3]
    for prefix in ('kfac.', 'train.'):
        if prefix in text:
            name = prefix + text.split(prefix)[1].split('/')[0].split(' ')[0]
            if name.startswith('train.grad'):
                name += ' backward' if 'transpose(' in text else ' forward'
            return name
    return 'other path' if 'tf_op=' in text else None


def main():
    path = sys.argv[1]
    if os.path.isdir(path):
        path = tracefile.find_trace(path)
    trace = tracefile.load(path)
    for plane, events in tracefile.device_ops(trace).items():
        own = tracefile.self_ns(events)
        programs = collections.defaultdict(list)
        for module in spans.step_modules(trace).get(plane, []):
            programs[module[0].partition('(')[0]].append(module)
        for name, modules in programs.items():
            scoped, after, category = (collections.Counter()
                                       for _ in range(3))
            for _, start, dur, _ in modules:
                before = 'program start'
                for event, ns in zip(events, own):
                    if not start <= event[1] < start + dur:
                        continue
                    scope = scope_of(event)
                    if scope is None:
                        after[before] += ns
                        category[event[3].rpartition('hlo_category=')[2]
                                 or 'none'] += ns
                    else:
                        scoped[scope] += ns
                        before = scope
            n = len(modules)
            durs = sorted(m[2] for m in modules)
            print(f'{plane} {name}: {n} runs, median '
                  f'{durs[n // 2] / 1e6:.3f} ms')
            for title, table in (('own time by scope', scoped),
                                 ('no source path, by category', category),
                                 ('no source path, by the scope before',
                                  after)):
                print(f'  {title} (ms a run; total '
                      f'{sum(table.values()) / n / 1e6:.3f}):')
                for key, ns in table.most_common(12):
                    print(f'    {ns / n / 1e6:9.3f}  {key}')


if __name__ == '__main__':
    main()
