#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and what a device event
carries. ``python3 benchmarks/tools/trace_summary.py <trace dir or file>
[out.json.gz]``; with a second argument the reduced trace is saved in the
format of ``benchmarks/tests/data/``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import tracefile  # noqa: E402


def main():
    path = sys.argv[1]
    if os.path.isdir(path):
        path = tracefile.find_trace(path)
    trace = tracefile.load(path)
    for plane in trace['planes']:
        print('plane', plane['name'])
        for line in plane['lines']:
            ev = line['events']
            print(f'  line {line["name"]!r}: {len(ev)} events')
            for e in ev[:3] + ev[len(ev) // 2:len(ev) // 2 + 3]:
                print('     ', e[0][:80], e[1], e[2], e[3][:400])
    for name, events in tracefile.device_ops(trace).items():
        scoped = tracefile.matching(events, ['kfac.'])
        print(name, 'ops', len(events), 'with a kfac. scope', len(scoped))
        for e in scoped[:5]:
            print('   ', e[0][:60], e[2], e[3][:300])
        print('   top', tracefile.top_ops(events, 5))
    if len(sys.argv) > 2:
        tracefile.save(trace, sys.argv[2])


if __name__ == '__main__':
    main()
