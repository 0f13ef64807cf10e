#!/usr/bin/env python3
"""Compact table of the runs logged under a directory (``sets.sh``): per
run the result line's metrics, ``correct`` and the compared numbers; then,
per metric, each set's median and quartile spread as the contract takes
them (``statistics.quantiles(values, n=4)``, (q3 - q1) / median)."""

import glob
import json
import os
import statistics
import sys


def rows_of(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.startswith('{')]


def main():
    sets = {}
    for path in sorted(glob.glob(os.path.join(sys.argv[1], '*.log'))):
        name = os.path.basename(path)[:-4]
        rows = rows_of(path)
        last = rows[-1] if rows and 'correct' in rows[-1] else None
        if last is None:
            print(name, 'NO RESULT')
            continue
        checks = {r['check']: r['value'] for r in rows
                  if r.get('phase') == 'check' and 'check' in r}
        setup = next((r for r in rows if r.get('phase') == 'setup'), {})
        ref = next((r.get('reference_s') for r in rows
                    if 'reference_s' in r), None)
        metrics = {k: v['value'] for k, v in last['metrics'].items()}
        print(json.dumps({
            'run': name, 'correct': last['correct'],
            'attempted': last['attempted'], 'failed': last['failed'],
            **{k: round(v, 4) for k, v in metrics.items()},
            **{k: float(f'{v:.3g}') for k, v in checks.items()},
            'compiles': setup.get('compiles'), 'hits': setup.get('cache_hits'),
            'reference_s': ref and round(ref, 1),
            'peak_gb': round(last['device']['memory_peak_bytes'] / 1e9, 2)}))
        if name.startswith('set'):
            for k, v in metrics.items():
                sets.setdefault((name[:4], k), []).append(v)
    for (s, k), vals in sorted(sets.items()):
        if len(vals) >= 2:
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            print(f'{s} {k}: n={len(vals)} median={med:.6g} '
                  f'spread={(q[2] - q[0]) / med:.5f} '
                  f'min={min(vals):.6g} max={max(vals):.6g}')


if __name__ == '__main__':
    main()
