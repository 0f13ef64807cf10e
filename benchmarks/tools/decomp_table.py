#!/usr/bin/env python3
"""Where the decomposition's time sits, bucket by bucket and stage by
stage: ``python3 benchmarks/tools/decomp_table.py <trace dir or file>
[out.json]``.

Reads the scopes ``engine.bucket_scope`` and ``ops.psd_inverse`` put
inside ``kfac.ComputeInverse*`` (``decomp.b<D>x<n>`` round
``decomp.cholesky`` / ``.solve_lower`` / ``.solve_upper`` / ``.damp`` /
``.settle`` / ``.write``). Per step program that holds them, a run of the
program being the mean over its runs in the trace:

- a row a bucket: device time of their own of its operations, split by
  stage (``other``: under the bucket and under no stage), how many
  operations that is and how many of them are custom calls (``Cholesky``
  and ``InvertDiagBlocksLowerTriangular`` on 128-blocks: the links of the
  sequential chain) and what they take; then the same time a matrix,
  per ``n * D^3`` (as TF/s of the task's work: if this column is flat over
  the buckets the arithmetic sets the pace) and per chain step (``groups *
  D / 128`` steps of 128 columns, groups as ``ops.inverse_tiling`` cuts
  ``n`` rows: if this column is flat the chain does);
- ``outside buckets``: what runs under ``scope`` and under no bucket (the
  trace averages, the damping vectors);
- the compiler's pathless operations behind ``scope`` by ``hlo_category``
  and by name without its number (``reducers/unscoped_after_scope_ms.py``'s
  rule).

A look by hand like ``scope_classes.py`` (PERF.md section 5); the
benchmark's metrics read the same scopes through ``reducers/``. A trace
without bucket scopes prints nothing but that.
"""

import collections
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
from harness import files, spans, tracefile  # noqa: E402

SCOPE = 'kfac.ComputeInverse'
STAGES = ('cholesky', 'solve_lower', 'solve_upper', 'damp', 'settle',
          'write', 'eigh', 'newton_schulz')
STAGE = re.compile(r'decomp\.(' + '|'.join(STAGES) + r')\b')
BLOCK_CALL = 'hlo_category=custom-call'


def groups_of(rows, dim):
    """How many groups of rows the decomposition cuts a bucket into."""
    from kfac_pytorch_tpu.ops.linalg import inverse_tiling
    size, _ = inverse_tiling(rows, dim)
    return -(-rows // size)


def table(trace):
    """{program name: {'runs', 'median_ms', 'buckets': {(D, n): row},
    'outside_ms', 'behind': {category: ms}, 'behind_by_name': {name: ms}}},
    times a run of the program."""
    bucket_of = files.load_module('reducers', 'decomp_bucket').bucket_of
    behind = files.load_module('reducers', 'unscoped_after_scope_ms').behind
    out = {}
    for plane, events in tracefile.device_ops(trace).items():
        own = tracefile.self_ns(events)
        programs = collections.defaultdict(list)
        for module in spans.step_modules(trace).get(plane, []):
            programs[module[0].partition('(')[0]].append(module)
        for name, modules in programs.items():
            rows = collections.defaultdict(
                lambda: collections.defaultdict(float))
            outside = 0.0
            for event, ns in zip(events, own):
                if SCOPE not in event[3] or not any(
                        m[1] <= event[1] < m[1] + m[2] for m in modules):
                    continue
                bucket = bucket_of(event)
                if bucket is None:
                    outside += ns
                    continue
                stage = STAGE.search(event[3])
                row = rows[bucket]
                row[stage.group(1) if stage else 'other'] += ns
                row['ops'] += 1
                if BLOCK_CALL in event[3]:
                    row['block_calls'] += 1
                    row['block_calls_ns'] += ns
            if not rows:
                continue
            after, named = collections.Counter(), collections.Counter()
            for event, ns in behind(events, own, modules, SCOPE):
                after[event[3].rpartition('hlo_category=')[2] or 'none'] += ns
                named[re.sub(r'\.\d+$', '', event[0])] += ns
            runs = len(modules)
            durs = sorted(m[2] for m in modules)
            out[f'{plane} {name}'] = {
                'runs': runs, 'median_ms': durs[runs // 2] / 1e6,
                'buckets': {b: {k: v / runs for k, v in row.items()}
                            for b, row in sorted(rows.items())},
                'outside_ms': outside / runs / 1e6,
                'behind': {k: v / runs / 1e6
                           for k, v in after.most_common()},
                'behind_by_name': {k: v / runs / 1e6
                                   for k, v in named.most_common(8)}}
    return out


def show(result):
    if not result:
        print('no operation under a decomp.b<D>x<n> scope in this trace')
    for name, prog in result.items():
        print(f'{name}: {prog["runs"]} runs, median {prog["median_ms"]:.3f} '
              f'ms; ms a run of the program')
        stages = [s for s in STAGES + ('other',)
                  if any(s in row for row in prog['buckets'].values())]
        head = (['bucket', 'total'] + list(stages)
                + ['ops', 'calls', 'calls_ms', 'ms/matrix', 'task TF/s',
                   'groups', 'ms/chain step'])
        print('  ' + ' '.join(f'{h:>12}' for h in head))
        whole = 0.0
        for (dim, n), row in prog['buckets'].items():
            total = sum(row.get(s, 0.0) for s in stages) / 1e6
            whole += total
            groups = groups_of(n, dim)
            cells = ([f'b{dim}x{n}', f'{total:.3f}']
                     + [f'{row.get(s, 0.0) / 1e6:.3f}' for s in stages]
                     + [f'{row["ops"]:.0f}', f'{row.get("block_calls", 0):.0f}',
                        f'{row.get("block_calls_ns", 0.0) / 1e6:.3f}',
                        f'{total / n:.4f}',
                        f'{n * dim ** 3 / (total / 1e3) / 1e12:.3f}'
                        if total else '-',
                        f'{groups}',
                        f'{total / (groups * max(dim // 128, 1)):.4f}'])
            print('  ' + ' '.join(f'{c:>12}' for c in cells))
        print(f'  under buckets {whole:.3f}, outside buckets (trace '
              f'averages, damping vectors) {prog["outside_ms"]:.3f}, '
              f'{SCOPE} {whole + prog["outside_ms"]:.3f}')
        print(f'  pathless operations behind {SCOPE}, by category (total '
              f'{sum(prog["behind"].values()):.3f}):')
        for category, ms in prog['behind'].items():
            print(f'    {ms:9.3f}  {category}')
        print('  the same by name:')
        for name, ms in prog['behind_by_name'].items():
            print(f'    {ms:9.3f}  {name}')


def main():
    path = sys.argv[1]
    if os.path.isdir(path):
        path = tracefile.find_trace(path)
    result = table(tracefile.load(path))
    show(result)
    if len(sys.argv) > 2:
        with open(sys.argv[2], 'w') as f:
            json.dump({name: dict(prog, buckets={
                f'b{d}x{n}': row for (d, n), row in prog['buckets'].items()})
                for name, prog in result.items()}, f, indent=1)


if __name__ == '__main__':
    main()
