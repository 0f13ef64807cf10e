#!/usr/bin/env python3
"""Walk a ladder of constant rates for a cell and say which of them
*trains* for three times its window.

    python3 benchmarks/tools/lr_ladder.py <cell> <seed> <out dir> <rate> [<rate> ...]

For each rate in turn it writes the rate into the cell's configuration
file (in place: run it in a throwaway copy, as the chip tool's is) and
runs the cell, first for 1.5 windows and, if that trains, for 3. A run
trains when ``failed`` is 0 (no step refused, no loss that is not finite,
no health counter raised, nothing compiled in the window), no loss of the
run is above 1.05 x the first, and the mean loss of the last cadence
period is below that of the first (``run.py``'s ``window`` row). A rate
that fails the shorter run would fail the longer one (every condition but
the last is cumulative), so it is not run for longer. The
first rate that trains stays in the file; exit code 1 if none does. Every
run's rows go to ``<out dir>/ladder_<rate>_<seconds>.log``; one summary
line a run is printed. The parent process never touches JAX (the chip
belongs to the child).

No rate of ``bert-base-squad``'s ladder 0.04 x 4^-k trains (PR 28), so this
tool's rule does not set its ``optimizer.lr``. The rule that does (PR 38;
PERF.md section 4): **two rungs of this ladder (a factor of 16) under the
lowest rate on record at which a step was refused, with the rung between
shown quiet** on 8 seeds and the chosen rate on 12, each for two windows
(``failed`` 0, every counter 0). PR 28's rule, the first rung at which no
step was refused on the seeds it ran, put the cell one rung under the
cliff, and three checks drew a seed that fell off it."""

import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import files  # noqa: E402


def trains(rows):
    """-> (verdict, the numbers it rests on) of one run's rows."""
    last = rows[-1] if rows and 'correct' in rows[-1] else None
    win = next((r for r in rows if r.get('phase') == 'window'), None)
    if last is None or win is None:
        return False, {'result': None}
    seen = {'steps': last['attempted'], 'failed': last['failed'],
            'correct': last['correct'], 'health': win['health'],
            'first_bad_step': win['first_bad_step'],
            **{k: win[k] for k in ('loss_first', 'loss_max', 'loss_last',
                                   'loss_mean_first_period',
                                   'loss_mean_last_period')},
            'kl_scale': next((r['kl_scale'] for r in rows
                              if 'kl_scale' in r), None)}
    ok = (last['failed'] == 0 and not any(win['health'].values())
          and win['loss_max'] <= 1.05 * win['loss_first']
          and win['loss_mean_last_period'] < win['loss_mean_first_period'])
    return ok, seen


def run(cell, seed, seconds, log):
    with open(log, 'w') as out, open(log[:-4] + '.err', 'w') as err:
        subprocess.run(
            [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
             cell, '--seed', str(seed), '--seconds', str(seconds),
             '--trace', '0'], stdout=out, stderr=err, check=False)
    with open(log) as f:
        return [json.loads(line) for line in f if line.startswith('{')]


def main():
    cell_name, seed, out_dir, *rates = sys.argv[1:]
    cell, _ = files.resolve_workload(cell_name)
    path, _ = files.find('configs', cell['config'], '.json')
    window = files.benchmark_json()['run_seconds']
    os.makedirs(out_dir, exist_ok=True)
    for rate in rates:
        with open(path) as f:
            text = f.read()
        # the one "lr" of the file, its other bytes left as they are
        text, n = re.subn(r'("lr": )[0-9.e-]+', r'\g<1>' + rate, text)
        if n != 1 or json.loads(text)['optimizer']['lr'] != float(rate):
            sys.exit(f'{path}: expected one optimizer.lr to rewrite')
        with open(path, 'w') as f:
            f.write(text)
        for seconds in (1.5 * window, 3 * window):
            rows = run(cell_name, seed, seconds, os.path.join(
                out_dir, f'ladder_{rate}_{seconds:g}.log'))
            ok, seen = trains(rows)
            print(json.dumps({'lr': float(rate), 'seconds': seconds,
                              'trains': ok, **seen}), flush=True)
            if not ok:
                break
        else:
            print(json.dumps({'chosen_lr': float(rate)}), flush=True)
            return
    sys.exit(1)


if __name__ == '__main__':
    main()
