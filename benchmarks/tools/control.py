#!/usr/bin/env python3
"""Read the control at a cell's own size: the reference one precision step
down (``kfac``: K-FAC state and arithmetic alone; ``all``: activations
too), put in the program's place and compared with the reference itself.

    python3 benchmarks/tools/control.py <cell> <kfac|all> <seed> [<seed> ...]

Prints every compared number per seed; the limits in the configuration
file sit below the smallest of these and above the largest that sound runs
of the program give (``PERF.md`` section 2). Needs no program state, so it
costs only the reference's time."""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from harness import check, files, weights  # noqa: E402
from harness.program import data_key  # noqa: E402


def main():
    import run
    run.place_cache()
    cell, _ = files.resolve_workload(sys.argv[1])
    config, _ = files.load_json('configs', cell['config'])
    traffic, _ = files.load_json('traffic', cell['traffic'])
    traffic = dict(traffic, chips=cell['chips'])
    plain = files.load_module('reference', config['plain'])
    ref_mod = files.load_kfac_reference(config)
    chk = config['check']
    mode = sys.argv[2]
    for seed in map(int, sys.argv[3:]):
        key = weights.seed_key(seed)
        out, secs = {}, {}
        for lower in (False, mode):
            t = time.perf_counter()
            out[lower] = ref_mod.run(
                plain, config, traffic,
                weights.params_fn(config['init']), key, data_key(key),
                chk['steps'], lower=lower,
                keep_factors=chk['sampled_layers'])
            secs[lower] = time.perf_counter() - t
        nums = check.numbers(out[mode], out[False])
        print(json.dumps({
            'cell': cell['name'], 'lower': mode, 'seed': seed,
            **{k: {'value': v, 'where': w, 'limit': chk['limits'][k]}
               for k, (v, w) in nums.items()},
            'reference_s': secs[False], 'control_s': secs[mode],
            'losses_control': out[mode]['losses'],
            'losses_reference': out[False]['losses']}), flush=True)


if __name__ == '__main__':
    main()
