"""The comparison that decides ``correct``.

Program and reference each give: the loss of the first steps, the
per-leaf norm of the optimizer's first momentum buffer (the first
gradient as the optimizer got it), the per-leaf norm of the parameters'
change after those steps, and the running-average factors of a few
sampled layers after the first step. Norm gaps are taken by the worst
leaf: |program's norm - reference's norm| over the larger of the
reference's norm of that leaf and of the median leaf (some gradients are
all but zero). Each number is printed beside its limit.
"""

import numpy as np


def _worst_norm_gap(prog, ref):
    if set(prog) != set(ref):
        raise ValueError('program and reference disagree on the leaves: '
                         f'{sorted(set(prog) ^ set(ref))[:6]}')
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for leaf, r in ref.items():
        gap = abs(prog[leaf] - r) / max(r, median, 1e-30)
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def numbers(prog, ref):
    """{name: (value, where)} of everything compared."""
    out = {}
    steps = min(len(prog['losses']), len(ref['losses']))
    gaps = [abs(p - r) / abs(r) for p, r in
            zip(prog['losses'][:steps], ref['losses'][:steps])]
    out['loss_gap'] = (max(gaps), f'step {int(np.argmax(gaps))}')
    out['first_update_norm_gap'] = _worst_norm_gap(prog['first_update'],
                                                   ref['first_update'])
    out['param_change_norm_gap'] = _worst_norm_gap(prog['param_change'],
                                                   ref['param_change'])
    worst, where = 0.0, None
    for layer, pair in ref.get('factors', {}).items():
        for side, fr in zip('AG', pair):
            fp = prog['factors'][layer]['AG'.index(side)]
            gap = float(np.linalg.norm(fp - fr) / np.linalg.norm(fr))
            if gap > worst:
                worst, where = gap, f'{layer}:{side}'
    if ref.get('factors'):
        out['factor_gap'] = (worst, where)
    return out


def judge(nums, limits):
    """-> (ok, rows): every number beside its limit."""
    rows, ok = [], True
    for name, (value, where) in nums.items():
        limit = limits[name]
        passed = bool(np.isfinite(value)) and value <= limit
        ok = ok and passed
        rows.append({'check': name, 'value': value, 'limit': limit,
                     'where': where, 'ok': passed})
    return ok, rows
