"""``kfac_plain``'s K-FAC, step for step, within a one-chip host's memory.

The same algorithm, conventions and device pass as ``kfac_plain.py`` (whose
``_device_step``, ``_inverse_of``, ``_grad_matrix`` and ``_write_matrix``
this file calls: read that module's docstring for the algebra), for a
configuration whose K-FAC state is too large for ``kfac_plain.run`` as it
stands: with 131 layers and 2.96 GB of float32 factors that function holds
the float64 running averages, the float64 Cholesky factors, every layer's
float64 gradient matrix AND every layer's preconditioned matrix at once,
and the run was killed at the host's 40 GiB (PERF.md, PR 39). Here a
layer's preconditioned gradient is written over its gradient as soon as it
is made and the KL clip's factor is applied in a second pass over the same
arrays, so neither dict of matrices exists; what the device pass returned
is dropped before the host algebra starts; and before anything else the
process hands back what compiling the step programs left in its heap (a
first run in a checkout compiles them in this process: ``_release_heap``).
The numbers are ``kfac_plain``'s
(``tests/test_sparse_lm.py`` holds the two against each other on the
rehearsal configuration).
"""

import gc

import jax
import numpy as np

from harness import files

_kp = files.load_module('reference', 'kfac_plain')


def _release_heap():
    """Drop JAX's caches of traced and compiled programs (the program's
    side of the run is over) and ask the C allocator to return freed pages
    to the system. Best effort: where either is not there, nothing."""
    import ctypes
    jax.clear_caches()
    gc.collect()
    try:
        ctypes.CDLL('libc.so.6').malloc_trim(0)
    except (OSError, AttributeError):
        pass


def run(model, cfg, traffic, make_params, param_key, data_key, steps,
        lower=False, keep_factors=()):
    """As ``kfac_plain.run``: same arguments, same result."""
    _release_heap()
    mcfg = cfg['model']
    layers = model.kfac_layers(mcfg)
    k, opt = cfg['kfac'], cfg['optimizer']
    act = cfg['dtype']['activations']
    if lower not in (False, 'kfac', 'all'):
        raise ValueError(f'lower={lower!r}')
    low_act = _kp._LOWER_ACT[act] if lower == 'all' else None
    stat_dtype = 'bfloat16' if lower else 'float32'
    frozen = _kp._Frozen(
        (mcfg, layers, act, low_act, stat_dtype,
         cfg['dtype']['matmul_precision']),
        (cfg['name'], act, low_act, stat_dtype))
    q = _kp._bf16 if lower else None

    shapes = model.param_shapes(mcfg)
    initial = jax.jit(lambda key: make_params(shapes, key))
    params = {p: np.asarray(v) for p, v in initial(param_key).items()}
    momentum = {p: np.zeros_like(v) for p, v in params.items()}
    factors = {l['path']: [np.eye(l['kernel'][-2] * (
        l['kernel'][0] * l['kernel'][1] if l['kind'] == 'conv' else 1)
        + int(l['bias'])), np.eye(l['kernel'][-1])] for l in layers}
    inverses = {}
    out = {'losses': [], 'factors': {}}
    w = k['ema_new_weight']

    for step in range(steps):
        batch = model.make_batch(
            mcfg, traffic,
            jax.random.fold_in(data_key, step % traffic['pool']))
        upd_f = step % traffic['fac_update_freq'] == 0
        upd_i = step % traffic['kfac_update_freq'] == 0
        loss, grads, stats = _kp._device_step(model, frozen, params, batch,
                                              upd_f)
        out['losses'].append(float(loss))
        grads = {p: np.asarray(v, np.float64) for p, v in grads.items()}
        if upd_f:
            seen = jax.device_get({p: s[2] for p, s in stats.items()})
            for layer in layers:
                path = layer['path']
                if seen[path] == 0:
                    continue        # no row came to it: nothing to average
                for side in (0, 1):
                    stat = np.asarray(
                        jax.device_get(stats[path][side]), np.float64)
                    avg = factors[path][side]
                    avg *= 1.0 - w
                    stat *= w
                    avg += stat
                    if q:
                        factors[path][side] = q(avg)
            if step == 0:
                out['factors'] = {p: [f.copy() for f in factors[p]]
                                  for p in keep_factors}
        del stats, batch, loss
        gc.collect()
        if upd_i:
            for layer in layers:
                fa, fg = factors[layer['path']]
                pi = (np.trace(fa) / fa.shape[0]) / (
                    np.trace(fg) / fg.shape[0])
                inverses[layer['path']] = (
                    _kp._inverse_of(
                        _kp._damped(fa, np.sqrt(k['damping'] * pi)), q),
                    _kp._inverse_of(
                        _kp._damped(fg, np.sqrt(k['damping'] / pi)), q))
        # G^-1 dW A^-1 = (A^-1 (G^-1 dW)')', written over dW at once
        vg = 0.0
        for layer in layers:
            inv_a, inv_g = inverses[layer['path']]
            mat = _kp._grad_matrix(layer, grads)
            pre = inv_a(inv_g(mat).T).T
            vg += float((pre * mat).sum())
            _kp._write_matrix(layer, grads, pre)
        nu = min(1.0, np.sqrt(k['kl_clip'] / abs(vg * opt['lr'] ** 2)))
        out.setdefault('kl_scale', []).append(float(nu))
        for layer in layers:
            _kp._kernel(layer, grads)[...] *= nu
            if layer['bias']:
                grads[layer['path'] + '/bias'] *= nu
        for p in params:
            u = grads[p] + opt['weight_decay'] * params[p]
            momentum[p] = (opt['momentum'] * momentum[p] + u).astype(
                np.float32)
            params[p] = (params[p] - opt['lr'] * momentum[p]).astype(
                np.float32)
        del grads
        if step == 0:
            out['first_update'] = {
                p: float(np.linalg.norm(m.astype(np.float64)))
                for p, m in momentum.items()}
    # the initial weights are made again, not kept
    out['param_change'] = {
        p: float(np.linalg.norm(params[p].astype(np.float64)
                                - np.asarray(v, np.float64)))
        for p, v in initial(param_key).items()}
    return out
