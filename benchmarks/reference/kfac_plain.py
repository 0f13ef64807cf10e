"""K-FAC round SGD with momentum, written down plainly.

The benchmark's yardstick for ``correct``: it imports nothing of the
program and takes nothing the program made. A model is one of the plain
forward passes beside this file; weights and batches come from the
benchmark's own seeded generators. The forward/backward pass and the
factor statistics run on the device as one jitted function (in the
configuration's activation dtype and matmul precision; statistics in
float32 at ``highest``); everything after it -- running averages, damping,
the damped factors' Cholesky solves, the KL clip, the optimizer -- runs on
the host in float64, layer by layer.

The algorithm (Martens & Grosse 2015; conventions of lzhangbv/kfac_pytorch
``inverse_dp`` as the configuration file states them), per K-FAC layer with
input rows ``a`` and output cotangents ``g`` of a batch of N:

- dense: ``a`` and ``g`` are averaged over any sequence axis; a ones
  column is appended to ``a`` for the bias; ``A = a'a / N``,
  ``G = (N g)'(N g) / N`` (the loss is a batch mean).
- conv: ``a`` becomes patch rows in ``(kh, kw, c_in)`` order (+ ones),
  divided by the number of output positions S; ``A = rows'rows / N``;
  ``g`` rows are scaled by ``N S``; ``G = rows'rows / (N S)``.
- rows: a layer that sees some of R rows (an expert of a routed layer).
  The plain model records ``(a, w)`` for it, ``a`` ``[R, d_in]`` and a row
  weight ``w`` in {0, 1}, and states ``loss_rows`` = T, the size of the
  loss's mean; ``n = max(sum w, 1)``; ``A = (a w)'(a w) / n``,
  ``G = (T g w)'(T g w) / n``. With ``w = 1`` and ``R = T`` that is
  ``dense`` on flat tokens. A layer with ``sum w = 0`` on a step keeps
  its running averages.
- running average from the identity: ``F <- (1 - w) F + w stat`` with
  ``w = ema_new_weight``.
- damping split by traces: ``pi = (tr A / dim A) / (tr G / dim G)``,
  ``A^-1 = (A + sqrt(damping pi) I)^-1``, ``G^-1 = (G + sqrt(damping / pi)
  I)^-1``.
- preconditioned gradient ``G^-1 dW A^-1`` with ``dW`` as ``[out, in(+1)]``.
- KL clip: all layers are scaled by ``min(1, sqrt(kl_clip / |sum(pre * dW)
  lr^2|))``.
- optimizer: ``u = g + wd p; m = mu m + u; p = p - lr m``.

A layer's weight is the whole leaf ``<path>/kernel`` (with ``<path>/bias``
where it has one), or, where the layer names ``leaf`` and ``index``, the
slice ``[index]`` of the stacked leaf ``leaf`` ``[E, d_in, d_out]`` (no
bias): experts kept as one leaf for a grouped product have a factor pair
each. The optimizer, ``first_update`` and ``param_change`` go by leaf.

``lower`` is the control, the same computation one precision step down:
``'kfac'`` lowers the K-FAC state and arithmetic alone (``float32 ->
bfloat16``: the statistics, the running averages, the damped factors, an
explicit inverse as a bfloat16 decomposition would be stored, and every
product with it); ``'all'`` lowers the activations as well (``float32 ->
bfloat16 -> float8_e4m3fn``).
"""

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import scipy.linalg

_LOWER_ACT = {'float32': 'bfloat16', 'bfloat16': 'float8_e4m3fn'}


def _rounder(name):
    """x -> x rounded to dtype ``name`` and back, cotangent likewise."""
    low = jnp.dtype(name)

    @jax.custom_vjp
    def rnd(x):
        return x.astype(low).astype(x.dtype)

    def fwd(x):
        return rnd(x), None

    def bwd(_, g):
        return (g.astype(low).astype(g.dtype),)

    rnd.defvjp(fwd, bwd)
    return rnd


def _patch_rows(x, layer):
    """[N, H, W, C] -> ([N * OH * OW, kh * kw * C], OH * OW)."""
    kh, kw, _, _ = layer['kernel']
    s, p = layer['stride'], layer['pad']
    n, h, w, c = x.shape
    oh, ow = (h + 2 * p - kh) // s + 1, (w + 2 * p - kw) // s + 1
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    cols = [xp[:, i:i + s * (oh - 1) + 1:s, j:j + s * (ow - 1) + 1:s, :]
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(cols, axis=-1).reshape(n * oh * ow, -1), oh * ow


def _stats(layer, a, g, stat_dtype):
    """The (A, G) statistics of one layer from its input and cotangent,
    and the number of rows they were taken from."""
    seen = None
    if layer['kind'] == 'rows':
        a, weight = a
        seen = weight.astype(jnp.float32).sum()
        weight = weight.astype(stat_dtype)[:, None]
    a = a.astype(stat_dtype)
    g = g.astype(stat_dtype)
    n = a.shape[0]
    if layer['kind'] == 'conv':
        rows, spatial = _patch_rows(a, layer)
        grows = g.reshape(-1, g.shape[-1]) * (n * spatial)
        gden = n * spatial
    elif layer['kind'] == 'rows':
        rows, grows = a, g * weight * layer['loss_rows']
        spatial = 1
        n = gden = jnp.maximum(seen, 1.0).astype(stat_dtype)
    else:
        rows = a.reshape(n, -1, a.shape[-1]).mean(axis=1)
        grows = g.reshape(n, -1, g.shape[-1]).mean(axis=1) * n
        spatial, gden = 1, n
    if layer['bias']:
        rows = jnp.concatenate(
            [rows, jnp.ones((rows.shape[0], 1), rows.dtype)], axis=-1)
    if seen is not None:
        rows = rows * weight
    rows = rows / spatial
    with jax.default_matmul_precision('highest'):
        big_a = (rows.T @ rows) / n
        big_g = (grows.T @ grows) / gden
    return (big_a.astype(stat_dtype), big_g.astype(stat_dtype),
            n if seen is None else seen)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _device_step(model, frozen, params, batch, with_stats):
    """loss, parameter gradients and (optionally) every layer's statistics."""
    cfg, layers, dtype, low_act, stat_dtype, precision = frozen.value
    rnd = _rounder(low_act) if low_act else (lambda x: x)

    def loss_fn(p, taps, shapes=None):
        return model.forward(cfg, p, batch, taps, jnp.dtype(dtype), rnd,
                             shapes)

    with jax.default_matmul_precision(precision):
        if not with_stats:
            (loss, _), grads = jax.value_and_grad(
                lambda p: loss_fn(p, {}), has_aux=True)(params)
            return loss, grads, None
        shapes = {}
        jax.eval_shape(lambda p: loss_fn(p, {}, shapes), params)
        taps = {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}
        (loss, acts), (grads, cots) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(params, taps)
    stats = {layer['path']: _stats(layer, acts[layer['path']],
                                   cots[layer['path']],
                                   jnp.dtype(stat_dtype))
             for layer in layers}
    return loss, grads, stats


class _Frozen:
    """Hashable wrapper so plain dicts can be static jit arguments."""

    def __init__(self, value, key):
        self.value, self._key = value, key

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


def _bf16(x):
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _damped(factor, eps):
    out = factor.copy()
    out.flat[::len(out) + 1] += eps
    return out


def _inverse_of(damped, q):
    """-> f(M) = damped^-1 M. Stated precision (``q`` None): a float64
    Cholesky solve. The control: the explicit inverse, formed from the
    rounded matrix, rounded, and multiplied with rounding."""
    if q is None:
        chol = scipy.linalg.cho_factor(damped, overwrite_a=True,
                                       check_finite=False)
        return lambda m: scipy.linalg.cho_solve(chol, m, check_finite=False)
    inv = q(np.linalg.inv(q(damped)))
    return lambda m: q(inv @ q(m))


def _kernel(layer, tree):
    """The layer's weight in ``tree``: a whole leaf, or one slice of a
    stacked leaf (a view: writing to it writes to the leaf)."""
    if 'leaf' in layer:
        if layer['bias']:
            raise ValueError(f'{layer["path"]}: a slice of a stacked leaf '
                             'has no bias')
        return tree[layer['leaf']][layer['index']]
    return tree[layer['path'] + '/kernel']


def _grad_matrix(layer, grads):
    k = np.asarray(_kernel(layer, grads), np.float64)
    mat = k.reshape(-1, k.shape[-1]).T
    if layer['bias']:
        b = np.asarray(grads[layer['path'] + '/bias'], np.float64)
        mat = np.concatenate([mat, b[:, None]], axis=1)
    return mat


def _write_matrix(layer, grads, mat):
    shape = layer['kernel']
    w = mat[:, :-1] if layer['bias'] else mat
    _kernel(layer, grads)[...] = w.T.reshape(shape)
    if layer['bias']:
        grads[layer['path'] + '/bias'] = mat[:, -1]


def run(model, cfg, traffic, make_params, param_key, data_key, steps,
        lower=False, keep_factors=()):
    """Train ``steps`` steps from the seed and return what ``correct``
    compares: ``losses`` (per step), ``first_update`` (per-leaf norm of
    the optimizer's first momentum buffer, i.e. the first gradient as the
    optimizer got it plus weight decay), ``param_change`` (per-leaf norm
    of ``p_steps - p_0``) and ``factors`` (the running averages of the
    layers in ``keep_factors`` after the first step).

    ``make_params(shapes, param_key) -> {path: array}`` is the
    benchmark's generator of weights; batches are ``model.make_batch`` of
    ``fold_in(data_key, i)``, the pool the program cycles through.
    """
    mcfg = cfg['model']
    layers = model.kfac_layers(mcfg)
    k, opt = cfg['kfac'], cfg['optimizer']
    act = cfg['dtype']['activations']
    if lower not in (False, 'kfac', 'all'):
        raise ValueError(f'lower={lower!r}')
    low_act = _LOWER_ACT[act] if lower == 'all' else None
    stat_dtype = 'bfloat16' if lower else 'float32'
    frozen = _Frozen(
        (mcfg, layers, act, low_act, stat_dtype,
         cfg['dtype']['matmul_precision']),
        (cfg['name'], act, low_act, stat_dtype))
    q = _bf16 if lower else None

    shapes = model.param_shapes(mcfg)
    params = {p: np.asarray(v) for p, v in jax.jit(
        lambda key: make_params(shapes, key))(param_key).items()}
    p0 = {p: v.copy() for p, v in params.items()}
    momentum = {p: np.zeros_like(v) for p, v in params.items()}
    factors = {l['path']: [np.eye(l['kernel'][-2] * (
        l['kernel'][0] * l['kernel'][1] if l['kind'] == 'conv' else 1)
        + int(l['bias'])), np.eye(l['kernel'][-1])] for l in layers}
    inverses = {}
    out = {'losses': [], 'factors': {}}
    w = k['ema_new_weight']
    pool = traffic['pool']

    for step in range(steps):
        batch = model.make_batch(
            mcfg, traffic, jax.random.fold_in(data_key, step % pool))
        upd_f = step % traffic['fac_update_freq'] == 0
        upd_i = step % traffic['kfac_update_freq'] == 0
        loss, grads, stats = _device_step(model, frozen, params, batch,
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
            stats = None
            if step == 0:
                out['factors'] = {p: [f.copy() for f in factors[p]]
                                  for p in keep_factors}
        if upd_i:
            for layer in layers:
                fa, fg = factors[layer['path']]
                pi = (np.trace(fa) / fa.shape[0]) / (
                    np.trace(fg) / fg.shape[0])
                inverses[layer['path']] = (
                    _inverse_of(_damped(fa, np.sqrt(k['damping'] * pi)), q),
                    _inverse_of(_damped(fg, np.sqrt(k['damping'] / pi)), q))
        mats, pres, vg = {}, {}, 0.0
        for layer in layers:
            inv_a, inv_g = inverses[layer['path']]
            mats[layer['path']] = _grad_matrix(layer, grads)
            # G^-1 dW A^-1 = (A^-1 (G^-1 dW)')'
            pres[layer['path']] = inv_a(inv_g(mats[layer['path']]).T).T
            vg += float((pres[layer['path']] * mats[layer['path']]).sum())
        nu = min(1.0, np.sqrt(k['kl_clip'] / abs(vg * opt['lr'] ** 2)))
        out.setdefault('kl_scale', []).append(float(nu))
        for layer in layers:
            _write_matrix(layer, grads, pres[layer['path']] * nu)
        for p in params:
            u = grads[p] + opt['weight_decay'] * params[p]
            momentum[p] = (opt['momentum'] * momentum[p] + u).astype(
                np.float32)
            params[p] = (params[p] - opt['lr'] * momentum[p]).astype(
                np.float32)
        if step == 0:
            out['first_update'] = {
                p: float(np.linalg.norm(m.astype(np.float64)))
                for p, m in momentum.items()}
    out['param_change'] = {
        p: float(np.linalg.norm(params[p].astype(np.float64)
                                - p0[p].astype(np.float64)))
        for p in params}
    return out
