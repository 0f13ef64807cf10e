"""Seeded generators of the benchmark: keys, weights, batch pools.

Weights are made by the benchmark, not by the program's initializers, so
that the program and the plain reference get the same arrays without
either taking anything from the other. A configuration's ``init`` list
holds ``[regex, kind, arg]`` rules, first match wins:
``zeros`` / ``ones`` / ``normal`` (std ``arg``) / ``normal_fan_in`` (std
``sqrt(arg / fan_in)``, fan-in = product of all but the last axis).
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def flatten(tree, prefix=''):
    """Nested dict -> {'a/b/c': leaf}."""
    out = {}
    for k, v in tree.items():
        path = f'{prefix}/{k}' if prefix else str(k)
        if hasattr(v, 'items'):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def unflatten(flat):
    tree = {}
    for path, v in flat.items():
        node = tree
        parts = path.split('/')
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def _one(rules, path, shape, key):
    for pattern, kind, *arg in rules:
        if re.search(pattern, path):
            if kind == 'zeros':
                return jnp.zeros(shape, jnp.float32)
            if kind == 'ones':
                return jnp.ones(shape, jnp.float32)
            std = float(arg[0])
            if kind == 'normal_fan_in':
                std = float(np.sqrt(arg[0] / np.prod(shape[:-1])))
            elif kind != 'normal':
                raise ValueError(f'unknown init kind {kind!r}')
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            return std * jax.random.normal(k, shape, jnp.float32)
    raise KeyError(f'no init rule matches parameter {path!r}')


def params_fn(rules):
    """-> ``make(shapes, key) -> {path: float32 array}``: traceable, the
    same arrays for the same paths, shapes and key. The key is an
    argument, not a constant of the traced program, so that one compiled
    program serves every seed."""
    def make(shapes, key):
        base = jax.random.fold_in(key, 0x5EED)
        return {path: _one(rules, path, tuple(shape), base)
                for path, shape in sorted(shapes.items())}
    return make
