"""Adapter to the system under test: build it, and read its state.

A builder (``benchmarks/builders/<name>.py``) knows one model family and
returns the pieces the trainers would build (model, optimizer,
preconditioner, loss, a traceable state initializer). This module turns
them into one ``Program``: the train state made on the device in one
jitted call with the benchmark's seeded weights in place of the
initializer's, the step function of ``training.build_train_step``, and the
batch pool. It also holds the few readers of the program's state that the
comparison needs (per-leaf norms, running averages of sampled layers,
health counters).
"""

import dataclasses
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import files, weights

if files.ROOT not in sys.path:
    sys.path.insert(0, files.ROOT)

POOL_SALT = 0xDA7A


@dataclasses.dataclass
class Program:
    step_fn: Any
    state: Any
    pool: list              # device-resident batches, cycled
    samples_per_step: int
    precond: Any            # None for the first-order leg
    mesh: Any


def data_key(key):
    return jax.random.fold_in(key, POOL_SALT)


def build(builder, plain, config, traffic, seed, kfac=True):
    """The program of one cell. ``plain`` is the configuration's plain
    model (for the batch generator only)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from kfac_pytorch_tpu import training

    chips = traffic['chips']
    key = weights.seed_key(seed)
    mesh = axis = None
    if chips > 1:
        mesh = Mesh(np.array(jax.devices()[:chips]), ('batch',))
        axis = 'batch'
    parts = builder.build(config, traffic, kfac=kfac, axis_name=axis)
    precond, tx = parts['precond'], parts['tx']
    make_params = weights.params_fn(config['init'])

    def make_state(rng):
        state = parts['init_state'](rng)
        flat = weights.flatten(state.params)
        params = weights.unflatten(
            make_params({p: v.shape for p, v in flat.items()}, rng))
        return state.replace(params=params, opt_state=tx.init(params))

    # discovers the layers (precond.setup) so that the specs exist
    jax.eval_shape(make_state, key)
    if mesh is None:
        state = jax.jit(make_state)(key)
    else:
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            training._state_specs(precond, axis),
            is_leaf=lambda v: isinstance(v, P))
        state = jax.jit(make_state, out_shardings=shardings)(key)

    step_fn = training.build_train_step(
        parts['model'], tx, precond, parts['loss_fn'], axis_name=axis,
        mesh=mesh, **parts.get('step_kwargs', {}))

    dkey = data_key(key)
    mcfg = config['model']

    def make_pool(k):
        return [plain.make_batch(mcfg, traffic, jax.random.fold_in(k, i))
                for i in range(traffic['pool'])]
    if mesh is None:
        pool = jax.jit(make_pool)(dkey)
    else:
        shard = NamedSharding(mesh, P('batch'))
        pool = jax.jit(make_pool, out_shardings=shard)(dkey)
    return Program(step_fn=step_fn, state=state, pool=pool,
                   samples_per_step=traffic['batch_per_chip'] * chips,
                   precond=precond, mesh=mesh)


# -- readers of the program's state ---------------------------------------

def _momentum(opt_state):
    found = [s.trace for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, 'trace'))
        if hasattr(s, 'trace')]
    if len(found) != 1:
        raise RuntimeError('expected one momentum buffer in the optimizer '
                           f'state, found {len(found)}')
    return found[0]


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def momentum_norms(state):
    """{path: norm} of the optimizer's momentum buffer."""
    return {p: float(v) for p, v in weights.flatten(
        jax.device_get(_leaf_norms(_momentum(state.opt_state)))).items()}


def param_change_norms(state, config, seed):
    """{path: ||p - p0||} against the seeded initial weights."""
    make_params = weights.params_fn(config['init'])

    @jax.jit
    def diff(params, key):
        flat = weights.flatten(params)
        p0 = make_params({p: v.shape for p, v in flat.items()}, key)
        return {p: jnp.sqrt(jnp.sum(jnp.square(v - p0[p])))
                for p, v in flat.items()}
    return {p: float(v) for p, v in jax.device_get(
        diff(state.params, weights.seed_key(seed))).items()}


def sampled_factors(state, precond, names):
    """{layer path: [A, G]} running averages of the named layers, read
    from the stacked-bucket state through the preconditioner's plan."""
    plan = precond.plan
    out = {}
    for i, meta in enumerate(plan.metas):
        if meta.name not in names:
            continue
        ba, ra, bg, rg, _ = plan.layer_rows[i]
        fa = state.kfac_state.factors[str(ba)][ra][:meta.in_dim, :meta.in_dim]
        fg = state.kfac_state.factors[str(bg)][rg][:meta.out_dim,
                                                   :meta.out_dim]
        out[meta.name] = [np.asarray(fa, np.float64),
                          np.asarray(fg, np.float64)]
    return out


def decomposition_populated(state):
    return all(bool(jnp.any(x != 0))
               for x in jax.tree.leaves(state.kfac_state.decomp))


def kfac_state_dtypes(state):
    """The dtypes the running averages and the decompositions are stored
    in (the configuration states one, ``dtype.factors``)."""
    ks = state.kfac_state
    return sorted({str(x.dtype)
                   for x in jax.tree.leaves((ks.factors, ks.decomp))})


#: the counters read where the configuration names none: the health
#: guard's, where the program runs with one
HEALTH_COUNTERS = ('health/skipped', 'health/rung', 'health/fallbacks')


def health_counters(metrics, names=None):
    """The counters of the newest step's metrics that must stay 0 (a step
    degraded to SGD or skipped must not pass as K-FAC). ``names`` is the
    configuration's ``check.counters`` (a router's dropped tokens, say):
    each has to be among the step's metrics."""
    if names is None:
        return {k: float(metrics[k]) for k in HEALTH_COUNTERS
                if k in metrics}
    return {k: float(metrics[k]) for k in names}
