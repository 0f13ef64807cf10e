"""The BERT builder with the timed path broken underneath: the optimizer's
updates are scaled to zero, so a step returns its parameters unchanged.
``correct`` has to come out false (``test_run_cpu.py``)."""

import optax

from harness import files


def build(config, traffic, kfac=True, axis_name=None):
    parts = files.load_module('builders', 'bert_squad').build(
        config, traffic, kfac=kfac, axis_name=axis_name)
    tx = optax.chain(parts['tx'], optax.scale(0.0))
    inner = parts['init_state']

    def init_state(rng):
        state = inner(rng)
        return state.replace(opt_state=tx.init(state.params))
    parts.update(tx=tx, init_state=init_state)
    return parts
