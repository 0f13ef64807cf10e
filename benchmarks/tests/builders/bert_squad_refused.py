"""The BERT builder with a loss that is never finite: the health guard
refuses every batch from the first step of set-up on. The run has to end
with a well-formed result, ``correct`` false and ``failed`` = ``attempted``
(``test_run_cpu.py``)."""

import jax.numpy as jnp

from harness import files


def build(config, traffic, kfac=True, axis_name=None):
    parts = files.load_module('builders', 'bert_squad').build(
        config, traffic, kfac=kfac, axis_name=axis_name)
    inner = parts['loss_fn']
    parts['loss_fn'] = lambda outputs, batch: inner(outputs, batch) * jnp.nan
    return parts
