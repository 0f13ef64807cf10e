"""Factory-surface parity tests (reference: kfac/__init__.py:8-16,
kfac/dp_kfac.py:4-39) and profiling helpers."""

import jax
import jax.numpy as jnp
import pytest

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu.utils import profiling


def test_get_kfac_module_binds_variant():
    for name in kfac.KFAC_VARIANTS:
        factory = kfac.get_kfac_module(name)
        p = factory(lr=0.2, damping=0.01)
        assert p.variant == name
        assert p.lr == 0.2


def test_get_kfac_module_unknown_raises():
    with pytest.raises(KeyError):
        kfac.get_kfac_module('nope')


def test_dp_kfac_facade_selects_dp_variants():
    assert kfac.DP_KFAC(inv_type='eigen').variant == 'eigen_dp'
    assert kfac.DP_KFAC(inv_type='inverse').variant == 'inverse_dp'


def test_variant_table_matches_reference_semantics():
    # MPD variants allreduce factor stats; DP variants keep them local
    assert kfac.KFAC(variant='inverse').stats_reduce == 'pmean'
    assert kfac.KFAC(variant='eigen').stats_reduce == 'pmean'
    assert kfac.KFAC(variant='inverse_dp').stats_reduce == 'local'
    assert kfac.KFAC(variant='eigen_dp').stats_reduce == 'local'
    # comm modes: eigen forces inverse comm (eigen.py:52); dp comm preds
    assert kfac.KFAC(variant='eigen').comm_mode == 'inverse'
    assert kfac.KFAC(variant='eigen_dp').comm_mode == 'pred'
    assert kfac.KFAC(variant='inverse').comm_mode == 'pred'
    assert kfac.KFAC(
        variant='inverse', communicate_inverse_or_not=True
    ).comm_mode == 'inverse'


def test_time_steps_returns_steady_state_stats():
    calls = []

    def fake_step(state, batch, **kw):
        calls.append(1)
        return state, jnp.float32(0.0)

    mean, std, state = profiling.time_steps(fake_step, 0, None, iters=4,
                                            warmup=2)
    assert len(calls) == 6
    assert mean >= 0 and std >= 0


def test_speed_report_logs_real_units(caplog):
    """speed_report must emit the canonical parseable SPEED line with the
    caller-supplied per-iteration unit count."""
    import logging

    calls = {'n': 0}

    def fake_step(state, batch, **kw):
        calls['n'] += 1
        return state, {'loss': jnp.float32(1.0)}

    log = logging.getLogger('speed-test')
    with caplog.at_level(logging.INFO, logger='speed-test'):
        profiling.speed_report(log, fake_step, 0, None, 256,
                               unit='imgs/sec', iters=3, warmup=1)
    assert calls['n'] == 4
    msg = caplog.records[-1].getMessage()
    assert msg.startswith('SPEED: iter time ') and 'imgs/sec' in msg
    # the canonical format round-trips through the log parser
    import os as _os
    import sys as _sys
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), '..'))
    from scripts.parse_logs import SPEED_RE
    assert SPEED_RE.search('x ' + msg)
