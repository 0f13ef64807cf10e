"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

The reference has no cluster-free multi-node test path (SURVEY.md §4); here
every distributed code path runs on a simulated mesh
(--xla_force_host_platform_device_count), the JAX-native equivalent.
"""

import os

# Both are read when jax is first imported / its CPU client first
# initializes — i.e. below, after this point. Children that tests start
# inherit them.
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['XLA_FLAGS'] = (
    os.environ.get('XLA_FLAGS', '')
    + ' --xla_force_host_platform_device_count=8')

try:
    import jax  # noqa: E402
except ModuleNotFoundError:
    # jax-less CI lanes (the fleet-sim job) run only the stdlib suites
    # (tests/test_sim.py, tests/test_lint.py); any jax-dependent test
    # module still fails loudly at its own import.
    jax = None

if jax is not None:
    # fp32 matmuls in tests: exact math, not MXU bf16 passthrough.
    jax.config.update('jax_default_matmul_precision', 'highest')


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'slow: multi-minute end-to-end drills (subprocess '
        "trainers etc.); deselect with -m 'not slow'")
    config.addinivalue_line(
        'markers', 'core: ~1-minute core subset (golden torch-reference '
        'parity, engine/preconditioner, factors/linalg, loss-convention '
        "guard); run with -m core (VERDICT r3 #9)")
    config.addinivalue_line(
        'markers', 'nightly: opt-in 20-40-epoch CPU training gates '
        '(VERDICT r4 weak #6) — skipped unless the -m expression names '
        "nightly or KFAC_NIGHTLY=1; run with -m nightly")


def pytest_collection_modifyitems(config, items):
    # nightly is OPT-IN: multi-10-minute CPU trainings must not ride
    # along with -m slow (the CI chaos job) or a bare pytest run. They
    # run only when explicitly selected: '-m nightly' (or any -m
    # expression mentioning it), or KFAC_NIGHTLY=1 for driver scripts
    # that cannot pass marker expressions.
    import pytest as _pytest
    if 'nightly' in (config.option.markexpr or '') \
            or os.environ.get('KFAC_NIGHTLY'):
        return
    skip = _pytest.mark.skip(
        reason='nightly tier: run with -m nightly (or KFAC_NIGHTLY=1)')
    for item in items:
        if 'nightly' in item.keywords:
            item.add_marker(skip)
