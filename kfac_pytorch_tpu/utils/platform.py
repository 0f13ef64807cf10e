"""Where compiled programs are kept.

The platform itself is chosen by JAX's own switches and nothing in this
repo overrides them: ``JAX_PLATFORMS=cpu`` (plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for a virtual
N-device mesh) for tests and scripts, unset on a machine with a TPU.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir():
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed git-ignored
    ``.jax_cache/`` of this checkout — never ``$HOME``, a temp name, a
    pid or a time: the path is part of the cache key, so a directory
    that moves never hits."""
    return (os.environ.get('JAX_COMPILATION_CACHE_DIR')
            or os.path.join(_CHECKOUT, '.jax_cache'))


def enable_compile_cache():
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return the directory. Where the
    environment names one, JAX already reads it and this sets no other.
    The one place in the repo that places the cache — the trainers
    and ``chip_smoke.py`` call it."""
    path = compile_cache_dir()
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        import jax
        jax.config.update('jax_compilation_cache_dir', path)
    return path
