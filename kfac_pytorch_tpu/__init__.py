"""TPU-native distributed K-FAC second-order optimization framework.

A from-scratch JAX/XLA re-design of the capabilities of lzhangbv/kfac_pytorch
(reference mounted at /root/reference): four distributed K-FAC preconditioner
variants (``inverse``, ``eigen``, ``inverse_dp``, ``eigen_dp``) behind the same
factory surface (reference: kfac/__init__.py:8-16, kfac/dp_kfac.py:4-39), built
TPU-first:

- Kronecker-factor statistics and preconditioning are pure-functional JAX ops
  batched onto the MXU (ops/).
- Activation / output-gradient capture replaces torch module hooks
  (reference: kfac/kfac_preconditioner_base.py:122-149) with Flax collections +
  a differentiable output-tap (capture.py, nn.py).
- Distribution replaces Horovod/NCCL/MPI (reference: kfac/backend.py,
  packages/tcmm/) with jax.sharding.Mesh + shard_map + XLA collectives over
  ICI/DCN (parallel/).
- Per-layer eigendecomposition work is padded into size-bucketed stacked
  arrays sharded over the mesh so eigh runs as one batched sharded XLA op —
  the TPU-idiomatic form of tcmm's multiBcast fused compute+broadcast
  (reference: packages/tcmm/src/communicator.cpp:75-117).
"""

try:
    import jax as _jax
except ModuleNotFoundError as _e:  # pragma: no cover - jax-less lanes
    if _e.name not in ('jax', 'jaxlib'):
        raise
    # jax-less environments (the CI fleet-sim and lint jobs, a bare
    # coordination host) still get the stdlib-only planes below —
    # coord/, service/, resilience/, sim/ — while the
    # optimizer surface stays absent and any use of it raises the
    # original, informative ModuleNotFoundError.
    _jax = None

if _jax is not None:
    from kfac_pytorch_tpu.preconditioner import (
        KFAC, KFACHyperParams, KFACState)
    from kfac_pytorch_tpu.scheduler import KFACParamScheduler
    from kfac_pytorch_tpu.health import HealthConfig, HealthState
    from kfac_pytorch_tpu import capture
    from kfac_pytorch_tpu import faults
    from kfac_pytorch_tpu import nn
    from kfac_pytorch_tpu import ops

from kfac_pytorch_tpu import resilience  # jax-free (elastic lazy-imports)

# Variant registry, mirroring the reference factory surface
# (reference: kfac/__init__.py:8-16) plus the beyond-reference 'ekfac'
# (George et al. 2018: per-example second moments in the joint
# Kronecker eigenbasis replace the eigenvalue outer product).
KFAC_VARIANTS = ('inverse', 'eigen', 'inverse_dp', 'eigen_dp', 'ekfac',
                 'ekfac_dp')


def get_kfac_module(kfac='eigen_dp'):
    """Return a KFAC factory pre-bound to a variant name.

    Parity with ``kfac.get_kfac_module`` (reference: kfac/__init__.py:15-16):
    the returned callable accepts the same hyper-parameters as ``KFAC``.
    """
    if kfac not in KFAC_VARIANTS:
        raise KeyError(f"unknown kfac variant {kfac!r}; choose from {KFAC_VARIANTS}")
    if _jax is None:
        raise ModuleNotFoundError(
            'jax is not installed: the K-FAC optimizer surface is '
            'unavailable (only the coordination/service/resilience/sim '
            'planes are importable in this environment)')

    def factory(*args, **kwargs):
        kwargs.setdefault('variant', kfac)
        return KFAC(*args, **kwargs)

    return factory


def DP_KFAC(*args, inv_type='eigen', **kwargs):
    """Distributed-preconditioning K-FAC facade.

    Parity with ``kfac.DP_KFAC`` (reference: kfac/dp_kfac.py:4-39): selects the
    eigen or explicit-inverse DP variant by ``inv_type``.
    """
    if _jax is None:
        raise ModuleNotFoundError(
            'jax is not installed: the K-FAC optimizer surface is '
            'unavailable (only the coordination/service/resilience/sim '
            'planes are importable in this environment)')
    variant = 'eigen_dp' if inv_type == 'eigen' else 'inverse_dp'
    kwargs.setdefault('variant', variant)
    return KFAC(*args, **kwargs)


__all__ = [
    'KFAC', 'KFACHyperParams', 'KFACState', 'KFACParamScheduler',
    'KFAC_VARIANTS', 'get_kfac_module', 'DP_KFAC', 'capture', 'nn', 'ops',
]
