"""Harness utilities — parity with the reference's examples/utils.py.

The metrics/lr/losses/checkpoint/profiling surface needs jax; runlog
(which the resilience plane lazy-imports from inside protocol code)
does not. In a jax-less environment (the CI fleet-sim/lint lanes, a
bare coordination host) only the jax-free part of this package loads —
same convention as the top-level ``kfac_pytorch_tpu/__init__.py``.
"""

try:
    from kfac_pytorch_tpu.utils.metrics import (
        Metric, HealthMonitor, PhaseTimers, accuracy)
    from kfac_pytorch_tpu.utils.lr import (
        warmup_multistep, polynomial_decay, inverse_sqrt)
    from kfac_pytorch_tpu.utils.losses import (
        label_smoothing_cross_entropy, sample_pseudo_labels)
    from kfac_pytorch_tpu.utils.checkpoint import (
        save_checkpoint, restore_checkpoint, find_resume_epoch,
        auto_resume, PreemptionGuard, StaleLineageError,
        wait_for_checkpoints, prune_checkpoints, reshard_kfac_state,
        write_world_stamp, read_world_stamp, read_world_stamp_info)
    from kfac_pytorch_tpu.utils.profiling import trace, time_steps
except ModuleNotFoundError as _e:  # pragma: no cover - jax-less lanes
    if _e.name not in ('jax', 'jaxlib'):
        raise

__all__ = [
    'Metric', 'HealthMonitor', 'PhaseTimers', 'accuracy', 'warmup_multistep',
    'polynomial_decay',
    'inverse_sqrt', 'label_smoothing_cross_entropy', 'sample_pseudo_labels',
    'save_checkpoint', 'restore_checkpoint', 'find_resume_epoch',
    'auto_resume',
    'PreemptionGuard', 'StaleLineageError', 'wait_for_checkpoints',
    'prune_checkpoints',
    'reshard_kfac_state', 'write_world_stamp', 'read_world_stamp',
    'read_world_stamp_info',
    'trace', 'time_steps',
]
