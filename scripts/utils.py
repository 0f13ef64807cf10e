"""Shared helpers for the research/benchmark scripts.

Capability parity with the reference's script helpers
(reference: scripts/utils.py:1-112 — shared log-parsing/plot utilities for
the offline analysis scripts). Here: the model-zoo resolver, timing, and
linear cost-model fitting. The platform is JAX's own business: run a
script on a virtual CPU mesh with ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import time


# --model flag values (models/__init__.py registry) that are ImageNet-scale;
# everything else in the zoo is CIFAR-scale (32x32, 10/100 classes).
IMAGENET_MODELS = frozenset({
    'resnet18', 'resnet34', 'resnet50', 'resnet101', 'resnet152',
    'resnext50', 'resnext101', 'inceptionv4', 'inception-v4'})


def build_vision_model(name, img=None, num_classes=None):
    """Resolve a ``--model`` flag to (model, img_size, num_classes) through
    the zoo registry (same name surface as the example entrypoints)."""
    from kfac_pytorch_tpu import models
    if name in IMAGENET_MODELS:
        img = img or (299 if 'inception' in name else 224)
        num_classes = num_classes or 1000
    else:
        img = img or 32
        num_classes = num_classes or 10
    return models.get_model(name, num_classes=num_classes), img, num_classes


def timeit(fn, *args, warmup=2, iters=10, vary=None):
    """Mean wall-clock seconds per call, fenced on the last output
    (``kfac_pytorch_tpu.utils.profiling.host_fence``).

    vary: optional ``vary(i) -> args`` callable producing per-iteration
    inputs, for A/B microbenches that must not time one (program,
    inputs) pair over and over.
    """
    from kfac_pytorch_tpu.utils.profiling import host_fence
    for i in range(warmup):
        out = fn(*(vary(i) if vary else args))
    host_fence(out)
    t0 = time.perf_counter()
    for i in range(iters):
        out = fn(*(vary(warmup + i) if vary else args))
    host_fence(out)
    return (time.perf_counter() - t0) / iters


def fit_linear(xs, ys):
    """Least-squares fit of ``y = alpha + beta * x`` (the alpha-beta
    latency/bandwidth model, reference scripts/comm_models.py:8-19)."""
    import numpy as np
    X = np.stack([np.ones(len(xs)), np.asarray(xs, float)], axis=1)
    (alpha, beta), *_ = np.linalg.lstsq(X, np.asarray(ys), rcond=None)
    return float(alpha), float(beta)
