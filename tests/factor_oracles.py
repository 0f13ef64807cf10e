"""Verbatim copies of ``ops/factors.py`` definitions as they stood before
PR 26, kept as test oracles.

- ``compute_a_conv`` here is the row-scaled form: the patch tensor reordered
  to ``(kh, kw, c)``, every row divided by ``spatial``, then ``x^T (x / n)``.
  The one-pass form in the package equals it up to rounding; the Pallas
  kernel ``pallas_capture.compute_a_conv`` reproduces ITS rounding sequence
  bit for bit.
- ``compute_g_conv`` likewise scales a copy of ``g`` (by ``N`` and
  ``spatial``) before ``x^T (x / rows)``.
- ``_stat_gemm``, ``compute_a_dense``, ``compute_g_dense`` pin the dense
  statistics: a change to the conv path must leave their jaxprs as they are
  (tests/test_factors.py).
"""

import jax.numpy as jnp
from jax import lax

_FACTOR_DTYPE = jnp.float32


def _stat_gemm(x, n):
    """Return ``x^T @ (x / n)`` in fp32 — the covariance GEMM of every factor."""
    return lax.dot_general(
        x, x / n,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=_FACTOR_DTYPE,
    ).astype(_FACTOR_DTYPE)


def extract_patches(x, kernel_size, strides, padding):
    n, h, w, c = x.shape
    kh, kw = kernel_size
    if isinstance(padding, str):
        pads = padding
    elif len(padding) == 2 and not isinstance(padding[0], (tuple, list)):
        pads = [(padding[0], padding[0]), (padding[1], padding[1])]
    else:
        pads = [tuple(p) for p in padding]
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=tuple(strides),
        padding=pads, dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    oh, ow = patches.shape[1:3]
    # conv_general_dilated_patches emits features channel-major (c, kh, kw);
    # reorder to (kh, kw, c) to align with HWIO kernel flattening.
    patches = patches.reshape(n, oh, ow, c, kh * kw)
    patches = patches.transpose(0, 1, 2, 4, 3).reshape(n, oh, ow, kh * kw * c)
    return patches


def _append_ones_column(x):
    ones = jnp.ones(x.shape[:-1] + (1,), dtype=x.dtype)
    return jnp.concatenate([x, ones], axis=-1)


def compute_a_dense(a, use_bias):
    if a.ndim > 2:
        a = a.mean(axis=tuple(range(1, a.ndim - 1)))
    n = a.shape[0]
    if use_bias:
        a = _append_ones_column(a)
    return _stat_gemm(a, n)


def compute_a_conv(a, kernel_size, strides, padding, use_bias):
    n = a.shape[0]
    patches = extract_patches(a, kernel_size, strides, padding)
    spatial = patches.shape[1] * patches.shape[2]
    rows = patches.reshape(-1, patches.shape[-1])
    if use_bias:
        rows = _append_ones_column(rows)
    rows = rows / spatial
    return _stat_gemm(rows, n)


def compute_g_dense(g, batch_averaged=True):
    if g.ndim > 2:
        g = g.mean(axis=tuple(range(1, g.ndim - 1)))
    n = g.shape[0]
    if batch_averaged:
        g = g * n
    return _stat_gemm(g, n)


def compute_g_conv(g, batch_averaged=True):
    n = g.shape[0]
    spatial = g.shape[1] * g.shape[2]
    rows = g.reshape(-1, g.shape[-1])
    if batch_averaged:
        rows = rows * n
    rows = rows * spatial
    return _stat_gemm(rows, rows.shape[0])
