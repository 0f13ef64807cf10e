"""Kronecker-factor statistics ops.

Semantics parity with the reference math layer (reference:
kfac/utils.py:33-140) but laid out for TPU: NHWC activations, HWIO conv
kernels, im2col via ``lax.conv_general_dilated_patches`` (one fused XLA op
instead of unfold+transpose chains), and all covariance GEMMs emitted as
single ``dot_general`` calls with fp32 accumulation so XLA tiles them onto
the MXU. Conv factor A never reorders or rescales its patch tensor: it is
built once and contracted with itself (:func:`compute_a_conv`).

Conventions
-----------
- Dense activations ``a``: ``[N, ..., d_in]`` — any middle dims are a
  sequence axis and are mean-reduced (reference: kfac/utils.py:97-99).
- Conv activations ``a``: ``[N, H, W, C]`` (NHWC; the reference is NCHW).
- Output-gradients ``g`` mirror the activations with ``d_out``/``C_out``.
- Factors are fp32 regardless of activation dtype (the reference computes
  them in fp32, optionally via fp16-in/fp32-accum tensor-core GEMM,
  kfac/utils.py:155-158 — the MXU bf16-in/fp32-accum path is the native
  equivalent here).
- The feature order of conv patches is ``(kh, kw, c_in)`` to match the
  flattening of an HWIO kernel, so factor A indexes align with
  ``kernel.reshape(-1, c_out)`` (the reference's ``(c_in, kh, kw)`` order
  likewise matches torch's OIHW flatten, kfac/utils.py:33-54 +
  kfac_preconditioner_inv.py:145-154).
"""

import jax
import jax.numpy as jnp
from jax import lax

# Factor statistics are accumulated in fp32. Inputs may be bf16 (model
# compute dtype) — dot_general with preferred_element_type=f32 is the MXU's
# native mixed-precision mode.
_FACTOR_DTYPE = jnp.float32


def _stat_gemm(x, n):
    """Return ``x^T @ (x / n)`` in fp32 — the covariance GEMM of every factor."""
    return lax.dot_general(
        x, x / n,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=_FACTOR_DTYPE,
    ).astype(_FACTOR_DTYPE)


def extract_patches(x, kernel_size, strides, padding):
    """im2col: ``[N, H, W, C] -> [N, OH, OW, kh*kw*C]``.

    Feature order is ``(kh, kw, c)`` — matches HWIO kernel flattening.
    Parity: ``_extract_patches`` (reference: kfac/utils.py:33-54).

    Args:
      x: NHWC input feature maps.
      kernel_size: ``(kh, kw)``.
      strides: ``(sh, sw)``.
      padding: ``(ph, pw)`` symmetric pad, or an explicit
        ``[(lo, hi), (lo, hi)]`` list (as produced by Flax padding configs).
    """
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
    """Factor A for a dense layer: ``[d_in(+1), d_in(+1)]``.

    Sequence axes are mean-reduced before the outer product; a ones column is
    appended when the layer has a bias. Parity: ``ComputeA.linear``
    (reference: kfac/utils.py:97-103).
    """
    if a.ndim > 2:
        a = a.mean(axis=tuple(range(1, a.ndim - 1)))
    n = a.shape[0]
    if use_bias:
        a = _append_ones_column(a)
    return _stat_gemm(a, n)


#: Conv A builds its patch tensor one of two ways (``_conv_a_form``). Below
#: this many input channels: the raw channel-major output of
#: ``conv_general_dilated_patches``; from it up: ``kh*kw`` shifted strided
#: slices concatenated on the channel axis. Set from two measurements
#: (TPU v5e, bf16, batch 128, 3x3 at 112^2, ms, raw / slices): C 24
#: 3.47 / 4.19, C 32 6.36 / 5.42 (PERF.md, PR 26). Slices of a narrow
#: input fill few of a vector's lanes; the raw form pays a convolution
#: that grows with C.
_RAW_PATCH_BELOW_CHANNELS = 32


def _self_gram(x, scale):
    """``scale * x^T x`` over every leading axis, accumulated in fp32 — the
    conv statistics' contraction: one tensor with itself, the scale applied
    to the ``f x f`` product instead of to a copy of ``x``."""
    lead = tuple(range(x.ndim - 1))
    gram = lax.dot_general(x, x, ((lead, lead), ((), ())),
                           preferred_element_type=_FACTOR_DTYPE)
    return gram.astype(_FACTOR_DTYPE) * jnp.asarray(scale, _FACTOR_DTYPE)


def explicit_pads(padding, in_hw, kernel_size, strides):
    """``((lo, hi), (lo, hi))`` zero padding for each padding form
    ``extract_patches`` accepts, as ``conv_general_dilated_patches`` reads
    it (shared with the Pallas conv A kernel)."""
    if isinstance(padding, str):
        return tuple(lax.padtype_to_pads(in_hw, kernel_size, strides,
                                         padding))
    if len(padding) == 2 and not isinstance(padding[0], (tuple, list)):
        return ((padding[0], padding[0]), (padding[1], padding[1]))
    return tuple(tuple(p) for p in padding)


def _raw_patches(a, kernel_size, strides, pads):
    """``[N, OH, OW, C*kh*kw]`` patches, features channel-major
    ``(c, kh, kw)``, as ``conv_general_dilated_patches`` emits them."""
    return lax.conv_general_dilated_patches(
        a, filter_shape=tuple(kernel_size), window_strides=tuple(strides),
        padding=list(pads), dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def _tap_patches(a, kernel_size, strides, pads):
    """``[N, OH, OW, kh*kw*C]`` patches in ``(kh, kw, c)`` order: one
    strided slice of the once-padded activation per kernel tap. A 1x1
    kernel's patches are the (strided) activation itself."""
    (kh, kw), (sh, sw) = kernel_size, strides
    if any(p != (0, 0) for p in pads):
        a = lax.pad(a, jnp.zeros((), a.dtype),
                    ((0, 0, 0), (*pads[0], 0), (*pads[1], 0), (0, 0, 0)))
    n, h, w, c = a.shape
    oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
    if (kh, kw, oh, ow) == (1, 1, h, w):
        return a
    taps = [lax.slice(a, (0, i, j, 0),
                      (n, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1, c),
                      (1, sh, sw, 1))
            for i in range(kh) for j in range(kw)]
    return taps[0] if len(taps) == 1 else jnp.concatenate(taps, axis=-1)


def _conv_a_form(kernel_size, channels):
    """How conv A builds its patch tensor for a layer of this shape:
    ``'1x1'`` (no patches), ``'raw'`` or ``'taps'``."""
    if tuple(kernel_size) == (1, 1):
        return '1x1'
    return 'raw' if channels < _RAW_PATCH_BELOW_CHANNELS else 'taps'


def _conv_a(form, a, kernel_size, strides, padding, use_bias):
    """:func:`compute_a_conv` with the patch form given: ``'raw'``, or
    ``'taps'`` (of which ``'1x1'`` is the one-tap case)."""
    n, c = a.shape[0], a.shape[-1]
    taps = kernel_size[0] * kernel_size[1]
    pads = explicit_pads(padding, a.shape[1:3], kernel_size, strides)
    build = _raw_patches if form == 'raw' else _tap_patches
    with jax.named_scope(f'conv_a.{form}'):
        patches = build(a, kernel_size, strides, pads)
        spatial = patches.shape[1] * patches.shape[2]
        scale = 1.0 / (spatial * spatial * n)
        cov = _self_gram(patches, scale)
        if use_bias:
            # the homogeneous coordinate: the patch rows' sum, contracted
            # like the product above (no ones column on the patch tensor)
            lead = (0, 1, 2)
            col = lax.dot_general(
                patches, jnp.ones(patches.shape[:3], patches.dtype),
                ((lead, lead), ((), ())),
                preferred_element_type=_FACTOR_DTYPE).astype(_FACTOR_DTYPE)
            col = col * jnp.asarray(scale, _FACTOR_DTYPE)
        if form == 'raw' and taps > 1:
            # (c, t) -> (t, c) on the product's rows and columns; the patch
            # tensor itself is never reordered
            cov = cov.reshape(c, taps, c, taps).transpose(1, 0, 3, 2)
            cov = cov.reshape(c * taps, c * taps)
            if use_bias:
                col = col.reshape(c, taps).T.reshape(-1)
        if use_bias:
            corner = jnp.full((1,), 1.0 / spatial, _FACTOR_DTYPE)
            cov = jnp.concatenate([
                jnp.concatenate([cov, col[:, None]], axis=1),
                jnp.concatenate([col, corner])[None, :]], axis=0)
        return cov


def compute_a_conv(a, kernel_size, strides, padding, use_bias):
    """Factor A for a conv layer: ``[kh*kw*C(+1), kh*kw*C(+1)]``.

    The covariance of the spatially normalized im2col rows (each row divided
    by the number of spatial positions, the bias ones column appended before
    that normalization). Parity: ``ComputeA.conv2d`` (reference:
    kfac/utils.py:86-94).

    One pass: the patch tensor is built once, in the activation's dtype,
    and contracted with itself in fp32; ``1 / (spatial^2 N)`` scales the
    product. How it is built follows from the layer's shape
    (:func:`_conv_a_form`); the feature order is ``(kh, kw, c_in)`` either
    way. The form taken shows in a trace as the scope ``conv_a.<form>``.
    """
    form = _conv_a_form(kernel_size, a.shape[-1])
    return _conv_a(form, a, kernel_size, strides, padding, use_bias)


def compute_g_dense(g, batch_averaged=True):
    """Factor G for a dense layer from output-gradients ``[N, ..., d_out]``.

    When the loss is batch-averaged, the implicit 1/N is undone so G is the
    covariance of per-example gradients. Parity: ``ComputeG.linear``
    (reference: kfac/utils.py:131-140).
    """
    if g.ndim > 2:
        g = g.mean(axis=tuple(range(1, g.ndim - 1)))
    n = g.shape[0]
    if batch_averaged:
        g = g * n
    return _stat_gemm(g, n)


def compute_g_conv(g, batch_averaged=True):
    """Factor G for a conv layer from output-gradients ``[N, OH, OW, C]``.

    Spatial positions are treated as extra samples, scaled by the spatial
    size to undo the conv-as-sum normalization. Parity: ``ComputeG.conv2d``
    (reference: kfac/utils.py:118-129).

    ``g`` is contracted with itself as it stands; the row scalings (``N``
    when batch-averaged, ``spatial``) and the ``1 / (N spatial)`` of the
    covariance meet in one factor on the product.
    """
    n = g.shape[0]
    spatial = g.shape[1] * g.shape[2]
    return _self_gram(g, n * spatial if batch_averaged else spatial / n)


def layer_rows_dense(a, g, use_bias, batch_averaged=True):
    """Aligned per-example row matrices for a dense layer — the raw rows
    whose covariances are :func:`compute_a_dense` / :func:`compute_g_dense`
    (same sequence-mean, bias-column, and batch-averaged-undo
    conventions). Returns ``(arows [N, d_in(+1)], grows [N, d_out], N)``;
    row ``b`` of both sides belongs to example ``b``, so the per-example
    gradient matrix is exactly ``grows[b] arows[b]^T`` — the E-KFAC
    second-moment input (George et al. 2018, beyond the reference)."""
    if a.ndim > 2:
        a = a.mean(axis=tuple(range(1, a.ndim - 1)))
    if g.ndim > 2:
        g = g.mean(axis=tuple(range(1, g.ndim - 1)))
    n = a.shape[0]
    if use_bias:
        a = _append_ones_column(a)
    if batch_averaged:
        g = g * n
    return a.astype(_FACTOR_DTYPE), g.astype(_FACTOR_DTYPE), n


def layer_rows_conv(a, g, kernel_size, strides, padding, use_bias,
                    batch_averaged=True):
    """Aligned per-patch row matrices for a conv layer — same row sets
    and normalizations as :func:`compute_a_conv` / :func:`compute_g_conv`
    (patch rows divided by the spatial size, g rows scaled by N and the
    spatial size), with rows index-aligned per (example, position) so the
    E-KFAC joint second moment can pair them. Returns
    ``(arows [N*OH*OW, kh*kw*C(+1)], grows [N*OH*OW, C_out], N)``."""
    n = a.shape[0]
    patches = extract_patches(a, kernel_size, strides, padding)
    spatial = patches.shape[1] * patches.shape[2]
    arows = patches.reshape(-1, patches.shape[-1])
    if use_bias:
        arows = _append_ones_column(arows)
    arows = arows / spatial
    grows = g.reshape(-1, g.shape[-1])
    if batch_averaged:
        grows = grows * n
    grows = grows * spatial
    return arows.astype(_FACTOR_DTYPE), grows.astype(_FACTOR_DTYPE), n


def ekfac_scales(arows, grows, qa, qg, n):
    """E-KFAC second moments in the joint Kronecker eigenbasis:
    ``s_ij = (1/n) sum_r (qg^T grows_r)_i^2 (arows_r^T qa)_j^2`` — the
    exact diagonal of ``(Qg (x) Qa)^T F_emp (Qg (x) Qa)`` for dense
    layers (per-example gradients ``g a^T``), the standard
    patch-independence approximation for conv. One projection pair plus
    one squared-feature GEMM; scale-consistent with the Kronecker
    eigenvalue outer product ``dg (x) da`` it replaces (both estimate the
    same diagonal, K-FAC via the independence factorization)."""
    pa = lax.dot_general(arows, qa, (((1,), (0,)), ((), ())),
                         preferred_element_type=_FACTOR_DTYPE)
    pg = lax.dot_general(grows, qg, (((1,), (0,)), ((), ())),
                         preferred_element_type=_FACTOR_DTYPE)
    return lax.dot_general(
        pg * pg, (pa * pa) / n,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=_FACTOR_DTYPE).astype(_FACTOR_DTYPE)


def update_running_avg(new, current, alpha):
    """Functional running average: ``alpha * new + (1 - alpha) * current``.

    Parity: ``update_running_avg`` (reference: kfac/utils.py:66-71), but
    returns the new value instead of mutating in place (XLA will fuse the
    axpy into surrounding ops).
    """
    alpha = jnp.asarray(alpha, dtype=current.dtype)
    return current * (1.0 - alpha) + new.astype(current.dtype) * alpha
