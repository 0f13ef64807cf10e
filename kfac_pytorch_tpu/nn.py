"""KFAC-aware Flax linen layers.

The reference instruments stock ``nn.Linear``/``nn.Conv2d`` with hooks
(reference: kfac/kfac_preconditioner_base.py:132-149). Here the layers
themselves carry the capture machinery (see ``capture.py``): they sow their
input into the ``'kfac_a'`` collection and add a differentiable zero tap to
their pre-activation output. When neither capture collection is active the
layers are exactly plain dense/conv — zero overhead.

Compute dtype may be bf16 (MXU-native) while params and factor statistics
stay fp32.
"""

from typing import Any, Callable, Optional, Sequence, Tuple, Union

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kfac_pytorch_tpu import capture

default_kernel_init = linen.initializers.lecun_normal()


def _overwrite(prev, new):
    # sow reducer: keep the latest call's value (matches hook overwrite
    # semantics for re-entrant modules, kfac_preconditioner_base.py:122-130).
    return new


class _KFACLayerMixin:
    """Shared capture plumbing for Dense/Conv."""

    def _capture_input(self, x):
        if self.kfac_enabled:
            self.sow(capture.ACTS, 'a', x, reduce_fn=_overwrite,
                     init_fn=lambda: ())

    def _tap_output(self, y):
        if not self.kfac_enabled:
            return y
        has_tap = (self.is_mutable_collection(capture.TAPS)
                   or self.has_variable(capture.TAPS, 'g'))
        if not has_tap:
            return y
        tap = self.variable(capture.TAPS, 'g',
                            lambda: jnp.zeros(y.shape, y.dtype))
        return y + tap.value


class Dense(linen.Module, _KFACLayerMixin):
    """Dense layer with K-FAC capture (reference hook target: ``nn.Linear``).

    Params: ``kernel [d_in, d_out]``, optional ``bias [d_out]``.
    """
    features: int
    use_bias: bool = True
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    kernel_init: Callable = default_kernel_init
    bias_init: Callable = linen.initializers.zeros_init()
    kfac_enabled: bool = True

    @linen.compact
    def __call__(self, x):
        d_in = x.shape[-1]
        kernel = self.param('kernel', self.kernel_init, (d_in, self.features),
                            self.param_dtype)
        bias = (self.param('bias', self.bias_init, (self.features,),
                           self.param_dtype) if self.use_bias else None)
        if self.kfac_enabled:
            capture.report_layer(capture.LayerMeta(
                name='/'.join(self.path), path=tuple(self.path), kind='dense',
                use_bias=self.use_bias,
                in_dim=d_in + int(self.use_bias), out_dim=self.features,
                kernel_shape=(d_in, self.features)), reads=(x,))
        self._capture_input(x)
        x, kernel = linen.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        y = lax.dot_general(x, kernel, (((x.ndim - 1,), (0,)), ((), ())))
        if bias is not None:
            y = y + jnp.asarray(bias, y.dtype)
        return self._tap_output(y)


class StackedDense(linen.Module, _KFACLayerMixin):
    """``E`` bias-free dense layers kept as ONE leaf ``kernel [E, d_in,
    d_out]`` and applied as one grouped product (the experts a routed layer
    holds). Input ``x [E, C, d_in]``: a buffer of ``C`` rows a layer, the
    rows past ``rows[e]`` zero; output ``[E, C, d_out]``.

    K-FAC treats every slice as a layer of its own (one ``LayerMeta`` of
    kind ``'stacked'`` with its ``index``): a factor pair from the rows
    that came to it. For that the layer sows, beside its input, ``n`` =
    ``rows`` (``[E]``, how many rows each slice got) and ``t`` =
    ``loss_rows``, the size of the loss's mean (all tokens, not the rows
    routed here): ``A_e = a_e'a_e / max(n_e, 1)``, ``G_e = (t g_e)'(t g_e)
    / max(n_e, 1)``, and a slice no row came to keeps its running averages
    (``engine.compute_layer_stats`` / ``update_factors``).
    """
    features: int
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    #: fan-in of ONE slice (the default initializer would take ``E * d_in``)
    kernel_init: Callable = linen.initializers.variance_scaling(
        1.0, 'fan_in', 'normal', batch_axis=(0,))
    kfac_enabled: bool = True

    @linen.compact
    def __call__(self, x, rows, loss_rows):
        n_stack, _, d_in = x.shape
        kernel = self.param('kernel', self.kernel_init,
                            (n_stack, d_in, self.features), self.param_dtype)
        if self.kfac_enabled:
            for e in range(n_stack):
                capture.report_layer(capture.LayerMeta(
                    name='/'.join(self.path) + f'/{e}', path=tuple(self.path),
                    kind='stacked', use_bias=False, in_dim=d_in,
                    out_dim=self.features,
                    kernel_shape=(d_in, self.features), index=e),
                    reads=(x, rows, loss_rows))
            self._capture_input(x)
            self.sow(capture.ACTS, 'n', jnp.asarray(rows, jnp.float32),
                     reduce_fn=_overwrite, init_fn=lambda: ())
            self.sow(capture.ACTS, 't', jnp.asarray(loss_rows, jnp.float32),
                     reduce_fn=_overwrite, init_fn=lambda: ())
        x, kernel = linen.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        y = jnp.einsum('ecd,edf->ecf', x, kernel)
        return self._tap_output(y)


class Conv(linen.Module, _KFACLayerMixin):
    """2-D convolution with K-FAC capture (reference hook target:
    ``nn.Conv2d``). NHWC inputs, HWIO kernel.

    Factor A's im2col (ops.compute_a_conv) uses exactly the geometry
    declared here; ``padding`` is resolved to explicit pairs at capture
    time so 'SAME'/'VALID' match what the conv executed.
    """
    features: int
    kernel_size: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: Union[str, Sequence] = 'SAME'
    use_bias: bool = True
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    kernel_init: Callable = default_kernel_init
    bias_init: Callable = linen.initializers.zeros_init()
    kfac_enabled: bool = True

    @linen.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        c_in = x.shape[-1]
        kernel = self.param('kernel', self.kernel_init,
                            (kh, kw, c_in, self.features), self.param_dtype)
        bias = (self.param('bias', self.bias_init, (self.features,),
                           self.param_dtype) if self.use_bias else None)
        pads = capture.canonical_padding(
            x.shape[1:3], self.kernel_size, self.strides, self.padding)
        if self.kfac_enabled:
            capture.report_layer(capture.LayerMeta(
                name='/'.join(self.path), path=tuple(self.path), kind='conv',
                use_bias=self.use_bias,
                in_dim=kh * kw * c_in + int(self.use_bias),
                out_dim=self.features,
                kernel_shape=(kh, kw, c_in, self.features),
                kernel_size=(kh, kw), strides=tuple(self.strides),
                padding=pads))
        self._capture_input(x)
        x, kernel = linen.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        y = lax.conv_general_dilated(
            x, kernel, window_strides=tuple(self.strides),
            padding=list(pads), dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        if bias is not None:
            y = y + jnp.asarray(bias, y.dtype)
        return self._tap_output(y)
