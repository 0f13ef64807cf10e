"""Golden tests for Kronecker-factor statistics ops.

Oracles are independent numpy implementations of the documented reference
semantics (reference: kfac/utils.py:33-140).
"""

import math
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from kfac_pytorch_tpu import capture, engine, ops
from kfac_pytorch_tpu.ops import factors

from tests import factor_oracles as oracle

pytestmark = pytest.mark.core


def np_patches(x, kh, kw, sh, sw, ph, pw):
    """Naive im2col oracle: NHWC -> [N, OH, OW, kh*kw*C], (kh, kw, c) order."""
    n, h, w, c = x.shape
    xp = np.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    out = np.zeros((n, oh, ow, kh * kw * c), dtype=x.dtype)
    for i in range(oh):
        for j in range(ow):
            win = xp[:, i * sh:i * sh + kh, j * sw:j * sw + kw, :]
            out[:, i, j, :] = win.reshape(n, -1)  # (kh, kw, c) row-major
    return out


def test_extract_patches_matches_naive():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 6, 5, 3).astype(np.float32)
    got = np.asarray(ops.extract_patches(jnp.asarray(x), (3, 2), (2, 1), (1, 0)))
    want = np_patches(x, 3, 2, 2, 1, 1, 0)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize('use_bias', [True, False])
def test_compute_a_dense(use_bias):
    rng = np.random.RandomState(1)
    a = rng.randn(8, 5).astype(np.float32)
    am = np.concatenate([a, np.ones((8, 1), np.float32)], 1) if use_bias else a
    want = am.T @ am / 8
    got = np.asarray(ops.compute_a_dense(jnp.asarray(a), use_bias))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_compute_a_dense_seq_mean():
    # sequence inputs are token-averaged first (reference kfac/utils.py:97-99)
    rng = np.random.RandomState(2)
    a = rng.randn(4, 7, 5).astype(np.float32)
    am = a.mean(1)
    am = np.concatenate([am, np.ones((4, 1), np.float32)], 1)
    want = am.T @ am / 4
    got = np.asarray(ops.compute_a_dense(jnp.asarray(a), True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('use_bias', [True, False])
def test_compute_a_conv(use_bias):
    rng = np.random.RandomState(3)
    x = rng.randn(3, 5, 5, 2).astype(np.float32)
    p = np_patches(x, 3, 3, 1, 1, 1, 1)  # [3,5,5,18]
    spatial = p.shape[1] * p.shape[2]
    rows = p.reshape(-1, p.shape[-1])
    if use_bias:
        rows = np.concatenate([rows, np.ones((rows.shape[0], 1), np.float32)], 1)
    rows = rows / spatial
    want = rows.T @ rows / 3
    got = np.asarray(ops.compute_a_conv(jnp.asarray(x), (3, 3), (1, 1), (1, 1),
                                        use_bias))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize('batch_averaged', [True, False])
def test_compute_g_dense(batch_averaged):
    rng = np.random.RandomState(4)
    g = rng.randn(6, 4).astype(np.float32)
    scaled = g * 6 if batch_averaged else g
    want = scaled.T @ scaled / 6 if batch_averaged else g.T @ g / 6
    # batch_averaged: G = g^T (g*N) = (gN)^T (gN) / N
    want = (g * 6).T @ (g * 6) / 6 if batch_averaged else g.T @ g / 6
    got = np.asarray(ops.compute_g_dense(jnp.asarray(g), batch_averaged))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('batch_averaged', [True, False])
def test_compute_g_conv(batch_averaged):
    rng = np.random.RandomState(5)
    g = rng.randn(3, 4, 4, 6).astype(np.float32)  # NHWC
    n, oh, ow, c = g.shape
    spatial = oh * ow
    rows = g.reshape(-1, c)
    if batch_averaged:
        rows = rows * n
    rows = rows * spatial
    want = rows.T @ rows / (n * spatial)
    got = np.asarray(ops.compute_g_conv(jnp.asarray(g), batch_averaged))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_update_running_avg():
    cur = jnp.ones((3, 3))
    new = jnp.full((3, 3), 2.0)
    out = ops.update_running_avg(new, cur, 0.25)
    np.testing.assert_allclose(np.asarray(out), 0.75 * 1 + 0.25 * 2)


# -- conv A in one pass (PR 26): each patch tensor built once and contracted
# -- with itself; the row-scaled form it replaced is the oracle


#: name -> (activation shape, kernel, strides, padding)
CONV_GEOMETRIES = {
    'conv1_7x7_s2_c3': ((2, 18, 18, 3), (7, 7), (2, 2), ((3, 3), (3, 3))),
    '3x3_s1_same': ((3, 8, 8, 4), (3, 3), (1, 1), 'SAME'),
    '3x3_s1_c40': ((2, 6, 6, 40), (3, 3), (1, 1), (1, 1)),
    '3x3_s2': ((3, 9, 9, 5), (3, 3), (2, 2), ((1, 1), (1, 1))),
    '1x1_s1': ((3, 6, 6, 7), (1, 1), (1, 1), 'VALID'),
    '1x1_s2': ((3, 7, 7, 6), (1, 1), (2, 2), 'VALID'),
    'rect_uneven_pads': ((3, 11, 9, 5), (2, 3), (3, 2), ((0, 1), (2, 0))),
}


def _conv_a(form, a, kernel, strides, padding, use_bias):
    """Conv A with one patch builder forced, or by the rule's own choice."""
    if form == 'rule':
        return ops.compute_a_conv(a, kernel, strides, padding, use_bias)
    return factors._conv_a(form, a, kernel, strides, padding, use_bias)


def np_conv_a(x, kernel, strides, padding, use_bias):
    """Float64 reference of the documented statistic."""
    (kh, kw), (sh, sw) = kernel, strides
    pads = factors.explicit_pads(padding, x.shape[1:3], kernel, strides)
    xp = np.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    n, h, w, c = xp.shape
    oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
    rows = np.stack([
        xp[:, i * sh:i * sh + kh, j * sw:j * sw + kw, :].reshape(n, -1)
        for i in range(oh) for j in range(ow)], axis=1).reshape(n * oh * ow, -1)
    if use_bias:
        rows = np.concatenate([rows, np.ones((len(rows), 1))], axis=1)
    rows = rows / (oh * ow)
    return rows.T @ rows / n


def _rel(x, ref):
    return np.linalg.norm(x - ref) / np.linalg.norm(ref)


@pytest.mark.parametrize('form', ['raw', 'taps', 'rule'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('use_bias', [True, False], ids=['bias', 'nobias'])
@pytest.mark.parametrize('geometry', list(CONV_GEOMETRIES))
def test_conv_a_one_pass_against_the_row_scaled_form(geometry, use_bias,
                                                     dtype, form):
    shape, kernel, strides, padding = CONV_GEOMETRIES[geometry]
    rng = np.random.RandomState(sum(map(ord, geometry)))
    a = jnp.asarray(rng.randn(*shape) + 0.5, dtype)
    got = np.asarray(_conv_a(form, a, kernel, strides, padding, use_bias))
    want = np.asarray(oracle.compute_a_conv(a, kernel, strides, padding,
                                            use_bias))
    assert got.dtype == np.float32 and got.shape == want.shape
    # the oracle's value, up to the roundings the new form leaves out
    tol = 5e-3 if dtype == 'bfloat16' else 1e-5
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    # ... and no further from the exact statistic of the same inputs
    exact = np_conv_a(np.asarray(a, np.float64), kernel, strides, padding,
                      use_bias)
    assert _rel(got, exact) <= 1.1 * _rel(want, exact) + 1e-6
    # a matrix contracted with itself: symmetric to float32 rounding
    assert np.abs(got - got.T).max() <= 1e-6 * np.abs(got).max()


@pytest.mark.parametrize('form', ['raw', 'taps'])
def test_conv_a_bias_row_and_corner_as_the_ones_column_gave_them(form):
    # no ones column rides on the patch tensor: the last row / column is
    # the patch rows' sum / (spatial^2 N), the corner 1 / spatial
    shape, kernel, strides, padding = CONV_GEOMETRIES['3x3_s2']
    a = jnp.asarray(np.random.RandomState(7).randn(*shape), jnp.float32)
    got = np.asarray(_conv_a(form, a, kernel, strides, padding, True))
    want = np.asarray(oracle.compute_a_conv(a, kernel, strides, padding,
                                            True))
    patches = np.asarray(oracle.extract_patches(a, kernel, strides, padding),
                         np.float64)
    n, oh, ow, _ = patches.shape
    col = patches.sum(axis=(0, 1, 2)) / ((oh * ow) ** 2 * n)
    np.testing.assert_allclose(got[-1, :-1], col, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(got[:-1, -1], want[:-1, -1], rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(got[-1, -1], 1.0 / (oh * ow), rtol=1e-6)
    np.testing.assert_allclose(got[-1, -1], want[-1, -1], rtol=1e-6)


@pytest.mark.parametrize('form', ['raw', 'taps', 'rule'])
def test_conv_a_feature_order_is_the_grad_matrix_flattening(form):
    # a one-hot activation lights one feature per kernel tap; its index has
    # to be where layer_grad_matrix puts that tap's weight
    kernel, c, c0, (y0, x0) = (3, 3), 4, 2, (3, 5)
    a = np.zeros((1, 7, 8, c), np.float32)
    a[0, y0, x0, c0] = 1.0
    got = np.asarray(_conv_a(form, jnp.asarray(a), kernel, (1, 1), (1, 1),
                             False))
    meta = capture.LayerMeta(
        name='conv', path=('conv',), kind='conv', use_bias=False,
        in_dim=9 * c, out_dim=1, kernel_shape=(3, 3, c, 1),
        kernel_size=kernel, strides=(1, 1), padding=((1, 1), (1, 1)))
    lit = set()
    for i in range(3):
        for j in range(3):
            k = np.zeros((3, 3, c, 1), np.float32)
            k[i, j, c0, 0] = 1.0
            gm = np.asarray(engine.layer_grad_matrix(
                meta, {'conv': {'kernel': jnp.asarray(k)}}))
            lit.add(int(np.flatnonzero(gm[0])[0]))
    assert len(lit) == 9
    assert set(np.flatnonzero(np.diag(got))) == lit
    # each tap sees the pixel at one output position: the product is diagonal
    spatial = 7 * 8
    np.testing.assert_allclose(got[sorted(lit), sorted(lit)],
                               1.0 / spatial ** 2, rtol=1e-6)
    assert np.count_nonzero(got) == 9


def test_conv_a_form_follows_the_layer_shape():
    below = factors._RAW_PATCH_BELOW_CHANNELS - 1
    assert factors._conv_a_form((1, 1), 3) == '1x1'
    assert factors._conv_a_form((1, 1), 2048) == '1x1'
    assert factors._conv_a_form((7, 7), 3) == 'raw'
    assert factors._conv_a_form((3, 3), below) == 'raw'
    assert factors._conv_a_form((3, 3), below + 1) == 'taps'
    assert factors._conv_a_form((3, 3), 512) == 'taps'


def _tensor_sizes(text):
    """Element counts of every result in lowered StableHLO text."""
    sizes = []
    for line in text.splitlines():
        if ' = ' not in line or '->' in line.split(' = ')[0]:
            continue
        result = line.rsplit('->', 1)[-1] if '->' in line else (
            line.rsplit(':', 1)[-1])
        for dims in re.findall(r'tensor<((?:\d+x)+)[a-z]', result):
            sizes.append(math.prod(int(d) for d in dims[:-1].split('x')))
    return sizes


@pytest.mark.parametrize('use_bias', [True, False], ids=['bias', 'nobias'])
@pytest.mark.parametrize('geometry,patch_tensors', [
    ('conv1_7x7_s2_c3', 1), ('3x3_s1_c40', 1), ('3x3_s2', 1),
    ('1x1_s2', 1), ('1x1_s1', 0)])
def test_conv_a_lowers_to_one_patch_sized_tensor(geometry, patch_tensors,
                                                 use_bias):
    # the row-scaled form made four (patches, their transpose, rows /
    # spatial, x / n); a second one in the new form is a regression
    shape, kernel, strides, padding = CONV_GEOMETRIES[geometry]
    shape = (64,) + shape[1:]    # nothing runs: rows enough to dwarf f x f
    a = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def lowered(fn):
        return jax.jit(lambda x: fn(x, kernel, strides, padding,
                                    use_bias)).lower(a).as_text()

    pads = factors.explicit_pads(padding, shape[1:3], kernel, strides)
    oh = (shape[1] + sum(pads[0]) - kernel[0]) // strides[0] + 1
    ow = (shape[2] + sum(pads[1]) - kernel[1]) // strides[1] + 1
    patch = shape[0] * oh * ow * kernel[0] * kernel[1] * shape[3]
    text = lowered(ops.compute_a_conv)
    assert sum(s >= patch for s in _tensor_sizes(text)) == patch_tensors
    assert text.count('stablehlo.dot_general') == 1 + use_bias
    # the reader does see the oracle's copies
    assert sum(s >= patch for s in
               _tensor_sizes(lowered(oracle.compute_a_conv))) >= 3


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('batch_averaged', [True, False],
                         ids=['averaged', 'summed'])
def test_conv_g_one_pass_against_the_row_scaled_form(batch_averaged, dtype):
    rng = np.random.RandomState(11)
    g = jnp.asarray(rng.randn(4, 5, 6, 7) * 1e-3, dtype)
    got = np.asarray(ops.compute_g_conv(g, batch_averaged))
    want = np.asarray(oracle.compute_g_conv(g, batch_averaged))
    assert got.dtype == np.float32 and got.shape == want.shape
    tol = 5e-3 if dtype == 'bfloat16' else 1e-5
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    rows = np.asarray(g, np.float64).reshape(-1, 7) * 30
    if batch_averaged:
        rows = rows * 4
    exact = rows.T @ rows / len(rows)
    assert _rel(got, exact) <= 1.1 * _rel(want, exact) + 1e-6
    assert np.abs(got - got.T).max() <= 1e-6 * np.abs(got).max()
    # g goes into the contraction as it is: no scaled copy of it
    text = jax.jit(lambda x: ops.compute_g_conv(x, batch_averaged)).lower(
        jax.ShapeDtypeStruct((64, 5, 6, 7), jnp.dtype(dtype))).as_text()
    assert not [n for n in _tensor_sizes(text) if n >= 64 * 5 * 6 * 7]
    assert text.count('stablehlo.dot_general') == 1


# -- dense statistics did not move (PR 26): BERT's step programs depend on it


@pytest.mark.parametrize('fn,args', [
    ('compute_a_dense', ((8, 5), True)),
    ('compute_a_dense', ((8, 5), False)),
    ('compute_a_dense', ((4, 7, 5), True)),
    ('compute_a_dense', ((4, 7, 5), False)),
    ('compute_g_dense', ((8, 5), True)),
    ('compute_g_dense', ((8, 5), False)),
    ('compute_g_dense', ((4, 7, 5), True)),
], ids=['a-2d-bias', 'a-2d-nobias', 'a-seq-bias', 'a-seq-nobias',
        'g-2d-averaged', 'g-2d-summed', 'g-seq-averaged'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_dense_statistics_trace_as_before(fn, args, dtype):
    shape, flag = args
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    now = jax.make_jaxpr(lambda v: getattr(factors, fn)(v, flag))(x)
    then = jax.make_jaxpr(lambda v: getattr(oracle, fn)(v, flag))(x)
    assert str(now) == str(then)
