"""Fused Pallas capture kernels (ops/pallas_capture.py, ISSUE 19).

Pins the numerical contract from the module docstring, under the Pallas
interpreter on the CPU tier:

1. Every STAT kernel (dense A/G, conv A/G, all bias x batch_averaged x
   padding/stride combinations) reproduces the ops/factors.py reference
   BIT-FOR-BIT when the row reduction fits one grid step — the strict-
   mode pins hold XLA's jit rewrites to the eager rounding sequence.
   Multi-tile runs (KFAC_CAPTURE_TR) stay value-equal; the VMEM cap
   (KFAC_CAPTURE_MAX_F) falls back to the reference exactly.
   The conv kernels' bit reference is the row-scaled form they implement
   (tests/factor_oracles.py: ops/factors.py before PR 26), their value
   reference the one-pass form of today's ops/factors.py.
2. The EMA epilogue is algebraically identical, DETERMINISTIC across
   repeated invocations, and within one fp32 rounding of the unfused
   two-pass program (its final combine FMA-contracts under jit — the
   one documented exception to bitwise); a traced alpha two-passes and
   stays fully bitwise.
3. ef_quantize emits the exact xc/bf16-wire/residual algebra of
   collectives.pmean_scatter_ef's two-pass branch, bitwise — including
   under an 8-device shard_map (the wire bytes never change; the
   comm_count '+pallas' spec pins the ledger side).
4. End-to-end world=1: a KFAC step with capture_impl='pallas'
   (including the fully fused update_factors_fused path DP variants
   take) matches capture_impl=None, and capture_impl='xla' IS the
   legacy path bit-for-bit.
5. The compile-count guard: a capture_impl ladder switch through the
   arbiter clears the variant cache exactly once; replaying the
   committed trajectory compiles nothing new.
"""

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import autotune, capture, training
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.ops import factors, pallas_capture

from tests import factor_oracles as oracle

pytestmark = pytest.mark.core


def _rng(seed=0):
    return np.random.RandomState(seed)


# ---------------------------------------------------------------------------
# 1. statistic-kernel bit parity vs ops/factors.py (single grid step)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('use_bias', [True, False])
def test_a_dense_bitwise(use_bias):
    a = jnp.asarray(_rng(1).randn(32, 12), jnp.float32)
    ref = factors.compute_a_dense(a, use_bias)
    got = pallas_capture.compute_a_dense(a, use_bias, interpret=True)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_a_dense_ndim3_seq_mean_bitwise():
    # [N, T, D] activations (the transformer capture shape): the
    # sequence mean happens OUTSIDE the kernel, identically to the
    # reference
    a = jnp.asarray(_rng(2).randn(8, 6, 10), jnp.float32)
    ref = factors.compute_a_dense(a, True)
    got = pallas_capture.compute_a_dense(a, True, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize('batch_averaged', [True, False])
def test_g_dense_bitwise(batch_averaged):
    g = jnp.asarray(_rng(3).randn(32, 9), jnp.float32)
    ref = factors.compute_g_dense(g, batch_averaged)
    got = pallas_capture.compute_g_dense(g, batch_averaged,
                                         interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize('batch_averaged', [True, False])
def test_g_conv_bitwise(batch_averaged):
    g = jnp.asarray(_rng(4).randn(4, 5, 5, 7), jnp.float32)
    ref = oracle.compute_g_conv(g, batch_averaged)
    got = pallas_capture.compute_g_conv(g, batch_averaged,
                                        interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    now = factors.compute_g_conv(g, batch_averaged)
    np.testing.assert_allclose(np.asarray(got), np.asarray(now),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize('use_bias', [True, False])
@pytest.mark.parametrize('strides', [(1, 1), (2, 2)])
@pytest.mark.parametrize('padding', ['SAME', 'VALID', (1, 1),
                                     ((1, 2), (0, 1))])
def test_a_conv_bitwise(use_bias, strides, padding):
    a = jnp.asarray(_rng(5).randn(4, 9, 9, 3), jnp.float32)
    # the kernel scales rows as the row-scaled form does (the package's
    # form before PR 26, kept as the oracle): its bits, and the one-pass
    # form's value
    ref = oracle.compute_a_conv(a, (3, 3), strides, padding, use_bias)
    got = pallas_capture.compute_a_conv(a, (3, 3), strides, padding,
                                        use_bias, interpret=True)
    assert got.shape == ref.shape
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    now = factors.compute_a_conv(a, (3, 3), strides, padding, use_bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(now),
                               rtol=1e-5, atol=1e-7)


def test_a_conv_rect_kernel_bitwise():
    # non-square taps exercise the (ki, kj) slice loop asymmetrically
    a = jnp.asarray(_rng(6).randn(3, 8, 10, 2), jnp.float32)
    ref = oracle.compute_a_conv(a, (1, 3), (1, 2), 'SAME', True)
    got = pallas_capture.compute_a_conv(a, (1, 3), (1, 2), 'SAME', True,
                                        interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_multi_tile_value_equal(monkeypatch):
    # KFAC_CAPTURE_TR splits the row reduction across grid steps: the
    # fp32 partial sums accumulate in row-tile order — value-equal up
    # to summation order, never a shape/scaling change
    monkeypatch.setenv('KFAC_CAPTURE_TR', '8')
    a = jnp.asarray(_rng(7).randn(32, 12), jnp.float32)
    ref = factors.compute_a_dense(a, True)
    got = pallas_capture.compute_a_dense(a, True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)
    # and the tile knob actually split the grid (divisor lowering)
    assert pallas_capture._row_tile(32, 12) == 8


def test_row_tile_lowers_to_divisor(monkeypatch):
    monkeypatch.setenv('KFAC_CAPTURE_TR', '7')
    assert pallas_capture._row_tile(32, 12) == 4   # nearest divisor <= 7
    monkeypatch.delenv('KFAC_CAPTURE_TR')
    # whole reduction fits the VMEM budget -> one grid step
    assert pallas_capture._row_tile(32, 12) == 32


def test_max_f_cap_falls_back_to_reference(monkeypatch):
    # a factor dim over the VMEM cap stays on the XLA path (bitwise
    # trivially — it IS the reference), with the EMA still applied
    monkeypatch.setenv('KFAC_CAPTURE_MAX_F', '8')
    a = jnp.asarray(_rng(8).randn(16, 12), jnp.float32)   # F=13 > 8
    cur = jnp.eye(13, dtype=jnp.float32)
    ref = factors.update_running_avg(
        factors.compute_a_dense(a, True), cur, 0.95)
    got = pallas_capture.compute_a_dense(a, True, ema=(cur, 0.95),
                                         interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# 2. the EMA epilogue contract
# ---------------------------------------------------------------------------

def _two_pass_ema(stat_fn, cur, alpha):
    return factors.update_running_avg(stat_fn(), cur, alpha)


@pytest.mark.parametrize('kind', ['a_dense', 'a_conv', 'g_dense',
                                  'g_conv'])
def test_ema_epilogue_within_one_rounding(kind):
    r = _rng(9)
    if kind == 'a_dense':
        x = jnp.asarray(r.randn(16, 10), jnp.float32)
        ref_stat = lambda: factors.compute_a_dense(x, True)
        fused = lambda ema: pallas_capture.compute_a_dense(
            x, True, ema=ema, interpret=True)
        f = 11
    elif kind == 'a_conv':
        x = jnp.asarray(r.randn(3, 7, 7, 2), jnp.float32)
        ref_stat = lambda: oracle.compute_a_conv(
            x, (3, 3), (1, 1), 'SAME', True)
        fused = lambda ema: pallas_capture.compute_a_conv(
            x, (3, 3), (1, 1), 'SAME', True, ema=ema, interpret=True)
        f = 19
    elif kind == 'g_dense':
        x = jnp.asarray(r.randn(16, 6), jnp.float32)
        ref_stat = lambda: factors.compute_g_dense(x, True)
        fused = lambda ema: pallas_capture.compute_g_dense(
            x, True, ema=ema, interpret=True)
        f = 6
    else:
        x = jnp.asarray(r.randn(3, 5, 5, 4), jnp.float32)
        ref_stat = lambda: oracle.compute_g_conv(x, True)
        fused = lambda ema: pallas_capture.compute_g_conv(
            x, True, ema=ema, interpret=True)
        f = 4
    cur = jnp.asarray(r.randn(f, f).astype(np.float32))
    stat = np.asarray(ref_stat())
    ref = np.asarray(_two_pass_ema(ref_stat, cur, 0.95))
    got = np.asarray(fused((cur, 0.95)))
    # algebraically identical; the final cur*(1-a) + stat*a combine may
    # FMA-contract under jit — ONE fewer fp32 rounding than the unfused
    # program (module docstring contract). A single dropped rounding is
    # worth <= ~1 ulp of the LARGER TERM (where the combine cancels,
    # ulp(ref) itself shrinks but the absolute error cannot), so the
    # bound is in ulps of the intermediate magnitudes
    mag = np.maximum(np.abs(np.asarray(cur)) * np.float32(0.05),
                     np.abs(stat) * np.float32(0.95))
    ulp = np.spacing(mag.astype(np.float32))
    assert np.all(np.abs(got - ref) <= 2 * ulp), (
        np.max(np.abs(got - ref) / ulp))
    # ...and deterministic: a second invocation is bit-identical
    again = np.asarray(fused((cur, 0.95)))
    assert np.array_equal(got, again)


def test_ema_stable_across_steps():
    # iterate the fused EMA as the preconditioner does (output feeds
    # back as `cur`): the trajectory tracks the unfused one within
    # accumulated single-rounding error and never drifts structurally
    r = _rng(10)
    x = jnp.asarray(r.randn(16, 10), jnp.float32)
    stat = factors.compute_a_dense(x, True)
    cur_ref = jnp.eye(11, dtype=jnp.float32)
    cur_fused = cur_ref
    for _ in range(10):
        cur_ref = factors.update_running_avg(stat, cur_ref, 0.95)
        cur_fused = pallas_capture.compute_a_dense(
            x, True, ema=(cur_fused, 0.95), interpret=True)
    np.testing.assert_allclose(np.asarray(cur_fused),
                               np.asarray(cur_ref),
                               rtol=1e-6, atol=1e-7)
    # symmetry is preserved exactly (both inputs symmetric)
    got = np.asarray(cur_fused)
    assert np.array_equal(got, got.T)


def test_traced_alpha_two_passes_bitwise():
    # a TRACED decay cannot be closed over by the kernel: the ema kwarg
    # falls back to stat-kernel + update_running_avg — fully bitwise vs
    # the reference (no fused emit involved)
    x = jnp.asarray(_rng(11).randn(16, 10), jnp.float32)
    cur = jnp.eye(11, dtype=jnp.float32)
    alpha = jnp.float32(0.95)                 # traced, not a python float
    assert not pallas_capture._ema_static((cur, alpha))
    ref = factors.update_running_avg(
        factors.compute_a_dense(x, True), cur, alpha)
    got = pallas_capture.compute_a_dense(x, True, ema=(cur, alpha),
                                         interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# 3. ef_quantize: the wire-quantize + error-feedback epilogue
# ---------------------------------------------------------------------------

def test_ef_quantize_bitwise_vs_two_pass():
    r = _rng(12)
    x = jnp.asarray(r.randn(8, 6, 6), jnp.float32)
    res = jnp.asarray(r.randn(8, 6, 6).astype(np.float32) * 1e-3)
    wire, new_res = pallas_capture.ef_quantize(x, res, interpret=True)
    xc = x + res
    ref_wire = xc.astype(jnp.bfloat16)
    ref_res = xc - ref_wire.astype(jnp.float32)
    assert wire.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(wire, dtype=np.float32),
                          np.asarray(ref_wire, dtype=np.float32))
    assert np.array_equal(np.asarray(new_res), np.asarray(ref_res))


def test_ef_quantize_bitwise_under_shard_map():
    # the fused epilogue inside the per-device program of an 8-way
    # shard_map (the pmean_scatter_ef call site): wire and residual
    # stay bitwise vs the two-pass algebra on every shard
    ndev = 8
    if len(jax.devices()) < ndev:
        pytest.skip('needs 8 host devices (conftest XLA_FLAGS)')
    mesh = Mesh(np.array(jax.devices()[:ndev]), ('x',))
    r = _rng(13)
    x = jnp.asarray(r.randn(ndev * 4, 6), jnp.float32)
    res = jnp.asarray(r.randn(ndev * 4, 6).astype(np.float32) * 1e-3)

    def fused(xs, rs):
        return pallas_capture.ef_quantize(
            xs, rs, interpret=pallas_capture.interpret_default())

    def two_pass(xs, rs):
        xc = xs + rs
        wire = xc.astype(jnp.bfloat16)
        return wire, xc - wire.astype(xs.dtype)

    kw = dict(mesh=mesh, in_specs=(P('x'), P('x')),
              out_specs=(P('x'), P('x')))
    # the Pallas INTERPRETER seeds its output loop carries without the
    # varying axes the out_shape declares (jax 0.9 hlo_interpreter), so
    # an interpreted kernel needs check_vma=False — the Mosaic lowering
    # on the chip does not (tests/test_chip_compile.py)
    w1, r1 = jax.jit(jax.shard_map(fused, check_vma=False, **kw))(x, res)
    w2, r2 = jax.jit(jax.shard_map(two_pass, **kw))(x, res)
    assert np.array_equal(np.asarray(w1, dtype=np.float32),
                          np.asarray(w2, dtype=np.float32))
    assert np.array_equal(np.asarray(r1), np.asarray(r2))


# ---------------------------------------------------------------------------
# 4. end-to-end world=1 parity through KFAC.step
# ---------------------------------------------------------------------------

class MLP(linen.Module):
    @linen.compact
    def __call__(self, x, train=True):
        x = knn.Dense(8, name='fc1')(x)
        x = linen.relu(x)
        x = knn.Dense(3, name='fc2')(x)
        return x


def _setup(variant, capture_impl, **kw):
    model = MLP()
    r = _rng(0)
    x = jnp.asarray(r.randn(4, 5), jnp.float32)
    y = jnp.asarray(r.randn(4, 3), jnp.float32)
    variables = capture.init(model, jax.random.PRNGKey(0), x)
    metas = capture.collect_layer_meta(model, variables, x)
    precond = kfac.KFAC(variant=variant, num_devices=1, axis_name=None,
                        bucket_fn=lambda d: 16,
                        capture_impl=capture_impl, **kw)
    precond.setup(metas)
    state = precond.init()
    loss_fn = lambda out: jnp.mean((out - y) ** 2)
    _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
        model, loss_fn, variables, x)
    return precond, state, grads, acts, gs


def _tree_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(flat_a, flat_b))


@pytest.mark.parametrize('variant', ['inverse', 'eigen_dp'])
def test_step_world1_pallas_matches_legacy(variant):
    """world=1 trajectory parity: 'pallas' (the DP variant takes the
    fully fused update_factors_fused path) preconditions identically to
    the legacy capture — same grads, same factor state — across two
    steps (step 2 consumes step 1's EMA)."""
    pre_x, st_x, grads, acts, gs = _setup(variant, None)
    pre_p, st_p, _, _, _ = _setup(variant, 'pallas')
    for _ in range(2):
        g_x, st_x = pre_x.step(st_x, grads, acts, gs)
        g_p, st_p = pre_p.step(st_p, grads, acts, gs)
    if variant == 'eigen_dp':
        # the DP variant takes update_factors_fused: the EMA emit may
        # FMA-contract (the documented one-rounding exception), so the
        # factor state tracks within ulp-level tolerance — and the
        # damped eigendecomposition amplifies that ulp into ~1e-4
        # relative on the preconditioned gradient (condition ~1/damping),
        # i.e. ~1e-5 of the gradient's scale on its near-zero entries
        for k in st_x.factors:
            np.testing.assert_allclose(
                np.asarray(st_p.factors[k]), np.asarray(st_x.factors[k]),
                rtol=1e-6, atol=1e-7)
        g_rtol, g_atol = 5e-4, 1e-5
    else:
        # stat kernels + two-pass EMA: fully bitwise
        assert _tree_equal(st_x.factors, st_p.factors)
        g_rtol, g_atol = 1e-6, 1e-8
    np.testing.assert_allclose(
        np.asarray(g_p['fc1']['kernel']), np.asarray(g_x['fc1']['kernel']),
        rtol=g_rtol, atol=g_atol)
    np.testing.assert_allclose(
        np.asarray(g_p['fc2']['kernel']), np.asarray(g_x['fc2']['kernel']),
        rtol=g_rtol, atol=g_atol)


def test_step_world1_xla_is_legacy_bitwise():
    """capture_impl='xla' routes through the identical ops/factors.py
    calls — bit-for-bit the None (legacy) program."""
    pre_n, st_n, grads, acts, gs = _setup('eigen', None)
    pre_x, st_x, _, _, _ = _setup('eigen', 'xla')
    g_n, st_n = pre_n.step(st_n, grads, acts, gs)
    g_x, st_x = pre_x.step(st_x, grads, acts, gs)
    assert _tree_equal(st_n.factors, st_x.factors)
    assert _tree_equal(g_n, g_x)


def test_auto_resolves_to_pallas():
    pre = kfac.KFAC(variant='eigen', capture_impl='auto')
    assert pre.resolved_capture_impl == 'pallas'
    assert kfac.KFAC(variant='eigen').resolved_capture_impl is None
    with pytest.raises(ValueError, match='capture_impl'):
        kfac.KFAC(variant='eigen', capture_impl='fused')


# ---------------------------------------------------------------------------
# 5. compile-count guard on ladder switches
# ---------------------------------------------------------------------------

def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def test_capture_ladder_switch_compile_count():
    """A capture_impl move through the arbiter clears the variant cache
    (trace-affecting, like comm_precision); steps at the committed rung
    then fill a bounded variant set, and REPLAYING the committed
    trajectory compiles exactly nothing."""
    r = _rng(0)
    batch = {'input': jnp.asarray(r.randn(8, 5), jnp.float32),
             'label': jnp.asarray(r.randint(0, 3, 8))}
    model = MLP()
    pre = kfac.KFAC(variant='eigen_dp', lr=0.05, damping=0.003,
                    num_devices=1, axis_name=None,
                    bucket_fn=lambda d: 16, capture_impl='xla')
    tx = training.sgd(0.05, momentum=0.9)
    state = training.init_train_state(model, tx, pre,
                                      jax.random.PRNGKey(0),
                                      batch['input'])
    step = training.build_train_step(model, tx, pre, _ce,
                                     axis_name=None, mesh=None)
    arb = autotune.arbiter_for(pre)
    for _ in range(3):
        state, _ = step(state, batch, lr=0.05, damping=0.003)
    assert step.variants
    # the ladder commit: xla -> pallas clears the cache exactly once
    arb.propose('tuner', capture_impl='pallas')
    assert pre.capture_impl == 'pallas'
    assert not step.variants
    for _ in range(4):
        state, m = step(state, batch, lr=0.05, damping=0.003)
    assert np.isfinite(float(m['loss']))
    committed = set(step.variants)
    assert committed
    # zero recompiles replaying the committed trajectory
    for _ in range(6):
        state, _ = step(state, batch, lr=0.05, damping=0.003)
    assert set(step.variants) == committed, (
        sorted(map(str, set(step.variants) - committed)))


def test_xla_routing_is_counted_and_reported_once(monkeypatch, capsys):
    """A statistic that asked for the fused kernel and cannot have it is
    never routed silently: inside a capture pass the routing is counted
    on both sides and reported in ONE line per run (every step variant
    re-traces the same pass); outside a pass it warns per shape."""
    monkeypatch.setenv('KFAC_CAPTURE_MAX_F', '8')
    monkeypatch.setattr(pallas_capture, '_WARNED', set())
    small = jnp.ones((4, 6), jnp.float32)
    wide = jnp.ones((4, 16), jnp.float32)
    for _ in range(2):
        with pallas_capture.routing_report():
            pallas_capture.compute_a_dense(small, True, interpret=True)
            pallas_capture.compute_g_dense(wide, True, interpret=True)
    err = capsys.readouterr().err
    assert err.count('capture_impl=pallas: 1 factor statistics fused, '
                     '1 on the XLA path (1 x factor dim over the fused '
                     'cap)') == 1
    pallas_capture.compute_g_dense(wide, True, interpret=True)
    assert 'stays on the XLA path' in capsys.readouterr().err


@pytest.mark.parametrize('strides,kernel,padding', [
    ((2, 2), (1, 1), 'VALID'),              # ResNet downsample
    ((2, 2), (3, 3), ((1, 1), (1, 1))),     # strided 3x3
    ((2, 2), (7, 7), ((3, 3), (3, 3))),     # conv1 geometry (small C ok)
    ((2, 1), (3, 2), 'SAME'),               # mixed strides, odd sizes
    ((3, 2), (2, 3), ((0, 1), (2, 0))),     # stride > kernel on one axis
])
def test_conv_a_strided_phases_bitwise(strides, kernel, padding):
    """The stride phases are de-interleaved before the kernel (Mosaic
    lowers no strided slice): pure data movement, so every stride keeps
    the reference's bits."""
    a = jnp.asarray(_rng(21).randn(3, 11, 9, 5), jnp.float32)
    got = pallas_capture.compute_a_conv(a, kernel, strides, padding, True,
                                        interpret=True)
    want = oracle.compute_a_conv(a, kernel, strides, padding, True)
    assert np.array_equal(np.asarray(got), np.asarray(want))
