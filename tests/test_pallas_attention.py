"""Pallas flash-attention block kernel tests (interpret mode on the CPU
mesh): the fused kernel must produce bitwise-compatible online-softmax
pieces and exact gradients vs the plain-XLA block implementation, both
standalone and composed into ring attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import importlib

from kfac_pytorch_tpu.ops.pallas_attention import flash_block_attn

# the package re-exports the function under the submodule's name, so the
# module object must come from importlib
ring_mod = importlib.import_module(
    'kfac_pytorch_tpu.parallel.ring_attention')

BH, LQ, LK, D = 4, 32, 32, 16
SCALE = D ** -0.5


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(BH, LQ, D), jnp.float32)
    k = jnp.asarray(rng.randn(BH, LK, D), jnp.float32)
    v = jnp.asarray(rng.randn(BH, LK, D), jnp.float32)
    mask = jnp.asarray(rng.rand(BH, LK) > 0.2, jnp.float32)
    return q, k, v, mask


def _reference(q, k, v, mask, q_start, k_start, causal):
    # additive bias, matching the framework's convention everywhere
    # (degenerate fully-masked rows keep their s-dependence)
    s = jnp.einsum('bqd,bkd->bqk', q, k) * SCALE
    if causal:
        qpos = q_start + jnp.arange(LQ)[:, None]
        kpos = k_start + jnp.arange(LK)[None, :]
        s = s + jnp.where(qpos >= kpos, 0.0, -1e30)
    s = s + jnp.where(mask[:, None, :] > 0.5, 0.0, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    return m, p.sum(-1), jnp.einsum('bqk,bkd->bqd', p, v)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('starts', [(0, 0), (64, 32)])
def test_kernel_matches_reference(causal, starts):
    q, k, v, mask = _inputs()
    m, l, pv = flash_block_attn(q, k, v, mask,
                                jnp.asarray(starts, jnp.int32), SCALE,
                                causal, True)
    rm, rl, rpv = _reference(q, k, v, mask, *starts, causal)
    np.testing.assert_allclose(np.asarray(m), np.asarray(rm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(rl),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(rpv),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize('tq,tk', [(8, 16), (16, 8), (32, 32)])
def test_kernel_tile_override_exact(monkeypatch, tq, tk):
    """KFAC_FLASH_TQ/TK (the on-chip tile-sweep knobs) change only the
    schedule, never the math: every tile shape must reproduce the
    reference exactly, including causal with non-zero global starts."""
    monkeypatch.setenv('KFAC_FLASH_TQ', str(tq))
    monkeypatch.setenv('KFAC_FLASH_TK', str(tk))
    q, k, v, mask = _inputs(seed=2)
    m, l, pv = flash_block_attn(q, k, v, mask,
                                jnp.asarray((64, 32), jnp.int32), SCALE,
                                True, True)
    rm, rl, rpv = _reference(q, k, v, mask, 64, 32, True)
    np.testing.assert_allclose(np.asarray(m), np.asarray(rm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(rl),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(rpv),
                               atol=1e-4, rtol=1e-4)
    # a non-dividing request falls back to a dividing power-of-two tile
    from kfac_pytorch_tpu.ops.pallas_attention import _fwd_tile
    monkeypatch.setenv('KFAC_FLASH_TK', '480')
    assert _fwd_tile('KFAC_FLASH_TK', 128, 640) == 128  # 480→256→128|640
    monkeypatch.setenv('KFAC_FLASH_TK', '512')
    assert _fwd_tile('KFAC_FLASH_TK', 128, 8192) == 512
    monkeypatch.setenv('KFAC_FLASH_TK', '512')
    assert _fwd_tile('KFAC_FLASH_TK', 128, 384) == 128  # clamp→pow2→divide
    monkeypatch.delenv('KFAC_FLASH_TK')
    assert _fwd_tile('KFAC_FLASH_TK', 128, 24) == 8


def test_kernel_gradients_match_xla_blocks():
    q, k, v, mask = _inputs(seed=1)
    q4 = q[:, None]  # [BH, 1(head), L, D] for the dispatch layout
    k4, v4 = k[:, None], v[:, None]

    def loss(impl, q4, k4, v4):
        out = ring_mod.ring_attention(
            q4, k4, v4, axis_name=None, causal=True,
            kv_mask=mask > 0.5, block_impl=impl)
        return (out.astype(jnp.float32) ** 2).sum()

    g_pallas = jax.grad(functools.partial(loss, 'pallas_interpret'),
                        argnums=(0, 1, 2))(q4, k4, v4)
    g_xla = jax.grad(functools.partial(loss, 'xla'),
                     argnums=(0, 1, 2))(q4, k4, v4)
    for a, b in zip(g_pallas, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_non_tile_multiple_length_values_and_grads():
    """L=160 (>128, not a multiple of 128): the dispatch must pad to the
    tile grid — regression for silent tail truncation."""
    rng = np.random.RandomState(3)
    B, H, L = 1, 2, 160
    mk = lambda: jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def loss(impl, q, k, v):
        out = ring_mod.ring_attention(q, k, v, axis_name=None, causal=True,
                                      block_impl=impl)
        return (out ** 2).sum(), out

    (lp, out_p), gp = jax.value_and_grad(
        functools.partial(loss, 'pallas_interpret'), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    (lx, out_x), gx = jax.value_and_grad(
        functools.partial(loss, 'xla'), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_gradients_finite_with_fully_future_blocks():
    """Causal ring steps where the K/V block lies entirely in this
    device's future leave the kernel's online-softmax m at its -1e30 init
    (every tile causally skipped — a contract the XLA block path does not
    share). Gradients through the combine must stay finite and equal to
    the XLA path's even with large-magnitude scores pressing on the
    recompute backward's exp."""
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ('seq',))
    rng = np.random.RandomState(4)
    B, H, L = 1, 2, 64
    # scale 10x: raw scores reach O(100), past exp overflow at ~88
    mk = lambda: jnp.asarray(10.0 * rng.randn(B, H, L, D), jnp.float32)
    q, k, v = mk(), mk(), mk()
    spec = P(None, None, 'seq', None)

    def loss(impl, q, k, v):
        out = jax.shard_map(
            functools.partial(ring_mod.ring_attention, axis_name='seq',
                              causal=True, block_impl=impl),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)(q, k, v)
        return (out.astype(jnp.float32) ** 2).sum()

    gp = jax.grad(functools.partial(loss, 'pallas_interpret'),
                  argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(functools.partial(loss, 'xla'),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_diag_tile_clamps_identity_on_needed_iterations():
    """The causal copy-elision clamps only run on real TPU (the
    interpreter can't evaluate vma-tagged meta), so pin their math here:
    for every grid iteration whose tile the kernel actually computes
    (last_q >= first_k), the clamped K-tile index must equal j and the
    clamped q-tile index must equal iq — a wrong clamp would feed the
    kernel the wrong tile with no test to catch it."""
    from kfac_pytorch_tpu.ops.pallas_attention import (_diag_k_tile,
                                                       _diag_q_tile)
    for q_start, k_start, tq, tk, nq, nk in [
            (0, 0, 8, 8, 4, 4), (0, 0, 128, 128, 3, 3),
            (64, 32, 16, 8, 5, 7), (256, 0, 128, 128, 2, 4),
            (0, 256, 8, 16, 6, 3), (96, 96, 32, 32, 4, 4)]:
        meta = jnp.asarray([q_start, k_start], jnp.int32)
        for iq in range(nq):
            for j in range(nk):
                last_q = q_start + (iq + 1) * tq - 1
                first_k = k_start + j * tk
                needed = last_q >= first_k
                kj = int(jnp.minimum(j, _diag_k_tile(iq, meta, tq, tk)))
                qi = int(jnp.maximum(
                    iq, _diag_q_tile(j, meta, tq, tk, nq)))
                if needed:
                    assert kj == j, (q_start, k_start, tq, tk, iq, j, kj)
                    assert qi == iq, (q_start, k_start, tq, tk, iq, j, qi)
                # skipped iterations may point anywhere in range
                assert 0 <= kj < nk and 0 <= qi < nq


def test_pallas_bwd_matches_recompute_bwd(monkeypatch):
    """The fused Pallas backward and the JAX blockwise-recompute backward
    are two implementations of the same VJP — gradients must match to
    numerical noise (causal + key masking + block offsets exercised)."""
    q, k, v, mask = _inputs(seed=5)
    starts = jnp.asarray((64, 32), jnp.int32)

    def loss(q, k, v):
        m, l, pv = flash_block_attn(q, k, v, mask, starts, SCALE, True,
                                    True)
        return (l ** 2).sum() + (pv ** 2).sum()

    grads = {}
    for impl in ['pallas', 'recompute']:
        monkeypatch.setenv('KFAC_ATTN_BWD_IMPL', impl)
        grads[impl] = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads['pallas'], grads['recompute']):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_bwd_impl_auto_policy():
    """'auto' resolves by static block key length: blockwise recompute
    below the hypothesized v5e crossover (ROADMAP S7), fused Pallas
    backward at/above it."""
    from kfac_pytorch_tpu.ops.pallas_attention import (
        AUTO_BWD_PALLAS_MIN_LK, _bwd_impl_for)
    assert _bwd_impl_for('auto', 1024) == 'recompute'
    assert _bwd_impl_for('auto', AUTO_BWD_PALLAS_MIN_LK - 128) == 'recompute'
    assert _bwd_impl_for('auto', AUTO_BWD_PALLAS_MIN_LK) == 'pallas'
    assert _bwd_impl_for('auto', 2 * AUTO_BWD_PALLAS_MIN_LK) == 'pallas'
    # explicit choices pass through untouched; junk is rejected
    assert _bwd_impl_for('pallas', 8) == 'pallas'
    assert _bwd_impl_for('recompute', 1 << 20) == 'recompute'
    with pytest.raises(ValueError):
        _bwd_impl_for('fused', 1024)


def test_fwd_impl_auto_policy(monkeypatch):
    """'auto' forward resolves by static block key length, mirroring the
    backward policy: XLA blockwise below the hypothesized v5e crossover
    (ROADMAP S7), the Pallas kernel at/above it."""
    from kfac_pytorch_tpu.parallel.ring_attention import (
        AUTO_FWD_PALLAS_MIN_LK, _default_block_impl, _fwd_impl_for)
    assert _fwd_impl_for('auto', 1024) == 'xla'
    assert _fwd_impl_for('auto', AUTO_FWD_PALLAS_MIN_LK - 128) == 'xla'
    assert _fwd_impl_for('auto', AUTO_FWD_PALLAS_MIN_LK) == 'pallas'
    assert _fwd_impl_for('auto', 2 * AUTO_FWD_PALLAS_MIN_LK) == 'pallas'
    # explicit choices pass through untouched; junk is rejected
    assert _fwd_impl_for('xla', 1 << 20) == 'xla'
    assert _fwd_impl_for('pallas', 8) == 'pallas'
    assert _fwd_impl_for('pallas_interpret', 8) == 'pallas_interpret'
    with pytest.raises(ValueError):
        _fwd_impl_for('fused', 1024)
    # off-TPU default stays 'xla' (tests run on the CPU mesh); cleared
    # env so a KFAC_ATTN_IMPL override in the test environment can't
    # perturb the default-path assertion
    monkeypatch.delenv('KFAC_ATTN_IMPL', raising=False)
    assert _default_block_impl() in ('xla', 'auto')


def test_ring_with_pallas_blocks_matches_dense():
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ('seq',))
    rng = np.random.RandomState(2)
    B, H, L = 2, 2, 64
    mk = lambda: jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
    q, k, v = mk(), mk(), mk()

    spec = P(None, None, 'seq', None)
    # check_vma=False: the Pallas interpreter does not yet propagate
    # varying-manual-axes through its closed_call (TPU lowering does)
    out = jax.jit(jax.shard_map(
        functools.partial(ring_mod.ring_attention, axis_name='seq',
                          causal=True, block_impl='pallas_interpret'),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False))(q, k, v)

    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * SCALE
    s = jnp.where(jnp.arange(L)[:, None] >= jnp.arange(L)[None, :],
                  s, -1e30)
    ref = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
