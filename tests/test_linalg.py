"""Batched symmetric linalg + exactness of identity padding."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from kfac_pytorch_tpu import ops

pytestmark = pytest.mark.core


def _spd(rng, *shape):
    a = rng.randn(*shape).astype(np.float32)
    return a @ np.swapaxes(a, -1, -2) + shape[-1] * np.eye(shape[-1],
                                                           dtype=np.float32)


def test_psd_inverse_batched():
    rng = np.random.RandomState(0)
    x = _spd(rng, 5, 8, 8)
    inv = np.asarray(ops.psd_inverse(jnp.asarray(x)))
    np.testing.assert_allclose(inv, np.linalg.inv(x), rtol=1e-3, atol=1e-4)


def test_sym_eig_reconstructs():
    rng = np.random.RandomState(1)
    x = _spd(rng, 3, 6, 6)
    d, q = ops.sym_eig(jnp.asarray(x))
    rec = np.asarray(q) @ (np.asarray(d)[..., None] * np.swapaxes(np.asarray(q), -1, -2))
    np.testing.assert_allclose(rec, x, rtol=1e-3, atol=1e-3)


def test_jacobi_eigh_matches_numpy():
    """Matmul-form Jacobi sweeps vs numpy eigh: eigenvalues, orthonormal
    eigenvectors, reconstruction — batched, single, and odd dims."""
    rng = np.random.RandomState(3)
    for shape in [(4, 16, 16), (2, 64, 64), (33, 33), (1, 9, 9)]:
        x = _spd(rng, *shape) / shape[-1]
        w, v = ops.jacobi_eigh(jnp.asarray(x))
        w, v = np.asarray(w), np.asarray(v)
        n = shape[-1]
        w_ref = np.linalg.eigvalsh(x)
        scale = np.abs(w_ref).max()
        np.testing.assert_allclose(w, w_ref, atol=1e-4 * scale, rtol=1e-4)
        # ascending order, orthonormal, reconstructs
        assert (np.diff(w, axis=-1) >= -1e-5 * scale).all()
        vtv = np.swapaxes(v, -1, -2) @ v
        np.testing.assert_allclose(vtv, np.broadcast_to(np.eye(n), vtv.shape),
                                   atol=5e-5)
        rec = v @ (w[..., None] * np.swapaxes(v, -1, -2))
        np.testing.assert_allclose(rec, x, atol=1e-4 * scale, rtol=1e-4)


def test_jacobi_paired_rotation_matches_dense():
    """'paired' (permute pairs adjacent, rotate 2x2 blocks elementwise)
    and 'dense' (packed-J matmuls) are two evaluations of the same
    rotation sequence — results must agree to rounding noise."""
    rng = np.random.RandomState(7)
    for shape in [(2, 16, 16), (1, 30, 30), (21, 21)]:
        x = _spd(rng, *shape) / shape[-1]
        wd, vd = ops.jacobi_eigh(jnp.asarray(x), rotate='dense')
        wp, vp = ops.jacobi_eigh(jnp.asarray(x), rotate='paired')
        np.testing.assert_allclose(np.asarray(wd), np.asarray(wp),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.abs(np.asarray(vd)),
                                   np.abs(np.asarray(vp)),
                                   rtol=1e-3, atol=1e-3)
    import pytest
    with pytest.raises(ValueError):
        ops.jacobi_eigh(jnp.eye(4), rotate='nope')


def test_sym_eig_jacobi_impl_dispatch():
    rng = np.random.RandomState(4)
    x = _spd(rng, 2, 12, 12)
    d1, q1 = ops.sym_eig(jnp.asarray(x), impl='jacobi')
    d2, q2 = ops.sym_eig(jnp.asarray(x), impl='xla')
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-4, atol=1e-3)
    # same eigenspaces: |Q1^T Q2| is a signed permutation (identity here,
    # eigenvalues are distinct and both sorted ascending)
    m = np.abs(np.swapaxes(np.asarray(q1), -1, -2) @ np.asarray(q2))
    np.testing.assert_allclose(m, np.broadcast_to(np.eye(12), m.shape),
                               atol=1e-2)


def test_clamp_eigvals():
    d = jnp.asarray([-1.0, 1e-12, 0.5])
    out = np.asarray(ops.clamp_eigvals(d, 1e-10))
    np.testing.assert_allclose(out, [0.0, 0.0, 0.5])


def test_add_scaled_identity_vector():
    x = jnp.zeros((2, 3, 3))
    out = np.asarray(ops.add_scaled_identity(x, jnp.asarray([1.0, 2.0])))
    np.testing.assert_allclose(out[0], np.eye(3))
    np.testing.assert_allclose(out[1], 2 * np.eye(3))


def test_masked_trace():
    x = jnp.asarray(np.diag([1.0, 2.0, 3.0, 4.0]).astype(np.float32))
    assert float(ops.masked_trace(x, 2)) == 3.0
    batch = jnp.stack([x, x])
    np.testing.assert_allclose(
        np.asarray(ops.masked_trace(batch, jnp.asarray([2, 3]))), [3.0, 6.0])


def test_identity_pad_exact_for_eigen_pred():
    """Padding factors with identity must not change the preconditioned
    gradient (the exactness claim in ops/linalg.py)."""
    rng = np.random.RandomState(2)
    da, dg, pad = 5, 4, 3
    A = _spd(rng, da, da)
    G = _spd(rng, dg, dg)
    grad = rng.randn(dg, da).astype(np.float32)
    damping = 0.01

    def eigen_pred(A, G, grad):
        dA, QA = np.linalg.eigh(A)
        dG, QG = np.linalg.eigh(G)
        v1 = QG.T @ grad @ QA
        v2 = v1 / (np.outer(dG, dA) + damping)
        return QG @ v2 @ QA.T

    want = eigen_pred(A, G, grad)
    Ap = np.asarray(ops.identity_pad(jnp.asarray(A), da + pad))
    Gp = np.asarray(ops.identity_pad(jnp.asarray(G), dg + pad))
    gp = np.zeros((dg + pad, da + pad), np.float32)
    gp[:dg, :da] = grad
    got = eigen_pred(Ap, Gp, gp)[:dg, :da]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # explicit-inverse path
    want_inv = np.linalg.inv(G + 0.1 * np.eye(dg)) @ grad @ np.linalg.inv(
        A + 0.1 * np.eye(da))
    got_inv = (np.linalg.inv(Gp + 0.1 * np.eye(dg + pad)) @ gp
               @ np.linalg.inv(Ap + 0.1 * np.eye(da + pad)))[:dg, :da]
    np.testing.assert_allclose(got_inv, want_inv, rtol=1e-4, atol=1e-5)


def test_subspace_eigh_tracks_drifting_factor():
    """Orthogonal-iteration warm eigh (the MXU-shaped warm kernel): from
    the PREVIOUS factor's eigenbasis, one tracking step on the drifted
    factor must deliver an orthonormal basis whose Rayleigh spectrum
    reconstructs the new factor — including a rank-deficient factor (the
    K-FAC regime) and the damped-inverse operator the preconditioner
    actually applies."""
    rng = np.random.RandomState(11)
    for shape, rank in [((3, 24, 24), None), ((2, 32, 32), 8)]:
        n = shape[-1]
        if rank is None:
            x0 = _spd(rng, *shape) / n
        else:  # rank-deficient: a a^T with a [*, n, rank]
            a = rng.randn(*shape[:-1], rank).astype(np.float32)
            x0 = a @ np.swapaxes(a, -1, -2) / n
        _, q0 = np.linalg.eigh(x0)
        drift = _spd(rng, *shape) / n
        x1 = (0.95 * x0 + 0.05 * drift).astype(np.float32)

        w, q = ops.subspace_eigh(jnp.asarray(x1), jnp.asarray(q0))
        w, q = np.asarray(w), np.asarray(q)
        qtq = np.swapaxes(q, -1, -2) @ q
        np.testing.assert_allclose(
            qtq, np.broadcast_to(np.eye(n), qtq.shape), atol=5e-5)
        rec = q @ (w[..., None] * np.swapaxes(q, -1, -2))
        scale = np.abs(x1).max()
        assert np.max(np.abs(rec - x1)) < 0.04 * scale, \
            np.max(np.abs(rec - x1)) / scale
        # the operator that matters: (X + lam I)^-1 via the decomposition.
        # The rank-deficient case concentrates its error in a tight
        # near-degenerate eigenvalue cluster whose members the tracker
        # deliberately leaves mixed (Tikhonov-suppressed rotations); with
        # damping below the cluster scale the inverse amplifies that, so
        # its bound is looser — the spectrum itself must still be right.
        lam = 1e-2
        op = q @ (np.swapaxes(q, -1, -2) /
                  (np.maximum(w, 0) + lam)[..., :, None])
        exact = np.linalg.inv(x1 + lam * np.eye(n, dtype=np.float32))
        err = (np.abs(op - exact).max(axis=(-2, -1))
               / np.abs(exact).max(axis=(-2, -1)))
        assert (err < (0.05 if rank is None else 0.25)).all(), err
        w_true = np.linalg.eigvalsh(x1)
        w_scale = np.abs(w_true).max()
        assert np.max(np.abs(np.sort(w, axis=-1) - w_true)) < 0.02 * w_scale
        # more steps -> tighter reconstruction
        w3, q3 = ops.subspace_eigh(jnp.asarray(x1), jnp.asarray(q0),
                                   steps=3)
        rec3 = (np.asarray(q3) @ (np.asarray(w3)[..., None]
                                  * np.swapaxes(np.asarray(q3), -1, -2)))
        assert np.max(np.abs(rec3 - x1)) <= np.max(np.abs(rec - x1)) + 1e-5


def test_sym_eig_subspace_dispatch():
    """impl='subspace' falls back to XLA QDWH with no basis (cold) and
    runs the tracker when a basis exists; 'auto' resolves to subspace."""
    rng = np.random.RandomState(12)
    x0 = _spd(rng, 2, 16, 16) / 16
    d_cold, q_cold = ops.sym_eig(jnp.asarray(x0), impl='subspace')
    d_xla, q_xla = ops.sym_eig(jnp.asarray(x0), impl='xla')
    np.testing.assert_allclose(np.asarray(d_cold), np.asarray(d_xla),
                               rtol=1e-5, atol=1e-6)
    x1 = 0.97 * x0 + 0.03 * _spd(rng, 2, 16, 16) / 16
    d1, q1 = ops.sym_eig(jnp.asarray(x1), impl='subspace', basis=q_cold)
    rec = (np.asarray(q1) @ (np.asarray(d1)[..., None]
                             * np.swapaxes(np.asarray(q1), -1, -2)))
    np.testing.assert_allclose(rec, x1, atol=0.04 * np.abs(x1).max())
    import os
    assert os.environ.get('KFAC_EIGH_IMPL', 'xla') == 'xla'  # test env
    d_auto, _ = ops.sym_eig(jnp.asarray(x1), impl='auto', basis=q_cold)
    np.testing.assert_allclose(np.asarray(d_auto), np.asarray(d1),
                               rtol=1e-5, atol=1e-6)


def test_subspace_eigh_constant_diagonal_slot_no_nan():
    """A batch slot whose factor is an exact multiple of identity (the
    all-padding bucket-slot case) has zero Rayleigh spread — the
    regularized rotation must come out 0, not 0/0 = NaN."""
    x = jnp.stack([2.0 * jnp.eye(8), jnp.zeros((8, 8))])
    q0 = jnp.stack([jnp.eye(8), jnp.eye(8)])
    w, q = ops.subspace_eigh(x, q0)
    assert np.isfinite(np.asarray(w)).all()
    assert np.isfinite(np.asarray(q)).all()
    np.testing.assert_allclose(np.asarray(w)[0], 2.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w)[1], 0.0, atol=1e-5)
    qtq = np.swapaxes(np.asarray(q), -1, -2) @ np.asarray(q)
    np.testing.assert_allclose(qtq, np.broadcast_to(np.eye(8), qtq.shape),
                               atol=1e-4)


def test_subspace_eigh_chained_tracking_no_accumulation():
    """50 chained warm fulls over a running-average factor stream (the
    cold_restart_every window at stat_decay=0.95): the damped-inverse
    operator error vs exact eigh must stay small THROUGHOUT — tracking
    error must not accumulate across the chain."""
    rng = np.random.RandomState(0)
    n, B, lam = 48, 24, 0.03

    A = np.eye(n, dtype=np.float32)
    q = jnp.asarray(np.eye(n, dtype=np.float32))
    track = jax.jit(lambda a, b: ops.subspace_eigh(a, b))
    errs = []
    for _ in range(50):
        a = rng.randn(B, n).astype(np.float32)
        A = 0.95 * A + 0.05 * (a.T @ a) / B
        w_ex, q_ex = np.linalg.eigh(A)
        wj, q = track(jnp.asarray(A), q)
        w, qn = np.asarray(wj), np.asarray(q)
        op = qn @ (qn.T / (np.maximum(w, 0) + lam)[:, None])
        ex = q_ex @ (q_ex.T / (np.maximum(w_ex, 0) + lam)[:, None])
        errs.append(np.abs(op - ex).max() / np.abs(ex).max())
    assert max(errs) < 0.06, (max(errs), errs[-5:])
    # no upward trend: the last 10 no worse than the first 10's envelope
    assert max(errs[-10:]) < max(errs[:10]) + 0.02, errs


def test_newton_schulz_inverse_warm_and_residual():
    """Seeded with the exact previous inverse under small drift, two NS
    iterations reach f32 noise; a garbage seed reports a large residual
    (the engine's fallback gate)."""
    rng = np.random.RandomState(5)
    a0 = _spd(rng, 3, 32, 32) / 32
    x0 = np.linalg.inv(a0)
    drift = _spd(rng, 3, 32, 32) / 32
    a1 = (0.95 * a0 + 0.05 * drift).astype(np.float32)

    x, resid = ops.newton_schulz_inverse(jnp.asarray(a1), jnp.asarray(x0))
    x, resid = np.asarray(x), np.asarray(resid)
    assert (resid < 1e-2).all(), resid
    np.testing.assert_allclose(x, np.linalg.inv(a1), rtol=5e-3, atol=1e-4)
    np.testing.assert_allclose(x, np.swapaxes(x, -1, -2), atol=1e-6)

    _, bad = ops.newton_schulz_inverse(jnp.asarray(a1),
                                       jnp.zeros_like(jnp.asarray(a1)))
    assert (np.asarray(bad) >= 1.0 - 1e-6).all()  # ||I|| — gate rejects


def test_warm_inverse_per_slot_gate():
    """ADVICE r2: the NS acceptance gate is per-slot — a zero-seeded slot
    falls back to the exact Cholesky inverse while its healthy
    bucket-mates keep the NS result (no bucket-wide cold restart)."""
    rng = np.random.RandomState(7)
    a0 = _spd(rng, 3, 32, 32) / 32
    drift = _spd(rng, 3, 32, 32) / 32
    a1 = (0.97 * a0 + 0.03 * drift).astype(np.float32)
    seed = np.linalg.inv(a0).astype(np.float32)
    seed[1] = 0.0  # slot 1: stale-to-death seed; 0 and 2 healthy

    out = np.asarray(ops.warm_inverse(jnp.asarray(a1), jnp.asarray(seed)))
    ns, resid = ops.newton_schulz_inverse(jnp.asarray(a1),
                                          jnp.asarray(seed))
    ns, resid = np.asarray(ns), np.asarray(resid)
    assert resid[1] >= 1.0 - 1e-6 and (resid[[0, 2]] < 0.05).all()
    # healthy slots: the NS result verbatim
    np.testing.assert_array_equal(out[0], ns[0])
    np.testing.assert_array_equal(out[2], ns[2])
    # failed slot: the batched Cholesky inverse, exact
    chol = np.asarray(ops.psd_inverse(jnp.asarray(a1)))
    np.testing.assert_array_equal(out[1], chol[1])
    np.testing.assert_allclose(out[1], np.linalg.inv(a1[1]),
                               rtol=5e-3, atol=1e-4)
    # all-healthy fast path: identical to plain NS
    good = np.linalg.inv(a0).astype(np.float32)
    out2 = np.asarray(ops.warm_inverse(jnp.asarray(a1), jnp.asarray(good)))
    ns2, _ = ops.newton_schulz_inverse(jnp.asarray(a1), jnp.asarray(good))
    np.testing.assert_array_equal(out2, np.asarray(ns2))


# ---------------------------------------------------------------------------
# The decomposition's device scopes (PR 45): ``decomp.b<D>x<n>`` a bucket
# (``engine.bucket_scope``) round ``decomp.<stage>`` (this module's
# functions). Metadata on the compiled operations and nothing else.
# ---------------------------------------------------------------------------

import contextlib  # noqa: E402
import re  # noqa: E402

from kfac_pytorch_tpu import engine  # noqa: E402
from kfac_pytorch_tpu.ops import linalg  # noqa: E402
from kfac_pytorch_tpu.plan import (LayerMeta, build_plan,  # noqa: E402
                                   pred_layout_record)

SOLVE = {'decomp.cholesky', 'decomp.solve_lower', 'decomp.solve_upper'}
ROWS = np.asarray([0, 1, 2, 3, 4, 1, 1, 3, 0], np.int32)


def _op_names(fn, *args):
    """Every ``op_name`` of the compiled program: what a profiler's trace
    shows as an operation's ``tf_op``."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _stages(names):
    return {s for n in names for s in re.findall(r'decomp\.[a-z_]+\b', n)}


def _tile_small(monkeypatch):
    """Tiling as the large buckets get it, at toy sizes: a bucket whose
    estimate ``rows * D^3 / 64`` passes 256 bytes goes in groups under
    256, and a matrix over that alone."""
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 256)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', 256)


def _structure_small(monkeypatch, dim, block):
    """The structured route as the large buckets get it, at toy sizes:
    from ``dim`` on, by blocks of ``block`` rows."""
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM', dim)
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_BLOCK', block)


TILED = SOLVE | {'decomp.damp', 'decomp.settle', 'decomp.write'}


@pytest.mark.parametrize('path, tiling, want', [
    ('psd_inverse', None, SOLVE),
    ('structured', None, SOLVE),
    ('damped_whole', (9, 8), SOLVE | {'decomp.damp'}),
    ('structured_whole', (9, 8), SOLVE | {'decomp.damp'}),
    ('damped_grouped', (4, 16), TILED),
    ('structured_grouped', (4, 16), TILED),
    ('damped_single', (1, 32), TILED),
    ('structured_single', (1, 32), TILED),
    ('damped_rows', (4, 16), TILED),
    ('structured_rows', (4, 16), TILED),
    ('warm_inverse', None, SOLVE | {'decomp.newton_schulz'}),
    ('sym_eig', None, {'decomp.eigh'}),
])
def test_stage_scopes_reach_the_compiled_operations_and_no_instruction(
        path, tiling, want, monkeypatch):
    """Every way a bucket is inverted, on both routes: whole, in groups
    of rows, the rows read through a table, one matrix at a time (the
    6,144-wide one, which went in panels of columns until PR 46)."""
    _tile_small(monkeypatch)
    kind = path.split('_')[-1]
    dim = {'whole': 8, 'single': 32}.get(kind, 16)
    if path.startswith('structured'):
        _structure_small(monkeypatch, dim, 4)
    assert ops.inverse_route(dim) == (
        'structured' if path.startswith('structured') else 'solves')
    n = 5 if kind == 'rows' else 9
    x = jnp.asarray(_spd(np.random.RandomState(0), n, dim, dim))
    damp = jnp.linspace(0.01, 0.1, 9)
    stored = jnp.zeros((9, dim, dim))
    commit = jnp.asarray(True)
    fn, args = {
        'psd_inverse': (ops.psd_inverse, (x,)),
        'structured': (ops.psd_inverse, (x,)),
        'warm_inverse': (lambda a, s: ops.warm_inverse(a, s), (x, stored)),
        'sym_eig': (lambda a: ops.sym_eig(a, impl='xla'), (x,)),
    }.get(path, (lambda a, d, p, c: ops.damped_psd_inverse(
        a, d, prev=p, guard=True, commit=c,
        rows=ROWS if kind == 'rows' else None),
        (x, damp, stored, commit)))
    if tiling is not None:
        assert ops.inverse_tiling(9, dim) == tiling
    assert _stages(_op_names(fn, *args)) == want
    # the lowered text (which prints no names) is the text without scopes
    named = jax.jit(lambda *a: fn(*a)).lower(*args).as_text()
    monkeypatch.setattr(jax, 'named_scope',
                        lambda name: contextlib.nullcontext())
    plain = jax.jit(lambda *a: fn(*a)).lower(*args).as_text()
    assert named == plain and 'decomp.' not in named


def _two_solves(x):
    """The route every bucket took until PR 46, spelt out."""
    chol = jnp.linalg.cholesky(x)
    eye = jnp.broadcast_to(jnp.eye(x.shape[-1], dtype=x.dtype), x.shape)
    y = jax.lax.linalg.triangular_solve(chol, eye, left_side=True,
                                        lower=True)
    return jax.lax.linalg.triangular_solve(chol, y, left_side=True,
                                           lower=True, transpose_a=True)


@pytest.mark.parametrize('case, shape', [
    ('single', (72, 72)),           # 9 blocks: 2,304 = 9 x 256
    ('batch', (3, 72, 72)),
    ('batch', (2, 144, 144)),       # 18 blocks: 4,608 = 18 x 256
    ('ragged', (3, 100, 100)),      # 12 blocks and a half: 3,200
    ('padded', (3, 72, 72)),        # blockdiag(A, I), as a bucket pads
    ('one_block', (2, 8, 8)),
])
def test_structured_inverse_is_the_inverse_and_exactly_symmetric(
        case, shape, monkeypatch):
    """The blocked triangular inverse and triangular product against
    ``np.linalg.inv`` in float64 and against the two dense solves, at
    ``test_psd_inverse_batched``'s tolerance; symmetric to the last bit,
    which the solves' result is not."""
    _structure_small(monkeypatch, 8, 8)
    d = shape[-1]
    rng = np.random.RandomState(d + len(shape))
    if case == 'padded':
        true = d - 13
        x = np.asarray(ops.identity_pad(
            jnp.asarray(_spd(rng, *shape[:-2], true, true)), d))
    else:
        x = _spd(rng, *shape)
    assert ops.inverse_route(d) == 'structured'
    inv = np.asarray(jax.jit(lambda a: ops.psd_inverse(a))(jnp.asarray(x)))
    assert inv.shape == x.shape and inv.dtype == np.float32
    np.testing.assert_allclose(inv, np.linalg.inv(x.astype(np.float64)),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(inv, np.asarray(_two_solves(jnp.asarray(x))),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_array_equal(inv, np.swapaxes(inv, -1, -2))
    if case == 'padded':
        # blockdiag(A, I)^-1 = blockdiag(A^-1, I), to the bit in the pad
        np.testing.assert_array_equal(inv[..., true:, true:],
                                      np.broadcast_to(np.eye(13), shape[:-2]
                                                      + (13, 13)))
        assert not inv[..., true:, :true].any()
    # under the threshold the same call is the two solves, to the bit
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM', d + 1)
    np.testing.assert_array_equal(
        np.asarray(ops.psd_inverse(jnp.asarray(x))),
        np.asarray(_two_solves(jnp.asarray(x))))


def _dots(text):
    """``(lhs shape, rhs shape, flop, precision)`` of every
    ``dot_general`` of a lowered text."""
    out = []
    for line in text.splitlines():
        if 'stablehlo.dot_general' not in line:
            continue
        lhs, rhs, res = (tuple(int(n) for n in t.split('x')[:-1])
                         for t in re.findall(r'tensor<([\dx]+f32)>', line)[-3:])
        contract = [int(i) for i in re.search(
            r'contracting_dims = \[([\d, ]+)\] x', line).group(1).split(',')]
        flop = 2 * int(np.prod(res)) * int(np.prod([lhs[i] for i in contract]))
        out.append((lhs, rhs, flop, re.search(
            r'precision = \[(\w+), (\w+)\]', line).groups()))
    return out


@pytest.mark.parametrize('rows, dim, block', [
    (3, 72, 8), (2, 144, 8), (3, 100, 8), (2, 64, 16)])
def test_structured_route_multiplies_no_zero_triangle(rows, dim, block,
                                                      monkeypatch):
    """The two stages' lowered text: GEMMs at float32 ``HIGHEST`` and one
    solve a diagonal block, no product of two full ``[D, D]`` operands,
    no triangular solve wider than a block, and as many multiply-adds as
    ``inverse_route_flop`` (``decomp_route_flop`` of the set-up record)
    states for the shape: 0.9-1.2 ``D^3`` where two dense solves spend
    ``2 D^3`` (a block row still multiplies three quarters of the square
    that holds the triangle so far, so not the task's ``2/3 D^3``)."""
    _structure_small(monkeypatch, 16, block)
    chol = jnp.zeros((rows, dim, dim), jnp.float32)
    # (a fresh function a lowering: jit's cache does not see a patched
    # module constant)
    text = jax.jit(lambda c: linalg._inverse_of_factor(c)).lower(
        chol).as_text()
    dots = _dots(text)
    assert dots and all(p == ('HIGHEST', 'HIGHEST') for *_, p in dots)
    assert not any(lhs[-2:] == (dim, dim) and rhs[-2:] == (dim, dim)
                   for lhs, rhs, _, _ in dots)
    # (the CPU lowers a triangular solve to LAPACK's trsm, the TPU to
    # ``stablehlo.triangular_solve``)
    solved = [int(n) for line in text.splitlines()
              if 'triangular_solve' in line or 'trsm' in line
              for n in re.findall(r'tensor<(?:\d+x)*(\d+)xf32>', line)]
    assert solved and max(solved) <= block
    leaves = sum(min(block, dim - lo) ** 3 for lo in range(0, dim, block))
    stated = ops.inverse_route_flop(rows, dim)
    assert stated == rows * _structured_flop(dim, block)
    assert sum(f for _, _, f, _ in dots) == stated - rows * (
        dim ** 3 // 3 + leaves)
    # the two stages: between the task's 2/3 D^3 and 3/4 of the solves'
    assert (rows * 2 * dim ** 3 // 3 < stated - rows * (dim ** 3 // 3)
            < rows * 3 * dim ** 3 // 2)
    # the two solves of the other route, counted the same way
    monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM', dim + 1)
    assert ops.inverse_route_flop(rows, dim) == rows * (7 * dim ** 3 // 3)
    assert not _dots(jax.jit(
        lambda c: linalg._inverse_of_factor(c)).lower(chol).as_text())


def _grouped_metas(dims, groups=()):
    """``dims``: (in, out) a layer; ``groups``: tuples of layer indices
    that read one input (the first leads)."""
    lead = {i: f'l{g[0]}' for g in groups for i in g}
    return {f'l{i}': LayerMeta(
        name=f'l{i}', path=(f'l{i}',), kind='dense', use_bias=False,
        in_dim=a, out_dim=g, kernel_shape=(a, g), input_group=lead.get(i))
        for i, (a, g) in enumerate(dims)}


def _bucket8(dim):
    return -(-dim // 8) * 8


#: the four benchmark configurations' shapes at toy size: a conv net's
#: many small buckets; an encoder whose query / key / value read one
#: input; two sparse decoders whose largest buckets go tile by tile, one
#: of them with input groups in the tiled bucket
TOY_PLANS = {
    'resnet': ([(27, 8), (72, 8), (8, 16), (72, 16), (16, 16), (17, 10)],
               ()),
    'bert': ([(16, 16)] * 3 + [(16, 32), (32, 16)] + [(16, 16)] * 3
             + [(16, 2)], ((0, 1, 2), (5, 6, 7))),
    'kanana': ([(16, 8)] * 9 + [(32, 16)] * 3, ()),
    'trinity': ([(16, 8)] * 9 + [(16, 16)] * 2 + [(32, 16)] * 3,
                ((0, 1), (2, 3), (9, 10))),
}
TOY_RECORDS = {
    # {D: [n, rows a group, columns a panel]}, sum n * D^3; a panel is
    # the whole matrix since PR 46 (the matrices that went in panels of
    # 16 and 3 columns go one at a time, whole)
    'resnet': ({'8': [3, 3, 8], '16': [5, 4, 16], '24': [1, 1, 24],
                '32': [1, 1, 32], '72': [2, 1, 72]}, 815104),
    'bert': ({'8': [1, 1, 8], '16': [15, 4, 16], '32': [2, 1, 32]}, 127488),
    'kanana': ({'8': [9, 9, 8], '16': [12, 4, 16], '32': [3, 1, 32]},
               9 * 8 ** 3 + 12 * 16 ** 3 + 3 * 32 ** 3),
    'trinity': ({'8': [9, 9, 8], '16': [16, 4, 16], '32': [3, 1, 32]},
                9 * 8 ** 3 + 16 * 16 ** 3 + 3 * 32 ** 3),
}


def _structured_flop(dim, block):
    """What the structured route spends on one matrix, counted from the
    algorithm and not from ``linalg._structured_products``: the
    factorisation, a dense solve a diagonal block, and two flop a
    multiply-add of ``L[i, :i] Y[:i, :i]`` (less the zero quarter the
    split into two column halves leaves out), ``Y[i, i] (...)`` and
    ``Y[i:, i]' Y[i:, :i+1]`` for every block row ``i``."""
    flop = dim ** 3 // 3
    for i, lo in enumerate(range(0, dim, block)):
        b = min(block, dim - lo)
        half = i // 2 * block
        flop += b ** 3 + 2 * b * (lo * lo - half * (lo - half)) + 2 * b * b * lo
        flop += 2 * b * (dim - lo) * (lo + b)
    return flop


def _toy_plan(name):
    dims, groups = TOY_PLANS[name]
    return build_plan(_grouped_metas(dims, groups), 1, 'pred',
                      bucket_fn=_bucket8)


@pytest.mark.parametrize('name', sorted(TOY_PLANS))
def test_setup_record_says_what_the_bucket_scopes_say(name, monkeypatch):
    _tile_small(monkeypatch)
    _structure_small(monkeypatch, 24, 8)
    plan = _toy_plan(name)
    record = pred_layout_record(plan)
    buckets, flop = TOY_RECORDS[name]
    assert record['decomp_buckets'] == buckets
    assert record['decomp_task_flop'] == sum(
        n * int(d) ** 3 for d, (n, _, _) in buckets.items())
    assert record['decomp_task_flop'] == flop
    # every bucket is there, tiled or whole; decomp_groups keeps to the
    # tiled ones, counted in groups and panels
    assert set(record['decomp_buckets']) == {str(d) for d in plan.buckets}
    assert record['decomp_groups'] == {
        d: [-(-n // size), int(d) // width]
        for d, (n, size, width) in buckets.items()
        if (size, width) != (n, int(d))}
    # the route of every bucket, structured exactly from the threshold
    # on, and what the routes spend beside what the task needs
    assert record['decomp_route'] == {
        d: 'structured' if int(d) >= 24 else 'solves' for d in buckets}
    assert record['decomp_route_flop'] == sum(
        n * (_structured_flop(int(d), 8) if int(d) >= 24
             else 7 * int(d) ** 3 // 3) for d, (n, _, _) in buckets.items())
    assert (record['decomp_task_flop'] < record['decomp_route_flop']
            <= 7 * record['decomp_task_flop'] // 3)


@pytest.mark.parametrize('name, method, structured', [
    ('trinity', 'cholesky', False), ('bert', 'cholesky', False),
    ('resnet', 'eigh', False), ('trinity', 'cholesky', True),
    ('resnet', 'cholesky', True)])
def test_compute_decomposition_puts_every_bucket_under_its_scope(
        name, method, structured, monkeypatch):
    """``decomp.b<D>x<n>``: ``n`` the RESULT rows, also where some rows
    are made of another's factor (``factor_row``: the factor bucket is
    shorter) and where a last group is moved back and makes rows twice;
    also where the buckets of 16 and more take the structured route."""
    _tile_small(monkeypatch)
    if structured:
        _structure_small(monkeypatch, 16, 8)
    plan = _toy_plan(name)
    rng = np.random.RandomState(3)
    factors = {str(d): jnp.asarray(_spd(rng, b.n_factor_rows, d, d))
               for d, b in plan.buckets.items()}
    names = _op_names(lambda f: engine.compute_decomposition(
        plan, f, 0.003, method, 1e-10, None, guard=True), factors)
    found = {(int(d), int(n)) for text in names
             for d, n in re.findall(r'decomp\.b(\d+)x(\d+)', text)}
    assert found == {(d, b.per_dev) for d, b in plan.buckets.items()}
    if name == 'trinity':
        b = plan.buckets[16]
        assert b.factor_row is not None and b.n_factor_rows < b.per_dev
        assert ops.inverse_tiling(b.per_dev, 16) == (4, 16)
    staged = [t for t in names if _stages([t])]
    assert staged and all('decomp.b' in t.split('decomp.')[1] or
                          re.search(r'decomp\.b\d+x\d+/.*decomp\.[a-z]', t)
                          for t in staged)
    # one bucket, one stage an operation
    assert all(len(re.findall(r'decomp\.b\d+x', t)) <= 1
               and len(_stages([t])) <= 1 for t in names)
    if method == 'cholesky':
        # the trace averages and the damping vectors stay outside
        assert any('decomp.' not in t and 'sqrt' in t for t in names)
