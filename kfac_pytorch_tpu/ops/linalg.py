"""Batched symmetric linear algebra for K-FAC factors — on-chip XLA linalg.

Replaces the reference's cuSOLVER/torch.linalg host-library calls
(``mat_inv``/``mat_eig``, reference: kfac/utils.py:11-30, and the tcmm CUDA
extension, packages/tcmm/src/tcmm_kernel.cu:56-116) with XLA's native
``cholesky``/``triangular_solve``/``eigh``, which batch across the leading
axis — the whole point of the stacked-bucket factor layout: one batched op
per bucket instead of a Python loop of per-layer decompositions.

All functions accept either a single matrix ``[D, D]`` or a stacked batch
``[L, D, D]``.

Identity padding: factors are padded from their true dim ``d`` to a bucket
dim ``D`` (the next multiple of the MXU tile, ``plan.default_bucket_fn``)
with an identity block. This is *exact* for both preconditioning
paths: padded eigenvectors live in the pad subspace, which is orthogonal to
the zero-padded gradient, so their terms vanish; for the explicit inverse,
blockdiag(A, I)^-1 = blockdiag(A^-1, I) and the pad block multiplies zero
gradient columns.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


#: batched matmul at HIGHEST internal precision: the structured inverse's
#: products (the precision the triangular solves have) and the warm-path
#: kernels (Newton-Schulz, subspace tracking), accuracy-sensitive
#: contractions all
_mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)

#: A bucket whose dim is at or over STRUCTURED_INVERSE_DIM takes the two
#: stages after the Cholesky factorisation by blocks of
#: STRUCTURED_INVERSE_BLOCK rows (:func:`_triangular_inverse`,
#: :func:`_triangular_product`); a smaller one solves against a dense
#: identity, as every bucket did until PR 46. Set from one chip A/B over
#: the benchmark's buckets (PERF.md section 5, PR 46; the whole inverse,
#: two solves over structured): 1.17 at 15 x 1,024, 1.11 at 4 x 1,152,
#: 1.23 at 12 x 1,536, 1.41 at 6 x 2,048, 2.1 at 3 x 4,608; blocks of 256
#: as fast as 128 and faster than 512. Under 1,024 the chain of 128-block
#: calls sets a bucket's pace and the two stages have little to give.
STRUCTURED_INVERSE_DIM = 1024
STRUCTURED_INVERSE_BLOCK = 256


def inverse_route(dim):
    """``'structured'`` or ``'solves'``: how :func:`psd_inverse` takes a
    matrix of that dim from its Cholesky factor to its inverse."""
    return 'structured' if dim >= STRUCTURED_INVERSE_DIM else 'solves'


def _blocks(dim, block):
    """``(lo, hi)`` of every block of rows; the last may be shorter."""
    edges = list(range(0, dim, block)) + [dim]
    return list(zip(edges, edges[1:]))


def _halfway(lo, block):
    """Where :func:`_triangular_inverse` splits the ``lo`` columns of the
    triangle so far: a whole number of blocks, 0 for no split."""
    return lo // block // 2 * block


def _structured_products(dim, block):
    """``(m, k, n)`` of every product the structured route makes for one
    matrix: :func:`_triangular_inverse`'s, then
    :func:`_triangular_product`'s."""
    out = []
    for lo, hi in _blocks(dim, block)[1:]:
        c = _halfway(lo, block)
        out += [(hi - lo, lo, c)] * bool(c) + [
            (hi - lo, lo - c, lo - c), (hi - lo, hi - lo, lo)]
    return out + [(hi - lo, dim - lo, hi) for lo, hi in _blocks(dim, block)]


def inverse_route_flop(rows, dim):
    """Floating-point operations :func:`psd_inverse` spends on ``rows``
    matrices of ``dim``, by the route their shape takes: the factorisation's
    ``D^3 / 3`` and two dense solves at ``D^3`` each, or the factorisation,
    the diagonal blocks' own solves and two for every multiply-add of the
    structured route's products (the task itself is ``D^3``:
    ``plan.pred_layout_record``'s ``decomp_task_flop``)."""
    if inverse_route(dim) == 'solves':
        return rows * (7 * dim ** 3 // 3)
    block = STRUCTURED_INVERSE_BLOCK
    leaves = sum((hi - lo) ** 3 for lo, hi in _blocks(dim, block))
    products = sum(2 * m * k * n
                   for m, k, n in _structured_products(dim, block))
    return rows * (dim ** 3 // 3 + leaves + products)


def _solve_identity(chol):
    """``L^-1`` by the dense solve ``L Y = I``."""
    eye = jnp.broadcast_to(jnp.eye(chol.shape[-1], dtype=chol.dtype),
                           chol.shape)
    return lax.linalg.triangular_solve(chol, eye, left_side=True, lower=True)


def _triangular_inverse(chol, block):
    """``L^-1`` of the lower-triangular ``chol [..., D, D]`` by block rows:
    ``Y[i, i] = L[i, i]^-1`` (the dense solve, on a block; the whole blocks
    in one batched call) and ``Y[i, :i] = -Y[i, i] (L[i, :i] Y[:i, :i])``,
    each block row written where it lies in a result that starts as zeros:
    the strict upper triangle is those zeros, never a product's result.
    The triangle so far is multiplied in two halves of its columns, the
    right half without the zero quarter above it (the chip's A/B, PERF.md
    section 5, PR 46: a tenth to a sixth faster than in one product)."""
    d = chol.shape[-1]
    blocks = _blocks(d, block)
    whole = [(lo, hi) for lo, hi in blocks if hi - lo == block]
    diag = _solve_identity(jnp.stack(
        [chol[..., lo:hi, lo:hi] for lo, hi in whole], axis=-3))
    diag = [diag[..., i, :, :] for i in range(len(whole))]
    diag += [_solve_identity(chol[..., lo:hi, lo:hi])
             for lo, hi in blocks[len(whole):]]
    mm = functools.partial(_mm, '...ij,...jk->...ik')
    y = jnp.zeros_like(chol)
    for (lo, hi), yii in zip(blocks, diag):
        row = yii
        if lo:
            c = _halfway(lo, block)
            t = mm(chol[..., lo:hi, c:lo], y[..., c:lo, c:lo])
            if c:
                t = jnp.concatenate(
                    [mm(chol[..., lo:hi, :lo], y[..., :lo, :c]), t], axis=-1)
            row = jnp.concatenate([-mm(yii, t), yii], axis=-1)
        y = lax.dynamic_update_slice(y, row, (0,) * (y.ndim - 2) + (lo, 0))
    return y


def _triangular_product(y, block):
    """``Y' Y`` of the lower-triangular ``y [..., D, D]``: block row ``i``
    of the lower half is ``Y[i:, i]' Y[i:, :i+1]`` (no operand reaches
    into the zeros above the diagonal blocks), the upper half the mirror
    of the lower: the result is symmetric to the last bit."""
    d = y.shape[-1]
    rows = []
    for lo, hi in _blocks(d, block):
        r = _mm('...ki,...kj->...ij', y[..., lo:, lo:hi], y[..., lo:, :hi])
        rows.append(jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(0, d - hi)]))
    low = jnp.concatenate(rows, axis=-2)
    i = jnp.arange(d)
    return jnp.where(i[:, None] >= i[None, :], low,
                     jnp.swapaxes(low, -1, -2))


def psd_inverse(x):
    """Cholesky-based inverse of an SPD matrix (batched).

    Parity: ``mat_inv(..., method='cholesky')`` (reference:
    kfac/utils.py:11-18). LAPACK's ``potri`` on the factor ``L``: the
    triangular inverse ``Y = L^-1``, then the triangular product
    ``Y' Y``. By shape (:func:`inverse_route`): a matrix under
    ``STRUCTURED_INVERSE_DIM`` solves ``L Y = I`` and ``L' X = Y`` against
    the dense identity (one XLA kernel chain a bucket); a larger one makes
    the same two results by blocks, from GEMMs that leave most of the
    zero triangle alone (about half of the solves' multiply-adds:
    :func:`inverse_route_flop`).

    Each of the three stages runs under a ``jax.named_scope`` of its own
    (``decomp.cholesky``, ``decomp.solve_lower``: ``L^-1``,
    ``decomp.solve_upper``: ``L^-T L^-1``, on either route): the
    operations the compiler expands them into carry the name in a trace,
    and nothing else changes.
    """
    with jax.named_scope('decomp.cholesky'):
        chol = jnp.linalg.cholesky(x)
    return _inverse_of_factor(chol)


def _inverse_of_factor(chol):
    """``L^-T L^-1`` of the Cholesky factor ``chol``: :func:`psd_inverse`'s
    two stages after the factorisation, by the route the shape takes."""
    block = STRUCTURED_INVERSE_BLOCK
    structured = inverse_route(chol.shape[-1]) == 'structured'
    with jax.named_scope('decomp.solve_lower'):
        y = (_triangular_inverse(chol, block) if structured
             else _solve_identity(chol))
    with jax.named_scope('decomp.solve_upper'):
        if structured:
            return _triangular_product(y, block)
        return lax.linalg.triangular_solve(
            chol, y, left_side=True, lower=True, transpose_a=True)


#: What the route of two solves costs a bucket in temporaries: the compiler
#: unrolls each triangular solve into D / 128 panel steps and keeps their
#: shrinking right-hand sides, D / 256 times the right-hand side's bytes
#: (sandbox compiles for a v5e, PR 39: 12 x 3,200^2 6.0 GB, 3 x 6,144^2
#: 10.8 GB, 8 x 2,048^2 0.95 GB; the Cholesky factorisation itself takes
#: the bucket's bytes once). A bucket whose estimate stays under
#: WHOLE_INVERSE_TEMP_BYTES is inverted whole: at or above the largest the
#: benchmark's dense cells invert (BERT-base's 12 x 3,200^2, 6.1 GB), so
#: their buckets go whole as they did. Since PR 46 the buckets of
#: STRUCTURED_INVERSE_DIM and more no longer take that route and hold a few
#: copies of a group instead (sandbox compiles: PERF.md section 6, PR 46);
#: the estimate is kept for them as the rule that sizes their groups, which
#: are what they were.
WHOLE_INVERSE_TEMP_BYTES = 6 * 2 ** 30
#: ... a larger one in groups of rows, each within this estimate; a matrix
#: that passes it alone goes alone
INVERSE_GROUP_TEMP_BYTES = 2 ** 30


def inverse_tiling(rows, dim, itemsize=4):
    """How a ``[rows, dim, dim]`` bucket is inverted, from its shape alone:
    ``(rows a group, columns a panel)``. ``(rows, dim)`` is whole. A panel
    is ``dim`` columns wide since PR 46: a matrix too large for the
    two-solve route's estimate is one of the structured route's, whose
    temporaries are a few copies of the matrix, not ``dim / 256``
    right-hand sides."""
    one = dim ** 3 * itemsize // 256    # a matrix's estimated temporaries
    if rows * one <= WHOLE_INVERSE_TEMP_BYTES:
        return rows, dim
    return max(1, min(rows, INVERSE_GROUP_TEMP_BYTES // one)), dim


def rows_finite(x):
    """``[rows, ...] -> [rows]`` bool: the row holds no NaN and no Inf."""
    return jnp.all(jnp.isfinite(x), axis=tuple(range(1, x.ndim)))


#: side of the square tiles :func:`tile_diagonal` reads: the lane width of
#: the device's (8, 128) tiling, which every default bucket dim is a
#: multiple of (``plan.default_bucket_fn``), so each slice is whole tiles
DIAGONAL_TILE = 128


@functools.partial(jax.jit, static_argnames='tile')
def tile_diagonal(x, tile=DIAGONAL_TILE):
    """The diagonal of ``x [..., D, D]``, read from the ``D / tile`` square
    tiles it runs through and from nothing else: ``tile / D`` of the
    operand's bytes. ``jnp.diagonal`` is a gather, for which the TPU's
    compiler first copies the whole operand into another layout (sandbox
    compile of ``[12, 3200, 3200]``, PR 42: 625 MiB of temporaries).
    ``tile``: a multiple of 128; a larger one reads more bytes in fewer
    operations (each tile is a kernel of its own in the program). Jitted,
    like the two ``settle_*`` helpers, for a caller that steps eagerly
    (tests do): one executable a shape where it was a dozen primitives;
    inside a jitted step the call is inlined."""
    d = x.shape[-1]
    parts = []
    for lo in range(0, d, tile):
        hi = min(lo + tile, d)
        on = jnp.eye(hi - lo, dtype=bool)
        parts.append(jnp.sum(jnp.where(on, x[..., lo:hi, lo:hi], 0),
                             axis=-1))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def diagonal_finite(x, tile=DIAGONAL_TILE):
    """``[..., D, D] -> [...]`` bool: no NaN and no Inf on the diagonal
    (:func:`tile_diagonal`). A witness for the whole matrix only where its
    caller says why: a Gram product (``engine.stats_finite``), a Cholesky
    inverse (:func:`inverse_rows_finite`)."""
    return jnp.all(jnp.isfinite(tile_diagonal(x, tile)), axis=-1)


def inverse_rows_finite(inv):
    """``[rows]`` bool: does the Cholesky inverse ``inv [rows, D, D]``
    (:func:`psd_inverse`) hold no NaN and no Inf, read from its diagonal
    alone. Exact for that operand on both of its routes, whatever went
    wrong on the way to it:

    * a NaN or Inf anywhere in the (symmetrised) input, or a pivot that is
      not positive, leaves a NaN in the Cholesky factor ``L`` at that
      pivot, and every later pivot is computed from it: the last one,
      ``L[D-1, D-1]``, is NaN, and ``inv[D-1, D-1] = 1 / L[D-1, D-1]^2``
      on either route (the last row of ``Y = L^-1`` is the last step of
      the last diagonal block's substitution, and the last diagonal entry
      of ``Y' Y`` is its square);
    * with ``L`` finite, the route of two solves (``inverse_route``) makes
      column ``j`` of the inverse from ``L L' x = e_j`` by a forward and a
      backward substitution. An entry ``k >= j`` of the forward result
      that is not finite enters every entry above it in the backward one
      (``Inf * 0`` is NaN, not 0), down to ``x[j]``, the diagonal entry.
      The structured route makes ``inv[j, j] = sum_{k >= j} Y[k, j]^2`` in
      one product (the block row's own diagonal block: every term is
      there, none is skipped), so an entry of ``Y`` that is not finite
      reaches the diagonal entry of its column;
    * what is left is a finite ``L^-1`` whose products overflow:
      ``|inv[i, j]| = |sum_k y[k, i] y[k, j]| <= max(inv[i, i], inv[j, j])``,
      on either route; the structured one's upper half is a copy of its
      lower half and adds nothing to read.

    ``tests/test_health.py`` poisons one off-diagonal element and sees the
    row caught, on both routes. NOT exact for an eigendecomposition or for
    an operand corrupted after it was formed: those keep a read of every
    element."""
    return diagonal_finite(inv)


def heal_rows(x, bad, fallback, beside=None):
    """``(x, beside)``, ``x [rows, ...]`` with every row ``r`` whose
    ``bad[r]`` is set replaced by ``fallback(r, beside)``: a loop over the
    bad rows alone, each written where it lies. On a healthy step it runs
    no iteration, so the operand is neither read nor written again and no
    second copy of it is held (what a ``jnp.where`` over the whole operand
    costs).

    ``beside``: an array the fallback reads, carried through the loop
    untouched and handed back: a caller that goes on to write into it uses
    the one handed back, so that the compiler sees one buffer pass through
    and copies nothing."""
    where = jnp.nonzero(bad, size=x.shape[0], fill_value=0)[0]

    def one_row(i, carry):
        out, side = carry
        r = where[i]
        return lax.dynamic_update_index_in_dim(
            out, fallback(r, side).astype(out.dtype), r, axis=0), side

    return lax.fori_loop(0, jnp.sum(bad.astype(jnp.int32)), one_row,
                         (x, beside))


@functools.partial(jax.jit, static_argnames='guard')
def settle_inverse_rows(inv, stored, guard, commit=None, first=0):
    """Fresh inverses ``inv [k, D, D]`` as they may be kept, beside the
    ``stored [rows, D, D]`` ones, of which they replace rows ``first`` to
    ``first + k``; returns ``(settled, stored)``. With ``guard`` a row that
    is not finite (:func:`inverse_rows_finite`) falls back to its stored
    inverse, or to the identity where none is stored yet (the cold state is
    all zeros and a stored SPD inverse has a positive diagonal, so the
    diagonal tells them apart). ``commit`` (a traced bool): where False
    every row keeps its stored value, cold or not. Only the rows at fault
    are touched (:func:`heal_rows`)."""
    if not guard and commit is None:
        return inv, stored
    rows, d = inv.shape[0], inv.shape[-1]
    bad = (jnp.logical_not(inverse_rows_finite(inv)) if guard
           else jnp.zeros((rows,), bool))
    if commit is not None:
        bad = jnp.logical_or(bad, jnp.logical_not(commit))

    def last_good(r, kept):
        old = lax.dynamic_index_in_dim(kept, first + r, 0, keepdims=False)
        cold = jnp.all(tile_diagonal(old) == 0)
        if commit is not None:
            cold = jnp.logical_and(cold, commit)
        return jnp.where(cold, jnp.eye(d, dtype=inv.dtype), old)

    return heal_rows(inv, bad, last_good, beside=stored)


def damped_psd_inverse(x, damp, prev=None, guard=False, commit=None,
                       rows=None):
    """``(x + damp I)^-1`` of a bucket ``x [rows, D, D]``, ``damp [rows]``:
    whole, or tile by tile where :func:`inverse_tiling` says so.

    ``rows`` (a static ``[n]`` array, ``n >= x.shape[0]``; None: ``x``'s
    rows in order): result row ``i`` is made of ``x[rows[i]]`` with
    ``damp[i]`` (layers that read one input keep one running average,
    damped for each by its own ``G``: ``plan.Bucket.factor_row``). The
    first ``x.shape[0]`` of them are ``x``'s rows in order, as the plan
    lays them: the whole groups among those are sliced as a bucket without
    ``rows`` slices them, and only the rows after are read from where they
    lie. As many matrices are inverted as that bucket would invert.

    A group is ``size`` consecutive rows, damped, inverted and written
    into the result where they belong, one group after the other, so that
    only one group's damped copy and Cholesky temporaries live at a time.
    The last group is moved back to end with the bucket, and makes again
    what the one before it made of the rows they share. That changes no
    product's terms.

    ``prev`` (the stored inverses) is what the groups are written over;
    with ``guard`` and ``commit`` each group is settled against the rows it
    is about to replace (:func:`settle_inverse_rows`), as
    ``engine.guard_decomposition`` settles a whole bucket.

    What is not one of :func:`psd_inverse`'s three stages has a scope too:
    ``decomp.damp`` (the reads of the rows and the damping added to them),
    ``decomp.settle`` and ``decomp.write`` (a group into the result)."""
    n, d = x.shape[0], x.shape[-1]
    if rows is not None and not np.array_equal(rows[:n], np.arange(n)):
        raise ValueError('rows must start with x\'s own rows in order')
    total = damp.shape[0]
    size, _ = inverse_tiling(total, d, x.dtype.itemsize)
    if size == total:
        with jax.named_scope('decomp.damp'):
            xs = add_scaled_identity(
                x if rows is None else jnp.take(x, jnp.asarray(rows), axis=0),
                damp)
        return psd_inverse(xs)

    def groups(count, first, read, out):
        """``count`` result rows from ``first`` on, ``read(start, k)``
        giving rows ``start`` to ``start + k`` of them undamped."""
        k = min(size, count)

        def one_group(i, out):
            start = jnp.minimum(i * k, count - k)
            with jax.named_scope('decomp.damp'):
                xs = add_scaled_identity(
                    read(start, k), lax.dynamic_slice_in_dim(
                        damp, first + start, k, axis=0))
            inv = psd_inverse(xs)
            with jax.named_scope('decomp.settle'):
                inv, out = settle_inverse_rows(inv, out, guard, commit,
                                               first=first + start)
            with jax.named_scope('decomp.write'):
                return lax.dynamic_update_slice_in_dim(
                    out, inv, first + start, axis=0)

        return lax.fori_loop(0, -(-count // k), one_group, out)

    def sliced(start, k):
        return lax.dynamic_slice_in_dim(x, start, k, axis=0)

    out = jnp.zeros((total, d, d), x.dtype) if prev is None else prev
    if rows is None:
        return groups(n, 0, sliced, out)
    # ``x``'s own rows as far as they fill whole groups, then the rest of
    # them with the rows made of another's factor: a last group moved back
    # once, as above, not once for each kind of row (that cost the sparse
    # decoders 8 more matrices of 2,048 an update, 17 ms: PERF.md, PR 43)
    whole = n // size * size
    if whole:
        out = groups(whole, 0, sliced, out)
    table = jnp.asarray(rows[whole:], jnp.int32)

    def by_row(start, k):
        # a row at a time, each copied from where it lies: a gather of
        # whole matrices took 4 ms a group of 8 x 2,048^2 on the chip, and
        # rows read inside the damping's own fusion made the compiler copy
        # all of ``x`` into another layout (PERF.md, PR 43)
        at = lax.dynamic_slice_in_dim(table, start, k)

        def one_row(j, buf):
            return lax.dynamic_update_slice_in_dim(
                buf, lax.dynamic_slice_in_dim(x, at[j], 1, axis=0), j,
                axis=0)

        return lax.fori_loop(0, k, one_row, jnp.zeros((k, d, d), x.dtype))

    return groups(total - whole, whole, by_row, out)


def newton_schulz_inverse(a, x0, iters=2):
    """Warm matrix inverse by Newton-Schulz iteration (batched):
    ``X <- X (2I - A X)``, seeded with a previous inverse.

    Between K-FAC inverse updates the damped factor drifts by
    O(1 - factor_decay), so the stored inverse satisfies
    ``||I - A X0|| << 1`` and each iteration SQUARES that residual —
    two iterations reach f32 noise for healthy tracking. Pure batched
    matmuls (the MXU-shaped warm path for the Cholesky variants, the
    inverse-side twin of :func:`subspace_eigh`). Symmetry is preserved
    by the iteration for symmetric ``a``/``x0``; a final symmetrization
    removes f32 drift.

    Returns ``(x, resid)`` where ``resid[i] = max |I - A_i X_i|`` after
    the last iteration — the caller gates acceptance on it (NS diverges
    when the seed is too stale: ``||I - A X0|| > 1``).
    """
    x = x0.astype(a.dtype)
    for _ in range(iters):
        ax = _mm('...ij,...jk->...ik', a, x)
        x = 2.0 * x - _mm('...ij,...jk->...ik', x, ax)
    x = 0.5 * (x + jnp.swapaxes(x, -1, -2))
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    resid = jnp.max(jnp.abs(eye - _mm('...ij,...jk->...ik', a, x)),
                    axis=(-2, -1))
    return x, resid


def warm_inverse(damped, seed, iters=2, accept_resid=0.05):
    """Newton-Schulz warm inverse with a PER-SLOT acceptance gate.

    Runs :func:`newton_schulz_inverse` seeded by ``seed`` and accepts
    each batch slot independently: slots whose final residual
    ``max |I - A X|`` clears ``accept_resid`` keep the NS result; the
    rest are recomputed by the batched Cholesky :func:`psd_inverse` and
    spliced in (one stale/zero-seeded slot must not drag its healthy
    bucket-mates back to cold Cholesky). The all-healthy fast path is
    guarded by an outer ``lax.cond`` so the Cholesky program only ever
    executes when some slot actually failed.
    """
    with jax.named_scope('decomp.newton_schulz'):
        ns, resid = newton_schulz_inverse(damped, seed, iters=iters)
    slot_ok = resid < accept_resid
    return lax.cond(
        jnp.all(slot_ok),
        lambda: ns,
        lambda: jnp.where(slot_ok[..., None, None], ns,
                          psd_inverse(damped)))


def sym_eig(x, impl=None, basis=None, sweeps=None):
    """Symmetric eigendecomposition ``(eigvals, eigvecs)`` (batched).

    Parity: ``mat_eig`` (reference: kfac/utils.py:22-30); runs on-chip
    instead of as a cuSOLVER host call.

    basis: optional previous eigenbasis (same shape as ``x``) to
    warm-start the Jacobi or subspace path. The caller must guarantee it
    is orthogonal (e.g. a prior decomposition's eigenvectors); it is
    ignored by the XLA path.

    impl: 'xla' (jnp.linalg.eigh — QDWH on TPU), 'jacobi' (the batched
    matmul-form Jacobi sweep kernel below), 'subspace' (warm-only
    orthogonal-iteration tracking — :func:`subspace_eigh`; falls back to
    XLA when no basis exists yet), 'auto', or None to read
    KFAC_EIGH_IMPL from the environment (default 'xla').

    'auto' resolves to 'subspace' on a hypothesis ROADMAP S3 carries from
    an earlier round's notes (not re-measured on today's v5e; what PR 21
    did measure there is the eigh COMPILE time): XLA QDWH eigh is
    iteration-bound (seconds at K-FAC bucket dims: [4,2304] ~ 9.8 s) and
    the gather-bound matmul-form Jacobi loses to it from 512 dims up
    (~79 s/call at [4,1024]); the subspace tracker is the only
    MXU-shaped form — cold decompositions still pay one QDWH, warm fulls
    are ~6 batched matmuls + a Cholesky.
    """
    impl = impl or os.environ.get('KFAC_EIGH_IMPL', 'xla')
    if impl == 'auto':
        impl = 'subspace'
    with jax.named_scope('decomp.eigh'):
        if impl == 'jacobi':
            return jacobi_eigh(x, sweeps=sweeps, basis=basis)
        if impl == 'subspace' and basis is not None:
            return subspace_eigh(x, basis, steps=sweeps)
        # QDWH: no warm-start notion ('subspace' with no basis lands here
        # too)
        eigvals, eigvecs = jnp.linalg.eigh(x)
        return eigvals, eigvecs


def _chol_qr(z, jitter=1e-6):
    """Batched CholeskyQR: orthonormalize the columns of ``z`` with one
    Gram matmul, one small Cholesky and one triangular solve — all
    MXU-shaped. A relative diagonal jitter keeps the Gram factor positive
    definite when ``z`` is ill-conditioned (the caller runs two passes,
    which restores orthogonality to working precision — CholeskyQR2)."""
    g = jnp.einsum('...ji,...jk->...ik', z, z,
                   precision=lax.Precision.HIGHEST)
    d = jnp.diagonal(g, axis1=-2, axis2=-1)
    scale = jnp.mean(d, axis=-1, keepdims=True)[..., None]
    eye = jnp.eye(z.shape[-1], dtype=z.dtype)
    r = jnp.linalg.cholesky(g + jitter * scale * eye)
    # q = z @ r^{-T}: columns of z against the lower Cholesky factor
    return lax.linalg.triangular_solve(r, z, left_side=False, lower=True,
                                       transpose_a=True)


def subspace_eigh(x, basis, steps=None, tau=0.01, clip=0.5):
    """Warm eigendecomposition by perturbative basis tracking: start from
    the previous eigenbasis instead of re-solving from scratch.

    The running-average K-FAC factors rotate slowly between
    decompositions (factor_decay ~= 0.95), so ``B = Q^T X Q`` is nearly
    diagonal in the stored basis. Each step applies the first-order
    eigenvector correction of perturbation theory — the skew-symmetric
    rotation ``K_ij = B_ij / (d_j - d_i)`` — and re-orthonormalizes with
    CholeskyQR2, which drives the off-diagonal mass down quadratically
    per step for separated eigenvalues. Near-degenerate pairs get their
    rotation Tikhonov-suppressed (``denom / (denom^2 + (tau*spread)^2)``):
    mixing inside an eigenvalue cluster is harmless, because any
    orthogonal basis of the cluster's invariant subspace yields the same
    preconditioner ``Q f(d) Q^T`` and the Rayleigh eigenvalues
    ``diag(Q^T X Q)`` stay correct. ``clip`` bounds individual rotation
    angles so a far-drifted basis degrades gracefully toward more steps
    rather than overshooting.

    Everything is batched matmuls plus one [n, n] Cholesky per step —
    the MXU-shaped replacement for QDWH/Jacobi in the warm path
    (KFAC_EIGH_IMPL=subspace|auto + warm_start_basis / basis_update_freq):
    QDWH at K-FAC bucket dims is expected to cost seconds (ROADMAP S3)
    while this costs ~6 matmuls.

    Returns unsorted ``(eigvals, eigvecs)`` like :func:`jacobi_eigh`.
    """
    steps = 2 if steps is None else max(int(steps), 1)
    q = basis.astype(x.dtype)
    for _ in range(steps):
        xq = _mm('...ij,...jk->...ik', x, q)
        b = _mm('...ji,...jk->...ik', q, xq)
        d = jnp.diagonal(b, axis1=-2, axis2=-1)
        # floor the spread at eps-relative scale: a constant-diagonal slot
        # (e.g. an all-padding identity block) has spread 0, and a tiny
        # (subnormal) floor would underflow in (tau*spread)**2 and make
        # reg = 0/0 — with the eps floor, reg = 0 there and k stays 0
        eps_floor = jnp.finfo(x.dtype).eps * (1.0 + jnp.max(jnp.abs(d),
                                                            axis=-1))
        spread = jnp.maximum(jnp.max(d, axis=-1) - jnp.min(d, axis=-1),
                             eps_floor)[..., None, None]
        denom = d[..., None, :] - d[..., :, None]        # d_j - d_i
        # reg's diagonal is exactly zero (denom there is 0), so k needs
        # no separate diagonal masking
        reg = denom / (denom * denom + (tau * spread) ** 2)
        k = jnp.clip(b * reg, -clip, clip)
        q = _chol_qr(q + _mm('...ij,...jk->...ik', q, k))
        q = _chol_qr(q)                                  # CholeskyQR2
    xq = _mm('...ij,...jk->...ik', x, q)
    w = jnp.sum(q * xq, axis=-2)
    return w, q


@functools.lru_cache(maxsize=None)
def _tournament_perms(n):
    """Per-round permutations putting each round's pairs adjacent
    ([p0, q0, p1, q1, ...]) plus their inverses — the gather tables for
    the 'paired' rotation form. Static numpy."""
    pairs = _tournament_pairs(n)                  # [n-1, n/2, 2]
    perms = pairs.reshape(n - 1, n)
    invs = np.empty_like(perms)
    rows = np.arange(n - 1)[:, None]
    invs[rows, perms] = np.arange(n)[None, :]
    return perms, invs


@functools.lru_cache(maxsize=None)
def _tournament_pairs(n):
    """Round-robin schedule: n-1 rounds of n/2 disjoint (p, q) pairs
    covering every index pair exactly once (circle method). Static numpy
    so it traces as constants."""
    assert n % 2 == 0, n
    circle = list(range(1, n))
    rounds = []
    for _ in range(n - 1):
        seats = [0] + circle
        pairs = [(seats[i], seats[n - 1 - i]) for i in range(n // 2)]
        rounds.append([(min(p, q), max(p, q)) for p, q in pairs])
        circle = circle[-1:] + circle[:-1]
    return np.asarray(rounds, np.int32)  # [n-1, n/2, 2]


def _givens_cs(app, aqq, apq, tiny):
    """Stable Givens (c, s) zeroing the symmetric 2x2 off-diagonal:
    tau = (aqq-app)/(2 apq), t the smaller root."""
    apq_safe = jnp.where(jnp.abs(apq) < tiny, 1.0, apq)
    tau = (aqq - app) / (2.0 * apq_safe)
    sgn = jnp.where(tau >= 0, 1.0, -1.0)
    t = sgn / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
    t = jnp.where(jnp.abs(apq) < tiny, 0.0, t)
    c = 1.0 / jnp.sqrt(1.0 + t * t)
    return c, t * c


def jacobi_eigh(x, sweeps=None, basis=None, rotate=None):
    """Batched symmetric eigendecomposition by cyclic Jacobi sweeps with
    matmul-applied rotations — the MXU-shaped alternative to XLA's QDWH
    eigh for the K-FAC factor regime (stacked buckets of dim <= ~1024).

    Each round zeroes n/2 disjoint off-diagonal pairs at once: the n/2
    Givens rotations are packed into one orthogonal matrix J and applied
    as A <- J^T A J, V <- V J — three [*, n, n] matmuls that batch over
    the bucket's layer axis and run on the MXU, instead of QDWH's long
    serial iteration. A sweep (n-1 rounds) touches every pair once;
    convergence is quadratic in sweeps. Replaces the role of the
    reference's cuSOLVER ``cusolverDnSsyevd`` (tcmm_kernel.cu:56-116) for
    small/medium factors.

    sweeps: fixed sweep count (static for XLA). Default: enough for f32
    (~1e-6 relative off-diagonal mass) across the bucket dims; 5 when
    warm-started (matches the cold default's accuracy even under the
    noisiest realistic factor drift — stat_decay 0.95 means the running
    average is ~95% the latest batch stat).
    basis: previous eigenbasis Q of a nearby matrix (K-FAC running-avg
    factors drift slowly between decompositions). The problem is rotated
    to Q^T x Q — near-diagonal, so Jacobi's quadratic phase starts
    immediately — and the result rotated back (Q @ V'). The caller must
    pass an ORTHOGONAL basis (cold zero-initialized state would silently
    corrupt results; the preconditioner gates warm starts on a
    decomposition existing).
    rotate: how a round applies its n/2 disjoint rotations. 'dense'
    packs them into one [n, n] J and does three n^3 matmuls (MXU-bound,
    the default). 'paired' permutes each round's pairs adjacent and
    applies the 2x2 rotations elementwise on the paired rows/columns —
    O(n^2) work per round (factor-n fewer flops, but gather/VPU-bound);
    identical results. None reads KFAC_JACOBI_ROT (default 'dense').
    Returns (eigvals, eigvecs) sorted ascending, matching eigh.
    """
    rotate = rotate or os.environ.get('KFAC_JACOBI_ROT', 'dense')
    if rotate not in ('dense', 'paired'):
        raise ValueError(f'rotate={rotate!r}: expected dense|paired')
    if basis is not None:
        # same precision rule as the cold path: f64 inputs stay f64
        cd = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
        basis_c = basis.astype(cd)
        rot = jnp.matmul(
            jnp.swapaxes(basis_c, -1, -2),
            jnp.matmul(x.astype(cd), basis_c, precision='highest'),
            precision='highest')
        rot = 0.5 * (rot + jnp.swapaxes(rot, -1, -2))
        w, vr = jacobi_eigh(rot, sweeps=5 if sweeps is None else sweeps,
                            rotate=rotate)
        v = jnp.matmul(basis_c, vr.astype(cd), precision='highest')
        return w.astype(x.dtype), v.astype(x.dtype)
    single = x.ndim == 2
    if single:
        x = x[None]
    n = x.shape[-1]
    odd = n % 2 == 1
    if odd:
        # blockdiag(A, [1]): the pad index starts decoupled (zero
        # off-diagonals) and Jacobi rotations with a zero pivot are
        # identity, so it stays decoupled — sliced off below
        x = identity_pad(x, n + 1)
        n = n + 1
    if sweeps is None:
        sweeps = 10 if n <= 512 else 12
    dtype = x.dtype
    # sweep in f32 for low/mixed-precision inputs, but keep f64 inputs in
    # f64 — downcasting would silently cap an x64 caller at f32 accuracy
    cdtype = jnp.float64 if dtype == jnp.float64 else jnp.float32
    a0 = x.astype(cdtype)
    eye = jnp.eye(n, dtype=cdtype)
    # derive from a0 (not a fresh constant) so the loop carry inherits
    # a0's varying-manual-axes type under shard_map — the carry must be
    # type-stable across rounds
    v0 = a0 * 0.0 + eye
    tiny = jnp.asarray(1e-30, cdtype)

    if rotate == 'dense':
        pairs = jnp.asarray(_tournament_pairs(n))   # [n-1, n/2, 2]
    else:
        perms_np, invs_np = _tournament_perms(n)
        perms = jnp.asarray(perms_np)
        invs = jnp.asarray(invs_np)

    def dense_round(r, carry):
        a, v = carry
        pq = pairs[r % (n - 1)]
        p, q = pq[:, 0], pq[:, 1]                   # [n/2] each
        rows_p = jnp.take(a, p, axis=-2)            # [L, n/2, n]
        app = jnp.take_along_axis(rows_p, p[None, :, None], -1)[..., 0]
        apq = jnp.take_along_axis(rows_p, q[None, :, None], -1)[..., 0]
        rows_q = jnp.take(a, q, axis=-2)
        aqq = jnp.take_along_axis(rows_q, q[None, :, None], -1)[..., 0]
        c, s = _givens_cs(app, aqq, apq, tiny)      # [L, n/2]
        batch = a.shape[0]
        j = jnp.broadcast_to(eye, a.shape)
        bidx = jnp.arange(batch)[:, None]
        pb = jnp.broadcast_to(p[None, :], (batch, p.shape[0]))
        qb = jnp.broadcast_to(q[None, :], (batch, q.shape[0]))
        j = j.at[bidx, pb, pb].set(c)
        j = j.at[bidx, qb, qb].set(c)
        j = j.at[bidx, pb, qb].set(s)
        j = j.at[bidx, qb, pb].set(-s)
        jt = jnp.swapaxes(j, -1, -2)
        a = jnp.matmul(jt, jnp.matmul(a, j, precision='highest'),
                       precision='highest')
        v = jnp.matmul(v, j, precision='highest')
        # re-symmetrize: rounding drift would otherwise accumulate
        a = 0.5 * (a + jnp.swapaxes(a, -1, -2))
        return a, v

    def paired_round(r, carry):
        # permute this round's pairs adjacent, rotate the 2x2 blocks
        # elementwise (O(n^2) per round vs the dense form's n^3 matmuls),
        # permute back
        a, v = carry
        idx = r % (n - 1)
        perm, inv = perms[idx], invs[idx]
        ap = jnp.take(jnp.take(a, perm, axis=-2), perm, axis=-1)
        d = jnp.diagonal(ap, axis1=-2, axis2=-1)    # [L, n]
        app, aqq = d[..., 0::2], d[..., 1::2]       # [L, n/2]
        apq = jnp.diagonal(ap[..., 0::2, 1::2], axis1=-2, axis2=-1)
        c, s = _givens_cs(app, aqq, apq, tiny)      # [L, n/2]
        cr = c[..., None]
        sr = s[..., None]

        def rot_rows(m):                            # J^T on the left:
            mr = m.reshape(m.shape[:-2] + (n // 2, 2, n))
            r0, r1 = mr[..., 0, :], mr[..., 1, :]
            out = jnp.stack([cr * r0 - sr * r1, sr * r0 + cr * r1],
                            axis=-2)
            return out.reshape(m.shape)

        ap = rot_rows(ap)
        ap = jnp.swapaxes(rot_rows(jnp.swapaxes(ap, -1, -2)), -1, -2)
        a = jnp.take(jnp.take(ap, inv, axis=-2), inv, axis=-1)
        vp = jnp.take(v, perm, axis=-1)             # V J: columns rotate
        vp = jnp.swapaxes(rot_rows(jnp.swapaxes(vp, -1, -2)), -1, -2)
        v = jnp.take(vp, inv, axis=-1)
        a = 0.5 * (a + jnp.swapaxes(a, -1, -2))
        return a, v

    round_step = dense_round if rotate == 'dense' else paired_round
    a, v = lax.fori_loop(0, sweeps * (n - 1), round_step, (a0, v0))
    w = jnp.diagonal(a, axis1=-2, axis2=-1)
    if odd:
        w = w[..., :-1]
        v = v[..., :-1, :-1]
    order = jnp.argsort(w, axis=-1)
    w = jnp.take_along_axis(w, order, -1)
    v = jnp.take_along_axis(v, order[..., None, :], -1)
    w = w.astype(dtype)
    v = v.astype(dtype)
    if single:
        w, v = w[0], v[0]
    return w, v


def clamp_eigvals(d, eps):
    """Zero out eigenvalues ``<= eps``.

    Parity: the ``dA * (dA > eps)`` clamp (reference:
    kfac_preconditioner_eigen.py:108-119).
    """
    return d * (d > eps).astype(d.dtype)


def add_scaled_identity(x, value):
    """``x + value * I`` (batched); ``value`` may be scalar or ``[L]``.

    Parity: ``_add_value_to_diagonal`` (reference:
    kfac_preconditioner_inv.py:106-107).
    """
    d = x.shape[-1]
    eye = jnp.eye(d, dtype=x.dtype)
    value = jnp.asarray(value, dtype=x.dtype)
    if value.ndim > 0:
        value = value[..., None, None]
    return x + value * eye


def masked_trace(x, true_dim):
    """Trace over the leading ``true_dim`` diagonal entries (batched).

    Identity-padded factors carry 1s on the pad diagonal; the damping pi
    ratio (reference: kfac_preconditioner_inv.py:118) must use the true
    trace, so the pad region is masked out. ``true_dim`` may be scalar or
    ``[L]`` for stacked inputs.
    """
    d = x.shape[-1]
    # the same entries as jnp.diagonal, read without the gather (whose
    # operand the TPU's compiler first copies into another layout: every
    # factor bucket, once an update)
    diag = tile_diagonal(x)
    idx = jnp.arange(d)
    true_dim = jnp.asarray(true_dim)
    mask = (idx < true_dim[..., None]) if true_dim.ndim > 0 else (idx < true_dim)
    return jnp.sum(diag * mask.astype(diag.dtype), axis=-1)


def identity_pad(x, target_dim):
    """Embed ``[d, d]`` (or ``[L, d, d]``) into ``[target_dim, target_dim]``
    as blockdiag(x, I) — the exact padding for bucketed factors."""
    d = x.shape[-1]
    if d == target_dim:
        return x
    pad = target_dim - d
    batch = x.shape[:-2]
    out = jnp.zeros(batch + (target_dim, target_dim), dtype=x.dtype)
    out = out.at[..., :d, :d].set(x)
    eye_idx = jnp.arange(d, target_dim)
    out = out.at[..., eye_idx, eye_idx].set(1.0)
    return out
