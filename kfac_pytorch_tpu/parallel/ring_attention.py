"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context support at all (SURVEY.md §5.7 — its max
sequence length is 384 and K-FAC averages the sequence axis away). This
framework makes long sequences first-class on TPU: shard the *sequence*
axis of a transformer over a mesh axis and compute exact attention with

- **ring attention** (:func:`ring_attention`): K/V shards rotate around
  the mesh axis via ``lax.ppermute`` (one ICI hop per step) while each
  device streams softmax online (flash-style running max / normalizer),
  so no device ever materializes the full [L, L] score matrix or the full
  K/V. Communication overlaps with the block matmuls under XLA's async
  collective scheduling. Memory per device: O(L_local * L_block).
- **Ulysses all-to-all** (:func:`ulysses_attention`): two
  ``lax.all_to_all``s swap the sequence shard for a *head* shard, run
  dense local attention on the full sequence for H/n heads, and swap
  back. Cheaper at moderate L (2 collectives instead of n-1 permutes) as
  long as the head count divides the axis.

Both are exact (match single-device softmax attention), jit-safe
(``lax.fori_loop``), support causal masking and key-padding masks, and
degenerate to plain attention when ``axis_name`` is None — the same
world=1 zero-comm property as the rest of ``parallel/``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


#: 'auto' forward crossover — a HYPOTHESIS carried in ROADMAP S7 from an
#: earlier round's notes (B=1 H=8 D=64 causal fwd+bwd; not re-measured
#: on today's v5e): the XLA blockwise path wins below this key length
#: (8k: 43.5 ms vs 59.4; 16k: 103.6 vs 180.9) while at 32k the [L, L]
#: scores no longer fit and the Pallas kernel is the only path (657 ms).
#: Lk is a static shape, so the choice is made at trace time — the same
#: policy shape as ops.pallas_attention.AUTO_BWD_PALLAS_MIN_LK.
AUTO_FWD_PALLAS_MIN_LK = 32768


def _default_block_impl():
    """'auto' on TPU (length-gated XLA/Pallas, see :func:`_fwd_impl_for`),
    'xla' elsewhere (interpret mode is for tests). KFAC_ATTN_IMPL
    overrides ('auto' | 'xla' | 'pallas' | 'pallas_interpret')."""
    import os
    env = os.environ.get('KFAC_ATTN_IMPL')
    if env:
        return env
    return 'auto' if jax.default_backend() == 'tpu' else 'xla'


def _fwd_impl_for(impl, lk):
    """Resolve the forward block implementation; 'auto' picks by the
    (static) key length of this block — XLA blockwise below the measured
    v5e crossover, the Pallas flash kernel at/above it."""
    if impl not in ('auto', 'xla', 'pallas', 'pallas_interpret'):
        raise ValueError(f'KFAC_ATTN_IMPL={impl!r}: expected '
                         "'auto', 'xla', 'pallas' or 'pallas_interpret'")
    if impl == 'auto':
        return 'pallas' if lk >= AUTO_FWD_PALLAS_MIN_LK else 'xla'
    return impl


def interpreted_attention_active():
    """True when attention blocks resolve to the Pallas interpreter.

    The interpreter's block-index machinery cannot evaluate the kernel's
    scalar-prefetch meta once shard_map's varying-manual-axes checker has
    tagged it (per-device ring offsets vary over the seq axis), so any
    shard_map enclosing interpreted attention must pass check_vma=False
    — training.build_train_step consults this. TPU lowering is unaffected
    (meta rides SMEM)."""
    return _default_block_impl() == 'pallas_interpret'


def _block_attn_dispatch(q, k, v, q_start, k_start, causal, kv_mask,
                         scale, block_impl):
    """One streaming block through the selected implementation.

    'xla': plain jnp ops (materializes the [Lq, Lk] block scores and lets
    XLA fuse); 'pallas'/'pallas_interpret': the fused flash kernel
    (ops/pallas_attention.py), which never materializes scores in HBM;
    'auto': length-gated choice between them (:func:`_fwd_impl_for`).
    Both return identical (m, l, pv).
    """
    block_impl = _fwd_impl_for(block_impl, k.shape[2])
    if block_impl == 'xla':
        bias = _bias_for_block(q_start, k_start, q.shape[2], k.shape[2],
                               causal, kv_mask)
        return _block_attn(q, k, v, bias, scale)
    from kfac_pytorch_tpu.ops.pallas_attention import flash_block_attn
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    # pad sequence lengths up to the kernel's tile grid (<=128: multiple
    # of 8; >128: multiple of 128). Padded keys are masked out (exact:
    # their exp terms are 0); padded query rows are sliced off — and
    # jnp.pad's VJP slices the cotangents back, so gradients stay exact.
    pad_to = lambda n: -(-n // 8) * 8 if n <= 128 else -(-n // 128) * 128
    Lqp, Lkp = pad_to(Lq), pad_to(Lk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Lqp - Lq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Lkp - Lk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Lkp - Lk), (0, 0)))
    maskf = (jnp.ones((B, Lk), jnp.float32) if kv_mask is None
             else kv_mask.astype(jnp.float32))
    maskf = jnp.pad(maskf, ((0, 0), (0, Lkp - Lk)))  # pad keys masked
    fold = lambda x: x.reshape(B * H, *x.shape[2:])
    maskf = jnp.repeat(maskf, H, axis=0)
    starts = jnp.stack([jnp.asarray(q_start, jnp.int32),
                        jnp.asarray(k_start, jnp.int32)])
    m, l, pv = flash_block_attn(
        fold(qp), fold(kp), fold(vp), maskf, starts, scale, causal,
        block_impl == 'pallas_interpret')
    unfold = lambda x: x.reshape(B, H, *x.shape[1:])[:, :, :Lq]
    return unfold(m), unfold(l), unfold(pv)


def _block_attn(q, k, v, bias, scale):
    """One streaming block: scores, masked, unnormalized softmax pieces.

    q: [B, H, Lq, D]; k/v: [B, H, Lk, D]; bias: broadcastable to
    [B, H, Lq, Lk] additive (-inf to mask). Returns (m, p, pv) with
    m: [B, H, Lq] block row max, p: exp(s - m), pv: p @ v.
    """
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    # the running max is a pure numerical shift: softmax is invariant to
    # it, so it must be a constant to autodiff (a half-stop-gradiented
    # max would corrupt the backward pass)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1))
    p = jnp.exp(s - m[..., None])
    pv = jnp.einsum('bhqk,bhkd->bhqd', p, v.astype(p.dtype),
                    preferred_element_type=jnp.float32)
    return m, p.sum(axis=-1), pv


def _merge(o, l, m, pv_j, l_j, m_j):
    """Merge one block's (pv, l, m) into running (o, l, m) — the online
    softmax recurrence."""
    m_new = jnp.maximum(m, m_j)
    c = jnp.exp(m - m_new)
    c_j = jnp.exp(m_j - m_new)
    o = o * c[..., None] + pv_j * c_j[..., None]
    l = l * c + l_j * c_j
    return o, l, m_new


def ring_attention(q, k, v, axis_name, causal=False, kv_mask=None,
                   scale=None, block_impl=None):
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Args:
      q: [B, H, Lq_local, D] local query shard.
      k, v: [B, H, Lk_local, D] local key/value shards (same sequence
        sharding as q).
      axis_name: mesh axis the sequence is sharded over (None = 1 device).
      causal: causal masking in *global* sequence positions.
      kv_mask: optional [B, Lk_local] bool, True = attend (key padding).
      scale: score scale; default 1/sqrt(D).

    Returns [B, H, Lq_local, D] — bitwise the same math as softmax
    attention over the gathered sequence.
    """
    scale = scale or (q.shape[-1] ** -0.5)
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    dtype = jnp.float32

    block_impl = block_impl or _default_block_impl()
    if axis_name is None:
        m, l, pv = _block_attn_dispatch(q, k, v, 0, 0, causal, kv_mask,
                                        scale, block_impl)
        return (pv / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # accumulators are derived from q (zeroed) rather than fresh constants
    # so they inherit q's full varying-manual-axes set — shard_map's vma
    # checker requires the loop carry to keep a stable type even when the
    # inputs also vary over other mesh axes (e.g. a 'data' axis)
    zq = (q * 0).astype(dtype)
    o = jnp.zeros((B, H, Lq, D), dtype) + zq
    l = zq.sum(axis=-1)
    m = l + _NEG_INF
    # carry the padding mask as f32 (collectives over bool are unreliable)
    zk = (k[:, 0, :, 0] * 0).astype(dtype)
    kv_mask = (1.0 + zk if kv_mask is None
               else kv_mask.astype(dtype) + zk)

    def body(t, carry):
        o, l, m, k_t, v_t, mask_t = carry
        src = (me - t) % n  # which global shard this K/V block came from
        m_j, l_j, pv_j = _block_attn_dispatch(
            q, k_t, v_t, me * Lq, src * Lk, causal, mask_t > 0.5, scale,
            block_impl)
        o, l, m = _merge(o, l, m, pv_j, l_j, m_j)
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        mask_t = lax.ppermute(mask_t, axis_name, perm)
        return o, l, m, k_t, v_t, mask_t

    o, l, m, *_ = lax.fori_loop(0, n, body, (o, l, m, k, v, kv_mask))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _bias_for_block(q_start, k_start, Lq, Lk, causal, kv_mask):
    """Additive bias [*, Lq, Lk] combining global-position causal masking
    and the key-padding mask for one K/V block."""
    bias = None
    if causal:
        qpos = q_start + jnp.arange(Lq)[:, None]
        kpos = k_start + jnp.arange(Lk)[None, :]
        bias = jnp.where(qpos >= kpos, 0.0, _NEG_INF)[None, None]
    if kv_mask is not None:
        pad = jnp.where(kv_mask, 0.0, _NEG_INF)[:, None, None, :]
        bias = pad if bias is None else bias + pad
    return bias


def ulysses_attention(q, k, v, axis_name, causal=False, kv_mask=None,
                      scale=None, block_impl=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Same contract as :func:`ring_attention` but requires ``H`` divisible
    by the axis size: all-to-all converts the sequence shard into a head
    shard, attention runs dense over the full sequence for H/n heads,
    and a second all-to-all restores sequence sharding.
    """
    scale = scale or (q.shape[-1] ** -0.5)
    if axis_name is None:
        return ring_attention(q, k, v, None, causal=causal,
                              kv_mask=kv_mask, scale=scale,
                              block_impl=block_impl)
    n = lax.axis_size(axis_name)
    B, H, Lq, D = q.shape
    if H % n:
        raise ValueError(f'ulysses needs heads ({H}) % axis ({n}) == 0')

    # [B, H, L_local, D] -> [B, H/n, L_global, D]
    swap = functools.partial(lax.all_to_all, axis_name=axis_name,
                             split_axis=1, concat_axis=2, tiled=True)
    unswap = functools.partial(lax.all_to_all, axis_name=axis_name,
                               split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = swap(q), swap(k), swap(v)
    maskg = None
    if kv_mask is not None:
        maskg = lax.all_gather(kv_mask.astype(jnp.float32), axis_name,
                               axis=1, tiled=True) > 0.5
    m, l, pv = _block_attn_dispatch(
        qg, kg, vg, 0, 0, causal, maskg,
        scale, block_impl or _default_block_impl())
    out = (pv / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return unswap(out)
