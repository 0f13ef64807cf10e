"""Sparse decoder LM whose layers are a recurrence or a softmax: Kimi Delta
Attention (KDA: a gated delta-rule linear attention with a per-channel
decay and a short convolution) and latent attention without positions,
3:1, sigmoid-routed experts with a shared expert, as
``moonshotai/Kimi-Linear-48B-A3B-Instruct`` publishes them (``config.json``,
``model_type: kimi_linear``; the defaults here) — one chip's share of it,
under K-FAC. The third decoder beside ``models.sparse_decoder`` and
``models.mixed_decoder``, of whose pieces it is built: ``RMSNorm``,
``LatentAttention`` (``rotary=False``), ``MoECounters``,
``parallel.moe.SwiGLU`` / ``BlockedSwiGLU`` / ``RoutedExperts``, flat
tokens, the ``L + 1``-id batch and the mean next-token loss.

Tokens are FLAT (``T = B L`` rows); only the two attentions fold them back
into sequences. Returns float32 logits ``[B, L, V]``. Per layer, two norms
(eps ``rms_norm_eps``), no biases but the one named:
``x += attn_i(norm1(x))``, ``x += ffn_i(norm2(x))``; final norm, untied
head.

*KDA layer* (``layer_kinds[i] == 'kda'``), ``u = norm1(x) [T, d]``, ``h``
heads held of ``d_k = d_v = 128``, ``n = 128 h``; everything after the
projections in float32:

- ``q~ = u W_q``, ``k~ = u W_k``, ``v~ = u W_v`` (``d -> n``);
- a short convolution on each of the three, own weights ``w [4, n]``
  (window first), depthwise, causal, within the sequence, zero left
  padding, no bias: ``c_t[j] = sum_{i=0..3} w[i, j] z_{t-3+i}[j]``; SiLU;
- ``q_t = l2norm(q_t) 128^-1/2``, ``k_t = l2norm(k_t)`` over a head's 128
  (``x / sqrt(sum x^2 + 1e-6)``); ``v_t`` as it is;
- decay, per key channel: ``g_t = -exp(A_log[head]) softplus((u W_fa)
  W_fb + dt_bias)``, ``W_fa: d -> 128``, ``W_fb: 128 -> n``;
  ``alpha_t = exp(g_t)``. The leaves ``A_log`` / ``dt_bias`` hold the
  DISTANCE from ``a_log_centre`` / ``dt_bias_centre`` (a benchmark's
  seeded weights are centred normals);
- step size, per head: ``beta_t = sigmoid(u W_b)``, ``W_b: d -> h``;
- recurrence, per head and sequence, ``S_0 = 0 [128, 128]``:
  ``S_t = (I - beta_t k_t k_t') Diag(alpha_t) S_{t-1} + beta_t k_t v_t'``,
  ``o_t = S_t' q_t`` (:func:`kda_chunked`);
- ``y_t = [RMSNorm_128(o_t) * sigmoid((u W_ga) W_gb + b_g)] W_o``: the norm
  over a head's 128 with one scale vector a layer, ``W_ga: d -> 128``,
  ``W_gb: 128 -> n`` (the layer's one bias), ``W_o: n -> d``.

*Latent layer* (``'latent'``): ``sparse_decoder.LatentAttention`` with
``rotary=False``: the 64 shared dimensions enter the score unrotated.

*Feed-forward*: the first ``first_k_dense`` layers a SwiGLU of
``intermediate_size``, its K-FAC factors in blocks of ``ffn_block``
(``parallel.moe.BlockedSwiGLU``; below); the others ``RoutedExperts``.

*The share.* The model is told its KDA heads (``kda_head_ids``), its
latent heads (``head_ids``), its experts and its rows of the vocabulary.
``W_fa``, ``W_ga`` and the convolution weights of held channels are
computed alike on every chip of a group; what absent heads and experts
would add is left out; nothing stands in for absent chips.

*K-FAC, weight by weight* (the written split of a recurrent layer: every
factored projection lies OUTSIDE the scan, so ``nn.Dense`` sows its input
and taps its output round it, and the scan is only differentiated
through). Kronecker-factored: ``q_proj`` / ``k_proj`` / ``v_proj`` /
``f_a_proj`` / ``g_a_proj`` / ``b_proj`` (ONE input group of six: one
``A`` of ``d``, a ``G`` and an inverse of ``A`` each), ``f_b_proj``,
``g_b_proj`` (with its bias: a ones column in its ``A``), ``o_proj``; the
latent layer's four; the shared and every held expert's ``gate`` / ``up``
(a group) / ``down``; the dense block's ``gate_j`` / ``up_j`` (one group of
``2 width / ffn_block``) and ``down_j``. First-order: the three
convolutions' weights, ``A_log``, ``dt_bias``, the output norm's scale,
every norm, the router, ``e_score_correction_bias`` (no gradient), the
embedding and the head.

*The wide dense block* (a written decision for a weight wider than a
decomposition here should take: 9,216 published): ``gate`` and ``up`` are
column blocks ``d -> ffn_block`` and ``down`` row blocks ``ffn_block -> d``
over the slices of its input, summed. The layer computes what an unsplit
SwiGLU computes; K-FAC's ``G`` of ``gate`` / ``up`` and ``A`` of ``down``
are block-diagonal, every block a layer of its own in the plan.

Device scopes: ``kda.conv`` (convolutions, SiLU, the norms of ``q``,
``k``), ``kda.gates`` (decay and ``beta``), ``kda.scan`` (the chunked
recurrence, computed again in the backward pass), ``kda.out`` (output norm
times gate); ``mla.attend``; the ``moe.*`` scopes. Counters
(``capture.COUNTERS``): ``MoECounters``' and ``kda/log_decay_min`` (the
most negative log-decay summed over one chunk, any channel, head or layer,
this step: how near :func:`kda_chunked`'s guard is to mattering),
``kda/state_absmax`` (largest ``|S|`` entry at a sequence's end).
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen
from jax import lax

from kfac_pytorch_tpu import capture
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.models.sparse_decoder import (
    LatentAttention, MoECounters, RMSNorm)
from kfac_pytorch_tpu.parallel.moe import BlockedSwiGLU, RoutedExperts

KDA, LATENT = 'kda', 'latent'
#: published ``linear_attn_config.full_attn_layers`` (1-indexed) of 27
_PUBLISHED_LATENT = (4, 8, 12, 16, 20, 24, 27)


def held_layer_kinds(num_layers, first_k_dense=1, period=4):
    """The kinds of the ``num_layers`` layers a cut in depth holds: the
    leading dense ones KDA, then whole periods of ``period - 1`` KDA layers
    and one latent layer (Kimi-Linear's published layers 1 and 5-8 for
    5)."""
    rest = [LATENT if (i + 1) % period == 0 else KDA
            for i in range(num_layers - first_k_dense)]
    return (KDA,) * first_k_dense + tuple(rest)


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=lax.Precision.HIGHEST)


def kda_chunked(q, k, v, g, beta, chunk):
    """The gated delta rule ``S_t = (I - beta_t k_t k_t') Diag(exp g_t)
    S_{t-1} + beta_t k_t v_t'``, ``o_t = S_t' q_t`` from ``S_0 = 0``, in
    chunks of ``chunk`` tokens. ``q, k, g [B, L, h, dk]``, ``v [B, L, h,
    dv]``, ``beta [B, L, h]``, float32, ``g <= 0`` -> ``(o [B, L, h, dv],
    S_L [B, h, dk, dv], G_C [B, h, N, dk])``, ``G_C`` each chunk's summed
    log-decay.

    With ``G_t`` the log-decay summed from the chunk's start through ``t``
    and ``S_0`` the state the chunk starts from, ``u_t = beta_t (v_t -
    S_0'(e^{G_t} k_t) - sum_{i<t} u_i (k_i' Diag(e^{G_t - G_i}) k_t))`` is
    one unit lower-triangular solve a chunk, linear in ``S_0``: ``u = u~ -
    w S_0``, both solved for every chunk at once. A ``lax.scan`` then
    carries ``S <- Diag(e^{G_C}) S + sum_i (e^{G_C - G_i} k_i) u_i'`` from
    chunk to chunk (two small products a step), and ``o_t = S_0'(e^{G_t}
    q_t) + sum_{i<=t} u_i (k_i' Diag(e^{G_t - G_i}) q_t)`` is batched over
    the chunks again. Every exponent is a difference ``G_t - G_i`` with
    ``i <= t`` (or ``G_t`` itself), so at most 0: ``e^{-G_i}`` alone, which
    the factored form ``(e^{G_t} q_t)'(e^{-G_i} k_i)`` needs, overflows
    float32 at the decays the published init reaches. A last chunk that is
    short is padded with tokens that change nothing (``g = 0``, ``beta =
    0``). Products in float32 at ``highest``.
    """
    batch, length, heads, dk = k.shape
    pad = -length % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (length + pad) // chunk

    def fold(x):        # [B, N C, h, d] -> [B, h, N, C, d]
        return x.reshape(batch, n, chunk, heads, -1).transpose(0, 3, 1, 2, 4)
    q, k, v, g, beta = (fold(x) for x in (q, k, v, g, beta[..., None]))
    total = jnp.cumsum(g, axis=3)                           # G_t
    t = np.arange(chunk)
    seen = (t[:, None] >= t[None, :])[..., None]            # i <= t
    decay = jnp.exp(jnp.where(
        seen, total[..., :, None, :] - total[..., None, :, :], -jnp.inf))
    kk = (k[..., :, None, :] * k[..., None, :, :] * decay).sum(-1)
    qk = (q[..., :, None, :] * k[..., None, :, :] * decay).sum(-1)
    kk = jnp.where(t[:, None] > t[None, :], kk, 0.0) * beta
    within = jnp.exp(total)                                 # e^{G_t}
    solved = lax.linalg.triangular_solve(
        kk, jnp.concatenate([beta * within * k, beta * v], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    w, u_own = solved[..., :dk], solved[..., dk:]
    last = total[..., -1, :]                                # G_C
    k_out = k * jnp.exp(last[..., None, :] - total)

    def carry(state, xs):
        w_n, u_n, k_n, last_n = xs
        u_n = u_n - _mm('bhck,bhkv->bhcv', w_n, state)
        after = (jnp.exp(last_n)[..., None] * state
                 + _mm('bhck,bhcv->bhkv', k_n, u_n))
        return after, (state, u_n)
    end, (start, u) = lax.scan(
        carry, jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (w, u_own, k_out, last)))
    start, u = jnp.moveaxis(start, 0, 2), jnp.moveaxis(u, 0, 2)
    o = (_mm('bhnck,bhnkv->bhncv', q * within, start)
         + _mm('bhnci,bhniv->bhncv', qk, u))
    o = o.transpose(0, 2, 3, 1, 4).reshape(batch, n * chunk, heads, -1)
    return o[:, :length], end, last


class ShortConv(linen.Module):
    """Depthwise causal convolution within the sequence, zero left padding,
    no bias, then SiLU: ``z [B, L, n]`` -> ``silu(sum_i w[i] z_{t-K+1+i})``,
    ``w [K, n]`` (first-order)."""
    size: int = 4

    @linen.compact
    def __call__(self, z):
        w = self.param('weight', linen.initializers.normal(self.size ** -0.5),
                       (self.size, z.shape[-1]), jnp.float32)
        length = z.shape[1]
        padded = jnp.pad(z, ((0, 0), (self.size - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[i] * padded[:, i:i + length]
                               for i in range(self.size)))


def _l2norm(x, eps=1e-6):
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


class KimiDeltaAttention(linen.Module):
    """Kimi Delta Attention over the heads this chip holds (the module's
    docstring has the equations). ``u [T, d]`` -> ``(y [T, d], stats)``,
    ``stats`` the layer's ``log_decay_min`` and ``state_absmax``."""
    head_ids: Tuple[int, ...]
    head_dim: int = 128
    rank: int = 128             # low-rank width of the f and g projections
    conv_size: int = 4
    chunk: int = 64
    a_log_centre: float = float(np.log(4.0))
    dt_bias_centre: float = -4.600
    eps: float = 1e-5
    dtype: Optional[Any] = None

    @linen.compact
    def __call__(self, u, batch, length):
        h, hd = len(self.head_ids), self.head_dim
        n, f32 = h * hd, jnp.float32

        def dense(width, name, use_bias=False):
            return knn.Dense(width, use_bias=use_bias, dtype=self.dtype,
                             name=name)
        # the six that read u: one input group, one A
        q, k, v = (dense(n, f'{x}_proj')(u) for x in 'qkv')
        f = dense(self.rank, 'f_a_proj')(u)
        z = dense(self.rank, 'g_a_proj')(u)
        b = dense(h, 'b_proj')(u)
        f = dense(n, 'f_b_proj')(f)
        z = dense(n, 'g_b_proj', use_bias=True)(z)
        a_log = self.a_log_centre + self.param(
            'A_log', linen.initializers.normal(0.80), (h,), f32)
        dt_bias = self.dt_bias_centre + self.param(
            'dt_bias', linen.initializers.normal(1.33), (n,), f32)

        with jax.named_scope('kda.conv'):
            q, k, v = (ShortConv(self.conv_size, name=f'{x}_conv')(
                y.astype(f32).reshape(batch, length, n)).reshape(
                    batch, length, h, hd)
                for x, y in zip('qkv', (q, k, v)))
            q, k = _l2norm(q) * hd ** -0.5, _l2norm(k)
        with jax.named_scope('kda.gates'):
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f.astype(f32) + dt_bias).reshape(batch, length, h, hd)
            beta = jax.nn.sigmoid(b.astype(f32)).reshape(batch, length, h)
        chunk = self.chunk

        # what the chunks make on the way is computed again in the
        # backward pass, not kept
        @jax.checkpoint
        def scan(q, k, v, g, beta):
            with jax.named_scope('kda.scan'):
                o, end, last = kda_chunked(q, k, v, g, beta, chunk)
                return o, lax.stop_gradient(
                    {'log_decay_min': last.min(),
                     'state_absmax': jnp.abs(end).max()})
        o, stats = scan(q, k, v, g, beta)
        with jax.named_scope('kda.out'):
            o = RMSNorm(self.eps, name='o_norm')(o).reshape(
                batch * length, n)
            o = (o * jax.nn.sigmoid(z.astype(f32))).astype(u.dtype)
        return dense(u.shape[-1], 'o_proj')(o), stats


class KDACounters(linen.Module):
    """The recurrence's counters (``capture.COUNTERS``), both the step's:
    ``log_decay_min`` and ``state_absmax`` over the KDA layers."""

    @linen.compact
    def __call__(self, stats):
        if not self.is_mutable_collection(capture.COUNTERS):
            return      # evaluation: nothing is counted
        for name, pick in (('log_decay_min', jnp.min),
                           ('state_absmax', jnp.max)):
            var = self.variable(capture.COUNTERS, name,
                                lambda: jnp.zeros((), jnp.float32))
            var.value = pick(jnp.stack([s[name] for s in stats]))


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    """Sizes as ``config.json`` publishes them (defaults: Kimi-Linear-48B-
    A3B), and this chip's share: ``kda_head_ids``, ``head_ids`` (latent),
    ``expert_ids``, ``vocab_size``, and ``layer_kinds`` of the layers it
    holds. The fields ``SparseDecoderConfig`` has too keep its names and
    meanings."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    layer_kinds: Tuple[str, ...] = tuple(
        LATENT if i + 1 in _PUBLISHED_LATENT else KDA for i in range(27))
    first_k_dense: int = 1
    intermediate_size: int = 9216
    #: width of a block of the dense SwiGLU's K-FAC factors
    ffn_block: int = 2304
    expert_width: int = 1024
    n_routed_experts: int = 256
    experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 2.446
    norm_topk: bool = True
    kv_rank: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_dim: int = 128
    kda_head_dim: int = 128
    kda_rank: int = 128
    kda_conv_size: int = 4
    kda_chunk: int = 64
    kda_a_log_centre: float = float(np.log(4.0))
    kda_dt_bias_centre: float = -4.600
    eps: float = 1e-5
    kda_head_ids: Tuple[int, ...] = tuple(range(32))
    head_ids: Tuple[int, ...] = tuple(range(32))
    expert_ids: Tuple[int, ...] = tuple(range(256))
    #: rows of each held expert's buffer: ``SparseDecoderConfig``'s
    expert_capacity: int = 512
    dtype: Optional[Any] = None


class HybridDecoderLayer(linen.Module):
    """``x [T, d]`` -> ``(x, counts, stats)``: KDA or latent attention
    (``kind``; ``stats`` None for latent), then the dense SwiGLU in blocks
    (``dense``; ``counts`` None) or the routed experts."""
    cfg: HybridDecoderConfig
    kind: str
    dense: bool

    @linen.compact
    def __call__(self, x, batch, length):
        c = self.cfg
        u = RMSNorm(c.eps, name='input_layernorm')(x)
        if self.kind == KDA:
            a, stats = KimiDeltaAttention(
                tuple(c.kda_head_ids), c.kda_head_dim, c.kda_rank,
                c.kda_conv_size, c.kda_chunk, c.kda_a_log_centre,
                c.kda_dt_bias_centre, c.eps, c.dtype, name='self_attn')(
                    u, batch, length)
        else:
            a, stats = LatentAttention(
                tuple(c.head_ids), c.qk_nope, c.qk_rope, c.v_dim, c.kv_rank,
                eps=c.eps, dtype=c.dtype, rotary=False, name='self_attn')(
                    u, batch, length), None
        x = x + a
        u = RMSNorm(c.eps, name='post_attention_layernorm')(x)
        if self.dense:
            return x + BlockedSwiGLU(c.intermediate_size, c.ffn_block,
                                     dtype=c.dtype, name='mlp')(u), None, stats
        y, counts = RoutedExperts(
            c.n_routed_experts, c.experts_per_tok, tuple(c.expert_ids),
            c.expert_width, c.n_shared_experts * c.expert_width,
            c.expert_capacity, c.routed_scale, c.norm_topk, c.dtype,
            name='mlp')(u)
        return x + y, counts, stats


class HybridDecoderLM(linen.Module):
    cfg: HybridDecoderConfig = HybridDecoderConfig()

    @linen.compact
    def __call__(self, tokens, train=True):
        del train       # no dropout
        c = self.cfg
        batch, length = tokens.shape
        x = linen.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name='embed_tokens')(tokens.reshape(-1))
        counts, stats = [], []
        for i, kind in enumerate(c.layer_kinds):
            x, n, s = HybridDecoderLayer(c, kind, i < c.first_k_dense,
                                         name=f'layer_{i}')(x, batch, length)
            if n is not None:
                counts.append(n)
            if s is not None:
                stats.append(s)
        if counts:
            MoECounters(name='moe')(counts)
        if stats:
            KDACounters(name='kda')(stats)
        x = RMSNorm(c.eps, name='norm')(x)
        logits = knn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                           kfac_enabled=False, name='lm_head')(x)
        return logits.astype(jnp.float32).reshape(batch, length, -1)


def hybrid_decoder_lm(vocab_size=163840, **kw):
    """Kimi-Linear-shaped sparse decoder with KDA and latent attention
    mixed (see the module's docstring); ``kda_head_ids``, ``head_ids``,
    ``expert_ids``, ``vocab_size`` and ``layer_kinds`` say which share of
    the published model this chip holds. ``kw``: fields of
    :class:`HybridDecoderConfig`."""
    return HybridDecoderLM(HybridDecoderConfig(vocab_size=vocab_size, **kw))
