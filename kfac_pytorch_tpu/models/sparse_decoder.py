"""Sparse decoder LM: latent attention (MLA) and sigmoid-routed experts with
shared experts, as DeepSeek-V3-shaped models publish them (defaults:
``kakaocorp/kanana-2-30b-a3b-instruct-2601``'s ``config.json``) — one chip's
share of it, under K-FAC.

Tokens are FLAT: ``__call__(tokens [B, L])`` flattens to ``T = B L`` rows
and every projection sees ``[T, d]``; only attention folds the rows back
into sequences. Returns float32 logits ``[B, L, V]``. The loss is the mean
over all ``T`` tokens of the next-token cross-entropy, the batch carrying
``L + 1`` ids a sequence (``input = ids[:, :-1]``, ``label = ids[:, 1:]``,
as ``examples/longcontext_lm.py`` cuts them), so no position is masked and
``T`` is the size of the loss's mean.

Per layer, ``x`` the residual stream, RMS norms in float32, no biases:

- attention, ``u = norm1(x)``: ``q = u W_q -> [T, h, 192]`` = ``q_nope``
  (128) | ``q_rope`` (64); ``u W_kva -> [T, 576]`` = ``c`` (512) |
  ``k_rope`` (64, one for all heads); ``kv = norm_kv(c) W_kvb -> [T, h,
  256]`` = ``k_nope`` (128) | ``v`` (128); rotary on ``q_rope`` and
  ``k_rope`` (interleaved pairs, positions within the sequence;
  ``LatentAttention(rotary=False)`` leaves both unrotated: kanana-2 rotates,
  ``models.hybrid_decoder``'s Kimi-Linear layers, ``mla_use_nope``, do
  not); causal ``softmax(q k' / sqrt(192)) v`` with the softmax in
  float32; ``x += . W_o``. No ``q_lora``.
- feed-forward, ``u = norm2(x)``: the first ``first_k_dense`` layers a
  SwiGLU of ``intermediate_size``; the others ``parallel.moe.RoutedExperts``.
- final norm, untied head.

*The share.* The model is told which heads (``head_ids``), which experts
(``expert_ids``) and how many rows of the vocabulary (``vocab_size``) it
holds; the router keeps its published width and top-k. What absent heads
and experts would add is left out and the partial result goes on; nothing
stands in for absent chips. Heads are alike, so ``head_ids`` only counts
them here and says which columns of the published projections these are.

*K-FAC, weight by weight.* Kronecker-factored, each with a factor pair of
its own: ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj``, ``o_proj``, the
dense and shared experts' ``gate`` / ``up`` / ``down`` (``nn.Dense`` on
flat tokens: ``A = a'a / T``, ``G = (T g)'(T g) / T`` — ``compute_a_dense``
takes no sequence mean of a 2-D input; BERT's sequence mean stays BERT's),
and every held routed expert's ``gate`` / ``up`` / ``down`` from the rows
routed to it (``nn.StackedDense``). First-order: the router (its gradient
comes through the top-k weights), ``e_score_correction_bias`` (no
gradient), the norms' scales, the embedding and the head (a vocabulary-sized
factor; ``kfac_enabled=False`` here, and a trainer passes
``exclude_vocabulary_size`` all the same).

The model counts, in the ``capture.COUNTERS`` collection (hand it to
``build_train_step(extra_mutable=...)``; the step's metrics then hold
them): ``moe/dropped`` (rows that found no room in an expert's buffer,
CUMULATIVE over the run and all layers), ``moe/rows_max`` and
``moe/rows_mean`` of the step (rows an expert held got: fullest expert of
any layer, mean over experts and layers).

*Shared with ``models.mixed_decoder``* (the decoder whose layers differ in
kind: window and full attention mixed) and ``models.hybrid_decoder`` (a
recurrence and latent attention mixed), named once, here: :class:`RMSNorm`,
:class:`MoECounters`, ``parallel.moe.SwiGLU`` / ``RoutedExperts``, flat
tokens, the ``L + 1``-id batch and the mean next-token loss; with the
hybrid decoder also :class:`LatentAttention`. This block's alone:
:func:`interleaved_rotary`, :class:`DecoderLayer` (two norms a block, one
kind of attention) and the fields of :class:`SparseDecoderConfig` marked
so.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen

from kfac_pytorch_tpu import capture
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.parallel.moe import RoutedExperts, SwiGLU


class RMSNorm(linen.Module):
    eps: float = 1e-6

    @linen.compact
    def __call__(self, x):
        scale = self.param('scale', linen.initializers.ones_init(),
                           (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + self.eps)
        return (xf * scale).astype(x.dtype)


def interleaved_rotary(x, positions, theta):
    """Rotate the pairs ``(x[..., 2i], x[..., 2i+1])`` of ``x [B, L, ...,
    D]`` by ``positions[l] * theta ** (-2i / D)``: the complex product
    ``(x_2i + j x_2i+1) exp(j angle)``, in float32."""
    d = x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freq        # [L, D/2]
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    re, im = xf[..., 0], xf[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    out = jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(linen.Module):
    """Multi-head latent attention without ``q_lora``, over the heads this
    chip holds. ``u [T, d]`` (``T = batch * length``) -> ``[T, d]``.
    ``rotary=False``: no positions (``mla_use_nope``), the ``qk_rope``
    dimensions of ``q`` and the shared ones of ``k`` enter the score as
    they are."""
    head_ids: Tuple[int, ...]
    qk_nope: int = 128
    qk_rope: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Optional[Any] = None
    rotary: bool = True

    @linen.compact
    def __call__(self, u, batch, length):
        h, nope, rope, vd = (len(self.head_ids), self.qk_nope, self.qk_rope,
                             self.v_dim)

        def dense(n, name):
            return knn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        q = dense(h * (nope + rope), 'q_proj')(u)
        ckv = dense(self.kv_rank + rope, 'kv_a_proj_with_mqa')(u)
        c = RMSNorm(self.eps, name='kv_a_layernorm')(ckv[:, :self.kv_rank])
        kv = dense(h * (nope + vd), 'kv_b_proj')(c)
        q = q.reshape(batch, length, h, nope + rope)
        kv = kv.reshape(batch, length, h, nope + vd)
        k_rope = ckv[:, self.kv_rank:].reshape(batch, length, rope)
        scale = 1.0 / np.sqrt(nope + rope)
        theta, rotary = self.rope_theta, self.rotary

        # the [B, h, L, L] scores are computed again in the backward pass,
        # not kept
        @jax.checkpoint
        def attend(q, kv, k_rope):
            with jax.named_scope('mla.attend'):
                pos = jnp.arange(length)
                q_rope = q[..., nope:]
                if rotary:
                    q_rope = interleaved_rotary(q_rope, pos, theta)
                    k_rope = interleaved_rotary(k_rope, pos, theta)
                s = (jnp.einsum('blhd,bmhd->bhlm', q[..., :nope],
                                kv[..., :nope])
                     + jnp.einsum('blhd,bmd->bhlm', q_rope, k_rope))
                s = s.astype(jnp.float32) * scale
                causal = pos[:, None] >= pos[None, :]
                s = jnp.where(causal, s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                return jnp.einsum('bhlm,bmhd->blhd', p, kv[..., nope:])

        out = attend(q, kv, k_rope).reshape(batch * length, h * vd)
        return dense(u.shape[-1], 'o_proj')(out)


@dataclasses.dataclass(frozen=True)
class SparseDecoderConfig:
    """Sizes as ``config.json`` publishes them (defaults: kanana-2-30b-a3b),
    and this chip's share: ``head_ids``, ``expert_ids``, ``vocab_size``.
    The latent block's alone: ``kv_rank``, ``qk_nope``, ``qk_rope``,
    ``v_dim``, ``head_ids`` and ``num_layers`` (every layer one kind); the
    other fields ``models.mixed_decoder.MixedDecoderConfig`` has too, under
    the same names and meanings."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    first_k_dense: int = 1
    intermediate_size: int = 6144
    expert_width: int = 768
    n_routed_experts: int = 128
    experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scale: float = 2.448
    norm_topk: bool = True
    kv_rank: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_dim: int = 128
    rope_theta: float = 1e6
    eps: float = 1e-6
    head_ids: Tuple[int, ...] = tuple(range(32))
    expert_ids: Tuple[int, ...] = tuple(range(128))
    #: rows of each held expert's buffer (static). An even router sends
    #: tokens * experts_per_tok / n_routed_experts rows a step; an
    #: untrained one is far from even (the benchmark's cell states the
    #: token count, the one size no routing can pass); `moe/dropped`
    #: says when a smaller one was short
    expert_capacity: int = 512
    dtype: Optional[Any] = None


class DecoderLayer(linen.Module):
    """``x [T, d]`` -> ``(x, counts)``: latent attention, then a dense
    SwiGLU (``dense``; ``counts`` None) or the routed experts."""
    cfg: SparseDecoderConfig
    dense: bool

    @linen.compact
    def __call__(self, x, batch, length):
        c = self.cfg
        u = RMSNorm(c.eps, name='input_layernorm')(x)
        x = x + LatentAttention(
            tuple(c.head_ids), c.qk_nope, c.qk_rope, c.v_dim, c.kv_rank,
            c.rope_theta, c.eps, c.dtype, name='self_attn')(u, batch, length)
        u = RMSNorm(c.eps, name='post_attention_layernorm')(x)
        if self.dense:
            return x + SwiGLU(c.intermediate_size, dtype=c.dtype,
                              name='mlp')(u), None
        y, counts = RoutedExperts(
            c.n_routed_experts, c.experts_per_tok, tuple(c.expert_ids),
            c.expert_width, c.n_shared_experts * c.expert_width,
            c.expert_capacity, c.routed_scale, c.norm_topk, c.dtype,
            name='mlp')(u)
        return x + y, counts


class MoECounters(linen.Module):
    """The model's counters (``capture.COUNTERS``): ``dropped`` adds up
    over steps and layers, ``rows_max`` / ``rows_mean`` are the step's."""

    @linen.compact
    def __call__(self, counts):
        if not self.is_mutable_collection(capture.COUNTERS):
            return      # evaluation: nothing is counted
        step = {k: jnp.stack([c[k] for c in counts]) for k in counts[0]}
        var = {name: self.variable(capture.COUNTERS, name,
                                   lambda: jnp.zeros((), jnp.float32))
               for name in ('dropped', 'rows_max', 'rows_mean')}
        var['dropped'].value += step['dropped'].sum()
        var['rows_max'].value = step['rows_max'].max()
        var['rows_mean'].value = step['rows_mean'].mean()


class SparseDecoderLM(linen.Module):
    cfg: SparseDecoderConfig = SparseDecoderConfig()

    @linen.compact
    def __call__(self, tokens, train=True):
        del train       # no dropout
        c = self.cfg
        batch, length = tokens.shape
        x = linen.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name='embed_tokens')(tokens.reshape(-1))
        counts = []
        for i in range(c.num_layers):
            x, n = DecoderLayer(c, i < c.first_k_dense,
                                name=f'layer_{i}')(x, batch, length)
            if n is not None:
                counts.append(n)
        if counts:
            MoECounters(name='moe')(counts)
        x = RMSNorm(c.eps, name='norm')(x)
        logits = knn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                           kfac_enabled=False, name='lm_head')(x)
        return logits.astype(jnp.float32).reshape(batch, length, -1)


def sparse_decoder_lm(vocab_size=128256, **kw):
    """Kanana-2 / DeepSeek-V3-shaped sparse decoder (see the module's
    docstring); ``head_ids``, ``expert_ids`` and ``vocab_size`` say which
    share of the published model this chip holds. ``kw``: fields of
    :class:`SparseDecoderConfig`."""
    return SparseDecoderLM(SparseDecoderConfig(vocab_size=vocab_size, **kw))
