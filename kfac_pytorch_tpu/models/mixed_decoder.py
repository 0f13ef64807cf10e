"""Sparse decoder LM whose layers differ in kind: window and full attention
mixed (grouped-query, gated, QK-normed), four norms a block, sigmoid-routed
experts with a shared expert, as ``arcee-ai/Trinity-Mini`` publishes them
(``config.json``, ``model_type: afmoe``; the defaults here) — one chip's
share of it, under K-FAC. Beside ``models.sparse_decoder`` (latent
attention, one kind of layer), of whose pieces it is built: ``RMSNorm``,
``MoECounters``, ``parallel.moe.SwiGLU`` / ``RoutedExperts``, flat tokens,
the ``L + 1``-id batch and the mean next-token loss.

Tokens are FLAT: ``__call__(tokens [B, L])`` flattens to ``T = B L`` rows;
only attention folds them back into sequences. Returns float32 logits
``[B, L, V]``. Per layer ``i``, ``x`` the residual stream, norms in float32,
no biases:

- attention, ``u = input_layernorm(x)``: ``q = u W_q -> [T, h, 128]``,
  ``k = u W_k -> [T, g, 128]``, ``v = u W_v -> [T, g, 128]``,
  ``z = u W_z -> [T, h 128]`` (the gate's projection, ``gate_proj``);
  ``q <- q_norm(q)``, ``k <- k_norm(k)``: RMS norms over the 128 of a head,
  one scale vector each a layer. Where ``layer_types[i]`` is
  ``sliding_attention``: rotary on ``q`` and ``k`` (halves, not interleaved
  pairs: ``x cos + rotate_half(x) sin``, positions within the sequence)
  and position ``l`` sees ``m`` with ``0 <= l - m < sliding_window``; where
  it is ``full_attention``: NO rotary, ``m <= l``. Query head ``j`` reads
  key/value head ``j // (h / g)`` (published counts), so the gradient of a
  key/value projection sums over the query heads of its group.
  ``softmax(q k' / sqrt(128)) v`` with the softmax in float32;
  ``a = (. * sigmoid(z)) W_o`` (the product in float32);
  ``x += post_attention_layernorm(a)``.
- feed-forward, ``u = pre_mlp_layernorm(x)``; ``y`` = a SwiGLU of
  ``intermediate_size`` in the first ``first_k_dense`` layers, else
  ``RoutedExperts`` (top-k of ``s + b``, weights ``s_i / (sum chosen s +
  1e-20) * route_scale``, + one shared SwiGLU);
  ``x += post_mlp_layernorm(y)``.
- the embedding's output times ``sqrt(hidden_size)`` (``mup_enabled``);
  final norm; untied head.

The sizes, the pattern, the window, the router and ``mup_enabled`` are the
configuration's keys. The gate on the attention's output, the query/key
norms, rotary on window layers only, the four norms and where the
``sqrt(d)`` goes are the published ``afmoe`` block (Hugging Face
``transformers``, ``models/afmoe/modeling_afmoe.py``; nothing here imports
it).

*The share.* The model is told its query heads (``q_head_ids``), its
key/value heads (``kv_head_ids``; every held query head's group has to be
held, and the groups held alike), its experts and its rows of the
vocabulary. What absent heads and experts would add is left out; nothing
stands in for absent chips.

*K-FAC, weight by weight.* Kronecker-factored, a ``G`` of its own each and
the ``A`` of their input group (layers called on one array have ONE ``A``,
found during the recorded trace: ``capture.input_groups``): ``q_proj`` /
``k_proj`` / ``v_proj`` / ``gate_proj`` (one group), ``o_proj``, the dense
and shared ``gate`` / ``up`` (one group) / ``down``, every held expert's
``gate`` / ``up`` (one group an expert, from the rows routed to it) /
``down``. First-order: the router, ``e_score_correction_bias`` (no
gradient), every norm scale with the query/key norms', the embedding and
the head.

Device scopes: ``attn.window`` / ``attn.full`` round the two kinds' scores,
softmax and values (computed again in the backward pass), the ``moe.*``
scopes of ``RoutedExperts``; the counters of ``MoECounters``.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen

from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.models.sparse_decoder import MoECounters, RMSNorm
from kfac_pytorch_tpu.parallel.moe import RoutedExperts, SwiGLU

SLIDING, FULL = 'sliding_attention', 'full_attention'


def held_layer_types(num_layers, first_k_dense=1, period=4):
    """The kinds of the ``num_layers`` layers a cut in depth holds: the
    leading dense ones window layers, then whole periods of ``period - 1``
    window layers and one full layer (Trinity-Mini's published layers 1
    and 4-7 for 5). Uncut (``first_k_dense=0``) the published pattern."""
    rest = [FULL if (i + 1) % period == 0 else SLIDING
            for i in range(num_layers - first_k_dense)]
    return (SLIDING,) * first_k_dense + tuple(rest)


def half_rotary(x, positions, theta):
    """``x cos + rotate_half(x) sin`` over the last axis of ``x [B, L, ...,
    D]``: the halves ``(x[..., i], x[..., i + D/2])`` rotated by
    ``positions[l] * theta ** (-2i / D)``, in float32."""
    d = x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freq        # [L, D/2]
    angle = jnp.concatenate([angle, angle], axis=-1)
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d,))
    xf = x.astype(jnp.float32)
    rotated = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return (xf * jnp.cos(angle) + rotated * jnp.sin(angle)).astype(x.dtype)


class GatedGroupedAttention(linen.Module):
    """Grouped-query attention with RMS-normed queries and keys and a
    sigmoid gate on its output, over the heads this chip holds; a window
    layer (``window``: rotary, ``0 <= l - m < window``) or a full one
    (``window=None``: no rotary, causal). ``u [T, d]`` -> ``[T, d]``."""
    q_head_ids: Tuple[int, ...]
    kv_head_ids: Tuple[int, ...]
    group_size: int             # published query heads a key/value head
    head_dim: int = 128
    window: Optional[int] = None
    rope_theta: float = 1e4
    eps: float = 1e-5
    dtype: Optional[Any] = None

    @linen.compact
    def __call__(self, u, batch, length):
        h, g, hd = len(self.q_head_ids), len(self.kv_head_ids), self.head_dim
        reads = tuple(q // self.group_size for q in self.q_head_ids)
        if reads != tuple(np.repeat(self.kv_head_ids, h // g)):
            raise ValueError(
                f'query heads {self.q_head_ids} read key/value heads '
                f'{reads}; held are {self.kv_head_ids}, each for '
                f'{h // g} query heads side by side')

        def dense(n, name):
            return knn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        q = dense(h * hd, 'q_proj')(u).reshape(batch, length, g, h // g, hd)
        k = dense(g * hd, 'k_proj')(u).reshape(batch, length, g, hd)
        v = dense(g * hd, 'v_proj')(u).reshape(batch, length, g, hd)
        z = dense(h * hd, 'gate_proj')(u)
        q = RMSNorm(self.eps, name='q_norm')(q)
        k = RMSNorm(self.eps, name='k_norm')(k)
        window, theta = self.window, self.rope_theta

        # the [B, h, L, L] scores are computed again in the backward pass,
        # not kept
        @jax.checkpoint
        def attend(q, k, v):
            with jax.named_scope('attn.full' if window is None
                                 else 'attn.window'):
                pos = jnp.arange(length)
                seen = pos[:, None] >= pos[None, :]
                if window is not None:
                    q, k = (half_rotary(t, pos, theta) for t in (q, k))
                    seen = seen & (pos[:, None] - pos[None, :] < window)
                s = jnp.einsum('blgrd,bmgd->bgrlm', q, k)
                s = s.astype(jnp.float32) / np.sqrt(hd)
                s = jnp.where(seen, s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                return jnp.einsum('bgrlm,bmgd->blgrd', p, v)

        out = attend(q, k, v).reshape(batch * length, h * hd)
        out = (out.astype(jnp.float32)
               * jax.nn.sigmoid(z.astype(jnp.float32))).astype(out.dtype)
        return dense(u.shape[-1], 'o_proj')(out)


@dataclasses.dataclass(frozen=True)
class MixedDecoderConfig:
    """Sizes as ``config.json`` publishes them (defaults: Trinity-Mini),
    and this chip's share: ``q_head_ids``, ``kv_head_ids``, ``expert_ids``,
    ``vocab_size``, and ``layer_types`` of the layers it holds."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = held_layer_types(32, 0)
    first_k_dense: int = 2
    intermediate_size: int = 6144
    expert_width: int = 1024
    n_routed_experts: int = 128
    experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scale: float = 2.826
    norm_topk: bool = True
    head_dim: int = 128
    num_attention_heads: int = 32       # published: the groups' size
    num_key_value_heads: int = 4
    sliding_window: int = 2048
    rope_theta: float = 1e4
    eps: float = 1e-5
    mup_enabled: bool = True
    q_head_ids: Tuple[int, ...] = tuple(range(32))
    kv_head_ids: Tuple[int, ...] = tuple(range(4))
    expert_ids: Tuple[int, ...] = tuple(range(128))
    #: rows of each held expert's buffer: ``SparseDecoderConfig``'s
    expert_capacity: int = 512
    dtype: Optional[Any] = None


class MixedDecoderLayer(linen.Module):
    """``x [T, d]`` -> ``(x, counts)``: window or full attention (``kind``),
    then a dense SwiGLU (``dense``; ``counts`` None) or the routed experts;
    a norm before and after each."""
    cfg: MixedDecoderConfig
    kind: str
    dense: bool

    @linen.compact
    def __call__(self, x, batch, length):
        c = self.cfg

        def norm(name):
            return RMSNorm(c.eps, name=name)
        a = GatedGroupedAttention(
            tuple(c.q_head_ids), tuple(c.kv_head_ids),
            c.num_attention_heads // c.num_key_value_heads, c.head_dim,
            c.sliding_window if self.kind == SLIDING else None,
            c.rope_theta, c.eps, c.dtype, name='self_attn')(
                norm('input_layernorm')(x), batch, length)
        x = x + norm('post_attention_layernorm')(a)
        u = norm('pre_mlp_layernorm')(x)
        if self.dense:
            y, counts = SwiGLU(c.intermediate_size, dtype=c.dtype,
                               name='mlp')(u), None
        else:
            y, counts = RoutedExperts(
                c.n_routed_experts, c.experts_per_tok, tuple(c.expert_ids),
                c.expert_width, c.n_shared_experts * c.expert_width,
                c.expert_capacity, c.routed_scale, c.norm_topk, c.dtype,
                name='mlp')(u)
        return x + norm('post_mlp_layernorm')(y), counts


class MixedDecoderLM(linen.Module):
    cfg: MixedDecoderConfig = MixedDecoderConfig()

    @linen.compact
    def __call__(self, tokens, train=True):
        del train       # no dropout
        c = self.cfg
        batch, length = tokens.shape
        x = linen.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                        name='embed_tokens')(tokens.reshape(-1))
        if c.mup_enabled:
            x = x * jnp.asarray(np.sqrt(c.hidden_size), x.dtype)
        counts = []
        for i, kind in enumerate(c.layer_types):
            x, n = MixedDecoderLayer(c, kind, i < c.first_k_dense,
                                     name=f'layer_{i}')(x, batch, length)
            if n is not None:
                counts.append(n)
        if counts:
            MoECounters(name='moe')(counts)
        x = RMSNorm(c.eps, name='norm')(x)
        logits = knn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype,
                           kfac_enabled=False, name='lm_head')(x)
        return logits.astype(jnp.float32).reshape(batch, length, -1)


def mixed_decoder_lm(vocab_size=200192, **kw):
    """Trinity-Mini-shaped sparse decoder with window and full attention
    mixed (see the module's docstring); ``q_head_ids``, ``kv_head_ids``,
    ``expert_ids``, ``vocab_size`` and ``layer_types`` say which share of
    the published model this chip holds. ``kw``: fields of
    :class:`MixedDecoderConfig`."""
    return MixedDecoderLM(MixedDecoderConfig(vocab_size=vocab_size, **kw))
