"""Expert parallelism: Switch-style top-1 mixture-of-experts with
``all_to_all`` token dispatch over an 'expert' mesh axis
(:class:`SwitchMoE`), and one chip's share of a top-k sigmoid-routed layer
with shared experts (:class:`RoutedExperts`, below it).

The reference has no MoE/expert parallelism. The TPU-native shape: one
expert FFN per mesh rank; each rank's local tokens are routed by a
(replicated) top-1 gate, packed into per-expert slots, exchanged with
TWO ``lax.all_to_all``s (dispatch and return — the canonical EP
collective pattern), processed by the rank-local expert, and combined
scaled by the gate probability.

K-FAC composes per-expert: the expert's Dense layers are ordinary
capture layers, so each rank's factors are computed from the token batch
ITS expert actually processed — owner-local (DP-KFAC-style) semantics
over the expert axis, with the data axis as the K-FAC world exactly as
in ``parallel/tp.py``. Padded (empty) slots are zero rows: they add
nothing to the G moments or the kernel block of A, but the bias-
augmentation column (ops.compute_a_dense appends ones) gives each empty
slot a unit contribution to A's bias-bias entry — so run EP K-FAC with
capacity sized near the actual load, or the bias coordinate of the
preconditioner is damped proportionally to the empty-slot fraction.

Capacity: ``capacity`` slots per (local rank -> expert) pair. With
``capacity = local token count`` no token can ever drop and the layer is
EXACTLY the dense computation ``y_t = p_t * FFN_{e_t}(x_t)`` (pinned by
tests/test_moe.py); smaller capacities drop overflow tokens to zero
output (standard Switch behavior, the memory/compute knob).
"""

from typing import Any, Optional, Tuple

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kfac_pytorch_tpu import nn as knn


class ExpertFFN(linen.Module):
    """One expert: Dense -> gelu -> Dense, both K-FAC capture layers."""
    d_model: int
    d_hidden: int

    @linen.compact
    def __call__(self, x):
        h = jax.nn.gelu(knn.Dense(self.d_hidden, name='w_in')(x))
        return knn.Dense(self.d_model, name='w_out')(h)


class SwitchMoE(linen.Module):
    """Top-1 routed MoE over ``axis`` (one expert per rank).

    Input ``[T_local, d_model]`` tokens (flatten batch x sequence first);
    output the same shape. The gate is a replicated plain Dense (not
    K-FAC-captured — its K-FAC treatment would need the router's
    load-balancing loss machinery; SGD-updated like LayerNorms). Returns
    ``(y, aux)`` with ``aux['gate_probs']`` for an optional
    load-balancing loss.

    ``axis=None`` degenerates to a single local expert (world=1 path,
    same convention as the rest of ``parallel/``).

    Gradient scaling (ADVICE r3): under the local-mean-loss convention
    (average over the DATA axis only — README "Loss conventions") the
    expert axis ALSO shards tokens, so the cross-axis gradient psum sums
    the ``ne`` per-shard means: gate and expert gradients (and their G
    factors) carry an extra factor of ``axis_size('expert')`` relative
    to a dense global-token-mean run. Consistent across mesh shapes
    (pinned by tests/test_moe.py), but a dense-tuned learning rate does
    NOT transfer — divide lr by the expert-axis size (or scale the loss
    by ``1/ne``) when porting hyperparameters from a dense run."""
    d_model: int
    d_hidden: int
    capacity: int
    axis: Optional[str] = 'expert'

    @linen.compact
    def __call__(self, x):
        T, d = x.shape
        n = 1 if self.axis is None else lax.axis_size(self.axis)
        C = self.capacity
        logits = linen.Dense(n, name='gate')(x)          # [T, n]
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)              # [T]
        p_top = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

        # slot position of each token within its expert's local buffer
        onehot = jax.nn.one_hot(expert, n, dtype=jnp.int32)   # [T, n]
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1         # [T, n]
        slot = pos.max(axis=-1)                               # [T]
        keep = slot < C                                       # overflow drops
        # dispatch tensor [T, n, C]: token t -> (expert e_t, slot)
        disp = (jax.nn.one_hot(expert, n)[:, :, None]
                * jax.nn.one_hot(jnp.where(keep, slot, 0), C)[:, None, :]
                * keep[:, None, None])
        xbuf = jnp.einsum('tec,td->ecd', disp, x)             # [n, C, d]

        if self.axis is not None:
            # dispatch all_to_all: rank r sends xbuf[e] to rank e and
            # receives every rank's buffer for ITS expert -> [n, C, d]
            # (n source ranks x C slots each)
            xbuf = lax.all_to_all(xbuf, self.axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        ybuf = ExpertFFN(self.d_model, self.d_hidden,
                         name='expert')(xbuf.reshape(-1, d))
        ybuf = ybuf.reshape(-1, C, d)
        if self.axis is not None:
            # return all_to_all: send each source rank its tokens back
            ybuf = lax.all_to_all(ybuf, self.axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        y = jnp.einsum('tec,ecd->td', disp, ybuf)
        return y * p_top[:, None], {'gate_probs': probs, 'dropped': ~keep}


@jax.custom_vjp
def gather_rows(src, index, valid, back_index, back_valid):
    """``out[i] = src[index[i]]`` where ``valid[i]``, else 0 — with the
    cotangent taken by a gather too: the caller knows the inverse map,
    ``back_index [n_src, m]`` / ``back_valid``: the (at most ``m``) rows
    of ``out`` that read row ``j`` of ``src``, so ``d src[j] = sum over m
    of d out[back_index[j, m]]``. Autodiff would scatter-add instead,
    which a TPU does a row at a time: the routing's two scatter-adds over
    32,768 buffer rows took 19 ms of a 246 ms step (PERF.md, PR 39)."""
    return jnp.where(valid[:, None], src[index], 0)


def _gather_rows_fwd(src, index, valid, back_index, back_valid):
    return gather_rows(src, index, valid, back_index, back_valid), (
        back_index, back_valid)


def _gather_rows_bwd(res, g):
    back_index, back_valid = res
    d_src = jnp.where(back_valid[..., None], g[back_index], 0).sum(axis=1)
    return d_src.astype(g.dtype), None, None, None, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


class SwiGLU(linen.Module):
    """``(silu(x W_gate) * x W_up) W_down``, no biases: three K-FAC
    capture layers on flat tokens ``[T, d]``."""
    width: int
    dtype: Optional[Any] = None

    @linen.compact
    def __call__(self, x):
        def dense(n, name):
            return knn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        h = jax.nn.silu(dense(self.width, 'gate')(x)) \
            * dense(self.width, 'up')(x)
        return dense(x.shape[-1], 'down')(h)


class BlockedSwiGLU(linen.Module):
    """:class:`SwiGLU` of ``width`` whose K-FAC factors are blocks of
    ``block``: ``gate_j`` / ``up_j`` the column blocks ``d -> block`` (all
    read ``x``: one input group, one ``A``; their ``G`` block-diagonal),
    ``down_j`` the row blocks ``block -> d`` over the slices of its input,
    summed (its ``A`` block-diagonal). It computes what the unsplit layer
    computes with the blocks' weights side by side; no factor is wider
    than ``max(d, block)``."""
    width: int
    block: int
    dtype: Optional[Any] = None

    @linen.compact
    def __call__(self, x):
        block = self.block
        if self.width % block:
            raise ValueError(f'{self.width} wide in blocks of {block}')

        def dense(n, name):
            return knn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        blocks = range(self.width // block)
        gate = [dense(block, f'gate_{j}')(x) for j in blocks]
        up = [dense(block, f'up_{j}')(x) for j in blocks]
        return sum(dense(x.shape[-1], f'down_{j}')(
            jax.nn.silu(gate[j]) * up[j]) for j in blocks)


class SwiGLUStack(linen.Module):
    """``E`` SwiGLU experts as three stacked leaves ``[E, d_in, d_out]``:
    one grouped product a projection over the row buffers ``[E, C, d]``
    (``nn.StackedDense``: a K-FAC factor pair for every expert)."""
    width: int
    dtype: Optional[Any] = None

    @linen.compact
    def __call__(self, xbuf, rows, loss_rows):
        def dense(n, name):
            return knn.StackedDense(n, dtype=self.dtype, name=name)
        h = jax.nn.silu(dense(self.width, 'gate')(xbuf, rows, loss_rows)) \
            * dense(self.width, 'up')(xbuf, rows, loss_rows)
        return dense(xbuf.shape[-1], 'down')(h, rows, loss_rows)


class RoutedExperts(linen.Module):
    """One chip's share of a sigmoid-routed top-k expert layer with shared
    experts (DeepSeek-V3's ``noaux_tc`` router with one group).

    ``x [T, d]`` flat tokens -> ``(y [T, d], counts)``. The router keeps
    its published ``n_routed`` outputs and chooses ``top_k`` of ``s + b``,
    ``s = sigmoid(x W_r)`` in float32 at ``highest`` (a top-k under
    bfloat16 flips near-ties), ``b`` the score-correction bias (a leaf
    that gets no gradient: it only enters the choice); the weights are
    ``s_i / sum over ALL chosen s`` (``norm_topk``) times ``scale``,
    wherever the chosen experts live. The layer is told which experts it
    holds (``expert_ids``) and computes ``sum over chosen i held here of
    w_i expert_i(x) + shared(x)``: what the absent experts would add is
    left out, and on one chip nothing is exchanged. The router is a
    first-order layer (its gradient comes through the weights).

    No token dropped: the rows routed to expert ``e`` are gathered into
    rows ``[0, n_e)`` of its buffer of ``capacity`` rows (static: a
    configuration states it; only ``capacity = T`` can never be short,
    since a token chooses an expert at most once) and go through
    :class:`SwiGLUStack`, one grouped product a projection; a row that
    finds no room is left out and counted in ``counts['dropped']``, which
    the model accumulates and a run holds at 0. ``counts['rows_max']`` /
    ``['rows_mean']``: rows routed to the fullest held expert / to a held
    expert on average, this step.

    Device scopes: ``moe.route`` (scores, top-k, weights), ``moe.dispatch``
    (ranks and the gather into buffers), ``moe.experts`` (the grouped
    products and their activation), ``moe.combine`` (each token's chosen
    rows gathered back and summed under its weights); the shared expert is
    three ordinary dense layers.
    """
    n_routed: int
    top_k: int
    expert_ids: Tuple[int, ...]
    expert_width: int
    shared_width: int
    capacity: int
    scale: float = 1.0
    norm_topk: bool = True
    dtype: Optional[Any] = None

    @linen.compact
    def __call__(self, x):
        T, d = x.shape
        E, C, k = len(self.expert_ids), self.capacity, self.top_k
        with jax.named_scope('moe.route'):
            logits = linen.Dense(
                self.n_routed, use_bias=False, dtype=jnp.float32,
                precision=lax.Precision.HIGHEST, name='router')(
                    x.astype(jnp.float32))
            bias = self.param('e_score_correction_bias',
                              linen.initializers.zeros_init(),
                              (self.n_routed,), jnp.float32)
            scores = jax.nn.sigmoid(logits)
            _, chosen = lax.top_k(scores + lax.stop_gradient(bias), k)
            w = jnp.take_along_axis(scores, chosen, axis=-1)     # [T, k]
            if self.norm_topk:
                w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
            w = w * self.scale
        with jax.named_scope('moe.dispatch'):
            # where each of the T*k choices goes: buffer of the e-th held
            # expert, row = how many earlier choices went there
            local = np.full(self.n_routed, E, np.int32)
            local[list(self.expert_ids)] = np.arange(E)
            where = jnp.asarray(local)[chosen.reshape(-1)]        # [T*k]
            onehot = (where[:, None] == jnp.arange(E)).astype(jnp.int32)
            rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
            routed = onehot.sum(axis=0)                           # [E]
            keep = (where < E) & (rank < C)
            slot = jnp.where(keep, where * C + rank, E * C)
            # the choice that fills each buffer row (E*C: none; a choice
            # with no room falls outside and is dropped by the scatter)
            choice = jnp.full((E * C,), T * k, jnp.int32).at[slot].set(
                jnp.arange(T * k, dtype=jnp.int32), mode='drop')
            filled = choice < T * k
            choice = jnp.where(filled, choice, 0)
            slot = jnp.where(keep, slot, 0)
            # both ways a gather: a buffer row reads one token, a token
            # is read by the (at most k) buffer rows of its choices
            xbuf = gather_rows(x, choice // k, filled, slot.reshape(T, k),
                               keep.reshape(T, k)).reshape(E, C, d)
            rows = jnp.minimum(routed, C).astype(jnp.float32)
        with jax.named_scope('moe.experts'):
            ybuf = SwiGLUStack(self.expert_width, dtype=self.dtype,
                               name='experts')(xbuf, rows, T)
        with jax.named_scope('moe.combine'):
            picked = gather_rows(ybuf.reshape(E * C, d), slot, keep,
                                 choice[:, None], filled[:, None])
            y = (picked.reshape(T, k, d).astype(jnp.float32)
                 * w[..., None]).sum(axis=1).astype(x.dtype)
        if self.shared_width:
            y = y + SwiGLU(self.shared_width, dtype=self.dtype,
                           name='shared')(x)
        routed = routed.astype(jnp.float32)
        counts = {'dropped': jnp.sum(routed - rows),
                  'rows_max': routed.max(), 'rows_mean': routed.mean()}
        return y, counts


def axis_rules(experts=('expert',)):
    """Mesh-plan rule marking these modules' factors expert-LOCAL state:
    each rank's expert is a different set of parameters, so its factor
    statistics must never reduce over the expert axis — zero factor
    bytes on that axis (the DP-KFAC owner-local trick), which
    ``MeshFactorPlan.comm_volume`` accounts and scripts/comm_count.py
    asserts against the HLO. Default matches :class:`SwitchMoE`'s
    rank-local ``ExpertFFN(name='expert')``.
    """
    from kfac_pytorch_tpu.meshplan import rules as _mr
    return (_mr.expert_local_rule(tuple(experts)),)
