"""Traced K-FAC step phases over the planned stacked-bucket layout.

Each function here is the XLA-uniform counterpart of one phase of the
reference pipeline (kfac_preconditioner_base.py:151-230):

  compute_layer_stats    ≙ _compute_factors   (ComputeA/ComputeG per layer)
  update_factors         ≙ running-avg update + _communicate_factors
                           (pmean for MPD; none for DP — inv_dp.py:93-95)
  compute_decomposition  ≙ _compute_inverse   (batched eigh / Cholesky on
                           the local shard = the distributed computation)
  gather_decomposition   ≙ _communicate_inverse (all-gather rows ≙
                           per-owner broadcast, eigen.py:122-134)
  compute_pred_*         ≙ _compute_pred (+ _communicate_pred for the
                           owner-computes path, inv.py:164-175)
  preconditioned_grads   ≙ _update_grad_in_place incl. KL clip
                           (inv.py:188-217)

All functions are written per-device: under a mesh they run inside
shard_map with the factor/decomposition state sharded on axis 0 (rows are
device-major, see plan.py); with ``axis_name=None`` they degenerate to the
world=1 path with zero communication.

Deviation from the reference: ``_add_value_to_diagonal`` there mutates the
stored running-average factor in place (inv.py:106-129), so damping
accumulates into the factor state across inverse updates. Here damping is
applied to a temporary — the mathematically intended semantics.
"""

import contextlib
import functools
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kfac_pytorch_tpu import capture, ops
from kfac_pytorch_tpu.parallel import collectives as coll

_PRED_PRECISION = lax.Precision.HIGHEST


def _key(bdim):
    return str(bdim)


# ---------------------------------------------------------------------------
# Grad matrix <-> param pytree
# ---------------------------------------------------------------------------

def layer_grad_matrix(meta, grads):
    """Matrix-form gradient [out_dim, in_dim(+bias col)] in fp32.

    Parity: ``_get_grad`` (reference: kfac_preconditioner_inv.py:145-154):
    conv kernels flatten to [out, kh*kw*c_in] (HWIO flatten matches the
    patch feature order, see ops/factors.py), bias appended as a column.
    """
    sub = capture.get_path(grads, meta.path)
    k = sub['kernel']
    if meta.kind == 'stacked':
        gm = k[meta.index].T
    elif meta.kind == 'dense':
        gm = k.T
    else:
        kh, kw, cin, cout = meta.kernel_shape
        gm = k.reshape(kh * kw * cin, cout).T
    gm = gm.astype(jnp.float32)
    if meta.use_bias:
        gm = jnp.concatenate([gm, sub['bias'].astype(jnp.float32)[:, None]],
                             axis=1)
    return gm


def write_grad_matrix(meta, grads, mat):
    """Inverse of :func:`layer_grad_matrix`: scatter a preconditioned
    matrix back into the grads pytree (reference:
    kfac_preconditioner_inv.py:178-186)."""
    sub = dict(capture.get_path(grads, meta.path))
    if meta.use_bias:
        w, b = mat[:, :-1], mat[:, -1]
        sub['bias'] = b.astype(sub['bias'].dtype)
    else:
        w = mat
    if meta.kind == 'stacked':
        kernel = sub['kernel'].at[meta.index].set(
            w.T.astype(sub['kernel'].dtype))
    elif meta.kind == 'dense':
        kernel = w.T
    else:
        kh, kw, cin, cout = meta.kernel_shape
        kernel = w.T.reshape(kh, kw, cin, cout)
    sub['kernel'] = kernel.astype(sub['kernel'].dtype)
    return capture.set_path(grads, meta.path, sub)


def _pad_mat(mat, dg, da):
    out, inn = mat.shape
    return jnp.pad(mat, ((0, dg - out), (0, da - inn)))


# ---------------------------------------------------------------------------
# Phase 1: factor statistics
# ---------------------------------------------------------------------------

def _capture_backend(capture_impl):
    """Resolve the capture knob to (module, kwargs) — 'pallas' routes
    through the fused kernels (ops/pallas_capture.py, imported lazily so
    the reference path never pays the Pallas import), anything else
    stays on the ops/factors.py reference."""
    if capture_impl == 'pallas':
        from kfac_pytorch_tpu.ops import pallas_capture
        return pallas_capture, {
            'interpret': pallas_capture.interpret_default()}
    return ops, {}


def compute_layer_stats(plan, acts, gs, batch_averaged=True,
                        capture_impl=None, stacks=None):
    """Per-layer Kronecker factor statistics from captured (a, g).

    ``stacks``: a dict to fill with ``{path: ((A, G) [E, d, d], n [E])}``,
    the batched statistics of every stacked leaf and the rows its slices
    got (for :func:`update_factor_rows`).

    A layer that reads another's ``A`` factor (``plan.a_leaders``: the
    layers of an input group) gets that layer's ``A`` statistic, the same
    array: it is computed once.

    ``capture_impl='pallas'`` computes every statistic with the fused
    Pallas kernels (interpreter mode off-TPU) — numerically pinned to
    the reference by tests/test_pallas_capture.py."""
    back, kw = _capture_backend(capture_impl)
    a_list, g_list = [], []
    if stacks is None:
        stacks = {}     # a stacked leaf's statistics: one batched product
    leaders = plan.a_leaders()
    with (back.routing_report() if back is not ops
          else contextlib.nullcontext()):
        for i, meta in enumerate(plan.metas):
            a = capture.layer_act(acts, meta)
            g = capture.layer_g(gs, meta)
            lead = plan.metas[leaders[i]]
            if meta.kind == 'stacked':
                if meta.path not in stacks:
                    sown = capture.get_path(acts, meta.path)
                    # a leaf whose slices read another leaf's slots takes
                    # that leaf's batched ``A`` (its leader is before it)
                    stacks[meta.path] = (_stacked_stats(
                        a, g, sown['n'], sown['t'],
                        None if lead is meta else stacks[lead.path][0][0]),
                        sown['n'])
                a_list.append(stacks[meta.path][0][0][meta.index])
                g_list.append(stacks[meta.path][0][1][meta.index])
            elif meta.kind == 'dense':
                a_list.append(
                    back.compute_a_dense(a, meta.use_bias, **kw)
                    if lead is meta else a_list[leaders[i]])
                g_list.append(back.compute_g_dense(g, batch_averaged,
                                                   **kw))
            else:
                a_list.append(back.compute_a_conv(
                    a, meta.kernel_size, meta.strides, meta.padding,
                    meta.use_bias, **kw))
                g_list.append(back.compute_g_conv(g, batch_averaged, **kw))
    return a_list, g_list


def _stacked_stats(a, g, n, t, a_stat=None):
    """(A, G) ``[E, d, d]`` of the ``E`` slices of a stacked leaf
    (``nn.StackedDense``) from its row buffers ``a [E, C, d_in]`` and
    cotangents ``g [E, C, d_out]``, ``n [E]`` rows each (the rest of a
    buffer is zero on both sides) and ``t`` the size of the loss's mean:
    ``A = a'a / max(n, 1)``, ``G = (t g)'(t g) / max(n, 1)``. Not
    ``compute_g_dense``'s scaling by the row count: the cotangents carry
    ``1 / t``, whatever part of the ``t`` rows came here. ``a_stat``: the
    ``A`` of another leaf that read the same buffers, taken as it is."""
    with jax.named_scope('kfac.expert_stats'):
        n = jnp.maximum(n, 1.0)[:, None, None]
        gram = functools.partial(jnp.einsum, 'ecd,ecf->edf',
                                 preferred_element_type=jnp.float32)
        return (gram(a, a) / n if a_stat is None else a_stat,
                gram(g, g) * (t * t / n))


def rows_seen(plan, acts):
    """``{bucket key: [n_rows] bool}``: False for the factor rows of a
    stacked slice no row came to this step, which keep their running
    averages (:func:`update_factors`); None where the plan has no
    stacked layer."""
    if not any(m.kind == 'stacked' for m in plan.metas):
        return None
    out = {}
    for bdim in plan.bucket_dims:
        flags = []
        for s in plan.buckets[bdim].factor_slots:
            meta = None if s is None else plan.metas[s.layer_idx]
            if meta is None or meta.kind != 'stacked':
                flags.append(jnp.ones((), bool))
            else:
                flags.append(
                    capture.get_path(acts, meta.path)['n'][meta.index] > 0)
        out[_key(bdim)] = jnp.stack(flags)
    return out


def stats_finite(plan, a_list, g_list, stacks):
    """``([L] bool, [L] bool)``: is layer ``i``'s ``A`` / ``G`` statistic
    (:func:`compute_layer_stats`) free of NaN and Inf, read from its
    diagonal (``ops.tile_diagonal``: 512 / d of its bytes). The health
    guard's witness for the captured tensors, which it no longer reads: a
    statistic is a Gram product of what was captured, so each captured
    element it reads is squared into a diagonal entry, where nothing can
    cancel it. A NaN or an Inf in a captured activation or output-gradient
    therefore shows on that layer's diagonal, exactly; so does a finite
    value whose square overflows float32 (above about 1.8e19), which the
    captured tensor's own ``isfinite`` let through. An off-diagonal sum is
    bounded by the larger of its two diagonal entries (Cauchy-Schwarz), so
    with finite inputs it overflows only where one of those does, to the
    rounding of the last addition (and a row that did is still caught as
    it is written, :func:`settle_factor_rows`). What the diagonal cannot
    see is a captured element that no statistic reads (the odd pixels
    under a 1x1 convolution of stride 2): such a value reaches no factor.
    A stacked leaf's slices are read once, from its batched statistics."""
    # 512-wide tiles: a statistic a layer and side is many small matrices,
    # and at 128 their tiles were a fifth of the update program's kernels
    ok = functools.partial(ops.diagonal_finite, tile=512)
    seen = {}       # an ``A`` that several layers read is read once

    def ok_once(stat):
        if id(stat) not in seen:
            seen[id(stat)] = ok(stat)
        return seen[id(stat)]
    leaf_ok = {path: (ok_once(both[0]), ok(both[1]))
               for path, (both, _) in stacks.items()}
    ok_a, ok_g = [], []
    for i, meta in enumerate(plan.metas):
        if meta.kind == 'stacked':
            ok_a.append(leaf_ok[meta.path][0][meta.index])
            ok_g.append(leaf_ok[meta.path][1][meta.index])
        else:
            ok_a.append(ok_once(a_list[i]))
            ok_g.append(ok(g_list[i]))
    return jnp.stack(ok_a), jnp.stack(ok_g)


class LayerStats(NamedTuple):
    """What :func:`compute_layer_stats` makes of one batch, with
    :func:`stats_finite`'s flags: a pure function of the captured tensors,
    so the trainer can make it before the health guard's ``cond`` and hand
    it to ``KFAC.step`` inside."""
    a_list: list
    g_list: list
    stacks: dict
    ok_a: jnp.ndarray
    ok_g: jnp.ndarray


def layer_stats(plan, acts, gs, batch_averaged=True, capture_impl=None):
    """:func:`compute_layer_stats` and :func:`stats_finite` in one."""
    stacks = {}
    a_list, g_list = compute_layer_stats(plan, acts, gs, batch_averaged,
                                         capture_impl, stacks)
    return LayerStats(a_list, g_list, stacks,
                      *stats_finite(plan, a_list, g_list, stacks))


def rows_ok(plan, stats):
    """``{bucket key: [n_rows] bool}``: :func:`stats_finite`'s flag of the
    statistic each factor row averages in (True for a dummy row, whose
    statistic is the identity)."""
    n = len(plan.metas)
    flat = jnp.concatenate([stats.ok_a, stats.ok_g, jnp.ones((1,), bool)])
    return {_key(bdim): flat[np.asarray(
        [2 * n if s is None else s.layer_idx + (0 if s.side == 'A' else n)
         for s in plan.buckets[bdim].factor_slots])]
        for bdim in plan.bucket_dims}


@functools.partial(jax.jit, static_argnames='guard')
def settle_factor_rows(fresh, old, take, guard, commit=None):
    """New running averages ``fresh [k, D, D]`` as they may be kept, in the
    pass that writes them.

    ``take``: ``[k]`` flags known before that pass (the row was seen, its
    statistic is finite, the batch is committed); a row with one of them
    False keeps ``old``. With ``guard`` the row's own flag comes out of the
    same pass (one fusion writes the rows and reduces ``isfinite`` over
    them: ``tests/test_chip_compile.py``), and a row that is still not
    finite is one whose STORED value was corrupt (silent data corruption):
    it starts again from the identity, its init() value, and re-accumulates
    from fresh statistics rather than staying NaN for the rest of the run
    (:func:`ops.heal_rows`: only such rows are written again). A statistic
    that is not finite never gets this far (its ``take`` flag) and a
    weighted mean of two finite values is finite, so the two together are
    "the last good row, or the identity where that is corrupt too".
    ``commit`` False: nothing is healed either."""
    if take:
        keep = functools.reduce(jnp.logical_and, take)
        fresh = jnp.where(keep[:, None, None], fresh, old)
    if not guard:
        return fresh
    bad = jnp.logical_not(ops.rows_finite(fresh))
    if commit is not None:
        bad = jnp.logical_and(bad, commit)
    eye = jnp.eye(fresh.shape[-1], dtype=fresh.dtype)
    return ops.heal_rows(fresh, bad, lambda r, _: eye)[0]


def rowwise_buckets(plan, stats_reduce):
    """Keys of the buckets whose running averages are updated row by row,
    over the stored rows (:func:`update_factor_rows`): the buckets too
    large to decompose whole (:func:`tiled_buckets`), whose stacked
    statistics and averaged copy would be as large again, where one
    device holds every row and takes the statistics from its own batch."""
    if stats_reduce != 'local' or plan.num_devices != 1:
        return ()
    return tiled_buckets(plan)


def _row_stat(plan, bdim, slot, a_list, g_list):
    if slot is None:
        return jnp.eye(bdim, dtype=jnp.float32)
    mat = a_list[slot.layer_idx] if slot.side == 'A' \
        else g_list[slot.layer_idx]
    return ops.identity_pad(mat, bdim)


def _stat_runs(plan, bdim, a_list, g_list, stacks):
    """The bucket's rows as runs ``(first row, statistics [k, D, D], seen
    [k] or None)`` in row order: a stacked leaf's slices that lie side by
    side, in their own order, are one run read from the leaf's batched
    statistics where they lie (:func:`compute_layer_stats`' ``stacks``);
    every other row is a run of one."""
    slots = plan.buckets[bdim].factor_slots
    runs, r = [], 0
    while r < len(slots):
        slot = slots[r]
        meta = None if slot is None else plan.metas[slot.layer_idx]
        if meta is None or meta.kind != 'stacked':
            runs.append((r, _row_stat(plan, bdim, slot, a_list, g_list)[None],
                         None))
            r += 1
            continue
        both, n = stacks[meta.path]
        k = 1
        while (r + k < len(slots) and slots[r + k] is not None
               and slots[r + k].side == slot.side
               and plan.metas[slots[r + k].layer_idx].path == meta.path
               and plan.metas[slots[r + k].layer_idx].index
               == meta.index + k):
            k += 1
        stat = both[0 if slot.side == 'A' else 1]
        stat = stat[meta.index:meta.index + k]
        runs.append((r, ops.identity_pad(stat, bdim),
                     n[meta.index:meta.index + k] > 0))
        r += k
    return runs


def update_factor_rows(plan, bdim, current, a_list, g_list, stacks,
                       factor_decay, guard, commit=None, stat_ok=None):
    """One bucket's running averages, a run of rows at a time written
    over ``current`` (a donated state's buffer is the result's; the
    bucket's statistics are never stacked): what :func:`stack_stats` and
    :func:`update_factors` with :func:`rows_seen`'s flags and the same
    ``guard``, ``stat_ok`` and ``commit`` do to a whole bucket, by the same
    :func:`settle_factor_rows`."""
    out = current
    for first, stat, came in _stat_runs(plan, bdim, a_list, g_list, stacks):
        k = stat.shape[0]
        old = lax.dynamic_slice_in_dim(out, first, k, axis=0)
        take = [] if came is None else [came]
        if guard:
            take.append(ops.rows_finite(stat) if stat_ok is None
                        else stat_ok[first:first + k])
        if commit is not None:
            take.append(jnp.broadcast_to(commit, (k,)))
        # (settled before it is written: the repair loop carries the run,
        # never the stored bucket)
        new = settle_factor_rows(
            ops.update_running_avg(stat, old, factor_decay), old, take,
            guard, commit)
        out = lax.dynamic_update_slice_in_dim(out, new, first, axis=0)
    return out


def stack_stats(plan, a_list, g_list, skip=()):
    """Scatter per-layer stats into the global stacked-bucket layout
    (identity padding; dummy rows are identity). ``skip``: keys of the
    buckets updated row by row instead (:func:`rowwise_buckets`)."""
    out = {}
    for bdim in plan.bucket_dims:
        if _key(bdim) in skip:
            continue
        b = plan.buckets[bdim]
        out[_key(bdim)] = jnp.stack([
            _row_stat(plan, bdim, s, a_list, g_list) for s in b.factor_slots])
    return out


def update_factors_fused(plan, factors_local, acts, gs, batch_averaged,
                         factor_decay):
    """World=1 local-stats capture with the EMA folded into the kernels.

    The fully fused form of compute_layer_stats -> stack_stats ->
    update_factors for the case with no factor communication and no
    row slicing (``stats_reduce='local'``, ``plan.num_devices == 1``):
    each real factor row is ONE Pallas kernel launch whose accumulator
    epilogue emits ``update_running_avg(stat, current, factor_decay)``
    directly — the stacked ``[rows, D, D]`` statistics tensor is never
    built. The statistic entering the EMA is bit-identical to the
    unfused capture; identity padding and dummy rows run the exact
    unfused arithmetic (``update_running_avg`` against
    ``identity_pad``'s eye padding / the eye dummy); the fused EMA
    combine itself is within one fp32 FMA rounding of the unfused
    program (see pallas_capture's numerical contract) and
    deterministic across steps. Returns the new factors dict.
    """
    from kfac_pytorch_tpu.ops import pallas_capture as pc
    with pc.routing_report():
        return _update_factors_fused(pc, plan, factors_local, acts, gs,
                                     batch_averaged, factor_decay)


def _update_factors_fused(pc, plan, factors_local, acts, gs, batch_averaged,
                          factor_decay):
    kw = {'interpret': pc.interpret_default()}
    new = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        b = plan.buckets[bdim]
        rows = []
        for r, s in enumerate(b.factor_slots):
            cur = factors_local[key][r]
            if s is None:
                rows.append(ops.update_running_avg(
                    jnp.eye(bdim, dtype=jnp.float32), cur, factor_decay))
                continue
            meta = plan.metas[s.layer_idx]
            if meta.kind == 'stacked':
                raise NotImplementedError(
                    f'{meta.name}: fused capture of a stacked layer')
            f = meta.in_dim if s.side == 'A' else meta.out_dim
            ema = (cur[:f, :f], factor_decay)
            if s.side == 'A':
                a = capture.layer_act(acts, meta)
                if meta.kind == 'dense':
                    stat = pc.compute_a_dense(a, meta.use_bias, ema=ema,
                                              **kw)
                else:
                    stat = pc.compute_a_conv(
                        a, meta.kernel_size, meta.strides, meta.padding,
                        meta.use_bias, ema=ema, **kw)
            else:
                g = capture.layer_g(gs, meta)
                if meta.kind == 'dense':
                    stat = pc.compute_g_dense(g, batch_averaged, ema=ema,
                                              **kw)
                else:
                    stat = pc.compute_g_conv(g, batch_averaged, ema=ema,
                                             **kw)
            if f == bdim:
                rows.append(stat)
            else:
                # pad region: EMA against identity_pad's eye padding —
                # elementwise identical to the unfused stacked update
                tmpl = ops.identity_pad(jnp.zeros((f, f), jnp.float32),
                                        bdim)
                row = ops.update_running_avg(tmpl, cur, factor_decay)
                rows.append(row.at[:f, :f].set(stat))
        new[key] = jnp.stack(rows)
    return new


def update_factors(plan, factors_local, stats_stacked, factor_decay,
                   stats_reduce, axis_name, comm_precision='fp32',
                   comm_err=None, capture_impl=None, extra_reduce=(),
                   seen=None, guard=False, stat_ok=None, commit=None):
    """Running-average update of the local factor shard.

    ``stats_reduce='pmean'``: MPD semantics — factors are the global-batch
    average (reference allreduce, inv.py:94-103).
    ``stats_reduce='local'``: DP semantics — the owner's local-batch stats
    only, no factor communication at all (reference: inv_dp.py:60-95).

    ``comm_precision``: wire dtype of the stats reduce
    (collectives.WIRE_DTYPES). The reduce is a REDUCE-SCATTER
    (:func:`collectives.pmean_scatter_ef` — each device consumes only
    its own device-major rows, so nothing is gathered back); lossy modes
    fold the quantization error into ``comm_err`` (the per-device
    error-feedback residual, keyed like the stats stack) — the residual
    re-enters the next reduce, so every device's time-averaged
    contribution to the factor EMAs stays unbiased. Returns
    ``(new_factors, new_comm_err)``; ``comm_err`` passes through
    untouched on the fp32 / local / world=1 paths.

    ``capture_impl='pallas'`` fuses the lossy reduce's wire-quantize +
    error-feedback prep into one Pallas pass
    (:func:`pallas_capture.ef_quantize`) — same wire bytes, one fewer
    elementwise sweep over the stacked stats.

    ``extra_reduce``: ``MeshFactorPlan.extra_reduce()`` tables —
    ``((tensor_axis, {bucket_key: int32 global rows}), ...)``. The
    marked rows are factor stats REPLICATED across that tensor axis
    (column-A / row-G, see meshplan.rules), pmean-reduced over it BEFORE
    the data-axis reduce/slice: mathematically the identity on
    synchronized ranks (exact-mean of identical f32 values), drift
    repair otherwise. The tensor wire carries no residual of its own —
    under a lossy ``comm_precision`` the cast error folds into the
    data-axis EF residual and re-enters the next data reduce; DP
    variants (``comm_err=None``) run the tensor wire EF-free.

    ``seen``: :func:`rows_seen`'s flags; a row whose flag is False keeps
    its running average (an expert no row was routed to).

    ``guard``: the health guard's screen, :func:`settle_factor_rows`. A
    row whose statistic is not finite keeps its average: by ``stat_ok``
    (:func:`rows_ok`) where the statistics are this device's own, by a read
    of the reduced rows where they come off the wire (``pmean``: another
    device's share or the error-feedback residual can be at fault, so the
    flags made before the reduce do not cover them). ``commit`` (a traced
    bool): where False every row stays as it was.
    """
    new = {}
    new_err = None if comm_err is None else dict(comm_err)
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        if key not in stats_stacked:
            continue    # updated row by row (rowwise_buckets)
        b = plan.buckets[bdim]
        stats = stats_stacked[key]
        err_in = None if comm_err is None else comm_err[key]
        for t_axis, rows_by_key in (extra_reduce or ()):
            rows = rows_by_key.get(key)
            if rows is None or len(rows) == 0:
                continue
            idx = jnp.asarray(rows)
            sub = jnp.take(stats, idx, axis=0)
            with jax.named_scope('kfac.CommunicateFactor'):
                red = coll.pmean_wire(sub, t_axis, comm_precision)
            if err_in is not None and comm_precision != 'fp32':
                err_in = err_in.at[idx].add(sub - red)
            stats = stats.at[idx].set(red)
        if stats_reduce == 'pmean':
            # only the reduce is CommunicateFactor — the EMA below is
            # compute, so a trace's attribution matches the reference's
            # exclude-parts phases
            with jax.named_scope('kfac.CommunicateFactor'):
                local, err = coll.pmean_scatter_ef(
                    stats, axis_name, comm_precision, err_in,
                    fused=(capture_impl == 'pallas'))
            if new_err is not None and err is not None:
                new_err[key] = err
        else:
            idx = coll.axis_index(axis_name)
            local = lax.dynamic_slice_in_dim(stats, idx * b.factor_per_dev,
                                             b.factor_per_dev, axis=0)
        def mine(flags):
            return lax.dynamic_slice_in_dim(
                flags[key], coll.axis_index(axis_name) * b.factor_per_dev,
                b.factor_per_dev)
        take = [] if seen is None else [mine(seen)]
        if guard:
            take.append(ops.rows_finite(local)
                        if stat_ok is None or stats_reduce == 'pmean'
                        else mine(stat_ok))
        if commit is not None:
            take.append(jnp.broadcast_to(commit, (b.factor_per_dev,)))
        new[key] = settle_factor_rows(
            ops.update_running_avg(local, factors_local[key], factor_decay),
            factors_local[key], take, guard, commit)
    return new, new_err


# ---------------------------------------------------------------------------
# Phase 2: decomposition (batched, on the local shard)
# ---------------------------------------------------------------------------

def _local_table(arr, axis_name):
    """Pick this device's row of a static [P, ...] table."""
    return jnp.take(jnp.asarray(arr), coll.axis_index(axis_name), axis=0)


def _local_rows(plan, tree, axis_name, comm_mode):
    """Per-bucket: this device's rows of a stored decomposition component
    (local already in 'pred' mode; sliced out of the gathered/replicated
    layout in 'inverse' mode)."""
    out = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        x = tree[key]
        if comm_mode == 'inverse':
            per_dev = plan.buckets[bdim].per_dev
            idx = coll.axis_index(axis_name)
            x = lax.dynamic_slice_in_dim(x, idx * per_dev, per_dev, axis=0)
        out[key] = x
    return out


def local_evecs(plan, decomp, axis_name, comm_mode):
    """This device's eigenbasis rows from a stored decomposition.

    Never-decomposed (all-zero) rows come back as the identity, so a warm
    request against a fresh state degrades to a cold decomposition
    instead of rotating into a zero 'basis' and corrupting it — a guard
    for direct ``KFAC.step(warm_basis=True)`` callers that bypass the
    trainer-side seen-inverse gate."""
    out = {}
    for key, q in _local_rows(plan, decomp['evecs'], axis_name,
                              comm_mode).items():
        valid = jnp.any(q != 0, axis=(-2, -1), keepdims=True)
        out[key] = jnp.where(valid, q, jnp.eye(q.shape[-1], dtype=q.dtype))
    return out


def local_invs(plan, decomp, axis_name, comm_mode):
    """This device's stored inverse rows (the Newton-Schulz warm seed).
    Unlike :func:`local_evecs`, never-computed (all-zero) slots stay zero
    — a zero seed has residual ``||I|| = 1`` and fails the NS acceptance
    gate, forcing the Cholesky fallback (an identity 'seed' could make
    NS diverge instead when ``||I - A|| > 1``)."""
    return _local_rows(plan, decomp['invs'], axis_name, comm_mode)


def _local_trace_avgs(plan, factors_local, axis_name):
    """Per-local-factor-row ``trace/true_dim`` averages (flat, concat over
    buckets in bucket_dims order) — the pi-damping inputs shared by the
    full and staggered Cholesky paths. O(D) per slot: cheap enough to
    recompute every step even when only a cohort is decomposed."""
    trace_parts, dim_parts = [], []
    for bdim in plan.bucket_dims:
        b = plan.buckets[bdim]
        tdl = _local_table(
            b.true_dims[:b.n_factor_rows].reshape(plan.num_devices,
                                                  b.factor_per_dev),
            axis_name)
        trace_parts.append(ops.masked_trace(factors_local[_key(bdim)], tdl))
        dim_parts.append(tdl)
    flat_tr = jnp.concatenate(trace_parts)
    flat_dim = jnp.concatenate(dim_parts).astype(jnp.float32)
    return flat_tr / flat_dim


#: NS acceptance threshold on the returned inverse's residual
#: ``max |I - A X|`` (measured AFTER the final iteration, i.e. the bound
#: on the accepted result itself): healthy tracking sits at f32 noise —
#: a slot that still carries >5% residual means its seed was too stale,
#: and the batched Cholesky recomputes THAT slot from scratch (per-slot
#: gate; healthy bucket-mates keep their NS result).
NS_ACCEPT_RESID = 0.05


def tiled_buckets(plan):
    """Keys of the buckets whose Cholesky inverse goes tile by tile
    (``ops.inverse_tiling``, from the bucket's shape)."""
    return tuple(
        _key(bdim) for bdim in plan.bucket_dims
        if ops.inverse_tiling(plan.buckets[bdim].per_dev, bdim)
        != (plan.buckets[bdim].per_dev, bdim))


def bucket_scope(bdim, n):
    """The device scope one bucket's decomposition runs under, inside
    ``kfac.ComputeInverse*``: ``decomp.b<D>x<n>``, ``D`` the bucket dim
    and ``n`` the matrices the bucket hands back on this device (result
    rows, not what a moved-back last group makes twice). Both are static
    in a compiled program, so a trace's reader takes the task from the
    name (``plan.pred_layout_record``'s ``decomp_buckets`` says the same);
    the stages' scopes nest inside it (``ops.psd_inverse``)."""
    return jax.named_scope(f'decomp.b{int(bdim)}x{int(n)}')


def compute_decomposition(plan, factors_local, damping, method, eps,
                          axis_name, basis_local=None, warm_sweeps=None,
                          invs_prev_local=None, impl=None,
                          stored_local=None, guard=False, commit=None):
    """Batched eigh or pi-damped Cholesky inverse of the local factor rows.

    eigh parity: eigen.py:98-119 / eigen_dp.py:62-75 (eigenvalue clamp
    ``d * (d > eps)``). Cholesky parity: inv.py:109-129 with
    ``pi = sqrt((trA/dimA)/(trG/dimG))`` scaled damping; both factor sides
    reduce to ``sqrt(damping * own_trace_avg / mate_trace_avg)`` on their
    diagonal, so one uniform expression covers A and G slots.

    basis_local: previous local eigenbasis rows (``local_evecs``) to
    warm-start the decomposition — only consulted on the eigh path and
    only effective when KFAC_EIGH_IMPL resolves to 'jacobi' (rotated
    sweeps) or 'subspace'/'auto' (perturbative tracking,
    ops.subspace_eigh). ``warm_sweeps`` overrides the warm iteration
    count (None = kernel default).

    invs_prev_local: previous local inverse rows (``local_invs``) to
    warm-start the Cholesky path by Newton-Schulz iteration
    (ops.newton_schulz_inverse) — per bucket, the NS result is accepted
    only when its residual ``max |I - A X|`` clears NS_ACCEPT_RESID
    (zero/stale seeds fail and fall back to the batched Cholesky inside
    ``lax.cond``, so the fallback costs nothing when tracking is
    healthy). ``warm_sweeps`` overrides the NS iteration count.

    impl: the eigh kernel selector forwarded to ``ops.sym_eig``
    ('xla'/'jacobi'/'subspace'/'auto'; None reads KFAC_EIGH_IMPL — the
    legacy env path). The preconditioner's ``decomp_impl`` knob routes
    through here so the autotuner's ladder rung is a traced-program
    choice, not an ambient env read.

    stored_local / guard: this device's rows of the stored decomposition
    (``local_decomposition``) and whether the health guard is on. Only the
    buckets of :func:`tiled_buckets` use them: their groups are written
    over the stored rows and screened as they are written
    (``ops.damped_psd_inverse``), and ``guard_decomposition`` is told to
    pass them by; a bucket inverted whole is guarded there as ever.
    ``commit`` (a traced bool, hoisted updates): where False those buckets
    keep their stored rows; the caller sees to the others.
    """
    if method == 'eigh':
        evals, evecs = {}, {}
        for bdim in plan.bucket_dims:
            key = _key(bdim)
            basis = None if basis_local is None else basis_local[key]
            with bucket_scope(bdim, factors_local[key].shape[0]):
                d, q = ops.sym_eig(factors_local[key], impl=impl,
                                   basis=basis,
                                   sweeps=warm_sweeps if basis is not None
                                   else None)
                evals[key] = ops.clamp_eigvals(d, eps)
            evecs[key] = q
        return {'evals': evals, 'evecs': evecs}

    # cholesky: per-slot traces (mate maps guarantee co-location, plan.py);
    # they and the damping vectors stay directly under the caller's scope
    flat_avg = _local_trace_avgs(plan, factors_local, axis_name)

    invs = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        b = plan.buckets[bdim]
        off = plan.local_flat_offsets[bdim]
        # a row that holds an inverse alone (a later member of an input
        # group) is made of the group's one running average, damped
        # against the member's own ``G``
        own_avg = (lax.dynamic_slice_in_dim(flat_avg, off, b.per_dev)
                   if b.factor_row is None else
                   jnp.take(flat_avg, off + jnp.asarray(b.factor_row)))
        mate_avg = jnp.take(flat_avg, _local_table(b.mate_flat, axis_name))
        damp_vec = jnp.sqrt(damping * own_avg / mate_avg)
        with bucket_scope(bdim, damp_vec.shape[0]):
            if invs_prev_local is None:
                # whole, or in groups of the bucket's rows where it is
                # large
                invs[key] = ops.damped_psd_inverse(
                    factors_local[key], damp_vec,
                    prev=None if stored_local is None
                    else stored_local['invs'][key], guard=guard,
                    commit=commit, rows=b.factor_row)
            else:
                with jax.named_scope('decomp.damp'):
                    damped = ops.add_scaled_identity(
                        factors_local[key] if b.factor_row is None else
                        jnp.take(factors_local[key], b.factor_row, axis=0),
                        damp_vec)
                invs[key] = ops.warm_inverse(
                    damped, invs_prev_local[key],
                    iters=2 if warm_sweeps is None
                    else max(int(warm_sweeps), 1),
                    accept_resid=NS_ACCEPT_RESID)
    return {'invs': invs}


def refresh_decomposition(plan, factors_local, decomp_prev, eps, axis_name,
                          comm_mode, communicate=True,
                          comm_precision='fp32'):
    """Cheap eigen refresh: new eigenvalues in the RETAINED eigenbasis.

    E-KFAC-style amortization (George et al. 2018 re-estimate scalings in
    a fixed Kronecker eigenbasis): between full eigendecompositions the
    basis Q drifts slowly, so ``d <- clamp(diag(Q^T F Q))`` re-fits the
    spectrum to the current running-average factors with two batched
    matmuls per bucket instead of an eigh. In comm_mode='inverse' only the
    eigenvalue VECTORS are re-gathered (the replicated basis stays put),
    shrinking the inverse-comm volume from O(d^2) to O(d) per factor.

    ``decomp_prev`` is the state's decomposition (local rows in 'pred'
    mode, gathered/replicated in 'inverse' mode); returns a decomposition
    in the same layout.
    """
    evals = {}
    evecs_local = local_evecs(plan, decomp_prev, axis_name, comm_mode)
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        q = evecs_local[key]
        f = factors_local[key]
        with bucket_scope(bdim, f.shape[0]):
            fq = jnp.einsum('mjk,mki->mji', f, q, precision=_PRED_PRECISION)
            d = jnp.sum(q * fq, axis=1)
            evals[key] = ops.clamp_eigvals(d, eps)
    if comm_mode == 'inverse':
        if communicate:
            evals = {k: coll.all_gather_rows_compressed(v, axis_name,
                                                        comm_precision)
                     for k, v in evals.items()}
        else:
            evals = gather_decomposition(plan, evals, axis_name,
                                         communicate=False)
        return {'evals': evals, 'evecs': decomp_prev['evecs']}
    return {'evals': evals, 'evecs': evecs_local}


def _cohort_table(tbl, cohort_idx, axis_name):
    """Select this device's row of a static ``[F, P, R]`` cohort table
    for a TRACED cohort index — the indirection that keeps one compiled
    program serving every cohort (no per-cohort step variants)."""
    t = jnp.take(jnp.asarray(tbl), cohort_idx, axis=0)
    return jnp.take(t, coll.axis_index(axis_name), axis=0)


def compute_cohort_decomposition(plan, cohorts, factors_local, cohort_idx,
                                 damping, method, eps, axis_name,
                                 impl=None, decomp_prev=None,
                                 comm_mode=None, warm_sweeps=None):
    """Decompose ONLY this step's cohort rows of the local factor shard.

    The staggered counterpart of :func:`compute_decomposition`:
    ``cohort_idx`` (traced, = ``step % num_cohorts``) selects the
    precomputed row tables (plan.build_cohorts) and the batched
    eigh/Cholesky runs over ``R_b`` rows per bucket instead of
    ``per_dev`` — ~``1/num_cohorts`` of the refresh-spike work per step.
    Returns cohort-shaped components (``[R_b, ...]`` rows per bucket);
    :func:`merge_cohort_decomposition` scatters them into the stored
    decomposition. Padding rows (off-peak cohorts) decompose a real
    factor row whose result the merge discards.

    Cholesky pi-damping uses fresh traces of ALL local rows (O(D) per
    slot) so each cohort row is damped exactly as the full path would
    damp it at this step.

    impl / decomp_prev / comm_mode: the ``decomp_impl`` iterative-
    kernel route for the staggered path. With an iterative impl and the
    stored decomposition (``decomp_prev`` + its ``comm_mode`` layout)
    the cohort rows warm-start from their own stored basis/inverse —
    the trainer only staggers after the first full decomposition, so a
    stored seed always exists; never-decomposed rows degrade safely
    (identity basis via ``local_evecs``, zero NS seed fails the
    residual gate and falls back to Cholesky).
    """
    sel = {bdim: _cohort_table(cohorts.rows[bdim], cohort_idx, axis_name)
           for bdim in plan.bucket_dims}
    if method == 'eigh':
        basis_local = None
        if (impl in ('subspace', 'jacobi', 'auto')
                and decomp_prev is not None):
            basis_local = local_evecs(plan, decomp_prev, axis_name,
                                      comm_mode)
        evals, evecs = {}, {}
        for bdim in plan.bucket_dims:
            key = _key(bdim)
            with bucket_scope(bdim, sel[bdim].shape[0]):
                f = jnp.take(factors_local[key], sel[bdim], axis=0)
                basis = (None if basis_local is None
                         else jnp.take(basis_local[key], sel[bdim], axis=0))
                d, q = ops.sym_eig(f, impl=impl, basis=basis,
                                   sweeps=warm_sweeps if basis is not None
                                   else None)
                evals[key] = ops.clamp_eigvals(d, eps)
            evecs[key] = q
        return {'evals': evals, 'evecs': evecs}

    invs_prev = None
    if impl == 'newton_schulz' and decomp_prev is not None:
        invs_prev = local_invs(plan, decomp_prev, axis_name, comm_mode)
    flat_avg = _local_trace_avgs(plan, factors_local, axis_name)
    invs = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        own_avg = jnp.take(flat_avg, _cohort_table(
            cohorts.own_flat[bdim], cohort_idx, axis_name))
        mate_avg = jnp.take(flat_avg, _cohort_table(
            cohorts.mate_flat[bdim], cohort_idx, axis_name))
        damp_vec = jnp.sqrt(damping * own_avg / mate_avg)
        with bucket_scope(bdim, damp_vec.shape[0]):
            with jax.named_scope('decomp.damp'):
                f = jnp.take(factors_local[key], sel[bdim], axis=0)
                damped = ops.add_scaled_identity(f, damp_vec)
            if invs_prev is None:
                invs[key] = ops.psd_inverse(damped)
            else:
                invs[key] = ops.warm_inverse(
                    damped, jnp.take(invs_prev[key], sel[bdim], axis=0),
                    iters=2 if warm_sweeps is None
                    else max(int(warm_sweeps), 1),
                    accept_resid=NS_ACCEPT_RESID)
    return {'invs': invs}


def _damped_cohort_factors(plan, cohorts, factors_local, cohort_idx,
                           damping, method, axis_name):
    """This device's cohort factor rows, damped exactly as the cohort
    decomposition would damp them (cholesky pi-damping; eigh rows ship
    raw — the eigh path damps in the pred denominators). The shard
    exchange sends THESE matrices, so the remote decomposition is
    bit-equivalent to the owner-local one."""
    flat_avg = None
    if method != 'eigh':
        flat_avg = _local_trace_avgs(plan, factors_local, axis_name)
    out = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        sel = _cohort_table(cohorts.rows[bdim], cohort_idx, axis_name)
        f = jnp.take(factors_local[key], sel, axis=0)
        if method != 'eigh':
            own_avg = jnp.take(flat_avg, _cohort_table(
                cohorts.own_flat[bdim], cohort_idx, axis_name))
            mate_avg = jnp.take(flat_avg, _cohort_table(
                cohorts.mate_flat[bdim], cohort_idx, axis_name))
            f = ops.add_scaled_identity(
                f, jnp.sqrt(damping * own_avg / mate_avg))
        out[key] = f
    return out


def compute_shard_decomposition(plan, cohorts, shard, factors_local,
                                cohort_idx, damping, method, eps,
                                axis_name, impl=None, decomp_prev=None,
                                comm_mode=None, warm_sweeps=None,
                                comm_precision='fp32'):
    """Mesh-sharded cohort decomposition: the active cohort's rows are
    decomposed balanced across ALL devices instead of owner-local.

    Three phases, all driven by the static ``plan.DecompShardPlan``
    tables at a TRACED cohort index (one compiled program, like the
    cohort path):

    1. each owner damps its cohort rows and the cohort is all-gathered
       (``kfac.DecompComm`` — P*R_b matrices per bucket on the wire);
    2. each device decomposes the ``S_b`` gathered slots its shard
       table names — ``Σ_b S_b·D³`` per-device work instead of the
       owner-local ``Σ_b R_b·D³``, the ~P× critical-path shrink;
    3. the results return via :func:`merge_shard_decomposition`'s
       second DecompComm gather.

    Returns this device's local results (``[S_b, ...]`` per bucket).
    Warm seeds (``decomp_impl`` iterative kernels) are read from the
    stored decomposition through the ``src_global`` row table —
    available only in the replicated comm_mode='inverse' layout, where
    every device holds every row's previous value; comm_pred shards the
    store, so its shard path always runs the cold kernel.
    """
    damped = _damped_cohort_factors(plan, cohorts, factors_local,
                                    cohort_idx, damping, method, axis_name)
    out_d, out_q, out_i = {}, {}, {}
    warm_ok = decomp_prev is not None and comm_mode == 'inverse'
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        gathered = coll.decomp_exchange_gather(damped[key], axis_name,
                                               comm_precision)
        src = _cohort_table(shard.src[bdim], cohort_idx, axis_name)
        mine = jnp.take(gathered, src, axis=0)
        if method == 'eigh':
            basis = None
            if impl in ('subspace', 'jacobi', 'auto') and warm_ok:
                rows = _cohort_table(shard.src_global[bdim], cohort_idx,
                                     axis_name)
                q = jnp.take(decomp_prev['evecs'][key], rows, axis=0)
                valid = jnp.any(q != 0, axis=(-2, -1), keepdims=True)
                basis = jnp.where(valid, q,
                                  jnp.eye(q.shape[-1], dtype=q.dtype))
            with bucket_scope(bdim, mine.shape[0]):
                d, q = ops.sym_eig(mine, impl=impl, basis=basis,
                                   sweeps=warm_sweeps if basis is not None
                                   else None)
                out_d[key] = ops.clamp_eigvals(d, eps)
            out_q[key] = q
        else:
            seed = None
            if impl == 'newton_schulz' and warm_ok:
                rows = _cohort_table(shard.src_global[bdim], cohort_idx,
                                     axis_name)
                seed = jnp.take(decomp_prev['invs'][key], rows, axis=0)
            with bucket_scope(bdim, mine.shape[0]):
                if seed is None:
                    out_i[key] = ops.psd_inverse(mine)
                else:
                    out_i[key] = ops.warm_inverse(
                        mine, seed,
                        iters=2 if warm_sweeps is None
                        else max(int(warm_sweeps), 1),
                        accept_resid=NS_ACCEPT_RESID)
    if method == 'eigh':
        return {'evals': out_d, 'evecs': out_q}
    return {'invs': out_i}


def merge_shard_decomposition(plan, shard, decomp_stored, shard_new,
                              cohort_idx, axis_name, comm_mode, method,
                              guard=True, comm_precision='fp32'):
    """Return the sharded cohort's results to their stored rows.

    The results are all-gathered (the second ``kfac.DecompComm`` leg)
    and every stored row GATHERS its fresh value through the static
    ``res_slot`` table — rows outside the cohort keep their stored bits
    exactly (their table entry is invalid, the ``where`` keeps the
    stored value), and because the merge is a gather there are no
    scatter collisions to order: the result is deterministic by
    construction. ``guard``: per-row non-finite screen, the staggered
    health contract (a blown remote decomposition row keeps the last
    good stored row).
    """
    F = shard.num_cohorts
    P = plan.num_devices

    def tables(bdim):
        if comm_mode == 'inverse':
            slots = jnp.take(jnp.asarray(shard.res_slot[bdim]),
                             cohort_idx, axis=0)
            valid = jnp.take(jnp.asarray(shard.res_valid[bdim]),
                             cohort_idx, axis=0)
        else:
            per_dev = plan.buckets[bdim].per_dev
            slots = _cohort_table(
                shard.res_slot[bdim].reshape(F, P, per_dev),
                cohort_idx, axis_name)
            valid = _cohort_table(
                shard.res_valid[bdim].reshape(F, P, per_dev),
                cohort_idx, axis_name)
        return slots, valid

    def pick(ok, fresh, stored):
        okr = ok.reshape(ok.shape + (1,) * (stored.ndim - 1))
        return jnp.where(okr, fresh, stored)

    out = dict(decomp_stored)
    if method == 'eigh':
        new_d, new_q = {}, {}
        for bdim in plan.bucket_dims:
            key = _key(bdim)
            dg = coll.decomp_exchange_gather(shard_new['evals'][key],
                                             axis_name, comm_precision)
            qg = coll.decomp_exchange_gather(shard_new['evecs'][key],
                                             axis_name, comm_precision)
            slots, ok = tables(bdim)
            fresh_d = jnp.take(dg, slots, axis=0)
            fresh_q = jnp.take(qg, slots, axis=0)
            if guard:
                # joint screen: a row commits its (evals, evecs) pair
                # together or not at all — a half-committed pair would
                # precondition in a basis its spectrum does not match
                ok = jnp.logical_and(ok, jnp.logical_and(
                    ops.rows_finite(fresh_d), ops.rows_finite(fresh_q)))
            new_d[key] = pick(ok, fresh_d, decomp_stored['evals'][key])
            new_q[key] = pick(ok, fresh_q, decomp_stored['evecs'][key])
        out['evals'], out['evecs'] = new_d, new_q
        return out
    new_i = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        xg = coll.decomp_exchange_gather(shard_new['invs'][key],
                                         axis_name, comm_precision)
        slots, ok = tables(bdim)
        fresh = jnp.take(xg, slots, axis=0)
        if guard:
            ok = jnp.logical_and(ok, ops.rows_finite(fresh))
        new_i[key] = pick(ok, fresh, decomp_stored['invs'][key])
    out['invs'] = new_i
    return out


def merge_cohort_decomposition(plan, cohorts, decomp_stored, cohort_new,
                               cohort_idx, axis_name, comm_mode, method,
                               communicate=True, guard=True,
                               comm_precision='fp32'):
    """Scatter freshly decomposed cohort rows into the stored
    decomposition; every other row keeps its stored bits exactly.

    comm_mode='pred': local scatter, zero comm (the owner's shard holds
    its own decomposition rows).

    comm_mode='inverse': the cohort rows are all-gathered — the
    double-buffered publish: only ``Σ_b R_b`` rows travel per step
    (~``1/num_cohorts`` of the full decomposition gather), and because
    the caller preconditions with the PREVIOUS table this gather has no
    same-step consumer, so XLA can overlap it with the pred einsums.
    With ``communicate=False`` (the CommunicateInverse ablation) each
    device scatters only its own rows at its global offsets.

    ``guard``: per-row non-finite screen — a blown cohort row keeps the
    last good stored row instead of poisoning the table (the staggered
    form of :func:`guard_decomposition`). Padding rows always rewrite
    the stored value (all duplicate scatter writes carry identical
    values, so the merge is deterministic and bit-stable).
    """
    def tables(bdim):
        if comm_mode == 'inverse' and communicate:
            rows = jnp.take(jnp.asarray(cohorts.global_rows[bdim]),
                            cohort_idx, axis=0)
            valid = jnp.take(jnp.asarray(cohorts.global_valid[bdim]),
                             cohort_idx, axis=0)
            gather = lambda x: coll.all_gather_rows_compressed(  # noqa: E731
                x, axis_name, comm_precision)
        elif comm_mode == 'inverse':
            F, PR = cohorts.global_rows[bdim].shape
            P = plan.num_devices
            rows = _cohort_table(
                cohorts.global_rows[bdim].reshape(F, P, PR // P),
                cohort_idx, axis_name)
            valid = _cohort_table(
                cohorts.global_valid[bdim].reshape(F, P, PR // P),
                cohort_idx, axis_name)
            gather = lambda x: x  # noqa: E731
        else:
            rows = _cohort_table(cohorts.rows[bdim], cohort_idx, axis_name)
            valid = _cohort_table(cohorts.valid[bdim], cohort_idx, axis_name)
            gather = lambda x: x  # noqa: E731
        return rows, valid, gather

    out = dict(decomp_stored)
    if method == 'eigh':
        new_d, new_q = {}, {}
        for bdim in plan.bucket_dims:
            key = _key(bdim)
            rows, valid, gather = tables(bdim)
            dn = gather(cohort_new['evals'][key])
            qn = gather(cohort_new['evecs'][key])
            ds = decomp_stored['evals'][key]
            qs = decomp_stored['evecs'][key]
            ok = valid
            if guard:
                ok = jnp.logical_and(ok, jnp.logical_and(
                    ops.rows_finite(dn), ops.rows_finite(qn)))
            d_prev = jnp.take(ds, rows, axis=0)
            q_prev = jnp.take(qs, rows, axis=0)
            new_d[key] = ds.at[rows].set(jnp.where(ok[:, None], dn, d_prev))
            new_q[key] = qs.at[rows].set(
                jnp.where(ok[:, None, None], qn, q_prev))
        out['evals'], out['evecs'] = new_d, new_q
        return out
    new_i = {}
    for bdim in plan.bucket_dims:
        key = _key(bdim)
        rows, valid, gather = tables(bdim)
        xn = gather(cohort_new['invs'][key])
        xs = decomp_stored['invs'][key]
        ok = valid
        if guard:
            ok = jnp.logical_and(ok, ops.rows_finite(xn))
        x_prev = jnp.take(xs, rows, axis=0)
        new_i[key] = xs.at[rows].set(
            jnp.where(ok[:, None, None], xn, x_prev))
    out['invs'] = new_i
    return out


def _layer_rows_padded(meta, acts, gs, batch_averaged, pg):
    """This layer's factor-convention row matrices (ops.layer_rows_*),
    feature-padded with zeros to the pred group's bucket dims — the one
    shared row/padding contract of both E-KFAC moment estimators."""
    if meta.kind == 'stacked':
        raise NotImplementedError(
            f'{meta.name}: E-KFAC moments of a stacked layer')
    a = capture.layer_act(acts, meta)
    g = capture.layer_g(gs, meta)
    if meta.kind == 'dense':
        arows, grows, n = ops.layer_rows_dense(
            a, g, meta.use_bias, batch_averaged)
    else:
        arows, grows, n = ops.layer_rows_conv(
            a, g, meta.kernel_size, meta.strides, meta.padding,
            meta.use_bias, batch_averaged)
    arows = jnp.pad(arows, ((0, 0), (0, pg.da - arows.shape[1])))
    grows = jnp.pad(grows, ((0, 0), (0, pg.dg - grows.shape[1])))
    return arows, grows, n


def update_ekfac_scales(plan, decomp, acts, gs, batch_averaged,
                        scales_prev, factor_decay, stats_reduce,
                        axis_name, comm_precision='fp32'):
    """E-KFAC second-moment update in the current (replicated) eigenbasis
    — beyond the reference (George et al. 2018, 'ekfac' variant).

    For every layer: project this device's captured rows into the
    layer's Kronecker eigenbasis and accumulate the squared-projection
    joint moment ``s = E[(Qg' grad_b Qa)^2]`` (ops.ekfac_scales) — two
    projections and one GEMM per layer, NO eigh. Under MPD semantics
    (``stats_reduce='pmean'``) the per-shard moments are pmean'd so s is
    the global-batch estimate, mirroring the factor pmean. EMA'd with
    ``factor_decay`` like the factors themselves.

    Requires the replicated decomposition layout (comm_mode='inverse'):
    every device holds every layer's basis. Rows are feature-padded with
    zeros to the bucket dims, so padded coordinates contribute zero to s
    and the identity-padded basis block keeps them inert — the same
    padding contract the pred path uses.

    Returns the new ``{group-key: [m, dg, da]}`` scales dict, stacked to
    match ``plan.pred_groups`` member order. A zero basis (no
    decomposition yet) projects everything to zero, so s stays zero and
    the pred path's validity guard keeps the plain Kronecker denominator
    — fresh starts and resumes degrade gracefully.
    """
    new = {}
    for gi, pg in enumerate(plan.pred_groups):
        member_scales = []
        for pos, i in enumerate(pg.layer_idx):
            meta = plan.metas[int(i)]
            arows, grows, n = _layer_rows_padded(meta, acts, gs,
                                                 batch_averaged, pg)
            qa = decomp['evecs'][_key(pg.da)][int(pg.row_a[pos])]
            qg = decomp['evecs'][_key(pg.dg)][int(pg.row_g[pos])]
            member_scales.append(ops.ekfac_scales(arows, grows, qa, qg, n))
        s_new = jnp.stack(member_scales)
        if stats_reduce == 'pmean':
            # lossy wire WITHOUT error feedback: the moments are EMAs of
            # squared projections (no sign structure for EF to protect)
            # and carrying a second residual tree is not worth the state
            with jax.named_scope('kfac.CommunicateFactor.scales'):
                s_new = coll.pmean_wire(s_new, axis_name, comm_precision)
        new[f'g{gi}'] = ops.update_running_avg(
            s_new, scales_prev[f'g{gi}'], factor_decay)
    return new


def update_ekfac_scales_local(plan, decomp_local, acts, gs,
                              batch_averaged, scales_prev, factor_decay,
                              axis_name):
    """Owner-local E-KFAC moments in the comm_pred layout ('ekfac_dp',
    beyond reference): DP-KFAC's owner-local-statistics semantics
    (reference inv_dp.py:60-95) applied to the per-example second
    moments — zero scale communication, ever.

    Uniform-SPMD construction: every device computes EVERY layer's
    moment from its OWN captured rows (the same per-layer static loop
    the factor stats use), projecting with the basis rows sitting at
    the slot the layer occupies in this device's local decomposition
    shard; a masked accumulation then keeps only the slots this device
    actually owns. Unowned layers project through an arbitrary local
    row — compute that is always discarded by the mask, the price of
    static shapes (no data-dependent control flow under jit).

    Returns ``{group-key: [K, dg, da]}`` local slot-ordered scales,
    aligned with ``compute_pred_local``'s member order.
    """
    new = {}
    for gi, pg in enumerate(plan.pred_groups):
        K = pg.local_member.shape[1]
        members = _local_table(pg.local_member, axis_name)       # [K]
        valid = _local_table(pg.local_valid, axis_name)          # [K]
        lra = _local_table(pg.local_row_a, axis_name)
        lrg = _local_table(pg.local_row_g, axis_name)
        slot_s = jnp.zeros((K, pg.dg, pg.da), jnp.float32)
        for pos, i in enumerate(pg.layer_idx):
            meta = plan.metas[int(i)]
            arows, grows, n = _layer_rows_padded(meta, acts, gs,
                                                 batch_averaged, pg)
            # dummy pad slots can repeat a member index: restrict the
            # selection to valid slots so exactly the owner slot (or
            # nothing) is picked
            sel = jnp.logical_and(members == pos, valid)         # [K]
            ra = jnp.sum(jnp.where(sel, lra, 0))
            rg = jnp.sum(jnp.where(sel, lrg, 0))
            qa = decomp_local['evecs'][_key(pg.da)][ra]
            qg = decomp_local['evecs'][_key(pg.dg)][rg]
            s_i = ops.ekfac_scales(arows, grows, qa, qg, n)
            slot_s = slot_s + jnp.where(sel[:, None, None], s_i[None], 0)
        new[f'g{gi}'] = ops.update_running_avg(
            slot_s, scales_prev[f'g{gi}'], factor_decay)
    return new


def rotate_ekfac_scales_local(plan, scales, evecs_prev_local,
                              evecs_new_local, axis_name):
    """Per-slot squared-overlap transport of owner-local scales across a
    basis change (the comm_pred counterpart of rotate_ekfac_scales):
    each local slot rotates by its OWN old/new basis rows."""
    out = {}
    for gi, pg in enumerate(plan.pred_groups):
        lra = _local_table(pg.local_row_a, axis_name)
        lrg = _local_table(pg.local_row_g, axis_name)
        qa_o = jnp.take(evecs_prev_local[_key(pg.da)], lra, axis=0)
        qg_o = jnp.take(evecs_prev_local[_key(pg.dg)], lrg, axis=0)
        qa_n = jnp.take(evecs_new_local[_key(pg.da)], lra, axis=0)
        qg_n = jnp.take(evecs_new_local[_key(pg.dg)], lrg, axis=0)
        ra = jnp.einsum('kij,kil->kjl', qa_o, qa_n,
                        precision=_PRED_PRECISION) ** 2
        rg = jnp.einsum('kij,kil->kjl', qg_o, qg_n,
                        precision=_PRED_PRECISION) ** 2
        s = scales[f'g{gi}']
        out[f'g{gi}'] = jnp.einsum(
            'kji,kjl,klm->kim', rg, s, ra, precision=_PRED_PRECISION)
    return out


def rotate_ekfac_scales(plan, scales, evecs_prev, evecs_new):
    """Re-express stored E-KFAC scales after a basis change.

    The EMA'd moments live in the OLD basis; after a full
    eigendecomposition replaces Q the diagonal moments cannot be mapped
    exactly (s is a diagonal in a basis that no longer exists), but the
    rotation ``s' = (Rg^2) s (Ra^2)^T`` with ``R = Q_new^T Q_old`` is the
    exact transport of the DIAGONAL approximation ``sum_kl s_kl
    (q_g,k q_a,l outer)^2`` between bases — it preserves the total mass
    and degrades to identity when the basis barely moved (warm tracking,
    refresh steps). Keeps the EMA history useful across basis updates
    instead of restarting the moments from zero."""
    out = {}
    for gi, pg in enumerate(plan.pred_groups):
        rotated = []
        s = scales[f'g{gi}']
        for pos in range(len(pg.layer_idx)):
            qa_o = evecs_prev['evecs'][_key(pg.da)][int(pg.row_a[pos])]
            qg_o = evecs_prev['evecs'][_key(pg.dg)][int(pg.row_g[pos])]
            qa_n = evecs_new['evecs'][_key(pg.da)][int(pg.row_a[pos])]
            qg_n = evecs_new['evecs'][_key(pg.dg)][int(pg.row_g[pos])]
            ra = jnp.einsum('ij,ik->jk', qa_o, qa_n,
                            precision=_PRED_PRECISION) ** 2
            rg = jnp.einsum('ij,ik->jk', qg_o, qg_n,
                            precision=_PRED_PRECISION) ** 2
            rotated.append(rg.T @ s[pos] @ ra)
        out[f'g{gi}'] = jnp.stack(rotated)
    return out


def where_finite_rows(new, prev, reinit_identity=False):
    """Per-leading-row non-finite screen over a ``{key: [rows, ...]}``
    dict: rows of ``new`` containing any NaN/Inf are replaced by the
    matching ``prev`` row. With ``reinit_identity=True`` a row whose
    ``prev`` is ALSO non-finite re-initializes to the identity instead —
    the factor-EMA heal path: a silently-corrupted stored factor block
    resets to its init() value on the next factor update and
    re-accumulates from fresh statistics, rather than staying NaN for
    the rest of the run."""
    out = {}
    for key, n in new.items():
        p = prev[key]
        good = ops.rows_finite(n)
        fb = p
        if reinit_identity:
            eye = jnp.eye(n.shape[-1], dtype=n.dtype)
            pgood = ops.rows_finite(p)
            fb = jnp.where(pgood[:, None, None], p, eye[None])
        good = good.reshape(good.shape + (1,) * (n.ndim - 1))
        out[key] = jnp.where(good, n, fb)
    return out


def local_decomposition(plan, decomp, axis_name, comm_mode, method):
    """This device's rows of a stored decomposition, RAW (unlike
    ``local_evecs`` no zero->identity substitution — the guard below
    does its own cold handling)."""
    if method == 'eigh':
        return {'evals': _local_rows(plan, decomp['evals'], axis_name,
                                     comm_mode),
                'evecs': _local_rows(plan, decomp['evecs'], axis_name,
                                     comm_mode)}
    return {'invs': _local_rows(plan, decomp['invs'], axis_name, comm_mode)}


def guard_decomposition(decomp_new, decomp_prev, method, done=(),
                        guard=True, commit=None):
    """Non-finite screen over a freshly-computed decomposition: per row,
    fall back to the last good decomposition, or to the identity when no
    good one exists yet (all-zero cold state).

    An eigh/Cholesky blowup (ill-conditioned factor, injected fault)
    then degrades that layer to its previous — still curvature-bearing —
    preconditioner instead of poisoning every subsequent step; a cold
    blowup degrades to the identity, i.e. plain gradient pass-through
    scaled by ``1/(1+damping)``. The healthy path's output is
    bit-identical to the unguarded computation.

    A Cholesky inverse is settled by :func:`ops.settle_inverse_rows`: its
    flag is read from its diagonal, which is exact for that operand
    (:func:`ops.inverse_rows_finite`), the stored bucket is read only for a
    row at fault, and only such a row is written again. ``commit`` (a
    traced bool, hoisted updates; Cholesky only): where False every row
    keeps its stored inverse; ``guard`` False leaves that alone. An
    eigendecomposition has no such witness (a NaN in one eigenvector says
    nothing of the others): it keeps a read of every element and a
    ``jnp.where`` over the bucket, as before.

    Layouts must match between ``decomp_new`` and ``decomp_prev`` (both
    local rows, or both gathered/replicated). Only the decomposition
    keys of ``decomp_new`` are consulted — extra state keys (E-KFAC
    scales) are screened separately by :func:`where_finite_rows`.
    ``done``: keys of the buckets settled already, as they were written
    (:func:`tiled_buckets`): passed through.
    """
    if method == 'eigh':
        assert guard and commit is None
        out_d, out_q = {}, {}
        for key in decomp_new['evecs']:
            dn, qn = decomp_new['evals'][key], decomp_new['evecs'][key]
            dp, qp = decomp_prev['evals'][key], decomp_prev['evecs'][key]
            good = jnp.logical_and(ops.rows_finite(dn), ops.rows_finite(qn))
            cold = jnp.logical_not(jnp.any(qp != 0, axis=(-2, -1)))
            eye = jnp.eye(qn.shape[-1], dtype=qn.dtype)
            fb_q = jnp.where(cold[:, None, None], eye[None], qp)
            fb_d = jnp.where(cold[:, None], jnp.ones_like(dp), dp)
            out_d[key] = jnp.where(good[:, None], dn, fb_d)
            out_q[key] = jnp.where(good[:, None, None], qn, fb_q)
        out = dict(decomp_new)
        out['evals'], out['evecs'] = out_d, out_q
        return out
    out = dict(decomp_new)
    out['invs'] = {
        key: xn if key in done else ops.settle_inverse_rows(
            xn, decomp_prev['invs'][key], guard, commit)[0]
        for key, xn in decomp_new['invs'].items()}
    return out


def gather_decomposition(plan, decomp_local, axis_name, communicate=True,
                         comm_precision='fp32'):
    """All-gather decomposition rows to every device (comm_inverse mode).

    ≙ per-owner broadcast of QA/dA/QG/dG or inverse factors (reference:
    eigen.py:122-134, inv.py:132-142). With ``communicate=False`` (the
    CommunicateInverse ablation) rows are placed at the owner's offset with
    zeros elsewhere — shapes stay global, zero comm.

    ``comm_precision``: wire dtype of the gather — bf16 halves the
    InverseComm payload, int8 quarters it with a per-row absmax scale
    (collectives.all_gather_rows_compressed). The loss is each owner's
    LOCAL quantization only (one contributor per row), and the pred path
    damps the decomposition anyway — see README "Communication
    compression" for when int8 is safe.
    """
    if communicate:
        return jax.tree.map(
            lambda x: coll.all_gather_rows_compressed(x, axis_name,
                                                      comm_precision),
            decomp_local)

    def place(x):
        per_dev = x.shape[0]
        full = jnp.zeros((plan.num_devices * per_dev,) + x.shape[1:], x.dtype)
        idx = coll.axis_index(axis_name)
        return lax.dynamic_update_slice_in_dim(full, x, idx * per_dev, axis=0)

    return jax.tree.map(place, decomp_local)


# ---------------------------------------------------------------------------
# Phase 3: preconditioning
# ---------------------------------------------------------------------------

def _pred_eigh(qg, dg, qa, da, gstack, damping, scales=None):
    v1 = jnp.einsum('mji,mjk,mkl->mil', qg, gstack, qa,
                    precision=_PRED_PRECISION)
    denom = dg[:, :, None] * da[:, None, :]
    if scales is not None:
        # E-KFAC: the per-example second moment replaces the Kronecker
        # eigenvalue outer product; an all-zero s (no moments accumulated
        # yet — fresh start or restored pre-ekfac checkpoint) falls back
        # to the Kronecker denominator per member
        valid = jnp.any(scales != 0, axis=(-2, -1), keepdims=True)
        denom = jnp.where(valid, scales, denom)
    v2 = v1 / (denom + damping)
    return jnp.einsum('mij,mjk,mlk->mil', qg, v2, qa,
                      precision=_PRED_PRECISION)


def _pred_inv(invg, inva, gstack, damping):
    del damping  # damping was folded into the inverse
    return jnp.einsum('mij,mjk,mkl->mil', invg, gstack, inva,
                      precision=_PRED_PRECISION)


def _group_grad_stack(plan, pg, grad_mats):
    return jnp.stack([_pad_mat(grad_mats[int(i)], pg.dg, pg.da)
                      for i in pg.layer_idx])


def _group_rows(pg, side, x, axis_name=None, local=False):
    """Pred group ``pg``'s rows of ``x``, a stored ``[rows, ...]``
    decomposition component of its ``side`` ('a' | 'g') bucket
    (``local``: this device's shard of it, and ``side='member'`` the
    device's members of the group's gradient stack).

    Where the plan laid the rows down as one run
    (``PredGroup.run_starts``) this is a slice, which the GEMM reads
    where it lies: static in the replicated layout and where every
    device's run starts alike (always on one device), one dynamic slice
    at this device's start otherwise. Anywhere else, a gather."""
    table, starts = pg.row_table(side, local), pg.run_starts(side, local)
    if not local:
        if starts is None:
            return jnp.take(x, jnp.asarray(table), axis=0)
        return lax.slice_in_dim(x, starts, starts + len(table), axis=0)
    if starts is None:
        return jnp.take(x, _local_table(table, axis_name), axis=0)
    k = table.shape[1]
    if (starts == starts[0]).all():
        return lax.slice_in_dim(x, int(starts[0]), int(starts[0]) + k,
                                axis=0)
    return lax.dynamic_slice_in_dim(x, _local_table(starts, axis_name), k,
                                    axis=0)


def _group_pred(pg, rows, decomp, gstack, damping, method, scales):
    """One group's batched apply; ``rows(side, x)`` picks the group's
    rows of a component of its ``side`` bucket."""
    ka, kg = _key(pg.da), _key(pg.dg)
    if method == 'eigh':
        evecs, evals = decomp['evecs'], decomp['evals']
        return _pred_eigh(rows('g', evecs[kg]), rows('g', evals[kg]),
                          rows('a', evecs[ka]), rows('a', evals[ka]),
                          gstack, damping, scales)
    invs = decomp['invs']
    return _pred_inv(rows('g', invs[kg]), rows('a', invs[ka]), gstack,
                     damping)


def compute_pred_replicated(plan, decomp, grad_mats, damping, method,
                            scales=None):
    """Preconditioning with replicated (gathered) decompositions — every
    device computes every layer's pred, zero comm (reference eigen path:
    all ranks run _compute_pred after broadcast, eigen.py:137-144).
    ``scales``: E-KFAC second moments keyed per pred group (replaces the
    Kronecker eigenvalue denominators, see update_ekfac_scales)."""
    preds = [None] * plan.num_layers
    for gi, pg in enumerate(plan.pred_groups):
        gstack = _group_grad_stack(plan, pg, grad_mats)
        pred = _group_pred(pg, functools.partial(_group_rows, pg), decomp,
                           gstack, damping, method,
                           None if scales is None else scales[f'g{gi}'])
        for pos, i in enumerate(pg.layer_idx):
            meta = plan.metas[int(i)]
            preds[int(i)] = pred[pos, :meta.out_dim, :meta.in_dim]
    return preds


def compute_pred_local(plan, decomp_local, grad_mats, damping, method,
                       axis_name, communicate=True, scales=None,
                       comm_precision='fp32'):
    """Owner-computes preconditioning + all-gather of the results
    (comm_pred mode — the DP-KFAC flagship path: only final preconditioned
    gradients travel, reference inv_dp.py:126-138 + inv.py:164-175).
    ``scales``: owner-local slot-ordered E-KFAC moments
    (update_ekfac_scales_local) replacing the Kronecker denominators."""
    preds = [None] * plan.num_layers
    for gi, pg in enumerate(plan.pred_groups):
        rows = functools.partial(_group_rows, pg, axis_name=axis_name,
                                 local=True)
        # this device's members of the stack: all of it, on one device
        g_loc = rows('member', _group_grad_stack(plan, pg, grad_mats))
        pred_loc = _group_pred(pg, rows, decomp_local, g_loc, damping,
                               method,
                               None if scales is None else scales[f'g{gi}'])
        if communicate:
            gathered = coll.all_gather_rows_compressed(pred_loc, axis_name,
                                                       comm_precision)
        else:
            gathered = gather_decomposition(
                plan, pred_loc, axis_name, communicate=False)
        for pos, i in enumerate(pg.layer_idx):
            meta = plan.metas[int(i)]
            row = int(pg.gathered_row[pos])
            preds[int(i)] = gathered[row, :meta.out_dim, :meta.in_dim]
    return preds


# ---------------------------------------------------------------------------
# Phase 4: KL clip + write-back
# ---------------------------------------------------------------------------

def preconditioned_grads(plan, grads, grad_mats, preds, lr, kl_clip,
                         skip_clip=False):
    """Scale preds by the KL clip factor and scatter into the grads pytree.

    Parity: ``_update_grad_in_place`` (reference: inv.py:188-217):
    ``nu = min(1, sqrt(kl_clip / |sum(pred * grad * lr^2)|))``; non-KFAC
    params pass through untouched.
    """
    if kl_clip is not None and not skip_clip:
        vg = jnp.zeros((), jnp.float32)
        for i in range(plan.num_layers):
            vg = vg + jnp.sum(preds[i] * grad_mats[i])
        vg = vg * (lr ** 2)
        nu = jnp.minimum(1.0, jnp.sqrt(kl_clip / jnp.abs(vg)))
    else:
        nu = jnp.float32(1.0)
    new_grads = grads
    stacks = {}     # a stacked leaf is written once, from all its slices
    for i, meta in enumerate(plan.metas):
        if meta.kind == 'stacked':
            stacks.setdefault(meta.path, {})[meta.index] = (preds[i] * nu).T
        else:
            new_grads = write_grad_matrix(meta, new_grads, preds[i] * nu)
    for path, slices in stacks.items():
        # nn.StackedDense reports every slice, so all of them are here
        sub = dict(capture.get_path(new_grads, path))
        sub['kernel'] = jnp.stack(
            [slices[e] for e in range(sub['kernel'].shape[0])]
        ).astype(sub['kernel'].dtype)
        new_grads = capture.set_path(new_grads, path, sub)
    return new_grads
