"""Thin collective wrappers with a degenerate world=1 path.

The reference guards every collective behind ``backend.comm.size() > 1``
(kfac_preconditioner_base.py:204-221) so single-process runs exercise the
full math path with zero comm; passing ``axis_name=None`` here gives the
same property. With an axis name, these lower to XLA collectives scheduled
over ICI (psum / all-gather), which also subsume the reference's tcmm
multi-stream overlap (communicator.cpp:62-72) via XLA async scheduling.
"""

import jax
import jax.numpy as jnp
from jax import lax


def pmean(x, axis_name):
    if axis_name is None:
        return x
    return lax.pmean(x, axis_name)


def psum(x, axis_name):
    if axis_name is None:
        return x
    return lax.psum(x, axis_name)


def all_gather_rows(x, axis_name):
    """Concatenate per-device row blocks along axis 0 (device-major) —
    the owner-broadcast replacement: owners hold their rows, the gather
    replicates all rows everywhere (reference broadcast-from-owner:
    kfac_preconditioner_eigen.py:122-134, inv.py:164-175).

    Implemented as scatter-to-own-offset + psum rather than
    ``lax.all_gather`` so shard_map's varying-manual-axes checker can
    statically prove the result replicated (all_gather output is not
    inferred invariant in current JAX); XLA lowers the masked psum to an
    ICI collective either way.
    """
    if axis_name is None:
        return x
    n = lax.axis_size(axis_name)
    per = x.shape[0]
    full = jnp.zeros((n * per,) + x.shape[1:], x.dtype)
    full = lax.dynamic_update_slice_in_dim(
        full, x, lax.axis_index(axis_name) * per, axis=0)
    return lax.psum(full, axis_name)


def average_grads(grads, axis_name):
    """Data-parallel gradient averaging inside shard_map.

    JAX's vma-aware shard_map already psums the gradient of a varying loss
    w.r.t. replicated (invariant) params — the allreduce the reference gets
    from hvd.DistributedOptimizer / DDP (examples/pytorch_cifar10_resnet.py:
    252-264) is inserted automatically by autodiff. With a per-device
    local-mean loss that psum yields the *sum* of shard means, so dividing
    by the axis size gives the global-batch average (Horovod's
    ``op=Average``). Tap gradients are varying, hence stay local — exactly
    the per-device ``g`` DP-KFAC's factor statistics need.
    """
    if axis_name is None:
        return grads
    n = lax.axis_size(axis_name)
    return jax.tree.map(lambda g: g / n, grads)


def axis_index(axis_name):
    if axis_name is None:
        return jnp.int32(0)
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    if axis_name is None:
        return 1
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# Compression-aware collectives (comm_precision)
# ---------------------------------------------------------------------------
#
# The factor collectives dominate K-FAC's comm budget (reference
# time_breakdown.py ledger: FactorComm 0.300 s / InverseComm 0.146 s at
# 64 ranks); every payload here is either an EMA input (factor stats) or
# a decomposition the pred path damps anyway, so low-precision wire
# formats are safe in a way raw-gradient compression is not. Three wire
# dtypes:
#
#   'fp32'  the exact baseline — every function below is bit-identical
#           to its uncompressed counterpart;
#   'bf16'  cast to bfloat16 on the wire (2x byte reduction), with an
#           error-feedback residual on the reduce path;
#   'int8'  per-leading-row absmax int8 quantization for the gather
#           collectives (4x + a [rows] fp32 scale vector). The REDUCE
#           path floors at bf16 even under 'int8': an XLA all-reduce
#           accumulates in the operand dtype, and int8 partial sums
#           overflow at world >= 2 — see reduce_wire_dtype.
#
# ``axis_name=None`` is always the zero-comm identity path: no cast, no
# quantization, no residual mutation — world=1 stays bit-exact.

WIRE_DTYPES = ('fp32', 'bf16', 'int8')

#: fp32 payload-byte multiplier per wire dtype (int8 ignores the
#: [rows]-scale side channel, which is O(rows) vs the O(rows*D*D) body).
WIRE_COMPRESSION = {'fp32': 1.0, 'bf16': 0.5, 'int8': 0.25}


def check_wire_dtype(comm_precision):
    if comm_precision not in WIRE_DTYPES:
        raise ValueError(f'comm_precision must be one of {WIRE_DTYPES}, '
                         f'got {comm_precision!r}')
    return comm_precision


def reduce_wire_dtype(comm_precision):
    """Wire dtype actually used by the REDUCE collectives: int8 degrades
    to bf16 because an XLA all-reduce accumulates in the operand dtype
    and int8 partial sums overflow (127 * world > 127). The gathers keep
    full int8 — each element has exactly one contributor."""
    return 'bf16' if comm_precision == 'int8' else comm_precision


def quantize_rows(x):
    """Per-leading-row symmetric int8 quantization: ``scale[r] =
    absmax(x[r]) / 127``, ``q = round(x / scale)``. An all-zero row gets
    scale 0 and quantizes (and dequantizes) to exact zeros."""
    absmax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)))
    scale = absmax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    shaped = scale.reshape(scale.shape + (1,) * (x.ndim - 1))
    shaped_safe = safe.reshape(shaped.shape)
    q = jnp.clip(jnp.round(x / shaped_safe), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_rows(q, scale, dtype=jnp.float32):
    shaped = scale.reshape(scale.shape + (1,) * (q.ndim - 1))
    return q.astype(dtype) * shaped.astype(dtype)


def _lossy(x, comm_precision):
    return (comm_precision != 'fp32'
            and jnp.issubdtype(x.dtype, jnp.floating))


def pmean_wire(x, axis_name, comm_precision='fp32'):
    """pmean over a low-precision wire (no error feedback): the operand
    is cast to the reduce wire dtype, summed by the collective in that
    dtype, and the mean is taken in fp32. Used where no persistent
    residual state exists (E-KFAC scale moments)."""
    if axis_name is None or not _lossy(x, comm_precision):
        return pmean(x, axis_name)
    wire = x.astype(jnp.bfloat16)
    total = lax.psum(wire, axis_name).astype(x.dtype)
    return total / lax.axis_size(axis_name)


def pmean_scatter_ef(x, axis_name, comm_precision, residual, fused=False):
    """Mean-reduce ``x`` across the axis and return THIS device's row
    block of the result (axis 0 is device-major-tiled, the stacked-
    bucket layout of plan.py) — a reduce-scatter, because the factor
    stats' only consumer is each owner's own row slice: an all-reduce
    would ship every row everywhere only to be sliced, ~2x the wire
    traffic and P x the materialized result for nothing.

    Lossy modes add error feedback (EF-SGD lineage: Seide et al. 2014,
    Karimireddy et al. 2019): each device sends ``Q(x + r)`` over the
    wire and carries ``r' = (x + r) - Q(x + r)`` — the quantization
    error re-enters the NEXT reduce instead of being lost, so the
    time-averaged contribution of every device is unbiased. Exactly the
    right shape for the A/G factor statistics, whose consumer is an EMA.
    The wire floors at bf16 even under 'int8' (reduce_wire_dtype): the
    collective must ARITHMETICALLY accumulate, and integer partial sums
    overflow. (Backends without native bf16 reduction — the CPU test
    mesh — promote the bf16 wire back to f32; EF still compensates the
    bf16 rounding the operand went through.)

    Returns ``(local_mean_rows, new_residual)``. ``residual`` may be
    None (fp32 mode) — passed through untouched. ``axis_name=None`` is
    the identity path: ``(x, residual)``, no compression, no residual
    mutation, full rows (P=1 owns everything).

    ``fused=True`` computes the lossy branch's quantize + residual prep
    as ONE Pallas pass (:func:`ops.pallas_capture.ef_quantize`, ISSUE
    19) instead of the three elementwise ops below — same xc/bf16/EF
    algebra, same wire values, so the FactorComm ledger bytes are
    unchanged (pinned by scripts/comm_count.py's ``+pallas`` spec). The
    psum_scatter itself stays out here: fusion moves compute, not wire
    bytes.
    """
    if axis_name is None:
        return x, residual
    n = lax.axis_size(axis_name)
    if not _lossy(x, comm_precision):
        red = lax.psum_scatter(x, axis_name, scatter_dimension=0,
                               tiled=True)
        return red / n, residual
    assert residual is not None, (
        'lossy pmean_scatter_ef requires an error-feedback residual '
        '(init the KFAC state with comm_precision set, see '
        'KFACState.comm_err)')
    if fused:
        from kfac_pytorch_tpu.ops import pallas_capture as _pc
        wire, new_residual = _pc.ef_quantize(
            x, residual, interpret=_pc.interpret_default())
    else:
        xc = x + residual
        # the bf16 rounding is taken with reduce_precision, which XLA
        # never elides. Written as xc - f32(bf16(xc)), the TPU
        # compiler's excess-precision pass folds the f32->bf16->f32
        # round trip away and the residual comes out as exactly 0 (seen
        # on a v5e, PERF.md PR 21): an error feedback feeding back
        # nothing. Same round-to-nearest-even bits as the cast.
        rounded = lax.reduce_precision(xc, exponent_bits=8,
                                       mantissa_bits=7)
        wire = rounded.astype(jnp.bfloat16)
        new_residual = xc - rounded
    red = lax.psum_scatter(wire, axis_name, scatter_dimension=0,
                           tiled=True).astype(x.dtype)
    return red / n, new_residual


def decomp_exchange_gather(x, axis_name, comm_precision='fp32'):
    """The mesh-sharded decomposition exchange collective: an
    :func:`all_gather_rows_compressed` under the ``kfac.DecompComm``
    named scope, so BOTH legs of the shard round trip (damped cohort
    factors out, decomposed results back) land in their own ledger
    phase — scripts/comm_count.py attributes by op_name scope, and the
    first-match taxonomy puts DecompComm ahead of the
    CommunicateInverse scope these gathers would otherwise inherit
    from the surrounding stagger phase. The byte price is modeled in
    closed form by ``FactorPlan.comm_volume(decomp_shard=...)`` and the
    two must agree byte-for-byte (the COMM_COUNT_ASSERT pin)."""
    with jax.named_scope('kfac.DecompComm'):
        return all_gather_rows_compressed(x, axis_name, comm_precision)


def all_gather_rows_compressed(x, axis_name, comm_precision='fp32'):
    """:func:`all_gather_rows` over a low-precision wire. bf16 ships the
    payload as bitcast uint16 (2 bytes — the integer wire survives every
    backend's float-normalization passes, where a bf16 SUM would be
    promoted back to f32); int8 sends per-leading-row absmax-scaled int8
    plus the [rows] fp32 scale vector (a second, O(rows) gather).
    Non-float payloads and ``axis_name=None`` pass through uncompressed.

    The masked-psum implementation is quantization-exact: every output
    element has exactly ONE non-zero contributor (its owner), so the
    integer sum reconstructs the owner's wire value bit-for-bit — the
    only loss is the owner's local quantization, never accumulation.
    """
    if axis_name is None or not _lossy(x, comm_precision):
        return all_gather_rows(x, axis_name)
    if comm_precision == 'bf16':
        wire = lax.bitcast_convert_type(x.astype(jnp.bfloat16),
                                        jnp.uint16)
        full = lax.bitcast_convert_type(all_gather_rows(wire, axis_name),
                                        jnp.bfloat16)
        return full.astype(x.dtype)
    q, scale = quantize_rows(x)
    qg = all_gather_rows(q, axis_name)
    sg = all_gather_rows(scale, axis_name)
    return dequantize_rows(qg, sg, x.dtype)
