"""Fused Pallas TPU kernels for the K-FAC capture hot path.

The per-step capture cost (ROADMAP item 2) is four XLA-scheduled passes
over the same activations/gradients, each paying its own HBM round trip:

  extract_patches -> A/G statistic GEMMs -> EMA update -> wire quantize

This module fuses them (`ops/pallas_attention.py` is the in-repo idiom
exemplar):

- :func:`compute_a_conv` builds im2col patch rows IN-KERNEL from the
  (zero-padded) NHWC activation tile and feeds them straight into the
  A-factor covariance GEMM — the ``[N*OH*OW, kh*kw*C]`` patch matrix is
  never materialized in HBM;
- :func:`compute_a_dense` / :func:`compute_g_dense` /
  :func:`compute_g_conv` run the statistic GEMM with the row scalings
  (batch-averaged undo, spatial normalization, bias ones-column) applied
  to the tile in VMEM;
- every kernel takes an optional ``ema=(current, alpha)`` epilogue that
  folds ``ops.update_running_avg`` into the fp32 accumulator emit — the
  factor EMA stops being a separate elementwise pass over ``[F, F]``;
- :func:`ef_quantize` is the wire-dtype epilogue of the compressed
  factor reduce (PR 8): one pass producing both the bf16 wire payload
  and the error-feedback residual, replacing the two-pass
  add/cast/subtract chain in ``collectives.pmean_scatter_ef``. The
  collective itself (psum_scatter) stays outside — fusion moves compute,
  not wire bytes (pinned by scripts/comm_count.py's ``+pallas`` spec).

Numerical contract (pinned by tests/test_pallas_capture.py under the
Pallas interpreter on CPU): every STAT kernel reproduces the
corresponding ``ops/factors.py`` reference BIT-FOR-BIT when the whole
row reduction fits one grid step (the default tile below the VMEM
budget) — same elementwise scalings in the same order, one
``dot_general`` of the same shape with ``preferred_element_type=f32``,
with strict-mode pins (``_pin``/``_div``) holding XLA's jit-time
rewrites (reciprocal-multiply, scalar hoisting across the dot) to the
reference's eager rounding sequence. Multi-tile runs accumulate the
same fp32 partial products in row-tile order (value-equal up to fp32
summation order). The EMA epilogue is the exception: its final
``cur*(1-a) + stat*a`` combine FMA-contracts under any jit (barriers
do not stop LLVM contraction on CPU), so it is pinned as algebraically
identical, deterministic across steps, and within one fp32 rounding of
the unfused program — while the statistic feeding it stays bitwise.
The conv A and conv G kernels scale their rows as ``factors.compute_a_conv``
and ``compute_g_conv`` did before those became one contraction with the
scale on the product (PR 26): their bits are pinned to that row-scaled
form (kept as tests/factor_oracles.py), their value to the current one.

Implementation selection follows the repo convention ('xla' | 'pallas' |
'auto'): :func:`interpret_default` returns True off-TPU so the same
traced program runs under the interpreter in the CPU test tier.
"""

import collections
import contextlib
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kfac_pytorch_tpu.ops import factors as _ref

#: fp32 elements a row tile may occupy (~1 MiB unpadded).
_TILE_ELEMS = 1 << 18

#: largest fused factor dimension: the kernels keep the full [F, F]
#: fp32 accumulator in VMEM scratch (F=1024 -> 4 MiB); a wider factor
#: is routed to the XLA reference (see routing_report). KFAC_CAPTURE_MAX_F
#: overrides (an on-chip sweep knob, like KFAC_FLASH_TQ/TK).
_MAX_FUSED_F = 1024

#: VMEM one kernel may plan for, handed to Mosaic as the scoped limit
#: (v5e's default scope is 16 MiB of its 128 MiB): the F=1024 kernels
#: hold the [F, F] fp32 accumulator, the double-buffered output and EMA
#: operand (20 MiB) beside the row tile.
_VMEM_LIMIT = 64 << 20

_WARNED = set()

#: per-reason routing counts of the capture pass being traced (engine
#: opens one :func:`routing_report` around its per-layer loop)
_TALLY = None

_ROUTE_REASONS = {'cap': 'factor dim over the fused cap',
                  'vmem': 'image tile over the VMEM limit'}


def _warn_once(key, msg):
    if key not in _WARNED:
        _WARNED.add(key)
        import sys
        # host-side stderr warning, keyed once per process; no traced
        # value flows through it
        print(f'kfac_pytorch_tpu: {msg}',  # kfac-lint: disable=trace-purity
              file=sys.stderr)


def _route_fused():
    if _TALLY is not None:
        _TALLY['fused'] += 1


def _route_xla(key, reason, msg):
    """A statistic that asked for the fused kernel and cannot have it:
    counted for the pass's one report, or — called outside a pass —
    warned about once per shape. Never silent."""
    if _TALLY is not None:
        _TALLY[reason] += 1
    else:
        _warn_once(key, msg + ' — this statistic stays on the XLA path')


@contextlib.contextmanager
def routing_report():
    """Count the fused / XLA-routed statistics of one capture pass and
    report them in ONE stderr line per run (per distinct outcome — every
    compiled step variant re-traces the same pass)."""
    # trace-time bookkeeping of STATIC routing decisions (shapes only):
    # no traced value flows in or out, nothing bakes into the program
    # kfac-lint: disable=trace-purity -- host-side routing tally
    global _TALLY
    outer, _TALLY = _TALLY, collections.Counter()
    try:
        yield
    finally:
        tally, _TALLY = _TALLY, outer
        fused = tally.pop('fused', 0)
        if tally:
            why = ', '.join(f'{n} x {_ROUTE_REASONS[r]}'
                            for r, n in sorted(tally.items()))
            msg = (f'capture_impl=pallas: {fused} factor statistics fused, '
                   f'{sum(tally.values())} on the XLA path ({why})')
            _warn_once(msg, msg)


def _vmem_bytes(blocks):
    """VMEM bytes of ``(shape, dtype, copies)`` blocks as Mosaic lays
    them out: the minor dim padded to 128 lanes, the second-minor to the
    dtype's sublane tile (8 rows of 32 bits)."""
    total = 0
    for shape, dtype, copies in blocks:
        item = jnp.dtype(dtype).itemsize
        sub = 8 * max(1, 4 // item)
        *major, rows, lanes = shape
        n = -(-rows // sub) * sub * -(-lanes // 128) * 128 * item
        for d in major:
            n *= d
        total += n * copies
    return total


def interpret_default():
    """Run the kernels under the Pallas interpreter off-TPU — the CPU
    tier-1 / simulated-mesh path (same convention as ring_attention's
    'pallas_interpret' block impl)."""
    return jax.default_backend() != 'tpu'


def _max_fused_f():
    # deliberate trace-time shape knob (the KFAC_FLASH_TQ/TK
    # precedent): moves the fused-vs-fallback split, never a traced
    # value; declared in envspec.py
    # kfac-lint: disable=trace-purity -- trace-time shape knob
    raw = os.environ.get('KFAC_CAPTURE_MAX_F')
    if raw is None:
        return _MAX_FUSED_F
    try:
        return int(raw)
    except ValueError:
        _warn_once('KFAC_CAPTURE_MAX_F',
                   f'KFAC_CAPTURE_MAX_F={raw!r} is not an int — using '
                   f'the default cap {_MAX_FUSED_F}')
        return _MAX_FUSED_F


def _row_tile(rows, elems_per_row):
    """Rows per grid step: the WHOLE reduction when it fits the VMEM
    budget (one grid step = one dot_general with the reference's exact
    shape — the bit-identity case), else the largest divisor of ``rows``
    under the budget. KFAC_CAPTURE_TR overrides (trace-time knob, like
    KFAC_FLASH_TQ/TK — lowered to the nearest divisor)."""
    # deliberate trace-time tiling knob (the KFAC_FLASH_TQ/TK
    # precedent): picks the grid split, never a traced value; declared
    # in envspec.py
    # kfac-lint: disable=trace-purity -- trace-time tiling knob
    raw = os.environ.get('KFAC_CAPTURE_TR')
    cap = max(1, _TILE_ELEMS // max(1, elems_per_row))
    if raw is not None:
        try:
            cap = max(1, int(raw))
        except ValueError:
            _warn_once('KFAC_CAPTURE_TR',
                       f'KFAC_CAPTURE_TR={raw!r} is not an int — using '
                       'the default VMEM-budget tile')
    t = max(1, min(cap, rows))
    while rows % t:
        t -= 1
    return t


def _vma(*arrays):
    """Union of the varying-manual-axes of the inputs — under shard_map
    the outputs vary over every axis the inputs do (the
    pallas_attention.py idiom)."""
    vma = frozenset()
    for x in arrays:
        vma = vma | jax.typeof(x).vma
    return vma


def _params(interpret, semantics):
    if interpret:
        return {}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


def _precision(dtype):
    """Sub-f32 operands take the MXU's native pass whatever
    ``jax_default_matmul_precision`` says — Mosaic refuses an fp32
    contraction of bf16 operands ("Bad lhs type"), and bf16 x bf16
    products are exact in the f32 accumulator anyway. f32 operands keep
    the ambient precision, like the ops/factors.py reference."""
    return None if dtype == jnp.float32 else lax.Precision.DEFAULT


def _pin(v, strict):
    """Pin an intermediate against reassociation. The eager reference
    (ops/factors.py) rounds after every op; the interpreter runs the
    whole kernel under one jit, where XLA's algebraic simplifier hoists
    scalar scalings across the dot (``dot(x*c, y) -> dot(x, y)*c``) and
    fuses mul+add into FMAs — one rounding where the reference has two.
    Strict (interpret) mode inserts an optimization barrier after each
    rounding step so the bit pattern matches the reference exactly; the
    Mosaic path skips them (no XLA simplifier runs inside the kernel,
    and the barrier may not lower)."""
    return lax.optimization_barrier(v) if strict else v


def _div(v, denom, strict):
    """True division matching the eager reference bit-for-bit: under a
    jit, XLA rewrites ``x / const`` into ``x * (1/const)`` — a
    different rounding whenever the reciprocal is inexact. Hiding the
    denominator behind a barrier (strict mode) forces the real divide
    instruction, exactly what the eager ``ops/factors.py`` ops emit."""
    if strict:
        denom = lax.optimization_barrier(jnp.float32(denom))
    return v / denom


def _ema_static(ema):
    """An EMA epilogue is foldable only with a STATIC decay (the
    preconditioner's python-float ``factor_decay``); a traced alpha
    cannot be closed over by the kernel — callers two-pass it."""
    return (ema is not None
            and isinstance(ema[1], (int, float))
            and not isinstance(ema[1], bool))


def _apply_ema(stat, ema):
    if ema is None:
        return stat
    cur, alpha = ema
    return _ref.update_running_avg(stat, cur, alpha)


# ---------------------------------------------------------------------------
# generic row-tiled statistic GEMM (dense A/G, conv G)
# ---------------------------------------------------------------------------

def _stat_kernel(*refs, denom, mults, append_ones, nsteps, ema_alpha,
                 has_ema, strict):
    if has_ema:
        x_ref, cur_ref, o_ref, acc_ref = refs
    else:
        x_ref, o_ref, acc_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = x_ref[...]
    # same elementwise scalings in the same order as ops/factors.py
    # (g*n then g*spatial; the ones column appended in the input dtype)
    for m in mults:
        t = _pin(t * m, strict)
    if append_ones:
        t = jnp.concatenate(
            [t, jnp.ones(t.shape[:-1] + (1,), t.dtype)], axis=-1)
    acc_ref[...] += lax.dot_general(
        t, _pin(_div(t, denom, strict), strict),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_precision(t.dtype),
        preferred_element_type=jnp.float32).astype(jnp.float32)

    @pl.when(i == nsteps - 1)
    def _emit():
        acc = acc_ref[...]
        if has_ema:
            # ops.update_running_avg folded into the accumulator emit:
            # current*(1-alpha) + new*alpha. The complement is computed
            # in f32 arithmetic (1.0 - f32(alpha)) because that is
            # EXACTLY what the reference does — update_running_avg
            # converts alpha to the factor dtype before subtracting
            alpha = jnp.float32(ema_alpha)
            acc = (_pin(cur_ref[...] * (1.0 - alpha), strict)
                   + _pin(acc * alpha, strict))
        o_ref[...] = acc


def _stat_rows(rows, denom, *, mults=(), append_ones=False, ema=None,
               interpret=False):
    """``rows^T @ (rows/denom)`` in fp32 with the row prep fused into
    the tile load — the Pallas counterpart of ``factors._stat_gemm``
    plus its callers' elementwise prep."""
    nrows, d = rows.shape
    f = d + 1 if append_ones else d
    has_ema = _ema_static(ema)
    two_pass_ema = ema if (ema is not None and not has_ema) else None
    tr = _row_tile(nrows, d)
    nsteps = nrows // tr
    kernel = functools.partial(
        _stat_kernel, denom=denom, mults=tuple(mults),
        append_ones=append_ones, nsteps=nsteps,
        ema_alpha=(float(ema[1]) if has_ema else 0.0), has_ema=has_ema,
        strict=interpret)
    in_specs = [pl.BlockSpec((tr, d), lambda i: (i, 0))]
    operands = [rows]
    vma_args = [rows]
    if has_ema:
        in_specs.append(pl.BlockSpec((f, f), lambda i: (0, 0)))
        operands.append(ema[0])
        vma_args.append(ema[0])
    out = pl.pallas_call(
        kernel,
        name='kfac_stat_rows',
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nsteps,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((f, f), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((f, f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((f, f), jnp.float32,
                                       vma=_vma(*vma_args)),
        interpret=interpret,
        # the row-tile grid carries the accumulator recurrence in
        # scratch -> must stay serial
        **_params(interpret, ('arbitrary',)))(*operands)
    return _apply_ema(out, two_pass_ema)


# ---------------------------------------------------------------------------
# conv A: patch extraction fused into the covariance GEMM
# ---------------------------------------------------------------------------

def _conv_a_kernel(*refs, taps, oh, ow, n, spatial, append_ones, nsteps,
                   ema_alpha, has_ema, strict):
    if has_ema:
        x_ref, cur_ref, o_ref, acc_ref = refs
    else:
        x_ref, o_ref, acc_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tn, _, _, _, c = x_ref.shape    # [tn, phases, Hq, Wq, C] (zero-padded)
    # im2col built in VMEM: one unit-stride window per (ki, kj) tap out
    # of the tap's stride phase (compute_a_conv de-interleaved the
    # strides away — Mosaic lowers neither a strided slice of a loaded
    # value nor a strided ref load of bf16 / C != 128), concatenated
    # feature-last -> (kh, kw, c) feature order, matching HWIO kernel
    # flattening (factors.extract_patches)
    cols = [x_ref[:, ph, pl.ds(r0, oh), pl.ds(c0, ow), :]
            for ph, r0, c0 in taps]      # each [tn, oh, ow, c]
    rows = jnp.concatenate(cols, axis=-1).reshape(tn * oh * ow,
                                                  len(taps) * c)
    if append_ones:
        rows = jnp.concatenate(
            [rows, jnp.ones(rows.shape[:-1] + (1,), rows.dtype)], axis=-1)
    rows = _pin(_div(rows, spatial, strict), strict)
    acc_ref[...] += lax.dot_general(
        rows, _pin(_div(rows, n, strict), strict),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_precision(rows.dtype),
        preferred_element_type=jnp.float32).astype(jnp.float32)

    @pl.when(i == nsteps - 1)
    def _emit():
        acc = acc_ref[...]
        if has_ema:
            # f32-arithmetic complement, like _stat_kernel's emit
            alpha = jnp.float32(ema_alpha)
            acc = (_pin(cur_ref[...] * (1.0 - alpha), strict)
                   + _pin(acc * alpha, strict))
        o_ref[...] = acc


# ---------------------------------------------------------------------------
# public API — signatures mirror ops/factors.py plus (ema=, interpret=)
# ---------------------------------------------------------------------------

def compute_a_dense(a, use_bias, *, ema=None, interpret=False):
    """Pallas :func:`factors.compute_a_dense` with the bias ones-column
    and the optional EMA epilogue fused. ``ema=(current [F, F] f32,
    alpha)`` returns ``update_running_avg(stat, current, alpha)``."""
    if a.ndim > 2:
        a = a.mean(axis=tuple(range(1, a.ndim - 1)))
    n = a.shape[0]
    f = a.shape[1] + (1 if use_bias else 0)
    if f > _max_fused_f():
        _route_xla(f'a_dense:{f}', 'cap',
                   f'capture: dense A factor dim {f} exceeds the fused cap')
        return _apply_ema(_ref.compute_a_dense(a, use_bias), ema)
    _route_fused()
    return _stat_rows(a, n, append_ones=use_bias, ema=ema,
                      interpret=interpret)


def compute_g_dense(g, batch_averaged=True, *, ema=None, interpret=False):
    """Pallas :func:`factors.compute_g_dense` (batch-averaged undo fused
    into the tile load)."""
    if g.ndim > 2:
        g = g.mean(axis=tuple(range(1, g.ndim - 1)))
    n = g.shape[0]
    if g.shape[1] > _max_fused_f():
        _route_xla(f'g_dense:{g.shape[1]}', 'cap',
                   f'capture: dense G factor dim {g.shape[1]} exceeds '
                   'the fused cap')
        return _apply_ema(_ref.compute_g_dense(g, batch_averaged), ema)
    _route_fused()
    return _stat_rows(g, n, mults=((n,) if batch_averaged else ()),
                      ema=ema, interpret=interpret)


def compute_g_conv(g, batch_averaged=True, *, ema=None, interpret=False):
    """Pallas :func:`factors.compute_g_conv` (the N and spatial scalings
    applied to the tile in VMEM, in the reference's order)."""
    n = g.shape[0]
    spatial = g.shape[1] * g.shape[2]
    rows = g.reshape(-1, g.shape[-1])
    if rows.shape[1] > _max_fused_f():
        _route_xla(f'g_conv:{rows.shape[1]}', 'cap',
                   f'capture: conv G factor dim {rows.shape[1]} exceeds '
                   'the fused cap')
        return _apply_ema(_ref.compute_g_conv(g, batch_averaged), ema)
    _route_fused()
    mults = (n, spatial) if batch_averaged else (spatial,)
    return _stat_rows(rows, rows.shape[0], mults=mults, ema=ema,
                      interpret=interpret)


def compute_a_conv(a, kernel_size, strides, padding, use_bias, *,
                   ema=None, interpret=False):
    """Pallas :func:`factors.compute_a_conv` with patch extraction fused
    into the covariance GEMM: the kernel slices the im2col taps out of
    the zero-padded NHWC activation tile in VMEM and contracts them
    directly — the ``[N*OH*OW, kh*kw*C]`` patch matrix never lands in
    HBM. Batch images ride the serial grid; the fp32 ``[F, F]``
    accumulator lives in scratch."""
    n, h, w, c = a.shape
    kh, kw = kernel_size
    sh, sw = strides
    f = kh * kw * c + (1 if use_bias else 0)
    if f > _max_fused_f():
        _route_xla(f'a_conv:{f}', 'cap',
                   f'capture: conv A factor dim {f} exceeds the fused cap')
        return _apply_ema(
            _ref.compute_a_conv(a, kernel_size, strides, padding,
                                use_bias), ema)
    (pt, pb), (pl_, pr) = _ref.explicit_pads(padding, (h, w), kernel_size,
                                             strides)
    hp, wp = h + pt + pb, w + pl_ + pr
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sw + 1
    spatial = oh * ow
    # stride phases the taps touch, in first-use order: tap (ki, kj)
    # reads rows ki + sh*i = phase ki % sh at offset ki // sh — a
    # unit-stride window once the phases are de-interleaved
    phases = list(dict.fromkeys(
        (ki % sh, kj % sw) for ki in range(kh) for kj in range(kw)))
    taps = tuple((phases.index((ki % sh, kj % sw)), ki // sh, kj // sw)
                 for ki in range(kh) for kj in range(kw))
    hq, wq = -(-hp // sh), -(-wp // sw)
    has_ema = _ema_static(ema)
    blocks = [((1, len(phases), hq, wq, c), a.dtype, 2),    # input tile
              ((len(taps), oh, ow, c), a.dtype, 1),         # live taps
              ((spatial, f), a.dtype, 2),                   # patch rows
              ((f, f), jnp.float32, 5 if has_ema else 3)]   # acc/out/ema
    if _vmem_bytes(blocks) > _VMEM_LIMIT:
        _route_xla(f'a_conv:{h}x{w}x{c}:{kh}x{kw}', 'vmem',
                   f'capture: conv A {kh}x{kw}/{sh} on [{h},{w},{c}] needs '
                   f'{_vmem_bytes(blocks) >> 20} MiB of VMEM per image')
        return _apply_ema(
            _ref.compute_a_conv(a, kernel_size, strides, padding,
                                use_bias), ema)
    _route_fused()
    # zero-pad (identical values to the reference's
    # conv_general_dilated_patches padding) up to whole strides, then
    # de-interleave the stride phases — both plain XLA data movement
    # over the INPUT, never the kh*kw-times-larger patch matrix
    xpad = jnp.pad(a, ((0, 0), (pt, pb + hq * sh - hp),
                       (pl_, pr + wq * sw - wp), (0, 0)))
    xpad = xpad.reshape(n, hq, sh, wq, sw, c)
    xph = jnp.stack([xpad[:, :, p, :, q, :] for p, q in phases], axis=1)
    two_pass_ema = ema if (ema is not None and not has_ema) else None
    # per-image VMEM footprint: the padded input tile + the in-flight
    # patch rows
    tn = _row_tile(n, len(phases) * hq * wq * c + spatial * f)
    nsteps = n // tn
    kernel = functools.partial(
        _conv_a_kernel, taps=taps, oh=oh, ow=ow, n=n,
        spatial=spatial, append_ones=use_bias, nsteps=nsteps,
        ema_alpha=(float(ema[1]) if has_ema else 0.0), has_ema=has_ema,
        strict=interpret)
    in_specs = [pl.BlockSpec((tn, len(phases), hq, wq, c),
                             lambda i: (i, 0, 0, 0, 0))]
    operands = [xph]
    vma_args = [xph]
    if has_ema:
        in_specs.append(pl.BlockSpec((f, f), lambda i: (0, 0)))
        operands.append(ema[0])
        vma_args.append(ema[0])
    out = pl.pallas_call(
        kernel,
        name='kfac_conv_a',
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nsteps,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((f, f), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((f, f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((f, f), jnp.float32,
                                       vma=_vma(*vma_args)),
        interpret=interpret,
        **_params(interpret, ('arbitrary',)))(*operands)
    return _apply_ema(out, two_pass_ema)


# ---------------------------------------------------------------------------
# wire-quantize + error-feedback epilogue (the compressed-reduce prep)
# ---------------------------------------------------------------------------

def _ef_kernel(x_ref, r_ref, w_ref, nr_ref):
    xc = x_ref[...] + r_ref[...]
    wire = xc.astype(jnp.bfloat16)
    w_ref[...] = wire
    nr_ref[...] = xc - wire.astype(x_ref.dtype)


def ef_quantize(x, residual, *, interpret=False):
    """One fused pass producing ``(wire bf16, new_residual)`` from the
    stacked stats and the error-feedback residual — the exact
    ``xc = x + r; wire = bf16(xc); r' = xc - f32(wire)`` algebra of
    ``collectives.pmean_scatter_ef``, emitted as a single Pallas kernel
    so the compressed reduce stops paying a separate elementwise pass.
    The psum_scatter stays with the caller: the wire VALUES (hence the
    ledger bytes) are byte-identical to the two-pass path."""
    assert x.shape == residual.shape, (x.shape, residual.shape)
    rows = x.shape[0]
    tail = x.shape[1:]
    elems = 1
    for d in tail:
        elems *= d
    tr = _row_tile(rows, elems)
    nsteps = rows // tr
    blk = (tr,) + tail
    idx = lambda i: (i,) + (0,) * len(tail)  # noqa: E731
    vma = _vma(x, residual)
    wire, new_residual = pl.pallas_call(
        _ef_kernel,
        name='kfac_ef_quantize',
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nsteps,),
            in_specs=[pl.BlockSpec(blk, idx), pl.BlockSpec(blk, idx)],
            out_specs=[pl.BlockSpec(blk, idx), pl.BlockSpec(blk, idx)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.bfloat16, vma=vma),
            jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
        ],
        interpret=interpret,
        **_params(interpret, ('parallel',)))(x, residual)
    return wire, new_residual
