"""Activation / output-gradient capture — the TPU replacement for torch hooks.

The reference captures per-layer inputs ``a`` with forward-pre-hooks and
output-gradients ``g`` with full-backward-hooks (reference:
kfac/kfac_preconditioner_base.py:122-149). JAX has no hooks; this module
implements the functional equivalent:

- **activations**: KFAC-aware layers (``kfac_pytorch_tpu.nn``) ``sow`` their
  input into the ``'kfac_a'`` Flax collection, returned as auxiliary output
  of ``apply`` when that collection is marked mutable.
- **output-gradients**: each layer adds a zero-valued *tap* variable (from
  the ``'kfac_tap'`` collection) to its pre-activation output
  ``y = y + tap``. Differentiating the loss w.r.t. the taps yields exactly
  ``dL/dy`` — the backward-hook ``grad_output`` — in the *same* backward
  pass that produces the parameter gradients.
- **static layer metadata** (kind, dims, conv geometry, param paths) is
  recorded at trace time through a thread-local registry, once, at setup
  (``collect_layer_meta``) — the analogue of ``_register_module_hooks``
  walking ``model.modules()``.

The capture cost is paid only in training steps that update factors
(``steps % fac_update_freq == 0`` gating lives in the trainer, which picks a
compiled step variant without capture otherwise — same semantics as the
hook gating at kfac/kfac_preconditioner_base.py:122-130).
"""

import dataclasses
import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Collection names.
ACTS = 'kfac_a'    # sown layer inputs
TAPS = 'kfac_tap'  # differentiable zero taps on layer outputs
#: what the model counts for the step's metrics (a router's dropped rows):
#: float32 scalars the model keeps in this collection; the trainer hands it
#: to ``build_train_step(extra_mutable=...)`` and every step's metrics hold
#: its leaves under their '/'-joined names
COUNTERS = 'counters'


@dataclasses.dataclass(frozen=True)
class LayerMeta:
    """Static description of one KFAC-supported layer.

    The analogue of the reference's ``self.modules`` entries plus the
    geometry that ``ComputeA``/``ComputeG`` read off the torch module
    (reference: kfac/utils.py:78-140).
    """
    name: str                 # '/'.join(path) — stable registry key
    path: Tuple[str, ...]     # module path inside the params pytree
    kind: str                 # 'dense' | 'conv' | 'stacked'
    use_bias: bool
    in_dim: int               # true factor-A dim (incl. bias column)
    out_dim: int              # true factor-G dim
    kernel_shape: Tuple[int, ...]   # param 'kernel' shape
    kernel_size: Optional[Tuple[int, int]] = None   # conv only
    strides: Optional[Tuple[int, int]] = None       # conv only
    padding: Optional[Tuple[Tuple[int, int], Tuple[int, int]]] = None  # explicit
    # 'stacked' only: the layer is slice ``[index]`` of the leaf ``kernel
    # [E, d_in, d_out]`` at ``path`` (``nn.StackedDense``), with a factor
    # pair of its own from the rows that came to it
    index: Optional[int] = None
    # dense kinds only: the name of the first of the layers that were
    # called on the SAME array as this one (equal ``use_bias``, and for
    # stacked slices the same index and row weighting), itself included;
    # None for a layer that reads its input alone. Found during the
    # recorded trace (``collect_layer_meta``), never declared: the members
    # of a group have one ``A`` (``plan.build_plan``)
    input_group: Optional[str] = None

    @property
    def grad_shape(self):
        """Matrix-form gradient shape [out_dim, in_dim] (bias col included)."""
        return (self.out_dim, self.in_dim)


# ---------------------------------------------------------------------------
# Trace-time metadata registry
# ---------------------------------------------------------------------------

_REGISTRY = threading.local()


def _registry_active() -> bool:
    return getattr(_REGISTRY, 'active', False)


def report_layer(meta: LayerMeta, reads=()) -> None:
    """Called by kfac_pytorch_tpu.nn layers during a recorded trace.

    ``reads``: what the layer's ``A`` is made of, as the objects the layer
    was called with (its input array; a stacked layer's row counts and the
    size of the loss's mean beside it). Two dense layers whose LAST calls
    read the same objects have the same ``A``: :func:`input_groups`."""
    if _registry_active():
        _REGISTRY.layers[meta.name] = meta
        # the objects are kept until the trace is over, so that an id is
        # not handed out twice
        _REGISTRY.reads[meta.name] = tuple(reads)


class _record_layers:
    def __enter__(self):
        _REGISTRY.layers = {}
        _REGISTRY.reads = {}
        _REGISTRY.active = True
        return _REGISTRY.layers

    def __exit__(self, *exc):
        _REGISTRY.active = False
        return False


def input_groups(metas, reads):
    """``metas`` with ``input_group`` set on the dense and stacked layers
    that share their input: same array (the same object during the trace),
    same ``use_bias``, same kind and stacked index, same row weighting.
    A conv layer never shares (its patches depend on kernel and stride)."""
    members = {}    # what a layer's A is made of -> the layers, in order
    for name, meta in metas.items():
        if meta.kind != 'conv' and reads.get(name):
            # a Python number (the size of the loss's mean) by value, an
            # array by identity
            key = (meta.kind, meta.index, meta.use_bias) + tuple(
                r if isinstance(r, (int, float)) else id(r)
                for r in reads[name])
            members.setdefault(key, []).append(name)
    out = dict(metas)
    for names in members.values():
        if len(names) > 1:
            for name in names:
                out[name] = dataclasses.replace(metas[name],
                                                input_group=names[0])
    return out


def collect_layer_meta(model, variables, *args, exclude_vocabulary_size=None,
                       **kwargs):
    """Discover KFAC-supported layers by tracing one apply (zero FLOPs).

    Returns ``{name: LayerMeta}`` in call order. ``exclude_vocabulary_size``
    drops dense layers with that output dim — the tied-embedding pre-softmax
    exclusion (reference: kfac_preconditioner_base.py:139-140). Dense layers
    that were called on one array come back as an input group
    (:func:`input_groups`).
    """
    with _record_layers() as layers:
        jax.eval_shape(
            lambda v: model.apply(v, *args, mutable=True, **kwargs),
            variables)
        metas = input_groups(dict(layers), _REGISTRY.reads)
    _REGISTRY.reads = {}
    if exclude_vocabulary_size is not None:
        metas = filter_vocab_head(metas, exclude_vocabulary_size)
    return metas


def filter_vocab_head(metas, vocab_size):
    """Drop the pre-softmax head: the FINAL captured layer, iff it is a
    dense with ``out_dim == vocab_size``. The reference
    (kfac_preconditioner_base.py:139-140) matches by dim at any position;
    that blunt match silently drops interior layers that merely share the
    dim — e.g. a KFACLSTMCell's 4H gate projections when vocab ==
    4*hidden — so here only the last-called layer is excluded and other
    matches are kept with a warning."""
    names = list(metas)
    drop = set()
    if names:
        last = metas[names[-1]]
        if last.kind == 'dense' and last.out_dim == vocab_size:
            drop.add(names[-1])
    interior = [k for k in names if k not in drop
                and metas[k].kind == 'dense'
                and metas[k].out_dim == vocab_size]
    if interior:
        import warnings
        warnings.warn(
            f'layers {interior} match exclude_vocabulary_size={vocab_size} '
            'but are not the trailing pre-softmax head — keeping them '
            'preconditioned', stacklevel=2)
    return {k: m for k, m in metas.items() if k not in drop}


# ---------------------------------------------------------------------------
# Apply / init helpers
# ---------------------------------------------------------------------------

def init(model, rngs, *args, **kwargs):
    """``model.init`` that strips capture collections from the variables.

    During ``init`` all collections are mutable, so taps and sown
    activations would otherwise leak into the returned (checkpointable)
    variables dict.
    """
    variables = model.init(rngs, *args, **kwargs)
    variables = dict(variables)
    variables.pop(ACTS, None)
    variables.pop(TAPS, None)
    if COUNTERS in variables:
        # the initializing call counted too: a run starts from zero
        variables[COUNTERS] = jax.tree.map(jnp.zeros_like,
                                           variables[COUNTERS])
    return variables


def make_zero_taps(model, variables, *args, axis_name=None, **kwargs):
    """Build the zero-tap pytree for one batch shape via ``eval_shape`` (free
    at trace time). The returned pytree is the differentiable input whose
    gradient is ``{layer: dL/dy}``.

    ``axis_name``: REQUIRED inside shard_map over a data-parallel axis.
    Zero constants are device-invariant, and JAX's vma-aware autodiff psums
    gradients of invariant inputs across the axis — which would silently
    sum per-example output-gradients from different devices. Marking the
    taps varying keeps their gradients local (each device sees its own
    ``g``, the reference's per-rank hook semantics,
    kfac_preconditioner_base.py:127-130).
    """
    shapes = jax.eval_shape(
        lambda v: model.apply(v, *args, mutable=True, **kwargs),
        variables)
    tap_shapes = shapes[1][TAPS]
    taps = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tap_shapes)
    if axis_name is not None:
        taps = jax.tree.map(lambda t: jax.lax.pcast(t, to='varying',
                                                    axis_name=axis_name),
                            taps)
    return taps


def apply_with_capture(model, variables, *args, taps=None, mutable=(),
                       **kwargs):
    """Run ``model.apply`` with capture active.

    Args:
      variables: full variables dict (params, batch_stats, ...).
      taps: zero-tap pytree from :func:`make_zero_taps`; differentiate the
        loss w.r.t. it to obtain output-gradients.
      mutable: extra mutable collections (e.g. ``['batch_stats']``).

    Returns ``(outputs, acts, other_mutated)`` where ``acts`` is the
    ``{layer: a}`` activation pytree.
    """
    v = dict(variables)
    if taps is not None:
        v[TAPS] = taps
    out, mutated = model.apply(v, *args, mutable=[ACTS] + list(mutable),
                               **kwargs)
    mutated = dict(mutated)
    acts = mutated.pop(ACTS, {})
    return out, acts, mutated


def all_finite(*trees):
    """Scalar bool: every inexact leaf of every tree is finite.

    The reduction feeding the health guard's batch screen (health.py):
    one fused all-reduce over the loss, gradients and captured (a, g)
    pytrees — integer/bool leaves are skipped (trivially finite), empty
    trees are healthy by definition.
    """
    checks = []
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
                checks.append(jnp.all(jnp.isfinite(leaf)))
    if not checks:
        return jnp.ones((), bool)
    return jnp.all(jnp.stack(checks))


def check_local_mean_loss(loss, batch, axis_name):
    """Trace-time guard for the LOCAL-mean loss convention (free: reads
    avals only, compiles to nothing).

    The engine's G-factor scaling assumes the loss fed to the capture
    backward is the mean over the LOCAL shard only (the reference's
    per-rank hook semantics: each rank's backward sees that rank's
    per-example output-gradients, kfac_preconditioner_base.py:122-130).
    A loss that was psum/pmean-normalized across the K-FAC world scales
    every cotangent by the shard count, so the preconditioner silently
    depends on the mesh shape — the round-3 postmortem bug
    (scripts/repro_mpd_eigen_orthogonal_axis.py, NOTES.md).

    Detection rides shard_map's varying-manual-axes (vma) tracking: the
    batch varies over the axes its shards differ on; a local-mean loss
    inherits those axes, while a cross-axis pmean/psum strips them.
    Raises ValueError on violation. No-ops where vma is unavailable
    (outside shard_map, or ``check_vma=False`` — but beware:
    ``check_vma=False`` ALSO disables the cross-axis cotangent psums the
    capture relies on, the postmortem's second trap).

    Caveat (ADVICE r4): only a FULLY cross-axis-reduced loss is detected.
    A loss whose *denominator* was globally normalized while the
    numerator still varies — e.g. the masked-LM pattern
    ``local_token_loss_sum / psum(token_count)`` — keeps the batch's vma
    through the varying numerator and passes this guard, yet it violates
    the local-mean convention whenever shards hold unequal token counts
    (each shard's cotangents are scaled by the *global* count instead of
    its own). Normalize by the LOCAL count and let the engine's gradient
    averaging handle the cross-shard mean.
    """
    if axis_name is None:
        return
    axes = {axis_name} if isinstance(axis_name, str) else set(axis_name)

    def vma_of(tree):
        out = set()
        for leaf in jax.tree.leaves(tree):
            out |= set(getattr(jax.typeof(leaf), 'vma', ()) or ())
        return out

    missing = (vma_of(batch) & axes) - vma_of(loss)
    if missing:
        raise ValueError(
            'K-FAC capture loss convention violation: the loss is '
            f'invariant over mesh axes {sorted(missing)} that the batch '
            'varies over — it was psum/pmean-normalized across the '
            'K-FAC world before the capture backward. The convention is '
            'the LOCAL-mean loss (mean over this shard only); average '
            'the GRADIENTS over the K-FAC world instead '
            '(parallel.average_grads). A globally-normalized loss '
            'scales every G factor by the shard count, making the '
            'preconditioner depend on the mesh shape. See README '
            '"Loss conventions" and '
            'scripts/repro_mpd_eigen_orthogonal_axis.py.')


def value_and_grad_with_capture(model, loss_fn, variables, *args,
                                mutable=(), wrt='params', axis_name=None,
                                **kwargs):
    """One fwd+bwd pass returning loss, outputs, param grads, and (a, g).

    The canonical capture entrypoint — the functional equivalent of the
    reference's forward/backward with hooks armed (one ``model(data)`` +
    ``loss.backward()``, kfac_preconditioner_base.py:122-130).

    ``loss_fn(outputs)`` must return a scalar (close over targets) and
    MUST be the LOCAL-mean loss — the mean over this shard's examples
    only, never psum/pmean-normalized across the mesh (see
    :func:`check_local_mean_loss`; ``training.build_train_step`` applies
    that guard automatically, direct harnesses should call it
    themselves).
    Pass ``axis_name`` when calling inside shard_map over a data-parallel
    axis (see :func:`make_zero_taps`); param grads then come back psummed
    over the axis (divide by axis size — ``parallel.average_grads``) while
    ``gs`` stays per-device local.
    Returns ``(loss, outputs, grads, acts, gs, other_mutated)`` with
    ``acts``/``gs`` keyed like the capture collections.
    """
    taps = make_zero_taps(model, variables, *args, axis_name=axis_name,
                          **kwargs)
    params = variables[wrt]
    rest = {k: val for k, val in variables.items() if k != wrt}

    def wrapped(p, t):
        out, acts, mutated = apply_with_capture(
            model, {wrt: p, **rest}, *args, taps=t, mutable=mutable, **kwargs)
        loss = loss_fn(out)
        return loss, (out, acts, mutated)

    (loss, (out, acts, mutated)), (grads, gs) = jax.value_and_grad(
        wrapped, argnums=(0, 1), has_aux=True)(params, taps)
    return loss, out, grads, acts, gs, mutated


# ---------------------------------------------------------------------------
# Pytree path utilities (layer name <-> collection / params subtrees)
# ---------------------------------------------------------------------------

def get_path(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def set_path(tree, path, value):
    """Functionally set ``tree[path] = value`` (dicts only)."""
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = set_path(tree[path[0]], path[1:], value)
    return out


def layer_act(acts, meta: LayerMeta):
    """Pull layer ``meta``'s sown activation out of the capture pytree."""
    return get_path(acts, meta.path)['a']


def layer_g(gs, meta: LayerMeta):
    """Pull layer ``meta``'s output-gradient out of the tap-grad pytree."""
    return get_path(gs, meta.path)['g']


def counter_metrics(extra_vars):
    """``{'moe/dropped': scalar, ...}``: the leaves of the model's
    :data:`COUNTERS` collection under their '/'-joined names (empty where
    the model keeps none)."""
    from flax import traverse_util
    return traverse_util.flatten_dict(
        dict(extra_vars.get(COUNTERS, {})), sep='/')


def canonical_padding(in_size, kernel_size, strides, padding):
    """Resolve a Flax-style padding spec to explicit per-dim (lo, hi) pairs
    for the given input spatial size — factor A's im2col must see exactly
    the padding the conv used."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == 'VALID':
            return ((0, 0), (0, 0))
        if p == 'SAME':
            out = []
            for s, k, st in zip(in_size, kernel_size, strides):
                o = -(-s // st)  # ceil
                total = max((o - 1) * st + k - s, 0)
                out.append((total // 2, total - total // 2))
            return tuple(out)
        raise ValueError(f'unsupported padding {padding!r}')
    out = []
    for p in padding:
        if isinstance(p, (tuple, list)):
            out.append((int(p[0]), int(p[1])))
        else:
            out.append((int(p), int(p)))
    return tuple(out)
