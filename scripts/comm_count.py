"""Compiler-level proof of the DP-KFAC communication story: count the
XLA collectives in each variant's COMPILED train step.

The reference's argument for DP-KFAC (kfac_preconditioner_*_dp.py) is
that it deletes the FactorComm (0.300 s) and shrinks the InverseComm
(0.146 s) terms of the 64-GPU MPD ledger (reference
scripts/time_breakdown.py:27). On TPU the equivalent evidence is
hardware-independent: lower the full jitted K-FAC train step over an
8-device mesh and count the all-reduce / all-gather /
collective-permute ops XLA actually emitted. MPD variants ('eigen',
'inverse') must show the factor-reduction collectives; DP variants
('eigen_dp', 'inverse_dp') must show NONE beyond the gradient allreduce
+ preconditioned-output gather; SGD is the gradient-allreduce floor.

Usage: JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python scripts/comm_count.py

Env knobs:
  COMM_COUNT_VARIANTS   space-separated variant specs; a ':bf16'/':int8'
                        suffix compiles the variant with that
                        comm_precision wire dtype (e.g. 'eigen:bf16');
                        a '+pallas' tag compiles it with the fused
                        Pallas capture kernels (e.g. 'eigen+pallas',
                        'eigen+pallas:bf16')
  COMM_COUNT_JSON       write the machine-readable per-variant ledger
                        (ops/bytes per collective kind + per-phase
                        per-dtype breakdown) to this path
  COMM_COUNT_ASSERT     fail unless the SGD floor contains only
                        gradient allreduces, every variant's floor is
                        byte-identical to SGD's, each compressed
                        spec shows >=40% K-FAC collective-byte reduction
                        vs its fp32 counterpart, and each '+pallas'
                        spec's ledger is byte-identical to its unfused
                        counterpart's (the CI smoke gate)
"""

import collections
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import models, training

#: one HLO instruction line: `%x = <result type> all-reduce(...)` — the
#: result type carries the payload shape(s) (tuples for variadic ops).
#: The async forms TPU/GPU backends emit for latency hiding
#: (all-reduce-start / -done pairs) are counted via their -start op,
#: whose result type carries the payload; -done carries none.
COLLECTIVE_LINE_RE = re.compile(
    r'= (.*?) ((?:all-reduce|all-gather|collective-permute|reduce-scatter|'
    r'all-to-all)(?:-start)?)\(')
SHAPE_RE = re.compile(r'\b([a-z]\w*)\[([0-9,]*)\]')
OP_NAME_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
DTYPE_BYTES = {'f32': 4, 'bf16': 2, 'f16': 2, 'f64': 8, 's32': 4,
               'u32': 4, 's64': 8, 'u64': 8, 's8': 1, 'u8': 1, 'pred': 1,
               'f8e4m3fn': 1, 'f8e5m2': 1, 'c64': 8, 'c128': 16,
               's16': 2, 'u16': 2}
_WARNED_DTYPES = set()

#: op_name scope substring -> ledger phase (first match wins; the scopes
#: are the engine's jax.named_scope taxonomy, which XLA carries through
#: SPMD partitioning into each collective's metadata). Everything else —
#: the autodiff gradient allreduce, the loss pmean, BN-stat syncs — is
#: the 'grad_or_other' floor that MUST stay byte-identical under any
#: comm_precision (compression never touches the SGD path).
PHASE_OF_SCOPE = (
    # DecompComm first: the shard exchange's gathers run INSIDE the
    # stagger ComputeInverse/CommunicateInverse scopes, and first-match
    # attribution must put them in their own ledger phase
    ('kfac.DecompComm', 'DecompComm'),
    ('kfac.CommunicateFactor', 'FactorComm'),
    ('kfac.CommunicateInverse', 'InverseComm'),
    ('kfac.Precondition', 'PredComm'),
    ('kfac.', 'KfacOther'),
)
FLOOR_PHASE = 'grad_or_other'


def _phase_of(op_name):
    for scope, phase in PHASE_OF_SCOPE:
        if scope in (op_name or ''):
            return phase
    return FLOOR_PHASE


def _payload_bytes_by_dtype(result_type, kind=''):
    """{hlo dtype token: payload bytes} of one collective's result."""
    shapes = SHAPE_RE.findall(result_type)
    if kind.endswith('-start') and result_type.lstrip().startswith('('):
        # an async -start op's tuple result is (operand aliases...,
        # outputs..., context scalars...): counting every element roughly
        # DOUBLES the volume (ADVICE r3). Drop the u32/s32 context
        # scalars, then keep only the output half.
        shapes = [s for s in shapes
                  if not (s[1] == '' and s[0] in ('u32', 's32'))]
        if shapes and len(shapes) % 2 == 0:
            shapes = shapes[len(shapes) // 2:]
        elif shapes:
            # the alias/output halves failed to pair 1:1 — the full tuple
            # gets counted, roughly doubling this op's volume (ADVICE r4:
            # flag it so a silently-doubled variant is visible in the
            # ledger instead of quietly inflating it)
            if 'odd-async-tuple' not in _WARNED_DTYPES:
                _WARNED_DTYPES.add('odd-async-tuple')
                print(f'warning: async {kind} result tuple has odd '
                      f'length {len(shapes)} — even alias/output split '
                      'assumption failed; counting the FULL tuple (may '
                      'double this op\'s bytes)', file=sys.stderr)
    out = {}
    for dt, dims in shapes:
        size = DTYPE_BYTES.get(dt)
        if size is None:
            if dt not in _WARNED_DTYPES:
                _WARNED_DTYPES.add(dt)
                print(f'warning: unknown dtype {dt!r} in collective '
                      'result type — assuming 4 bytes', file=sys.stderr)
            size = 4
        n = 1
        for d in dims.split(','):
            if d:
                n *= int(d)
        out[dt] = out.get(dt, 0) + n * size
    return out


def _payload_bytes(result_type, kind=''):
    return sum(_payload_bytes_by_dtype(result_type, kind).values())


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


def parse_variant_spec(spec):
    """'eigen' | 'eigen:bf16' | 'eigen+shard:bf16' | 'eigen_dp>inverse'
    -> (variant, comm_precision). '+'-tags ('+shard', '+pallas') stay
    part of the variant name — a compressed tagged spec's fp32
    counterpart is the tagged spec, not the untagged one (different
    programs, different byte model). A '>mode' tag (ISSUE 14) likewise stays part of the
    variant name: the spec lowers the variant AFTER a live
    ``KFAC.replan(comm_mode=mode)`` — the program the autotuner's
    applied comm-mode switch actually runs — and the assert gate pins
    its K-FAC phase bytes against ``FactorPlan.comm_volume`` for the
    switched mode."""
    variant, _, precision = spec.partition(':')
    return variant, (precision or 'fp32')


def parse_capture_tags(variant_tagged):
    """'eigen+pallas' -> ('eigen', shard=False, capture='pallas');
    '+'-tags compose ('eigen+shard+pallas'). Unknown tags fail loudly —
    a typo'd tag must not silently lower the untagged program."""
    base, *tags = variant_tagged.split('+')
    unknown = sorted(set(tags) - {'shard', 'pallas'})
    if unknown:
        raise SystemExit(
            f'unknown variant tag(s) {unknown} in {variant_tagged!r} '
            "(known: '+shard', '+pallas')")
    return (base, 'shard' in tags,
            'pallas' if 'pallas' in tags else None)


def parse_replan_tag(variant):
    """'eigen_dp>inverse' -> ('eigen_dp', 'inverse'); no tag -> (v, None)."""
    base, _, mode = variant.partition('>')
    return base, (mode or None)


def parse_mesh_tag(variant):
    """'eigen@dp2xtp2' -> ('eigen', 'dp2xtp2'); no tag -> (v, None).
    An '@mesh' spec lowers the AXIS-AWARE program: the preconditioner
    step on a composed mesh (meshplan subsystem), with every collective
    attributed to the mesh axis its replica groups actually cross."""
    base, _, spec = variant.partition('@')
    return base, (spec or None)


# -- per-axis attribution (composed meshes) ---------------------------------

REPLICA_GROUPS_RE = re.compile(
    r'replica_groups=(\{\{[0-9, ]*(?:\},\{[0-9, ]*)*\}\}'
    r'|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)')
SOURCE_TARGET_RE = re.compile(r'source_target_pairs=(\{\{[0-9,{} ]*\}\})')
_IOTA_RE = re.compile(
    r'^\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?$')


def parse_replica_groups(line):
    """Device-id groups of one HLO collective line, or None.

    Handles both serializations XLA emits: the literal
    ``{{0,2},{1,3}}`` list and the iota form ``[2,2]<=[4]`` /
    ``[2,2]<=[2,2]T(1,0)`` (groups = iota over the total, reshaped to
    the source dims, transposed, re-flattened to [n_groups, size]).
    collective-permute's ``source_target_pairs`` parse as 2-element
    groups — a pair crosses whatever axis separates its endpoints.
    """
    m = REPLICA_GROUPS_RE.search(line)
    if m is None:
        m = SOURCE_TARGET_RE.search(line)
        if m is None:
            return None
        body = m.group(1)[2:-2]
        return [tuple(int(x) for x in grp.split(','))
                for grp in body.split('},{') if grp]
    text = m.group(1)
    im = _IOTA_RE.match(text)
    if im:
        out_dims = [int(x) for x in im.group(1).split(',')]
        src_dims = [int(x) for x in im.group(2).split(',')]
        ids = np.arange(int(np.prod(src_dims))).reshape(src_dims)
        if im.group(3):
            ids = ids.transpose([int(x) for x in im.group(3).split(',')])
        ids = ids.reshape(out_dims)
        return [tuple(int(x) for x in row) for row in ids]
    body = text[2:-2]
    return [tuple(int(x) for x in grp.split(','))
            for grp in body.split('},{') if grp]


def axis_of_groups(groups, mesh_shape, axis_names, data_names):
    """Which mesh axis a collective's replica groups cross.

    Device ids are global and row-major over the mesh shape (the
    make_composed_mesh construction), so each member's axis coordinates
    are its unravel. Returns 'data' when every varying coordinate is a
    data/sequence axis (the K-FAC world — multi-axis worlds still count
    as one), the axis name when exactly one non-data axis varies, 'self'
    for degenerate single-member groups, and a '+'-joined label for
    anything mixed (no K-FAC collective should ever produce one).
    """
    varying = set()
    for grp in groups:
        coords = [np.unravel_index(d, mesh_shape) for d in grp]
        for k, name in enumerate(axis_names):
            if len({c[k] for c in coords}) > 1:
                varying.add(name)
    if not varying:
        return 'self'
    if varying <= set(data_names):
        return 'data'
    non_data = sorted(varying - set(data_names))
    if len(non_data) == 1 and len(varying) == 1:
        return non_data[0]
    # crosses a non-data axis AND something else — no K-FAC collective
    # should produce this; the '+' label makes it loud in the ledger
    return '+'.join(sorted(varying))


def collective_ledger(variant, ndev=8, model_name='resnet20', model=None,
                      hw=32, comm_precision='fp32', comm_prefetch=False):
    """Machine-readable collective ledger over the compiled
    (SPMD-partitioned) HLO of one full factor+inverse+precondition+update
    step: op counts and payload bytes per collective kind, plus a
    per-phase (named-scope taxonomy) x per-dtype breakdown — the
    compiler-level proof that a ``comm_precision`` wire dtype shrinks
    FactorComm/InverseComm/PredComm while the gradient-allreduce floor
    stays byte-identical."""
    if len(jax.devices()) < ndev or ndev < 2:
        raise SystemExit(
            f'need a >=2-device mesh (have {len(jax.devices())}, asked '
            f'{ndev}): on one device XLA elides every collective and the '
            'ledger would read all-zero. Run with JAX_PLATFORMS=cpu '
            'XLA_FLAGS=--xla_force_host_platform_device_count=8.')
    mesh = Mesh(np.array(jax.devices()[:ndev]), ('batch',))
    rng = np.random.RandomState(0)
    batch = {'input': jnp.asarray(rng.randn(2 * ndev, hw, hw, 3),
                                  jnp.float32),
             'label': jnp.asarray(rng.randint(0, 10, 2 * ndev))}
    if model is None:
        model = models.get_model(model_name, num_classes=10)
    tx = training.sgd(0.1, momentum=0.9)
    # 'eigen+shard': the variant's staggered step with mesh-sharded
    # decomposition (decomp_shard=True implies stagger) — the lowered
    # program is ONE staggered step whose two DecompComm gathers the
    # analytic model prices in closed form. 'variant>mode' (ISSUE 14):
    # lower the program AFTER a live KFAC.replan to the other comm
    # mode — the exact program the autotuner's applied switch runs.
    # '+pallas' (ISSUE 19): the variant with capture_impl='pallas' —
    # fused Pallas capture kernels compute the SAME factor statistics
    # and the SAME wire values, so the program's collective ledger must
    # be byte-identical to the untagged counterpart's (the assert gate
    # below pins exactly that)
    variant_tagged, replan_to = parse_replan_tag(variant)
    base, decomp_shard, capture_impl = parse_capture_tags(variant_tagged)
    precond = None
    if variant != 'sgd':
        precond = kfac.KFAC(variant=base, lr=0.1, damping=0.003,
                            fac_update_freq=1,
                            kfac_update_freq=2 if decomp_shard else 1,
                            num_devices=ndev, axis_name='batch',
                            assignment='balanced',
                            comm_precision=comm_precision,
                            comm_prefetch=comm_prefetch,
                            decomp_shard=decomp_shard,
                            capture_impl=capture_impl)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0),
                                      batch['input'])
    step = training.build_train_step(model, tx, precond, _ce,
                                     axis_name='batch', mesh=mesh,
                                     extra_mutable=('batch_stats',),
                                     donate=False)
    if replan_to is not None:
        # the live switch: rebuild the plan, carry the state (verbatim
        # here — same layout), retrace. What gets lowered below is the
        # SWITCHED program, byte-pinned against comm_volume(comm_mode=)
        state = state.replace(kfac_state=precond.replan(
            jax.device_get(state.kfac_state), comm_mode=replan_to))
    # build the full factor+inverse variant WITHOUT executing a step
    # (AOT lower/compile only — executing first would compile the same
    # program twice) and read the compiled SPMD module's text
    from kfac_pytorch_tpu.preconditioner import KFACHyperParams
    hyper = KFACHyperParams(lr=jnp.float32(0.1), damping=jnp.float32(0.003))
    if decomp_shard:
        jitted = step.make_variant(True, False, stagger_update=True)
    else:
        jitted = step.make_variant(precond is not None,
                                   precond is not None,
                                   prefetch=comm_prefetch)
    txt = jitted.lower(state, batch, hyper).compile().as_text()
    counts = collections.Counter()
    bytes_by_kind = collections.Counter()
    by_phase = {}
    for line in txt.splitlines():
        m = COLLECTIVE_LINE_RE.search(line)
        if not m:
            continue
        result_type, kind = m.groups()
        per_dtype = _payload_bytes_by_dtype(result_type, kind)
        total = sum(per_dtype.values())
        counts[kind] += 1
        bytes_by_kind[kind] += total
        om = OP_NAME_RE.search(line)
        phase = _phase_of(om.group(1) if om else '')
        rec = by_phase.setdefault(
            phase, {'ops': 0, 'bytes': 0, 'by_dtype': {}})
        rec['ops'] += 1
        rec['bytes'] += total
        for dt, b in per_dtype.items():
            rec['by_dtype'][dt] = rec['by_dtype'].get(dt, 0) + b
    led = {
        'variant': variant,
        'comm_precision': comm_precision,
        'comm_prefetch': bool(comm_prefetch),
        'capture_impl': capture_impl,
        'ops': dict(counts),
        'bytes': dict(bytes_by_kind),
        'by_phase': by_phase,
        'total_bytes': int(sum(bytes_by_kind.values())),
    }
    if decomp_shard:
        # the closed-form DecompComm byte price of ONE staggered step
        # under this layout — the COMM_COUNT_ASSERT pin compares the
        # measured by_phase['DecompComm'] bytes against this exactly
        led['decomp_comm_analytic'] = int(precond.plan.comm_volume(
            stats_reduce=precond.stats_reduce, method=precond.method,
            comm_precision=comm_precision,
            decomp_shard=precond.decomp_shard_plan)['DecompComm'])
    if replan_to is not None:
        # the closed-form per-phase byte price of the SWITCHED program
        # (FactorPlan.comm_volume for the replanned mode) — the
        # COMM_COUNT_ASSERT pin compares the measured K-FAC phases
        # against this byte-for-byte (the ISSUE 14 acceptance
        # criterion: the HLO ledger matches the analytic model for the
        # program the applied switch runs)
        led['comm_mode'] = replan_to
        led['comm_mode_analytic'] = {
            k: int(v) for k, v in precond.plan.comm_volume(
                stats_reduce=precond.stats_reduce, method=precond.method,
                comm_precision=comm_precision).items()}
    return led


def collective_counts(variant, ndev=8, model_name='resnet20', model=None,
                      hw=32, comm_precision='fp32'):
    """({op_kind: count}, {op_kind: bytes}) over the compiled
    (SPMD-partitioned) HLO of one full
    factor+inverse+precondition+update step."""
    led = collective_ledger(variant, ndev=ndev, model_name=model_name,
                            model=model, hw=hw,
                            comm_precision=comm_precision)
    return led['ops'], led['bytes']


def composed_ledger(base_variant, mesh_spec, comm_precision='fp32',
                    batch=8):
    """Per-AXIS collective ledger of the axis-aware preconditioner step
    on a composed mesh (meshplan subsystem) — the compiler-level proof
    of the composed-mesh communication story: factor statistics psum
    over the tensor axis exactly the rows the plan marks (column-A /
    row-G), the expert axis carries ZERO factor bytes (owner-local
    DP-KFAC per expert), and the data-axis phases price exactly as the
    base ``FactorPlan.comm_volume``.

    The lowered program feeds ORACLE capture inputs (acts/gs/grads as
    explicit shard_map operands) into ``KFAC.step``: the ledger pins the
    preconditioner's own collectives, independent of how the model
    forward/backward produced the statistics — and independent of the
    legacy-jax in-body autodiff defect tests/helpers.py documents.
    """
    import functools
    from jax.sharding import PartitionSpec as P
    from kfac_pytorch_tpu.capture import LayerMeta
    from kfac_pytorch_tpu.meshplan import axes as axes_mod
    from kfac_pytorch_tpu.parallel import mesh as meshlib
    from kfac_pytorch_tpu.parallel import moe, tp

    axes = axes_mod.parse_mesh_spec(mesh_spec)
    need = axes_mod.total_devices(axes)
    if len(jax.devices()) < need:
        raise SystemExit(
            f'mesh {mesh_spec!r} needs {need} devices (have '
            f'{len(jax.devices())}) — run with JAX_PLATFORMS=cpu '
            f'XLA_FLAGS=--xla_force_host_platform_device_count={need}')
    mesh, _ = meshlib.make_composed_mesh(mesh_spec)
    names = tuple(a.name for a in axes)
    shape = axes_mod.mesh_shape(axes)
    data_names = axes_mod.data_axis_names(axes)

    # synthetic capture layer set: column/row tensor slices when the
    # mesh has a tensor axis, an expert-local FFN when it has an expert
    # axis, plus one plain data-world head (unmatched by any rule)
    def dense(name, din, dout):
        return LayerMeta(name=name, path=tuple(name.split('/')),
                         kind='dense', use_bias=True, in_dim=din + 1,
                         out_dim=dout, kernel_shape=(din, dout))
    DIN, DH, DOUT = 24, 32, 16
    metas, rules = {}, []
    if any(a.role == 'tensor' for a in axes):
        metas[('l1', 'slice')] = dense('l1/slice', DIN, DH)
        metas[('l2', 'slice')] = dense('l2/slice', DH, DOUT)
        rules += list(tp.axis_rules(column=('l1',), row=('l2',)))
    if any(a.role == 'expert' for a in axes):
        metas[('expert', 'w_in')] = dense('expert/w_in', DIN, DH)
        metas[('expert', 'w_out')] = dense('expert/w_out', DH, DIN)
        rules += list(moe.axis_rules())
    metas[('head',)] = dense('head', DIN, DOUT)

    pre = kfac.KFAC(variant=base_variant, lr=0.1, damping=0.003,
                    assignment='balanced', comm_precision=comm_precision,
                    mesh_axes=mesh_spec,
                    mesh_rules=tuple(rules) or None)
    pre.setup(metas)
    state0 = pre.init()

    rng = np.random.RandomState(0)

    def leaf(*dims):
        a = jnp.asarray(rng.randn(*dims), jnp.float32)
        return jnp.broadcast_to(a, shape + tuple(dims))

    def insert(tree, path, value):
        d = tree
        for p in path[:-1]:
            d = d.setdefault(p, {})
        d[path[-1]] = value

    acts, gs, grads = {}, {}, {}
    for path, m in metas.items():
        din = m.in_dim - 1
        insert(acts, path, {'a': leaf(batch, din)})
        insert(gs, path, {'g': leaf(batch, m.out_dim)})
        insert(grads, path, {'kernel': leaf(din, m.out_dim),
                             'bias': leaf(m.out_dim)})

    kspecs = pre.state_pspecs()
    lead = P(*names)
    tree_specs = jax.tree.map(lambda _: lead, (grads, acts, gs))

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(kspecs,) + tree_specs,
                       out_specs=(lead, kspecs))
    def step(kstate, grads, acts, gs):
        sq = lambda t: jax.tree.map(
            lambda a: a.reshape(a.shape[len(shape):]), t)
        new_grads, new_state = pre.step(kstate, sq(grads), sq(acts),
                                        sq(gs))
        exp = lambda t: jax.tree.map(
            lambda a: a.reshape((1,) * len(shape) + a.shape), t)
        return exp(new_grads), new_state

    txt = jax.jit(step).lower(state0, grads, acts, gs) \
                       .compile().as_text()

    counts = collections.Counter()
    bytes_by_kind = collections.Counter()
    by_phase = {}
    by_axis = {}
    total_devices = int(np.prod(shape))
    for line in txt.splitlines():
        m = COLLECTIVE_LINE_RE.search(line)
        if not m:
            continue
        result_type, kind = m.groups()
        per_dtype = _payload_bytes_by_dtype(result_type, kind)
        total = sum(per_dtype.values())
        counts[kind] += 1
        bytes_by_kind[kind] += total
        om = OP_NAME_RE.search(line)
        phase = _phase_of(om.group(1) if om else '')
        rec = by_phase.setdefault(
            phase, {'ops': 0, 'bytes': 0, 'by_dtype': {}})
        rec['ops'] += 1
        rec['bytes'] += total
        for dt, b in per_dtype.items():
            rec['by_dtype'][dt] = rec['by_dtype'].get(dt, 0) + b
        groups = parse_replica_groups(line)
        if groups is None and 'replica_groups={}' in line:
            groups = [tuple(range(total_devices))]
        axis = (axis_of_groups(groups, shape, names, data_names)
                if groups is not None else 'unattributed')
        arec = by_axis.setdefault(axis, {})
        prec = arec.setdefault(phase, {'ops': 0, 'bytes': 0})
        prec['ops'] += 1
        prec['bytes'] += total
    mp = pre.mesh_plan
    analytic = {ax: {k: int(v) for k, v in d.items()}
                for ax, d in mp.comm_volume(
                    stats_reduce=pre.stats_reduce, method=pre.method,
                    comm_precision=comm_precision).items()}
    return {
        'variant': f'{base_variant}@{mesh_spec}',
        'comm_precision': comm_precision,
        'comm_prefetch': False,
        'capture_impl': None,
        'mesh': mesh_spec,
        'mesh_axes': names,
        'data_axes': list(data_names),
        'tensor_axes': list(mp.tensor_axes),
        'expert_axes': list(mp.expert_axes),
        'pipeline_axes': list(mp.pipeline_axes),
        'ops': dict(counts),
        'bytes': dict(bytes_by_kind),
        'by_phase': by_phase,
        'by_axis_phase': by_axis,
        'axis_analytic': analytic,
        'total_bytes': int(sum(bytes_by_kind.values())),
    }


def check_composed(ledgers):
    """The composed-mesh assert gate: for every '@mesh' spec,

    (a) the EXPERT (and pipeline) axes carry ZERO collective bytes — in
        every phase, gradient floor included: the owner-local factor
        trick means nothing the preconditioner lowers may cross them;
    (b) the TENSOR axis carries exactly the analytic FactorComm bytes
        (``MeshFactorPlan.comm_volume``) and NOTHING else;
    (c) the data-axis K-FAC phases price byte-for-byte at the base
        ``FactorPlan.comm_volume`` closed form — the mesh layer changes
        where bytes flow, never how many the data world pays;
    (d) no collective crosses a mixed axis set ('+'-labels) or escapes
        attribution.
    """
    for spec, led in ledgers.items():
        if 'by_axis_phase' not in led:
            continue
        by_axis = led['by_axis_phase']
        analytic = led['axis_analytic']
        for ax in led['expert_axes'] + led['pipeline_axes']:
            got = by_axis.get(ax)
            assert got is None, (
                f'{spec}: collectives cross the {ax} axis: {got} — '
                'expert/pipeline factor state is owner-local; this '
                'axis must carry exactly zero bytes')
        bad = [ax for ax in by_axis
               if '+' in ax or ax == 'unattributed']
        assert not bad, (
            f'{spec}: unattributable/mixed-axis collectives {bad}: '
            f'{ {ax: by_axis[ax] for ax in bad} }')
        for ax in led['tensor_axes']:
            t = dict(by_axis.get(ax, {}))
            want = analytic[ax]['FactorComm']
            got = t.pop('FactorComm', {}).get('bytes', 0)
            assert got == want, (
                f'{spec}: tensor-axis FactorComm {got} B != analytic '
                f'{want} B — the marked-row psum and its byte model '
                'diverged')
            assert not t, (
                f'{spec}: tensor axis {ax} carries non-FactorComm '
                f'collectives {t} — the tensor axis prices exactly one '
                'collective family')
        data = by_axis.get('data', {})
        for phase in ('FactorComm', 'InverseComm', 'PredComm'):
            got = data.get(phase, {}).get('bytes', 0)
            want = analytic['data'][phase]
            assert got == want, (
                f'{spec}: data-axis {phase} {got} B != analytic '
                f'{want} B — the composed program and the base '
                'comm_volume diverged')


def check_floor(ledgers):
    """The smoke-job gate: (a) the 'sgd' ledger contains ONLY
    gradient-path collectives (all-reduce kinds, no gathers, nothing
    attributed to a K-FAC phase), and (b) every compressed spec's
    'grad_or_other' floor phase is byte-identical to its fp32
    counterpart's — a comm_precision wire dtype must never leak into the
    gradient path. Raises AssertionError with the offending record."""
    assert 'sgd' in ledgers, 'check_floor needs an sgd ledger'
    sgd = ledgers['sgd']
    bad = [k for k in sgd['ops']
           if not k.startswith('all-reduce')]
    assert not bad, f'unexpected collectives in the SGD floor: {bad}'
    assert set(sgd['by_phase']) <= {FLOOR_PHASE}, (
        'SGD ledger attributes collectives to a K-FAC phase: '
        f'{sorted(sgd["by_phase"])}')
    for spec, led in ledgers.items():
        variant, precision = parse_variant_spec(spec)
        if precision == 'fp32':
            continue
        # a compressed spec with no fp32 counterpart would make every
        # check below vacuous — fail loudly instead of going green
        # having asserted nothing (e.g. a CI edit that drops the fp32
        # baselines to save time)
        assert variant in ledgers, (
            f'{spec}: no fp32 counterpart {variant!r} in the ledger set '
            '— the floor/compression gates need the baseline; add '
            f'{variant!r} to COMM_COUNT_VARIANTS')
        floor = ledgers[variant]['by_phase'].get(
            FLOOR_PHASE, {}).get('bytes', 0)
        got = led['by_phase'].get(FLOOR_PHASE, {}).get('bytes', 0)
        assert got == floor, (
            f'{spec}: grad/other floor {got} B != {variant} (fp32) '
            f'floor {floor} B — compression (or a regression) touched '
            'the gradient path')
        assert set(led['by_phase'][FLOOR_PHASE]['by_dtype']) == \
            set(ledgers[variant]['by_phase'][FLOOR_PHASE]['by_dtype']), (
            f'{spec}: floor phase dtype set changed vs {variant}')


def main():
    ndev = min(len(jax.devices()), 8)
    model_name = os.environ.get('COMM_COUNT_MODEL', 'resnet20')
    print(f'model={model_name} ndev={ndev} (counts from the compiled '
          'SPMD module)')
    # variant specs: 'eigen' (fp32) or 'eigen:bf16' / 'eigen:int8'
    # (compressed factor collectives, parallel/collectives.py wire dtypes)
    specs = tuple(os.environ.get(
        'COMM_COUNT_VARIANTS',
        'sgd eigen inverse eigen_dp inverse_dp '
        'eigen@dp2xtp2 eigen_dp@dp2xtp2 eigen_dp@dp2xep2').split())
    ledgers = {}
    for spec in specs:
        variant, precision = parse_variant_spec(spec)
        mesh_base, mesh_spec = parse_mesh_tag(variant)
        if mesh_spec:
            led = composed_ledger(mesh_base, mesh_spec,
                                  comm_precision=precision)
            ledgers[spec] = led
            per_axis = '; '.join(
                f'{ax}: ' + ', '.join(
                    f'{p} {r["bytes"]}B' for p, r in sorted(d.items()))
                for ax, d in sorted(led['by_axis_phase'].items()))
            print(f'{spec:>17}: ops {led["ops"]}  per-axis {{{per_axis}}}',
                  flush=True)
            continue
        led = collective_ledger(variant, ndev=ndev, model_name=model_name,
                                comm_precision=precision)
        ledgers[spec] = led
        phases = ', '.join(
            f'{p}: {r["bytes"] / 2**20:.2f}'
            for p, r in sorted(led['by_phase'].items()))
        print(f'{spec:>17}: ops {led["ops"]}  MiB by phase {{{phases}}}',
              flush=True)

    kinds = sorted({k for r in ledgers.values() for k in r['ops']})
    print('\nvariant            '
          + '  '.join(f'{k + " (n/MiB)":>26}' for k in kinds))
    for spec, led in ledgers.items():
        print(f'{spec:<17} ' + '  '.join(
            f'{led["ops"].get(k, 0):>16}/{led["bytes"].get(k, 0)/2**20:8.2f}'
            for k in kinds))

    json_path = os.environ.get('COMM_COUNT_JSON')
    if json_path:
        import json
        doc = {'model': model_name, 'ndev': ndev,
               'sgd_floor_bytes': (ledgers['sgd']['total_bytes']
                                   if 'sgd' in ledgers else None),
               'variants': ledgers}
        with open(json_path, 'w') as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f'\nwrote {json_path}')

    # the ledger analog (reference scripts/time_breakdown.py:27): K-FAC
    # comm VOLUME beyond the SGD gradient-allreduce floor
    if 'sgd' in ledgers:
        sgd_bytes = ledgers['sgd']['total_bytes']
        print(f'\nSGD gradient-allreduce floor: {sgd_bytes / 2**20:.2f} '
              'MiB')
        for spec, led in ledgers.items():
            if spec == 'sgd':
                continue
            extra = led['total_bytes'] - sgd_bytes
            print(f'{spec:>17}: +{extra / 2**20:8.2f} MiB K-FAC comm per '
                  'full factor+inverse step')
        # per-spec compression summary against its fp32 counterpart
        for spec, led in ledgers.items():
            variant, precision = parse_variant_spec(spec)
            if precision == 'fp32' or variant not in ledgers:
                continue
            base = ledgers[variant]['total_bytes'] - sgd_bytes
            comp = led['total_bytes'] - sgd_bytes
            if base > 0:
                print(f'{spec:>17}: {100 * (1 - comp / base):.0f}% K-FAC '
                      f'collective-byte reduction vs {variant} (fp32)')
        for spec, led in ledgers.items():
            if 'decomp_comm_analytic' in led:
                meas = led['by_phase'].get('DecompComm', {}).get('bytes', 0)
                print(f'{spec:>17}: DecompComm measured '
                      f'{meas / 2**20:.3f} MiB vs analytic '
                      f'{led["decomp_comm_analytic"] / 2**20:.3f} MiB '
                      '(per staggered step)')
            if 'comm_mode_analytic' in led:
                for phase in ('FactorComm', 'InverseComm', 'PredComm'):
                    meas = led['by_phase'].get(phase, {}).get('bytes', 0)
                    print(f'{spec:>17}: switched-program {phase} measured '
                          f'{meas / 2**20:.3f} MiB vs analytic '
                          f'{led["comm_mode_analytic"][phase] / 2**20:.3f}'
                          ' MiB')
            if led.get('capture_impl') == 'pallas':
                cp = spec.replace('+pallas', '')
                if cp in ledgers:
                    same = led['by_phase'] == ledgers[cp]['by_phase']
                    print(f'{spec:>17}: fused-capture per-phase ledger '
                          f'{"identical to" if same else "DIVERGED from"}'
                          f' {cp}')
        if 'eigen' in ledgers and 'eigen_dp' in ledgers:
            e = ledgers['eigen']['total_bytes'] - sgd_bytes
            edp = ledgers['eigen_dp']['total_bytes'] - sgd_bytes
            if e > 0:
                print(f'\nDP-KFAC deletes {100 * (1 - edp / e):.0f}% of '
                      "MPD eigen's K-FAC comm volume — the FactorComm-"
                      'deletion claim (reference time_breakdown.py:27), '
                      'compiler-verified')

    if os.environ.get('COMM_COUNT_ASSERT'):
        check_floor(ledgers)
        check_composed(ledgers)
        for spec, led in ledgers.items():
            variant, precision = parse_variant_spec(spec)
            if precision == 'fp32':
                continue
            assert variant in ledgers and 'sgd' in ledgers, (
                f'{spec}: the >=40% reduction gate needs both the fp32 '
                f'counterpart {variant!r} and the sgd floor in '
                'COMM_COUNT_VARIANTS')
            sgd_bytes = ledgers['sgd']['total_bytes']
            base = ledgers[variant]['total_bytes'] - sgd_bytes
            comp = led['total_bytes'] - sgd_bytes
            assert base > 0 and comp <= 0.6 * base, (
                f'{spec}: expected >=40% K-FAC collective-byte reduction '
                f'vs {variant}, got {base} -> {comp}')
        # the DecompComm pin: a '+shard' spec's measured shard-exchange
        # bytes must equal FactorPlan.comm_volume's closed-form price
        # EXACTLY, and its gradient floor must be byte-identical to the
        # SGD program's — the shard gathers shrink compute, never touch
        # the gradient path
        for spec, led in ledgers.items():
            analytic = led.get('decomp_comm_analytic')
            if analytic is None:
                continue
            measured = led['by_phase'].get('DecompComm', {}).get('bytes', 0)
            assert measured == analytic, (
                f'{spec}: measured DecompComm {measured} B != analytic '
                f'FactorPlan.comm_volume {analytic} B — the shard '
                'exchange and its byte model diverged')
            # the floor pin compares against the UNSHARDED base
            # variant's program (same preconditioner, same health-guard
            # psum — the SGD program lacks the guard's 4-byte batch_ok
            # reduce, so it is not the right baseline here; the SGD
            # floor itself stays pinned gradient-only by check_floor)
            variant, _ = parse_variant_spec(spec)
            unsharded = variant.partition('+')[0]
            # a shard spec with no unsharded counterpart would make the
            # floor pin vacuously green — fail loudly instead (the same
            # hardening the compressed-spec gates got in PR 8 review)
            assert unsharded in ledgers, (
                f'{spec}: no unsharded counterpart {unsharded!r} in the '
                'ledger set — the gradient-floor pin needs it; add '
                f'{unsharded!r} to COMM_COUNT_VARIANTS')
            base_floor = ledgers[unsharded]['by_phase'].get(
                FLOOR_PHASE, {}).get('bytes', 0)
            got = led['by_phase'].get(FLOOR_PHASE, {}).get('bytes', 0)
            assert got == base_floor, (
                f'{spec}: grad/other floor {got} B != {unsharded} '
                f'floor {base_floor} B — decomp_shard touched the '
                'gradient path')
        # the fused-capture pin (ISSUE 19): a '+pallas' spec lowers the
        # variant with capture_impl='pallas' — the Pallas kernels fuse
        # patch-extract, the factor GEMMs, the EMA and the wire-quantize
        # epilogue into the CAPTURE compute, but emit the same xc/bf16/
        # EF wire values (parallel/collectives.py pins the algebra), so
        # the FactorComm ledger — and every other comm phase — must be
        # byte-identical to the unfused counterpart's. Fusion moves
        # compute, never wire bytes.
        for spec, led in ledgers.items():
            if led.get('capture_impl') != 'pallas':
                continue
            counterpart = spec.replace('+pallas', '')
            assert counterpart in ledgers, (
                f'{spec}: no unfused counterpart {counterpart!r} in the '
                'ledger set — the fused-capture byte pin needs it; add '
                f'{counterpart!r} to COMM_COUNT_VARIANTS')
            other = ledgers[counterpart]
            fc = led['by_phase'].get('FactorComm', {})
            fc0 = other['by_phase'].get('FactorComm', {})
            assert fc == fc0, (
                f'{spec}: FactorComm ledger {fc} != {counterpart} '
                f'FactorComm ledger {fc0} — the fused capture epilogue '
                'changed the wire program (it must only move compute)')
            assert led['by_phase'] == other['by_phase'], (
                f'{spec}: per-phase ledger diverged from {counterpart} '
                'outside FactorComm — the fused capture path leaked '
                'into another comm phase')
            assert led['total_bytes'] == other['total_bytes'], (
                f'{spec}: total {led["total_bytes"]} B != {counterpart} '
                f'total {other["total_bytes"]} B')
        # the comm-mode pin (ISSUE 14): a '>mode' spec's SWITCHED
        # program must price every K-FAC comm phase byte-for-byte at
        # FactorPlan.comm_volume's closed form for the new mode, and
        # its gradient floor must be byte-identical to the UNswitched
        # base variant's program — a replan reroutes factor traffic,
        # never the gradient path
        for spec, led in ledgers.items():
            analytic = led.get('comm_mode_analytic')
            if analytic is None:
                continue
            for phase in ('FactorComm', 'InverseComm', 'PredComm'):
                measured = led['by_phase'].get(phase, {}).get('bytes', 0)
                assert measured == analytic[phase], (
                    f'{spec}: measured {phase} {measured} B != analytic '
                    f'FactorPlan.comm_volume {analytic[phase]} B — the '
                    'replanned program and its byte model diverged')
            base = parse_replan_tag(parse_variant_spec(spec)[0])[0]
            assert base in ledgers, (
                f'{spec}: no unswitched counterpart {base!r} in the '
                'ledger set — the gradient-floor pin needs it; add '
                f'{base!r} to COMM_COUNT_VARIANTS')
            base_floor = ledgers[base]['by_phase'].get(
                FLOOR_PHASE, {}).get('bytes', 0)
            got = led['by_phase'].get(FLOOR_PHASE, {}).get('bytes', 0)
            assert got == base_floor, (
                f'{spec}: grad/other floor {got} B != {base} floor '
                f'{base_floor} B — the comm-mode replan touched the '
                'gradient path')
        print('COMM_COUNT_ASSERT: floor + compression + decomp-shard '
              '+ comm-mode + fused-capture + composed-mesh gates passed')


if __name__ == '__main__':
    main()
