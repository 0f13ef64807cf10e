"""Axis-aware K-FAC on composed meshes (kfac_pytorch_tpu/meshplan).

Spec grammar, rule matching and the analytic per-axis comm volume are
pure-python. The parity tests feed ORACLE capture operands (acts/gs/
grads as explicit shard_map inputs) into ``pre.step`` — the backend's
in-body shard_map autodiff is unusable here (see tests/test_tp.py), and
the preconditioner's own collectives are forward-only and exact — and
assert the composed dp×tp / dp×ep preconditioned step BITWISE equal to
the dp-only reference, plus axis-aware replan round-trips carrying the
factor EMAs row-exact."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kfac_pytorch_tpu import meshplan as mp
from kfac_pytorch_tpu.capture import LayerMeta
from kfac_pytorch_tpu.parallel import mesh as meshlib
from kfac_pytorch_tpu.parallel import moe, tp
from kfac_pytorch_tpu.preconditioner import KFAC

ND, B = 2, 8


# ---------------------------------------------------------------------------
# spec grammar + rules (pure python)
# ---------------------------------------------------------------------------

def test_parse_mesh_spec_grammar():
    axes = mp.parse_mesh_spec('dp2xtp4')
    assert [(a.name, a.size, a.role) for a in axes] == [
        ('data', 2, 'data'), ('model', 4, 'tensor')]
    axes = mp.parse_mesh_spec('dp2xsp2xtp2xep1xpp1=stages')
    assert [a.role for a in axes] == [
        'data', 'sequence', 'tensor', 'expert', 'pipeline']
    assert axes[-1].name == 'stages'
    assert mp.world_size(axes) == 4          # data x sequence only
    assert mp.total_devices(axes) == 8       # every axis
    assert mp.data_axis_names(axes) == ('data', 'seq')
    # round-trip through format
    assert mp.parse_mesh_spec(mp.format_mesh_spec(axes)) == axes
    # AxisSpec tuples pass through (and re-validate)
    assert mp.parse_mesh_spec(axes) == axes


@pytest.mark.parametrize('bad', [
    'tp2',                # no data/sequence axis
    'dp2xtp2xtp2',        # duplicate axis name
    'dp2xtp2xtp2=m2',     # two tensor axes
    'dp2xzz2',            # unknown tag
    'dp0',                # non-positive size
])
def test_parse_mesh_spec_rejects(bad):
    with pytest.raises(ValueError):
        mp.parse_mesh_spec(bad)


def test_layer_axis_rule_validation():
    with pytest.raises(ValueError):
        mp.LayerAxisRule(pattern='x', a_roles=('data',))
    with pytest.raises(ValueError):
        mp.LayerAxisRule(pattern='x', local_roles=('tensor',))
    # reducing factors over expert/pipeline is never legal
    with pytest.raises(ValueError):
        mp.LayerAxisRule(pattern='x', a_roles=('expert',))


def test_default_rules_match_megatron_names():
    rules = mp.default_rules()
    col = mp.match_rule(rules, 'self_attn/w_q/slice')
    assert col is not None and col.a_roles == ('tensor',) \
        and col.g_roles == ()
    row = mp.match_rule(rules, 'ffn/w_2/slice')
    assert row is not None and row.g_roles == ('tensor',) \
        and row.a_roles == ()
    exp = mp.match_rule(rules, 'expert/w_in')
    assert exp is not None and exp.local_roles == ('expert',)
    assert mp.match_rule(rules, 'head') is None
    # first match wins
    first = mp.LayerAxisRule(pattern='w_q', g_roles=('tensor',))
    assert mp.match_rule((first,) + rules, 'self_attn/w_q/slice') is first


# ---------------------------------------------------------------------------
# shared oracle fixtures
# ---------------------------------------------------------------------------

def _dense(name, din, dout):
    return LayerMeta(name=name, path=tuple(name.split('/')), kind='dense',
                     use_bias=True, in_dim=din + 1, out_dim=dout,
                     kernel_shape=(din, dout))


def _tp_metas():
    return {('l1', 'slice'): _dense('l1/slice', 6, 4),
            ('l2', 'slice'): _dense('l2/slice', 4, 5)}


def _moe_metas():
    return {('expert', 'w_in'): _dense('expert/w_in', 6, 4),
            ('expert', 'w_out'): _dense('expert/w_out', 4, 5)}


def _oracle_inputs(metas, seed=0, lead=(ND,)):
    """Per-data-rank capture operands with leading dims ``lead``."""
    rng = np.random.RandomState(seed)

    def arr(*shape):
        return jnp.asarray(rng.randn(*(lead + shape)), jnp.float32)

    acts, gs, grads = {}, {}, {}
    for path, m in metas.items():
        din, dout = m.kernel_shape
        node_a = acts
        node_g = gs
        node_gr = grads
        for k in path[:-1]:
            node_a = node_a.setdefault(k, {})
            node_g = node_g.setdefault(k, {})
            node_gr = node_gr.setdefault(k, {})
        node_a[path[-1]] = {'a': arr(B, din)}
        node_g[path[-1]] = {'g': arr(B, dout)}
        node_gr[path[-1]] = {'kernel': arr(din, dout), 'bias': arr(dout)}
    return acts, gs, grads


TP_RULES = tp.axis_rules(column=('l1',), row=('l2',))
MOE_RULES = moe.axis_rules(experts=('expert',))


# ---------------------------------------------------------------------------
# plan construction + analytic comm volume (pure python)
# ---------------------------------------------------------------------------

def test_build_mesh_plan_tensor_rows_and_dp_degenerate():
    from kfac_pytorch_tpu.plan import build_plan, same_row_layout
    metas = _tp_metas()
    plan = mp.build_mesh_plan(metas, 'dp2xtp2', comm_mode='inverse',
                              rules=TP_RULES)
    # column layer contributes its A row, row layer its G row
    assert plan.tensor_reduce_rows('model') == 2
    marked = {r for rws in plan.tensor_rows['model'].values() for r in rws}
    assert len(marked) == 2
    # the base plan IS the dp-only plan over the data world
    ref = build_plan(metas, num_devices=2, comm_mode='inverse')
    assert same_row_layout(plan.base, ref)
    assert plan.world_size == 2 and plan.axis_name == 'data'


def test_comm_volume_per_axis_analytic():
    metas = _tp_metas()
    # no captured layer matches an expert-local rule here: the plan
    # builds (expert-replicated fallback) but says so out loud
    with pytest.warns(UserWarning, match='expert axis'):
        plan = mp.build_mesh_plan(metas, 'dp2xtp2xep1xpp1',
                                  comm_mode='inverse',
                                  rules=TP_RULES + MOE_RULES)
    vol = plan.comm_volume(stats_reduce='mean', method='eigh')
    # tensor axis: ONLY FactorComm, bytes = sum over marked rows of D^2*4
    want = sum(bdim * bdim * 4 * len(rws)
               for bdim, rws in plan.tensor_rows['model'].items())
    assert vol['model']['FactorComm'] == want > 0
    assert all(v == 0 for k, v in vol['model'].items()
               if k != 'FactorComm')
    # expert/pipeline axes: zero factor bytes by construction
    assert all(v == 0 for v in vol['expert'].values())
    assert all(v == 0 for v in vol['stage'].values())
    # bf16 wire halves the tensor payload
    vol16 = plan.comm_volume(stats_reduce='mean', method='eigh',
                             comm_precision='bf16')
    assert vol16['model']['FactorComm'] * 2 == want


def test_extra_reduce_env_knob(monkeypatch):
    plan = mp.build_mesh_plan(_tp_metas(), 'dp2xtp2', comm_mode='inverse',
                              rules=TP_RULES)
    assert plan.extra_reduce()          # live by default
    monkeypatch.setenv('KFAC_MESH_TP_REDUCE', '0')
    assert plan.extra_reduce() == ()


def test_stage_partition():
    metas = _tp_metas()
    s0 = mp.stage_partition(metas, 2, 0)
    s1 = mp.stage_partition(metas, 2, 1)
    assert set(s0) | set(s1) == set(metas) and not set(s0) & set(s1)
    explicit = mp.stage_partition(metas, 2, 1,
                                  stage_of=lambda name: 1)
    assert set(explicit) == set(metas)
    with pytest.raises(ValueError):
        mp.stage_partition(metas, 2, 0, stage_of=lambda name: 1)


# ---------------------------------------------------------------------------
# KFAC wiring
# ---------------------------------------------------------------------------

def test_kfac_mesh_axes_derives_world():
    pre = KFAC(variant='eigen', mesh_axes='dp2xtp2', mesh_rules=TP_RULES)
    assert pre.num_devices == 2 and pre.axis_name == 'data'
    with pytest.raises(ValueError):
        KFAC(variant='eigen', mesh_axes='dp2xtp2', num_devices=4)
    with pytest.raises(ValueError):
        KFAC(variant='eigen', mesh_axes='dp2xtp2', axis_name='batch')
    with pytest.raises(ValueError):
        KFAC(variant='eigen', mesh_rules=TP_RULES)  # rules without mesh


def _mesh_step(pre, mesh, n_extra, grads, acts, gs):
    """One preconditioned step with oracle operands; state replicated
    over every non-data mesh axis, inputs sharded over all axes."""
    kspecs = pre.state_pspecs()
    names = tuple(n for n, _ in mesh.shape.items())
    lead = len(names)
    io_spec = P(*names)

    # on a composed mesh the state comes out replicated over the
    # non-data axes BY VALUE (duplicated operands; the tests assert it
    # bitwise) but varying BY TYPE — the operands are sharded over every
    # axis — so the kspecs out_specs cannot be statically inferred there
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(kspecs, io_spec, io_spec, io_spec),
                       out_specs=(io_spec, kspecs),
                       check_vma=n_extra == 0)
    def step(kstate, grads, acts, gs):
        def sq(t):
            return jax.tree.map(
                lambda a: a.reshape(a.shape[lead:]), t)
        g2, st2 = pre.step(kstate, sq(grads), sq(acts), sq(gs))
        exp = lambda t: jax.tree.map(  # noqa: E731
            lambda a: a.reshape((1,) * lead + a.shape), t)
        return exp(g2), st2

    return step(pre.init(), grads, acts, gs)


def _dup(tree, axis, n):
    """Tile a leading-[data,...] tree with an extra mesh axis."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(
            jnp.expand_dims(a, axis),
            a.shape[:axis] + (n,) + a.shape[axis:]), tree)


def _dp_reference(metas, grads, acts, gs, variant='eigen'):
    pre = KFAC(variant=variant, lr=0.1, damping=0.01,
               num_devices=ND, axis_name='data')
    pre.setup(metas)
    mesh = meshlib.make_mesh(ND, axis_name='data')
    return _mesh_step(pre, mesh, 0, grads, acts, gs)


def test_dp_only_mesh_spec_bit_identical_to_legacy():
    """KFAC(mesh_axes='dp2') is the SAME preconditioner as the legacy
    KFAC(num_devices=2, axis_name='data') — bitwise, grads and state."""
    metas = _tp_metas()
    acts, gs, grads = _oracle_inputs(metas)
    gref, stref = _dp_reference(metas, grads, acts, gs)

    pre = KFAC(variant='eigen', lr=0.1, damping=0.01, mesh_axes='dp2')
    pre.setup(metas)
    mesh, _ = meshlib.make_composed_mesh('dp2')
    got, stc = _mesh_step(pre, mesh, 0, grads, acts, gs)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got, gref)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), stc.factors, stref.factors)


def test_composed_dp_tp_parity_bitwise():
    """dp2xtp2 with the tensor-axis factor reduce LIVE: replicated
    slice-capture operands make the pmean an average of identical f32
    values (exact for a power-of-2 world), so the composed step is
    BITWISE the dp-only reference and tp-invariant across model ranks."""
    metas = _tp_metas()
    acts, gs, grads = _oracle_inputs(metas)
    gref, stref = _dp_reference(metas, grads, acts, gs)

    pre = KFAC(variant='eigen', lr=0.1, damping=0.01,
               mesh_axes='dp2xtp2', mesh_rules=TP_RULES)
    pre.setup(metas)
    assert pre.mesh_plan.extra_reduce()   # the reduce is in the trace
    mesh, _ = meshlib.make_composed_mesh('dp2xtp2')
    got, stc = _mesh_step(pre, mesh, 1,
                          _dup(grads, 1, 2), _dup(acts, 1, 2),
                          _dup(gs, 1, 2))
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves_with_path(gref)):
        a = np.asarray(a)
        b = np.asarray(b)
        label = jax.tree_util.keystr(path)
        assert np.array_equal(a[:, 0], a[:, 1]), \
            f'{label}: not tp-invariant'
        assert np.array_equal(a[:, 0], b.reshape(a[:, 0].shape)), \
            f'{label}: composed != dp-only'
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), stc.factors, stref.factors)


def test_composed_dp_ep_owner_local_parity_bitwise():
    """dp2xep2 with PER-EXPERT capture operands: each expert rank's
    preconditioned step must BITWISE equal a dp-only run fed only that
    expert's capture — owner-local factors, zero cross-expert mixing
    (the zero-FactorComm claim, numerically)."""
    metas = _moe_metas()
    NE = 2
    pre = KFAC(variant='eigen', lr=0.1, damping=0.01,
               mesh_axes='dp2xep2', mesh_rules=MOE_RULES)
    pre.setup(metas)
    assert pre.mesh_plan.extra_reduce() == ()   # nothing to reduce
    mesh, _ = meshlib.make_composed_mesh('dp2xep2')

    per_e = [_oracle_inputs(metas, seed=10 + e) for e in range(NE)]
    stack = lambda i: jax.tree.map(  # noqa: E731
        lambda *a: jnp.stack(a, axis=1), *[pe[i] for pe in per_e])
    acts, gs, grads = stack(0), stack(1), stack(2)
    got, _ = _mesh_step(pre, mesh, 1, grads, acts, gs)

    for e in range(NE):
        a_e, g_e, gr_e = per_e[e]
        want, _ = _dp_reference(metas, gr_e, a_e, g_e)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a)[:, e],
                np.asarray(b).reshape(np.asarray(a)[:, e].shape)),
            got, want)


# ---------------------------------------------------------------------------
# axis-aware replan round-trips
# ---------------------------------------------------------------------------

def _factor_leaves(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state.factors)]


@pytest.mark.parametrize('spec,rules', [
    ('dp2xtp2', TP_RULES),
    ('dp2xep2', MOE_RULES),
])
def test_replan_composed_to_dp_round_trip(spec, rules):
    """dp×tp→dp and dp×ep→dp keep the data world, so the factor EMAs
    carry ROW-EXACT through replan — and the round trip back restores
    the composed plan with the state again untouched."""
    metas = _tp_metas() if 'tp' in spec else _moe_metas()
    acts, gs, grads = _oracle_inputs(metas)
    pre = KFAC(variant='eigen', lr=0.1, damping=0.01,
               mesh_axes=spec, mesh_rules=rules)
    pre.setup(metas)
    mesh, _ = meshlib.make_composed_mesh(spec)
    _, st = _mesh_step(pre, mesh, 1,
                       _dup(grads, 1, 2), _dup(acts, 1, 2),
                       _dup(gs, 1, 2))
    before = _factor_leaves(st)

    carried = pre.replan(st, mesh_axes='dp2')
    assert pre.mesh_axes is not None and len(pre.mesh_axes) == 1
    assert pre.mesh_plan.extra_reduce() == ()
    for a, b in zip(before, _factor_leaves(carried)):
        np.testing.assert_array_equal(a, b)

    back = pre.replan(carried, mesh_axes=spec)
    assert [x.name for x in pre.mesh_axes] == \
        [x.name for x in mp.parse_mesh_spec(spec)]
    for a, b in zip(before, _factor_leaves(back)):
        np.testing.assert_array_equal(a, b)

    cleared = pre.replan(back, mesh_axes=None)
    assert pre.mesh_axes is None and pre.mesh_plan is None
    for a, b in zip(before, _factor_leaves(cleared)):
        np.testing.assert_array_equal(a, b)


def test_replan_mesh_axes_exclusive_with_world_args():
    pre = KFAC(variant='eigen', mesh_axes='dp2xtp2', mesh_rules=TP_RULES)
    pre.setup(_tp_metas())
    with pytest.raises(ValueError):
        pre.replan(num_devices=4)       # resize goes through mesh_axes
    with pytest.raises(ValueError):
        pre.replan(mesh_axes='dp4', num_devices=4)
