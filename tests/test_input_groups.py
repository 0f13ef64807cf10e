"""One ``A`` for K-FAC layers that read one input (``capture.input_groups``,
``plan.build_plan``, the engine): found during the recorded trace, never
declared; ONE running average and ONE statistic a group; an inverse of it
for every member, damped by the member's own ``G`` (the trace-split damping
is per layer, so the inverse cannot be shared and stay K-FAC's). Everything
the program keeps is, bit for bit, what a plan with an ``A`` a layer keeps."""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, engine, models, ops, training
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.capture import LayerMeta
from kfac_pytorch_tpu.plan import (build_plan, pred_layout_record,
                                   same_row_layout, without_input_groups)
from kfac_pytorch_tpu.utils.checkpoint import reshard_kfac_state

jax.config.update('jax_default_matmul_precision', 'highest')


class Toy(linen.Module):
    """q / k / v on one array, a gate / up pair on another, stacked gate /
    up on one buffer; layers that must NOT share beside them."""

    @linen.compact
    def __call__(self, x, img):
        def dense(n, name, bias=False):
            return knn.Dense(n, use_bias=bias, name=name)
        q, k, v = (dense(n, name)(x) for n, name in
                   ((6, 'q'), (4, 'k'), (4, 'v')))
        biased = dense(6, 'biased', bias=True)(x)       # another use_bias
        copy = dense(6, 'copy')(x + 0.0)                # another array
        h = q * jnp.tanh(biased + copy) + jnp.pad(k * v, ((0, 0), (0, 2)))
        y = jax.nn.silu(dense(8, 'gate')(h)) * dense(8, 'up')(h)
        y = dense(6, 'down')(y)
        buf = jnp.stack([y[:3], y[1:4]])                # [2, 3, 6]
        rows = jnp.asarray([3.0, 2.0])
        e = (knn.StackedDense(5, name='e_gate')(buf, rows, 4)
             * knn.StackedDense(5, name='e_up')(buf, rows, 4))
        other = knn.StackedDense(5, name='e_other')(buf, rows * 1.0, 4)
        c1 = knn.Conv(3, (3, 3), name='conv_a')(img)
        c2 = knn.Conv(3, (3, 3), name='conv_b')(img)    # same array: conv
        return (e.sum() + other.sum() + (c1 * c2).sum()
                + dense(2, 'head')(y).sum())


def _toy():
    model = Toy()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 5))
    img = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 6, 2))
    variables = model.init(jax.random.PRNGKey(2), x, img)
    variables = {'params': variables['params']}
    return model, variables, x, img


def test_groups_are_found_during_the_trace_not_declared():
    model, variables, x, img = _toy()
    metas = capture.collect_layer_meta(model, variables, x, img)
    group = {n: m.input_group for n, m in metas.items()}
    assert group['q'] == group['k'] == group['v'] == 'q'
    assert group['gate'] == group['up'] == 'gate'
    # a slice shares with the slice of the same index on the same buffer
    # and row counts
    for e in range(2):
        assert group[f'e_gate/{e}'] == group[f'e_up/{e}'] == f'e_gate/{e}'
        assert group[f'e_other/{e}'] is None        # other row counts
    # another use_bias, another array, alone on its input, a convolution
    for name in ('biased', 'copy', 'down', 'head', 'conv_a', 'conv_b'):
        assert group[name] is None, name
    # nothing outlives the trace, and a second trace finds the same
    assert capture._REGISTRY.reads == {}
    assert capture.collect_layer_meta(model, variables, x, img) == metas
    # the head dropped with its group's first member: the next one leads
    only = {n: m for n, m in metas.items() if n != 'q'}
    plan = build_plan(only, 1, 'pred')
    names = [m.name for m in plan.metas]
    assert plan.a_groups()[0] == [names.index('k'), names.index('v')]


def test_plan_keeps_one_factor_a_group_and_an_inverse_a_member():
    model, variables, x, img = _toy()
    metas = capture.collect_layer_meta(model, variables, x, img)
    plan = build_plan(metas, 1, 'pred')
    own = build_plan(without_input_groups(metas), 1, 'pred')
    names = [m.name for m in plan.metas]
    record = pred_layout_record(plan)
    assert record['a_groups'] == 4 and record['a_rows_saved'] == 5
    assert record['pred_operand_takes'] == 0
    assert pred_layout_record(own)['a_groups'] == 0
    # every layer keeps the rows of the plan with an A a layer ...
    assert {d: b.n_rows for d, b in plan.buckets.items()} == {
        d: b.n_rows for d, b in own.buckets.items()}
    (d, b), = plan.buckets.items()
    # ... but five of them hold an inverse alone, last in the bucket
    assert b.n_factor_rows == b.n_rows - 5
    assert [s.factor_of is not None for s in b.slot_of_row] == (
        [False] * b.n_factor_rows + [True] * 5)
    for i, lead in enumerate(plan.a_leaders()):
        ba, ra, bg, rg, _ = plan.layer_rows[i]
        assert ra == plan.layer_rows[lead][1] < b.n_factor_rows
        inv = plan.inv_row_a[i]
        assert b.factor_row[inv] == ra
        assert (inv == ra) == (lead == i)
        assert b.slot_of_row[inv].layer_idx == i
        # damped against its OWN G, a G against the factor it reads
        flat = lambda r: plan.local_flat_offsets[d] + b.factor_row[r]  # noqa
        assert b.mate_flat[0, inv] == flat(rg)
        assert b.mate_flat[0, rg] == flat(ra)
    assert [names[i] for i in plan.a_groups()[0]] == ['q', 'k', 'v']
    # each pred group's rows are one run, in its own member order
    for pg in plan.pred_groups:
        for rows in (pg.row_a, pg.row_g):
            assert np.array_equal(rows, rows[0] + np.arange(len(rows)))
    assert not same_row_layout(plan, own)


def test_conv_model_plan_is_its_parent_style_plan():
    model = models.get_model('resnet20')
    x = jnp.zeros((2, 32, 32, 3))
    variables = jax.eval_shape(
        lambda: capture.init(model, jax.random.PRNGKey(0), x, train=False))
    metas = capture.collect_layer_meta(model, variables, x, train=False)
    assert all(m.input_group is None for m in metas.values())
    plan = build_plan(metas, 1, 'pred')
    own = build_plan(without_input_groups(metas), 1, 'pred')
    assert same_row_layout(plan, own)
    assert all(b.n_factor_rows == b.n_rows and b.factor_row is None
               for b in plan.buckets.values())
    assert plan.inv_row_a == [r[1] for r in plan.layer_rows]
    assert [(pg.dg, pg.da, list(pg.row_a), list(pg.row_g))
            for pg in plan.pred_groups] == [
        (pg.dg, pg.da, list(pg.row_a), list(pg.row_g))
        for pg in own.pred_groups]


def _bert_base_metas():
    """BERT-base's 73 K-FAC layers as its trace finds them: query, key and
    value of a block on one array."""
    metas = {}

    def dense(name, d_in, d_out, group=None):
        metas[name] = LayerMeta(
            name=name, path=tuple(name.split('/')), kind='dense',
            use_bias=True, in_dim=d_in + 1, out_dim=d_out,
            kernel_shape=(d_in, d_out), input_group=group)
    for i in range(12):
        for n in ('query', 'key', 'value'):
            dense(f'l{i}/{n}', 768, 768, f'l{i}/query')
        dense(f'l{i}/output', 768, 768)
        dense(f'l{i}/ffn', 768, 3072)
        dense(f'l{i}/ffn_output', 3072, 768)
    dense('qa_outputs', 768, 2)
    return metas


def test_bert_base_keeps_24_fewer_factor_rows():
    plan = build_plan(_bert_base_metas(), 1, 'pred')
    record = pred_layout_record(plan)
    assert (record['a_groups'], record['a_rows_saved']) == (12, 24)
    assert record['pred_operand_takes'] == 0
    assert record['pad_flop_share'] == 1.1605     # the apply is unchanged
    assert {d: (b.n_rows, b.n_factor_rows)
            for d, b in plan.buckets.items()} == {
        128: (1, 1), 768: (60, 60), 896: (61, 37), 3072: (12, 12),
        3200: (12, 12)}
    assert sorted((pg.dg, pg.da, len(pg.layer_idx))
                  for pg in plan.pred_groups) == [
        (128, 896, 1), (768, 896, 24), (768, 896, 24), (768, 3200, 12),
        (3072, 896, 12)]


# -- the arithmetic is that of an A a layer, to the last bit ----------------

CFG = dict(
    vocab_size=48, hidden_size=24, layer_types=('sliding_attention',
                                                'full_attention'),
    first_k_dense=1, intermediate_size=40, expert_width=12,
    n_routed_experts=8, experts_per_tok=3, n_shared_experts=1,
    head_dim=8, num_attention_heads=8, num_key_value_heads=4,
    q_head_ids=(2, 3, 6, 7), kv_head_ids=(1, 3), sliding_window=4,
    expert_ids=(0, 2, 3, 5, 7), expert_capacity=20)


def _train(share, steps=3, variant='inverse_dp', **kw):
    model = models.mixed_decoder_lm(**CFG)
    pre = kfac.KFAC(variant=variant, lr=0.01, damping=0.003,
                    fac_update_freq=2, kfac_update_freq=2, kl_clip=0.001,
                    factor_decay=0.95, num_devices=1, **kw)
    tx = training.sgd(0.01, momentum=0.9)
    sample = jnp.zeros((2, 10), jnp.int32)
    variables = capture.init(model, {'params': jax.random.PRNGKey(0)},
                             sample)
    metas = capture.collect_layer_meta(model, variables, sample,
                                       exclude_vocabulary_size=48)
    # sharing is off by building the metas without groups
    pre.setup(metas if share else without_input_groups(metas))
    state = training.init_train_state(model, tx, pre, jax.random.PRNGKey(0),
                                      sample)

    def ce(out, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, batch['label']).mean()
    step = training.build_train_step(model, tx, pre, ce, donate=False,
                                     extra_mutable=(capture.COUNTERS,))
    losses = []
    for i in range(steps):
        ids = jax.random.randint(jax.random.PRNGKey(i), (2, 11), 0, 48)
        state, m = step(state, {'input': ids[:, :-1], 'label': ids[:, 1:]})
        losses.append(float(m['loss']))
    return pre, state, losses


@pytest.fixture(scope='module')
def both():
    return _train(True), _train(False)


def test_shared_factor_is_each_members_own_to_the_last_bit(both):
    (pre, state, _), (pre2, state2, _) = both
    plan, own = pre.plan, pre2.plan
    assert pred_layout_record(plan)['a_groups'] == 2 + 1 + 1 + 5
    assert pred_layout_record(own)['a_groups'] == 0
    f, f2 = state.kfac_state.factors, state2.kfac_state.factors
    x, x2 = state.kfac_state.decomp['invs'], state2.kfac_state.decomp['invs']
    assert {k: v.shape[0] for k, v in f.items()} == {
        str(d): b.n_factor_rows for d, b in plan.buckets.items()}
    saved = sum(v.shape[0] for v in f2.values()) - sum(
        v.shape[0] for v in f.values())
    assert saved == pred_layout_record(plan)['a_rows_saved'] == 3 * 2 + 2 + 5
    for i, meta in enumerate(plan.metas):
        ba, ra, bg, rg, _ = plan.layer_rows[i]
        ba2, ra2, bg2, rg2, _ = own.layer_rows[i]
        # the one stored A is every member's own A; its G; both inverses
        np.testing.assert_array_equal(f[str(ba)][ra], f2[str(ba2)][ra2],
                                      err_msg=meta.name)
        np.testing.assert_array_equal(f[str(bg)][rg], f2[str(bg2)][rg2])
        np.testing.assert_array_equal(
            x[str(ba)][plan.inv_row_a[i]], x2[str(ba2)][ra2],
            err_msg=meta.name)
        np.testing.assert_array_equal(x[str(bg)][rg], x2[str(bg2)][rg2])


def test_preconditioned_steps_are_those_of_an_a_a_layer(both):
    (_, state, losses), (_, state2, losses2) = both
    assert losses == losses2
    for a, b in zip(jax.tree.leaves((state.params, state.opt_state)),
                    jax.tree.leaves((state2.params, state2.opt_state))):
        np.testing.assert_array_equal(a, b)


def test_statistic_of_a_group_is_computed_once(both):
    (pre, _, _), _ = both
    plan = pre.plan
    model = models.mixed_decoder_lm(**CFG)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 10), 0, 48)
    variables = capture.init(model, {'params': jax.random.PRNGKey(0)}, ids)

    def loss_fn(logits):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, ids).mean()
    _, _, _, acts, gs, _ = capture.value_and_grad_with_capture(
        model, loss_fn, variables, ids, mutable=(capture.COUNTERS,))
    stats = engine.layer_stats(plan, acts, gs)
    leaders = plan.a_leaders()
    for i, lead in enumerate(leaders):
        assert (stats.a_list[i] is stats.a_list[lead]) == (
            plan.metas[i].kind == 'dense') or lead == i
    # a stacked leaf takes its leader leaf's batched A as it is
    gate, up = (('layer_1', 'mlp', 'experts', n) for n in ('gate', 'up'))
    assert stats.stacks[up][0][0] is stats.stacks[gate][0][0]
    assert stats.stacks[up][0][1] is not stats.stacks[gate][0][1]
    # the flags' rows are the factor rows
    for d, b in plan.buckets.items():
        assert engine.rows_ok(plan, stats)[str(d)].shape == (
            b.n_factor_rows,)
        assert engine.rows_seen(plan, acts)[str(d)].shape == (
            b.n_factor_rows,)


def test_tiled_and_rowwise_paths_read_the_shared_factor(both, monkeypatch):
    """The buckets too large to invert whole: groups of rows, each made of
    the factor row its ``Bucket.factor_row`` names."""
    from kfac_pytorch_tpu.ops import linalg
    (_, state, losses), _ = both
    one = 128 ** 3 * 4 // 256
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 10 * one)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', 4 * one)
    pre, tiled, tiled_losses = _train(True)
    assert engine.tiled_buckets(pre.plan) == ('128',)
    assert pre.hoists_update
    # as many groups as a bucket of that many rows of its own would take
    b = pre.plan.buckets[128]
    assert b.n_factor_rows < b.n_rows
    assert pred_layout_record(pre.plan)['decomp_groups'] == {
        '128': [-(-b.n_rows // 4), 1]}
    for a, b in zip(losses, tiled_losses):
        assert a == pytest.approx(b, rel=1e-5)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(tiled.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # (the second factor update follows parameters that differ by the
    # tiled inverse's rounding)
    np.testing.assert_allclose(tiled.kfac_state.factors['128'],
                               state.kfac_state.factors['128'], rtol=2e-3,
                               atol=1e-5)


def test_damped_inverse_of_named_rows(monkeypatch):
    from kfac_pytorch_tpu.ops import linalg
    m = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32))
    x = jnp.einsum('rij,rkj->rik', m, m) / 32
    rows = np.asarray([0, 1, 2, 3, 1, 1, 3], np.int32)
    damp = jnp.linspace(0.01, 0.1, 7)
    want = ops.psd_inverse(ops.add_scaled_identity(x[rows], damp))
    np.testing.assert_array_equal(
        ops.damped_psd_inverse(x, damp, rows=rows), want)
    one = 16 ** 3 * 4 // 256
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 4 * one)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', 3 * one)
    assert ops.inverse_tiling(7, 16) == (3, 16)
    stored = jax.random.normal(jax.random.PRNGKey(1), (7, 16, 16))
    got = jax.jit(lambda a, p: ops.damped_psd_inverse(
        a, damp, prev=p, guard=True, rows=rows))(x, stored)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert ops.damped_psd_inverse(x, damp, rows=rows).shape == (7, 16, 16)


# -- what does not read such rows keeps an A a layer, by name ---------------

@pytest.mark.parametrize('kw,why', [
    (dict(variant='eigen_dp'), 'eigh'),
    (dict(variant='ekfac_dp'), 'eigh, E-KFAC'),
    (dict(variant='inverse_dp', stagger=True), 'stagger'),
    (dict(variant='inverse_dp', num_devices=2), 'more than one device'),
])
def test_variants_that_do_not_read_shared_rows_say_so(kw, why, caplog):
    model, variables, x, img = _toy()
    metas = capture.collect_layer_meta(model, variables, x, img)
    pre = kfac.KFAC(**dict(dict(num_devices=1, kfac_update_freq=4), **kw))
    with caplog.at_level(logging.INFO,
                         logger='kfac_pytorch_tpu.preconditioner'):
        pre.setup(metas)
    assert pred_layout_record(pre.plan)['a_groups'] == 0
    assert all(b.n_factor_rows == b.n_rows
               for b in pre.plan.buckets.values())
    said = [r.getMessage() for r in caplog.records
            if 'keep an A each' in r.getMessage()]
    assert len(said) == 1 and f'({why} does not read' in said[0]
    # the variant that does says nothing and records the groups
    caplog.clear()
    with caplog.at_level(logging.INFO,
                         logger='kfac_pytorch_tpu.preconditioner'):
        ok = kfac.KFAC(variant='inverse_dp', num_devices=1)
        ok.setup(metas)
    assert not any('keep an A each' in r.getMessage()
                   for r in caplog.records)
    line, = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith('precond.setup')]
    assert 'a_groups 4' in line and 'a_rows_saved 5' in line


@pytest.mark.parametrize('kw', [dict(num_devices=2),
                                dict(num_devices=1,
                                     distribute_layer_factors=True)])
def test_build_plan_refuses_groups_it_cannot_lay_out(kw):
    """``KFAC._plan_metas`` alone decides who keeps an ``A`` a layer; the
    layout refuses what it was not to be handed."""
    model, variables, x, img = _toy()
    metas = capture.collect_layer_meta(model, variables, x, img)
    with pytest.raises(ValueError, match='without_input_groups'):
        build_plan(metas, comm_mode='inverse', **kw)
    build_plan(without_input_groups(metas), comm_mode='inverse', **kw)


def test_replan_to_more_devices_gives_every_layer_its_own_rows(both):
    """A live replan (here: to the eigen variant's plan and back is not
    possible; to two devices) rebuilds the plan with an A a layer and
    carries the one factor and each member's inverse into it."""
    (pre, state, _), (pre2, state2, _) = both
    new = kfac.KFAC(variant='inverse_dp', lr=0.01, damping=0.003,
                    num_devices=1)
    new.setup(without_input_groups({m.name: m for m in pre.plan.metas}))
    moved = reshard_kfac_state(pre, new, state.kfac_state,
                               carry_decomp=True)
    for key in moved.factors:
        np.testing.assert_array_equal(moved.factors[key],
                                      state2.kfac_state.factors[key])
        np.testing.assert_array_equal(moved.decomp['invs'][key],
                                      state2.kfac_state.decomp['invs'][key])
    # and KFAC.replan strips the groups for a target that cannot read them
    metas = {m.name: m for m in pre.plan.metas}
    assert pre._plan_metas(metas) is metas
    stripped = pre._plan_metas(metas, num_devices=2)
    assert all(m.input_group is None for m in stripped.values())
    assert dataclasses.replace(
        metas['layer_0/mlp/up'], input_group=None) == stripped[
            'layer_0/mlp/up']
