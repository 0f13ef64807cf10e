"""The sparse decoder (``models.sparse_decoder_lm``: latent attention,
sigmoid-routed experts) and what it forced in the engine: stacked layers
with a factor pair a slice, idle experts, the router's counters, the
decomposition in groups of a bucket's rows. Tiny sizes on the CPU, seeded,
float32 at ``highest``; the model is held against the benchmark's plain
reference (``benchmarks/reference/sparse_lm_plain.py``), which imports
nothing of the program."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, engine, models, ops, training
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu.models.sparse_decoder import interleaved_rotary
from kfac_pytorch_tpu.parallel.moe import RoutedExperts
from kfac_pytorch_tpu.plan import build_plan

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks')
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)
from harness import files, weights  # noqa: E402

jax.config.update('jax_default_matmul_precision', 'highest')

#: a small model's share: 5 of 8 experts, 4 heads, 20 rows a buffer
CFG = dict(
    vocab_size=48, hidden_size=24, num_hidden_layers=2,
    first_k_dense_replace=1, intermediate_size=40, moe_intermediate_size=12,
    n_routed_experts_published=8, num_experts_per_tok=3, n_shared_experts=2,
    routed_scaling_factor=2.448, norm_topk_prob=True, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e4,
    rms_norm_eps=1e-6, head_ids=[0, 1, 2, 3],
    expert_ids=[0, 2, 3, 5, 7], seq_len=10, tokens_per_step=20,
    expert_capacity=20)
TRAFFIC = dict(batch_per_chip=2, chips=1)


def build(cfg):
    return models.sparse_decoder_lm(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_layers=cfg['num_hidden_layers'],
        first_k_dense=cfg['first_k_dense_replace'],
        intermediate_size=cfg['intermediate_size'],
        expert_width=cfg['moe_intermediate_size'],
        n_routed_experts=cfg['n_routed_experts_published'],
        experts_per_tok=cfg['num_experts_per_tok'],
        n_shared_experts=cfg['n_shared_experts'],
        routed_scale=cfg['routed_scaling_factor'],
        kv_rank=cfg['kv_lora_rank'], qk_nope=cfg['qk_nope_head_dim'],
        qk_rope=cfg['qk_rope_head_dim'], v_dim=cfg['v_head_dim'],
        rope_theta=cfg['rope_theta'], head_ids=tuple(cfg['head_ids']),
        expert_ids=tuple(cfg['expert_ids']),
        expert_capacity=cfg['expert_capacity'])


@pytest.fixture(scope='module')
def plain():
    return files.load_module('reference', 'sparse_lm_plain')


def seeded(plain, cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    shapes = plain.param_shapes(cfg)
    flat = {p: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
            + (1.0 if p.endswith('/scale') else 0.0)
            for i, (p, s) in enumerate(sorted(shapes.items()))}
    batch = plain.make_batch(cfg, TRAFFIC, jax.random.fold_in(key, 999))
    return flat, batch


def program_loss(cfg, flat, batch):
    model = build(cfg)

    def loss(params):
        logits = model.apply({'params': weights.unflatten(params)},
                             batch['input'])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch['label']).mean()
    return jax.jit(jax.value_and_grad(loss))(flat)


def test_block_is_the_plain_reference(plain):
    """Loss and every leaf's gradient, float32 at highest."""
    flat, batch = seeded(plain, CFG)
    assert set(flat) == set(weights.flatten(capture.init(
        build(CFG), {'params': jax.random.PRNGKey(0)},
        batch['input'])['params']))
    loss, grads = program_loss(CFG, flat, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: plain.forward(CFG, p, batch, {}, jnp.float32)[0]))(flat)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    for path in flat:
        np.testing.assert_allclose(
            grads[path], ref_grads[path], rtol=2e-4,
            atol=2e-5 * float(jnp.abs(ref_grads[path]).max()) + 1e-9,
            err_msg=path)
    # the bias of the choice gets no gradient; the router does
    assert not np.any(grads['layer_1/mlp/e_score_correction_bias'])
    assert np.any(grads['layer_1/mlp/router/kernel'])


def _expert_layer(ids, capacity=20):
    return RoutedExperts(n_routed=8, top_k=3, expert_ids=tuple(ids),
                         expert_width=12, shared_width=24,
                         capacity=capacity, scale=2.448)


def _expert_params(key, dim=24):
    full = _expert_layer(range(8))
    x = jax.random.normal(jax.random.fold_in(key, 1), (20, dim))
    params = full.init(key, x)['params']
    params = jax.tree.map(
        lambda p: 0.4 * jax.random.normal(jax.random.fold_in(key, p.size),
                                          p.shape), params)
    return full, params, x


def test_expert_shares_add_up_to_the_uncut_layer():
    """Over all shares of the experts, with the shared expert counted
    once, the parts sum to what the layer gives holding all of them."""
    full, params, x = _expert_params(jax.random.PRNGKey(3))
    whole, counts = full.apply({'params': params}, x)
    shared = params['shared']
    hid = jax.nn.silu(x @ shared['gate']['kernel']) * (
        x @ shared['up']['kernel'])
    shared_out = hid @ shared['down']['kernel']
    total = shared_out
    for ids in ((0, 1, 2), (3, 4), (5, 6, 7)):
        mine = dict(params, experts=jax.tree.map(
            lambda k: k[jnp.asarray(ids)], params['experts']))
        part, _ = _expert_layer(ids).apply({'params': mine}, x)
        total = total + (part - shared_out)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)
    assert float(counts['dropped']) == 0
    assert float(counts['rows_mean']) == 20 * 3 / 8


def test_head_shares_add_up_to_the_uncut_model(plain):
    """One layer of attention: the outputs of the head shares sum to the
    output with every head (``o_proj`` is linear in the heads)."""
    cfg = dict(CFG, num_hidden_layers=1)
    flat, batch = seeded(plain, cfg, seed=5)
    h, nope, rope, vd = 4, 8, 4, 8

    def attn_out(head_ids, params):
        from kfac_pytorch_tpu.models.sparse_decoder import LatentAttention
        layer = LatentAttention(tuple(head_ids), nope, rope, vd, 16, 1e4)
        u = jax.random.normal(jax.random.PRNGKey(8), (20, 24))
        return layer.apply({'params': params}, u, 2, 10)
    p = weights.unflatten(flat)['layer_0']['self_attn']
    whole = attn_out(range(h), p)
    total = 0.0
    for ids in ((0, 1), (2,), (3,)):
        cols = lambda w: np.concatenate(       # noqa: E731
            [np.arange(i * w, (i + 1) * w) for i in ids])
        mine = dict(
            p, q_proj={'kernel': p['q_proj']['kernel'][:, cols(nope + rope)]},
            kv_b_proj={'kernel': p['kv_b_proj']['kernel'][:, cols(nope + vd)]},
            o_proj={'kernel': p['o_proj']['kernel'][cols(vd)]})
        total = total + attn_out(ids, mine)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)


def test_routing_against_a_hand_written_case():
    """sigmoid scores, top-k of score + bias, weights over the chosen's
    own scores normalised over ALL chosen, times the scale."""
    layer = RoutedExperts(n_routed=4, top_k=2, expert_ids=(1, 3),
                          expert_width=2, shared_width=0, capacity=4,
                          scale=2.448)
    x = jnp.eye(3)
    params = layer.init(jax.random.PRNGKey(0), x)['params']
    logits = np.array([[2.0, 1.0, 0.0, -1.0],      # chooses 0, 1
                       [0.0, -2.0, 0.5, 3.0],      # 3, 2; the bias: 3, 0
                       [-1.0, 0.3, 0.2, 0.1]])     # 1, 2; the bias: 1, 3
    bias = np.array([0.0, 0.0, -1.0, 0.0])
    params = dict(params, router={'kernel': jnp.asarray(logits)},
                  e_score_correction_bias=jnp.asarray(bias))
    ones = jax.tree.map(jnp.ones_like, params['experts'])
    params['experts'] = ones
    y, counts = layer.apply({'params': params}, x)
    s = 1 / (1 + np.exp(-logits))
    # an expert of all-ones kernels on a one-hot row: silu(1) * 1 summed
    # over its 2 hidden units, in every output column
    expert = 2 * (1 / (1 + np.exp(-1.0)))
    want = np.array([s[0, 1] / (s[0, 0] + s[0, 1]),
                     s[1, 3] / (s[1, 3] + s[1, 0]),
                     1.0]) * 2.448 * expert    # row 2 holds both its choices
    np.testing.assert_allclose(y, np.tile(want[:, None], (1, 3)), rtol=1e-6)
    assert float(counts['rows_max']) == 2 and float(counts['dropped']) == 0


def test_interleaved_rotary_is_the_complex_product():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3, 8))
    pos = jnp.arange(7)
    got = interleaved_rotary(x, pos, 1e4)
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    freq = 1e4 ** (-np.arange(0, 8, 2) / 8)
    z = z * np.exp(1j * np.arange(7)[None, :, None, None] * freq)
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # a rotation: what attention reads, q . k, sees the distance alone
    q, k = x[:, :, 0], x[:, :, 1]
    a = jnp.einsum('bld,bmd->blm', interleaved_rotary(q, pos, 1e4),
                   interleaved_rotary(k, pos, 1e4))
    b = jnp.einsum('bld,bmd->blm', interleaved_rotary(q, pos + 5, 1e4),
                   interleaved_rotary(k, pos + 5, 1e4))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# -- a stacked layer under K-FAC is its slices as separate layers ----------

ROWS = (5, 0, 3)        # rows routed to each of three experts: one idle
D_IN, D_OUT, CAP, T = 6, 4, 5, 16


class Stacked(knn.linen.Module):
    @knn.linen.compact
    def __call__(self, xbuf):
        rows = jnp.asarray(ROWS, jnp.float32)
        return knn.StackedDense(D_OUT, name='experts')(xbuf, rows, T)


class Separate(knn.linen.Module):
    @knn.linen.compact
    def __call__(self, xbuf):
        return [knn.Dense(D_OUT, use_bias=False, name=f'expert_{e}')(
            xbuf[e, :max(n, 1)]) for e, n in enumerate(ROWS)]


def _stacked_and_separate(seed=0):
    key = jax.random.PRNGKey(seed)
    xbuf = jax.random.normal(key, (3, CAP, D_IN))
    live = jnp.arange(CAP)[None, :] < jnp.asarray(ROWS)[:, None]
    xbuf = xbuf * live[..., None]
    kernel = jax.random.normal(jax.random.fold_in(key, 1), (3, D_IN, D_OUT))
    target = jax.random.normal(jax.random.fold_in(key, 2), (3, CAP, D_OUT))

    def loss_stacked(y):
        return jnp.sum(jnp.square(y - target) * live[..., None]) / T

    def loss_separate(ys):
        return sum(jnp.sum(jnp.square(y - target[e, :y.shape[0]])
                           * live[e, :y.shape[0], None])
                   for e, y in enumerate(ys)) / T
    out = {}
    for name, model, params, loss_fn in (
            ('stacked', Stacked(), {'experts': {'kernel': kernel}},
             loss_stacked),
            ('separate', Separate(),
             {f'expert_{e}': {'kernel': kernel[e]} for e in range(3)},
             loss_separate)):
        variables = {'params': params}
        metas = capture.collect_layer_meta(model, variables, xbuf)
        plan = build_plan(metas, 1, 'pred')
        loss, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            model, loss_fn, variables, xbuf)
        out[name] = (plan, loss, grads, acts, gs)
    return out


def test_stacked_layer_statistics_are_those_of_separate_layers():
    both = _stacked_and_separate()
    plan, loss, grads, acts, gs = both['stacked']
    a_list, g_list = engine.compute_layer_stats(plan, acts, gs)
    plan2, loss2, grads2, acts2, gs2 = both['separate']
    np.testing.assert_allclose(loss, loss2, rtol=1e-6)
    a2, g2 = engine.compute_layer_stats(plan2, acts2, gs2)
    for e, n in enumerate(ROWS):
        if n == 0:
            assert not np.any(a_list[e]) and not np.any(g_list[e])
            continue
        np.testing.assert_allclose(a_list[e], a2[e], rtol=1e-5, atol=1e-7)
        # compute_g_dense scales by its own row count n where the loss's
        # mean is over T: (n g)'(n g) / n against (T g)'(T g) / n
        np.testing.assert_allclose(g_list[e], g2[e] * (T / n) ** 2,
                                   rtol=1e-5, atol=1e-7)


def test_stacked_gradient_slices_are_read_preconditioned_and_written_back():
    both = _stacked_and_separate(seed=4)
    plan, _, grads, _, _ = both['stacked']
    plan2, _, grads2, _, _ = both['separate']
    mats = [engine.layer_grad_matrix(m, grads) for m in plan.metas]
    mats2 = [engine.layer_grad_matrix(m, grads2) for m in plan2.metas]
    for a, b in zip(mats, mats2):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    key = jax.random.PRNGKey(9)
    decomp = {'invs': {str(d): jax.random.normal(
        jax.random.fold_in(key, d), (plan.buckets[d].n_rows, d, d))
        for d in plan.bucket_dims}}
    assert plan.layer_rows == plan2.layer_rows
    preds = engine.compute_pred_replicated(plan, decomp, mats, 0.0, 'chol')
    preds2 = engine.compute_pred_replicated(plan2, decomp, mats2, 0.0,
                                            'chol')
    new = engine.preconditioned_grads(plan, grads, mats, preds, 0.1, 1e-3)
    new2 = engine.preconditioned_grads(plan2, grads2, mats2, preds2, 0.1,
                                       1e-3)
    for e in range(3):
        np.testing.assert_allclose(new['experts']['kernel'][e],
                                   new2[f'expert_{e}']['kernel'], rtol=1e-5)
    # one slice alone goes back into its place and leaves the others
    one = engine.write_grad_matrix(plan.metas[1], grads, jnp.ones((4, 6)))
    np.testing.assert_array_equal(one['experts']['kernel'][1], 1.0)
    np.testing.assert_array_equal(one['experts']['kernel'][0],
                                  grads['experts']['kernel'][0])


def test_idle_expert_keeps_its_running_averages():
    plan, _, _, acts, gs = _stacked_and_separate()['stacked']
    a_list, g_list = engine.compute_layer_stats(plan, acts, gs)
    stats = engine.stack_stats(plan, a_list, g_list)
    key = jax.random.PRNGKey(2)
    before = {k: jax.random.normal(jax.random.fold_in(key, i), v.shape)
              for i, (k, v) in enumerate(stats.items())}
    seen = engine.rows_seen(plan, acts)
    after, _ = engine.update_factors(plan, before, stats, 0.95, 'local',
                                     None, seen=seen)
    for i, meta in enumerate(plan.metas):
        ba, ra, bg, rg, _ = plan.layer_rows[i]
        for b, r in ((ba, ra), (bg, rg)):
            same = np.array_equal(after[str(b)][r], before[str(b)][r])
            assert same == (ROWS[meta.index] == 0)
    # a plan without stacked layers has no flags to carry
    assert engine.rows_seen(_stacked_and_separate()['separate'][0], {}) \
        is None


def test_rowwise_update_is_the_stacked_update_with_its_guard():
    plan, _, _, acts, gs = _stacked_and_separate()['stacked']
    stacks = {}
    a_list, g_list = engine.compute_layer_stats(plan, acts, gs,
                                                stacks=stacks)
    key = jax.random.PRNGKey(2)
    before = {str(d): jax.random.normal(
        jax.random.fold_in(key, d), (plan.buckets[d].n_rows, d, d))
        for d in plan.bucket_dims}
    d = plan.bucket_dims[0]
    # the three experts' A rows and their G rows: two runs of three
    assert [(r, s.shape[0]) for r, s, _ in engine._stat_runs(
        plan, d, a_list, g_list, stacks)] == [(0, 3), (3, 3)]
    before[str(d)] = before[str(d)].at[0, 0, 0].set(jnp.nan)   # a bad row
    stats = engine.stack_stats(plan, a_list, g_list)
    want, _ = engine.update_factors(plan, before, stats, 0.95, 'local',
                                    None, seen=engine.rows_seen(plan, acts))
    want = engine.where_finite_rows(want, before, reinit_identity=True)
    got = engine.update_factor_rows(plan, d, before[str(d)], a_list, g_list,
                                    stacks, 0.95, guard=True)
    np.testing.assert_array_equal(got, want[str(d)])
    np.testing.assert_array_equal(got[0], np.eye(d))    # healed
    kept = engine.update_factor_rows(
        plan, d, before[str(d)], a_list, g_list, stacks, 0.95, guard=True,
        commit=jnp.zeros((), bool))
    np.testing.assert_array_equal(kept, before[str(d)])  # not committed
    assert engine.stack_stats(plan, a_list, g_list, skip=(str(d),)) == {}
    # nothing is too large here: no bucket goes row by row
    assert engine.rowwise_buckets(plan, 'local') == ()


def _train(capacity, steps=3, poison=()):
    cfg = dict(CFG, expert_capacity=capacity)
    model = build(cfg)
    pre = kfac.KFAC(variant='inverse_dp', lr=0.01, damping=0.003,
                    fac_update_freq=2, kfac_update_freq=2, kl_clip=0.001,
                    factor_decay=0.95, num_devices=1)
    tx = training.sgd(0.01, momentum=0.9)
    state = training.init_train_state(model, tx, pre, jax.random.PRNGKey(0),
                                      jnp.zeros((2, 10), jnp.int32))

    def ce(out, batch):
        loss = optax.softmax_cross_entropy_with_integer_labels(
            out, batch['label']).mean()
        return jnp.where(batch['poison'], jnp.nan, loss)
    step = training.build_train_step(model, tx, pre, ce, donate=False,
                                     extra_mutable=(capture.COUNTERS,))
    plain = files.load_module('reference', 'sparse_lm_plain')
    mets = []
    _train.states = [state]
    for i in range(steps):
        batch = plain.make_batch(cfg, TRAFFIC, jax.random.PRNGKey(i))
        batch['poison'] = jnp.asarray(i in poison)
        state, m = step(state, batch)
        _train.states.append(state)
        mets.append({k: float(v) for k, v in m.items()})
    _train.state = state
    return pre, mets


@pytest.fixture(scope='module')
def roomy_run():
    pre, mets = _train(20)
    return pre, mets, _train.state.params


def test_dropped_rows_are_counted_and_add_up_over_steps(roomy_run):
    pre, roomy, _ = roomy_run
    assert [m['moe/dropped'] for m in roomy] == [0, 0, 0]
    assert all(m['moe/rows_max'] >= m['moe/rows_mean'] for m in roomy)
    assert all(np.isfinite(m['loss']) for m in roomy)
    record = kfac.plan.pred_layout_record(pre.plan)
    assert record['stacked_layers'] == 3 * 5
    assert record['pred_operand_takes'] == 0
    _, tight = _train(2)
    dropped = [m['moe/dropped'] for m in tight]
    assert dropped[0] > 0
    assert dropped[0] < dropped[1] < dropped[2]      # cumulative
    # every step's own count is the rows beyond the buffers
    assert dropped[0] >= tight[0]['moe/rows_max'] - 2 > 0


@pytest.mark.parametrize('route', ['solves', 'structured'])
def test_training_with_tiled_buckets_is_training_with_whole_ones(
        roomy_run, route, monkeypatch):
    """The buckets too large to invert whole take other code (groups of
    rows written over the stored inverses, running averages a row at a
    time): made to apply to this tiny model's one bucket, three steps give
    what they give with the bucket whole. Also where the groups' matrices
    take the structured route from the Cholesky factor to the inverse (by
    blocks of 32 here), as the benchmark's tiled buckets do."""
    from kfac_pytorch_tpu.ops import linalg
    _, whole, params = roomy_run
    one = 128 ** 3 * 4 // 256
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 10 * one)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', 4 * one)
    if route == 'structured':
        monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM', 128)
        monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_BLOCK', 32)
    pre, tiled = _train(20)
    assert kfac.plan.pred_layout_record(pre.plan)['decomp_route'] == {
        '128': route}
    assert engine.tiled_buckets(pre.plan) == ('128',)
    assert engine.rowwise_buckets(pre.plan, 'local') == ('128',)
    record = kfac.plan.pred_layout_record(pre.plan)
    rows = pre.plan.buckets[128].n_rows
    assert record['decomp_groups'] == {'128': [-(-rows // 4), 1]}
    for a, b in zip(whole, tiled):
        assert a['loss'] == pytest.approx(b['loss'], rel=1e-5)
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(_train.state.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_refused_batch_leaves_a_hoisted_update_uncommitted(monkeypatch):
    """With a tiled bucket the factor and inverse updates run before the
    health guard's cond (KFAC.hoists_update), committed by its flag: a
    refused batch on an update step leaves factors, inverses, parameters
    and the cumulative counter as they were."""
    from kfac_pytorch_tpu.ops import linalg
    one = 128 ** 3 * 4 // 256
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 10 * one)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', 4 * one)
    pre, mets = _train(2, poison=(2,))      # step 2 updates factors
    assert pre.hoists_update
    assert [m['health/ok'] for m in mets] == [1, 1, 0]
    assert mets[2]['health/skipped'] == 1
    before, after = _train.states[2], _train.states[3]
    for a, b in zip(jax.tree.leaves((before.kfac_state.factors,
                                     before.kfac_state.decomp,
                                     before.params, before.extra_vars)),
                    jax.tree.leaves((after.kfac_state.factors,
                                     after.kfac_state.decomp,
                                     after.params, after.extra_vars))):
        np.testing.assert_array_equal(a, b)
    assert int(after.kfac_state.step) == int(before.kfac_state.step) + 1
    assert mets[2]['moe/dropped'] == mets[1]['moe/dropped'] > 0
    # and the update steps before it did change them
    first = _train.states[0].kfac_state
    assert not np.array_equal(first.factors['128'],
                              before.kfac_state.factors['128'])
    assert np.any(before.kfac_state.decomp['invs']['128'] != 0)


# -- the decomposition in groups of a bucket's rows --------------------------

def _spd(key, rows, dim):
    m = jax.random.normal(key, (rows, dim, 2 * dim))
    return jnp.einsum('rij,rkj->rik', m, m) / (2 * dim)


def test_tiled_decomposition_is_the_whole_bucket(monkeypatch):
    from kfac_pytorch_tpu.ops import linalg
    x = _spd(jax.random.PRNGKey(0), 7, 16)
    damp = jnp.linspace(0.01, 0.1, 7)
    whole = ops.damped_psd_inverse(x, damp)
    np.testing.assert_allclose(
        whole, ops.psd_inverse(ops.add_scaled_identity(x, damp)))
    one = 16 ** 3 * 4 // 256
    # 7 rows in groups of 3 (the last group overlaps its neighbour), then
    # one row a group (each whole: until PR 46 in 4 panels of columns),
    # on the route of two solves and on the structured one
    for group_bytes, tiling, structured in (
            (3 * one, (3, 16), False), (one // 4, (1, 16), False),
            (3 * one, (3, 16), True), (one // 4, (1, 16), True)):
        monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 4 * one)
        monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', group_bytes)
        monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_DIM',
                            16 if structured else 2048)
        monkeypatch.setattr(linalg, 'STRUCTURED_INVERSE_BLOCK', 4)
        assert ops.inverse_route(16) == (
            'structured' if structured else 'solves')
        assert ops.inverse_tiling(7, 16) == tiling
        assert ops.inverse_tiling(4, 16) == (4, 16)
        # (a fresh function a jit: its cache does not see a patched
        # constant)
        tiled = jax.jit(lambda a, d: ops.damped_psd_inverse(a, d))(x, damp)
        np.testing.assert_allclose(tiled, whole, rtol=1e-5, atol=1e-6)
        if structured:
            np.testing.assert_array_equal(tiled, jnp.swapaxes(tiled, 1, 2))


def test_tiled_decomposition_writes_over_the_stored_rows_and_screens_them(
        monkeypatch):
    from kfac_pytorch_tpu.ops import linalg
    one = 16 ** 3 * 4 // 256
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 4 * one)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', 3 * one)
    x = _spd(jax.random.PRNGKey(0), 7, 16)
    damp = jnp.full((7,), 0.05)
    want = ops.psd_inverse(ops.add_scaled_identity(x, damp))
    stored = jax.random.normal(jax.random.PRNGKey(1), (7, 16, 16))
    stored = stored.at[5].set(0.0)          # row 5: nothing stored yet
    bad = x.at[2, 0, 0].set(jnp.nan).at[5, 1, 1].set(jnp.nan)
    got = jax.jit(lambda a, p: ops.damped_psd_inverse(
        a, damp, prev=p, guard=True))(bad, stored)
    for r in (0, 1, 3, 4, 6):
        np.testing.assert_allclose(got[r], want[r], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[2], stored[2])    # the last good one
    np.testing.assert_array_equal(got[5], np.eye(16))   # cold: identity
    # not committed (a refused batch, hoisted update): the stored rows stay
    kept = jax.jit(lambda a, p: ops.damped_psd_inverse(
        a, damp, prev=p, guard=True, commit=jnp.zeros((), bool)))(x, stored)
    np.testing.assert_array_equal(kept, stored)
    # the engine's whole-bucket guard passes such a bucket by
    new = {'invs': {'16': got}}
    assert engine.guard_decomposition(
        new, {'invs': {'16': stored}}, 'chol', done=('16',)
    )['invs']['16'] is got


def test_tiling_comes_from_the_buckets_shape():
    # every bucket the benchmark's other cells invert goes whole ...
    for rows, dim in ((12, 3200), (12, 3072), (61, 896), (60, 768),
                      (7, 2304), (1, 4608)):
        assert ops.inverse_tiling(rows, dim) == (rows, dim)
    # ... the sparse decoder's 2,048 bucket in 16 groups of 8 rows, and
    # its three 6,144s one at a time, each whole (in 4 panels of columns
    # until PR 46: the structured route holds a few copies of a matrix
    # where a solve held 24 right-hand sides)
    assert ops.inverse_tiling(126, 2048) == (8, 2048)
    assert ops.inverse_tiling(136, 2048) == (8, 2048)
    assert ops.inverse_tiling(3, 6144) == (1, 6144)
    assert ops.inverse_tiling(101, 768) == (101, 768)
    # and those two are buckets of the structured route, as every bucket
    # of 1,024 and more
    assert [ops.inverse_route(d) for d in (768, 896, 1024, 2048, 6144)] == [
        'solves', 'solves', 'structured', 'structured', 'structured']


def test_bucket_under_the_threshold_lowers_as_before():
    """compute_decomposition's program for a plan whose buckets all go
    whole is the one it was: psd_inverse of the damped bucket."""
    metas = {f'l{i}': capture.LayerMeta(
        name=f'l{i}', path=(f'l{i}',), kind='dense', use_bias=True,
        in_dim=9, out_dim=5, kernel_shape=(8, 5)) for i in range(3)}
    plan = build_plan(metas, 1, 'pred')
    factors = {str(d): _spd(jax.random.PRNGKey(d), plan.buckets[d].n_rows, d)
               for d in plan.bucket_dims}

    def now(f):
        return engine.compute_decomposition(plan, f, 0.003, 'chol', 1e-10,
                                            None)

    def before(f):
        flat_avg = engine._local_trace_avgs(plan, f, None)
        invs = {}
        for bdim in plan.bucket_dims:
            b = plan.buckets[bdim]
            off = plan.local_flat_offsets[bdim]
            own = jax.lax.dynamic_slice_in_dim(flat_avg, off, b.per_dev)
            mate = jnp.take(flat_avg, engine._local_table(b.mate_flat, None))
            damped = ops.add_scaled_identity(f[str(bdim)],
                                             jnp.sqrt(0.003 * own / mate))
            invs[str(bdim)] = ops.psd_inverse(damped)
        return {'invs': invs}
    assert str(jax.make_jaxpr(now)(factors)) == str(
        jax.make_jaxpr(before)(factors))


@pytest.mark.parametrize('variant,warned', [('inverse_dp', False),
                                            ('eigen_dp', True)])
def test_a_variant_that_cannot_hoist_says_so_at_setup(variant, warned,
                                                      monkeypatch, caplog):
    """A bucket too large to invert whole under a variant that updates it
    inside the health guard's cond: ``setup`` names the buckets."""
    from kfac_pytorch_tpu.ops import linalg
    one = 128 ** 3 * 4 // 256
    monkeypatch.setattr(linalg, 'WHOLE_INVERSE_TEMP_BYTES', 2 * one)
    monkeypatch.setattr(linalg, 'INVERSE_GROUP_TEMP_BYTES', one)
    metas = {f'l{i}': capture.LayerMeta(
        name=f'l{i}', path=(f'l{i}',), kind='dense', use_bias=True,
        in_dim=9, out_dim=5, kernel_shape=(8, 5)) for i in range(3)}
    pre = kfac.KFAC(variant=variant, lr=0.01, damping=0.003, num_devices=1)
    with caplog.at_level('WARNING', logger='kfac_pytorch_tpu'):
        pre.setup(metas)
    assert engine.tiled_buckets(pre.plan) == ('128',)
    assert pre.hoists_update is not warned
    assert ('too large to invert whole' in caplog.text) is warned
