"""The decoder whose layers are a recurrence or a softmax
(``models.hybrid_decoder_lm``: Kimi Delta Attention and latent attention
without positions, sigmoid-routed experts with one shared expert, the
dense block's K-FAC factors in blocks). Tiny sizes on the CPU, seeded,
float32 at ``highest``; the model is held against the benchmark's plain
reference (``benchmarks/reference/hybrid_lm_plain.py``: the recurrence
token by token), which imports nothing of the program."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kfac_pytorch_tpu import capture, engine, models
from kfac_pytorch_tpu.models import hybrid_decoder as hd
from kfac_pytorch_tpu.models.sparse_decoder import LatentAttention
from kfac_pytorch_tpu.parallel.moe import BlockedSwiGLU, SwiGLU
from kfac_pytorch_tpu.plan import build_plan, pred_layout_record

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks')
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)
from harness import files, weights  # noqa: E402

jax.config.update('jax_default_matmul_precision', 'highest')

#: a small model's share: a dense KDA layer, a KDA layer and a latent layer
#: with experts; 2 of 4 heads of 16 in both attentions, 5 of 8 experts;
#: sequences of 40 in chunks of 16, so the last chunk is ragged
CFG = dict(
    vocab_size=48, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=96, ffn_block=32,
    moe_intermediate_size=12, num_experts_published=8,
    num_experts_per_token=3, num_shared_experts=1,
    routed_scaling_factor=2.446, moe_renormalize=True, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    linear_attn_config=dict(head_dim=16, num_heads=2,
                            short_conv_kernel_size=4),
    kda_rank=8, kda_chunk=16, kda_a_log_centre=float(np.log(4.0)),
    kda_dt_bias_centre=-4.6, rms_norm_eps=1e-5, kda_head_ids=[1, 3],
    head_ids=[0, 2], layer_kinds_held=['kda', 'kda', 'latent'],
    expert_ids=[0, 2, 3, 5, 7], seq_len=40, tokens_per_step=80,
    expert_capacity=80)
TRAFFIC = dict(batch_per_chip=2, chips=1)


@pytest.fixture(scope='module')
def plain():
    return files.load_module('reference', 'hybrid_lm_plain')


@pytest.fixture(scope='module')
def build():
    return files.load_module('builders', 'hybrid_lm').build_model


def seeded(plain, cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    shapes = plain.param_shapes(cfg)
    flat = {p: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
            + (1.0 if p.endswith('/scale') else 0.0)
            for i, (p, s) in enumerate(sorted(shapes.items()))}
    batch = plain.make_batch(cfg, TRAFFIC, jax.random.fold_in(key, 999))
    return flat, batch


# -- the chunked scan against the recurrence ---------------------------------

def _scan_inputs(decay, length=40, heads=2, dk=16, dv=16, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, length, heads)
    q, k = (jax.random.normal(key[i], shape + (dk,)) for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(key[2], shape + (dv,))
    g = -decay * jnp.exp(0.5 * jax.random.normal(key[3], shape + (dk,)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], shape))
    return q, k, v, g, beta


@pytest.mark.parametrize('chunk', [8, 16], ids=['even', 'ragged'])
@pytest.mark.parametrize('decay', [0.04, 2.0], ids=['mild', 'strong'])
def test_chunked_scan_is_the_recurrence(plain, chunk, decay):
    """Outputs, the final state and the gradient of every input, at the
    init's mild decay and at ``g ~ -2`` a token, where ``e^{-G_i}`` alone
    has left float32 within one chunk."""
    xs = _scan_inputs(decay)
    mix = jax.random.normal(jax.random.PRNGKey(9), (2, 40, 2, 16))

    def chunked(*xs):
        o, end, last = hd.kda_chunked(*xs, chunk)
        return (o * mix).sum() + (end ** 2).sum(), (o, end, last)

    def stepwise(*xs):
        o, end = plain.delta_rule(*xs)
        return (o * mix).sum() + (end ** 2).sum(), (o, end)
    (_, (o, end, last)), grads = jax.jit(jax.value_and_grad(
        chunked, argnums=range(5), has_aux=True))(*xs)
    (_, (want_o, want_end)), want = jax.jit(jax.value_and_grad(
        stepwise, argnums=range(5), has_aux=True))(*xs)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(end, want_end, rtol=1e-4, atol=1e-6)
    for name, got, ref in zip('qkvgb', grads, want):
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(
            got, ref, rtol=2e-4, atol=2e-5 * float(jnp.abs(ref).max()),
            err_msg=name)
    # each chunk's summed log-decay, the padded tokens adding nothing
    g = np.asarray(xs[3])
    pad = -40 % chunk
    g = np.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0)))
    np.testing.assert_allclose(
        last, g.reshape(2, -1, chunk, 2, 16).sum(2).transpose(0, 2, 1, 3),
        rtol=1e-5)


def test_unguarded_form_overflows_where_the_chunked_one_does_not():
    """At ``g ~ -2`` a token a chunk of 64 sums to ``G ~ -128``:
    ``e^{-G_i}``, which a factored ``(e^{G_t} q_t)'(e^{-G_i} k_i)`` needs,
    is past float32; every exponent the chunked form takes is at most 0."""
    q, k, v, g, beta = _scan_inputs(2.0, length=64)
    total = jnp.cumsum(g, axis=1)
    assert not np.isfinite(np.asarray(jnp.exp(-total))).all()
    def summed(*xs):
        out = hd.kda_chunked(*xs, 64)
        return out[0].sum(), out
    (_, (o, end, last)), grads = jax.jit(jax.value_and_grad(
        summed, argnums=range(5), has_aux=True))(q, k, v, g, beta)
    assert float(last.min()) < -88          # ln of float32's largest
    assert np.isfinite(o).all() and np.isfinite(end).all()
    assert all(np.isfinite(x).all() for x in grads)


# -- the model against the plain reference -----------------------------------

def test_block_is_the_plain_reference(plain, build):
    """Logits' loss and every leaf's gradient of a model with both kinds
    of layer and a ragged last chunk, float32 at highest."""
    flat, batch = seeded(plain, CFG)
    model = build(CFG)
    init = jax.eval_shape(lambda: capture.init(
        model, {'params': jax.random.PRNGKey(0)}, batch['input']))
    assert set(flat) == set(weights.flatten(init['params']))
    assert {p: v.shape for p, v in weights.flatten(init['params']).items()
            } == {p: tuple(s) for p, s in plain.param_shapes(CFG).items()}

    def loss(params):
        logits = model.apply({'params': weights.unflatten(params)},
                             batch['input'])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch['label']).mean(), logits
    (got, logits), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        flat)
    want, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: plain.forward(CFG, p, batch, {}, jnp.float32)[0]))(flat)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    logp = jax.nn.log_softmax(logits.reshape(-1, CFG['vocab_size']))
    np.testing.assert_allclose(-jnp.take_along_axis(
        logp, batch['label'].reshape(-1, 1), axis=-1).mean(), want,
        rtol=1e-5)
    for path in flat:
        np.testing.assert_allclose(
            grads[path], ref_grads[path], rtol=3e-4,
            atol=3e-5 * float(jnp.abs(ref_grads[path]).max()) + 1e-9,
            err_msg=path)
    assert not np.any(grads['layer_1/mlp/e_score_correction_bias'])
    for path in ('layer_1/mlp/router/kernel', 'layer_0/self_attn/A_log',
                 'layer_1/self_attn/dt_bias', 'layer_0/self_attn/q_conv/weight',
                 'layer_1/self_attn/o_norm/scale',
                 'layer_0/self_attn/g_b_proj/bias'):
        assert np.any(grads[path]), path


def test_counters_are_the_scan_s_own(plain, build):
    flat, batch = seeded(plain, CFG, seed=2)
    model = build(CFG)
    variables = jax.eval_shape(lambda: capture.init(
        model, {'params': jax.random.PRNGKey(0)}, batch['input']))
    assert set(capture.counter_metrics(variables)) == {
        'moe/dropped', 'moe/rows_max', 'moe/rows_mean',
        'kda/log_decay_min', 'kda/state_absmax'}
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         variables[capture.COUNTERS])
    _, mutated = jax.jit(lambda p, c: model.apply(
        {'params': weights.unflatten(p), capture.COUNTERS: c},
        batch['input'], mutable=[capture.COUNTERS]))(flat, zeros)
    got = capture.counter_metrics(mutated)
    assert float(got['kda/log_decay_min']) < 0
    assert float(got['kda/state_absmax']) > 0
    # evaluation counts nothing, and asks for no such collection
    jax.eval_shape(lambda p: model.apply({'params': weights.unflatten(p)},
                                         batch['input']), flat)


def test_held_layer_kinds_and_the_registry():
    assert hd.held_layer_kinds(5) == (hd.KDA,) * 4 + (hd.LATENT,)
    kinds = hd.HybridDecoderConfig().layer_kinds
    assert [i + 1 for i, k in enumerate(kinds) if k == hd.LATENT] == [
        4, 8, 12, 16, 20, 24, 27]
    assert len(kinds) == 27
    for name in ('hybrid_decoder_lm', 'HybridDecoderLM',
                 'HybridDecoderConfig', 'held_layer_kinds',
                 'mixed_decoder_lm', 'sparse_decoder_lm'):
        assert hasattr(models, name), name
    assert isinstance(models.hybrid_decoder_lm(vocab_size=8),
                      models.HybridDecoderLM)


def test_latent_attention_rotates_unless_told_not_to():
    """``rotary=False`` is the reference's unrotated score; the default
    still rotates (kanana's layer)."""
    key = jax.random.PRNGKey(4)
    u = jax.random.normal(key, (20, 24))
    layer = LatentAttention((0, 1), 8, 4, 8, 16)
    params = layer.init(key, u, 2, 10)['params']
    nope = LatentAttention((0, 1), 8, 4, 8, 16, rotary=False)
    a, b = (m.apply({'params': params}, u, 2, 10) for m in (layer, nope))
    assert not np.allclose(a, b)
    # position 0 is rotated by nothing
    np.testing.assert_allclose(a.reshape(2, 10, -1)[:, 0],
                               b.reshape(2, 10, -1)[:, 0], rtol=1e-5,
                               atol=1e-6)


# -- the shares add up -------------------------------------------------------

def _kda(ids):
    return hd.KimiDeltaAttention(tuple(ids), head_dim=16, rank=8, chunk=16)


def test_kda_head_shares_add_up_to_the_uncut_reference_layer(plain):
    """4 heads split 2 ways; ``f_a_proj``, ``g_a_proj`` whole and the
    convolution weights of held channels on every share: the shares'
    outputs sum to the output with every head, which is the plain
    reference's layer."""
    cfg = dict(CFG, num_hidden_layers=1, layer_kinds_held=['kda'],
               kda_head_ids=[0, 1, 2, 3])
    flat, batch = seeded(plain, cfg, seed=5)
    p = weights.unflatten(flat)['layer_0']['self_attn']
    _, acts = plain.forward(cfg, flat, batch, {}, jnp.float32)
    u = acts['layer_0/self_attn/q_proj']
    whole, _ = _kda(range(4)).apply({'params': p}, u, 2, 40)
    np.testing.assert_allclose(
        whole, acts['layer_0/self_attn/o_proj'] @ p['o_proj']['kernel'],
        rtol=1e-4, atol=1e-5)
    total = 0.0
    for ids in ((0, 1), (2, 3)):
        cols = np.concatenate([np.arange(16 * j, 16 * j + 16) for j in ids])
        by_col = {n: {'kernel': p[n]['kernel'][:, cols]}
                  for n in ('q_proj', 'k_proj', 'v_proj', 'f_b_proj')}
        mine = dict(
            p, **by_col,
            g_b_proj={'kernel': p['g_b_proj']['kernel'][:, cols],
                      'bias': p['g_b_proj']['bias'][cols]},
            b_proj={'kernel': p['b_proj']['kernel'][:, np.array(ids)]},
            o_proj={'kernel': p['o_proj']['kernel'][cols]},
            A_log=p['A_log'][np.array(ids)], dt_bias=p['dt_bias'][cols],
            **{f'{x}_conv': {'weight': p[f'{x}_conv']['weight'][:, cols]}
               for x in 'qkv'})
        total = total + _kda(ids).apply({'params': mine}, u, 2, 40)[0]
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_latent_head_shares_add_up_to_the_uncut_reference_layer(plain):
    cfg = dict(CFG, num_hidden_layers=1, layer_kinds_held=['latent'],
               first_k_dense_replace=1, head_ids=[0, 1, 2, 3])
    flat, batch = seeded(plain, cfg, seed=6)
    p = weights.unflatten(flat)['layer_0']['self_attn']
    _, acts = plain.forward(cfg, flat, batch, {}, jnp.float32)
    u = acts['layer_0/self_attn/q_proj']

    def layer(ids):
        return LatentAttention(tuple(ids), 8, 4, 8, 16, eps=1e-5,
                               rotary=False)
    whole = layer(range(4)).apply({'params': p}, u, 2, 40)
    np.testing.assert_allclose(
        whole, acts['layer_0/self_attn/o_proj'] @ p['o_proj']['kernel'],
        rtol=1e-4, atol=1e-5)
    total = 0.0
    for ids in ((0, 1), (2, 3)):
        q = np.concatenate([np.arange(12 * j, 12 * j + 12) for j in ids])
        kv = np.concatenate([np.arange(16 * j, 16 * j + 16) for j in ids])
        o = np.concatenate([np.arange(8 * j, 8 * j + 8) for j in ids])
        mine = dict(p, q_proj={'kernel': p['q_proj']['kernel'][:, q]},
                    kv_b_proj={'kernel': p['kv_b_proj']['kernel'][:, kv]},
                    o_proj={'kernel': p['o_proj']['kernel'][o]})
        total = total + layer(ids).apply({'params': mine}, u, 2, 40)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)


def test_blocked_swiglu_is_the_unsplit_one():
    """The blocks' weights side by side in an unsplit SwiGLU give the
    blocked layer's output and gradient; only K-FAC's factors are blocks."""
    key = jax.random.PRNGKey(8)
    x = jax.random.normal(key, (20, 24))
    blocked = BlockedSwiGLU(36, 12)
    p = blocked.init(key, x)['params']
    whole = {'gate': {'kernel': jnp.concatenate(
                 [p[f'gate_{j}']['kernel'] for j in range(3)], axis=1)},
             'up': {'kernel': jnp.concatenate(
                 [p[f'up_{j}']['kernel'] for j in range(3)], axis=1)},
             'down': {'kernel': jnp.concatenate(
                 [p[f'down_{j}']['kernel'] for j in range(3)], axis=0)}}
    got = blocked.apply({'params': p}, x)
    np.testing.assert_allclose(got, SwiGLU(36).apply({'params': whole}, x),
                               rtol=1e-4, atol=1e-4)
    g_blocked = jax.grad(lambda p: (blocked.apply({'params': p}, x) ** 2
                                    ).sum())(p)
    g_whole = jax.grad(lambda p: (SwiGLU(36).apply({'params': p}, x) ** 2
                                  ).sum())(whole)
    np.testing.assert_allclose(
        jnp.concatenate([g_blocked[f'down_{j}']['kernel'] for j in range(3)]),
        g_whole['down']['kernel'], rtol=1e-4,
        atol=1e-5 * float(jnp.abs(g_whole['down']['kernel']).max()))
    np.testing.assert_allclose(
        jnp.concatenate([g_blocked[f'gate_{j}']['kernel'] for j in range(3)],
                        axis=1), g_whole['gate']['kernel'], rtol=1e-4,
        atol=1e-5 * float(jnp.abs(g_whole['gate']['kernel']).max()))
    with pytest.raises(ValueError, match='blocks of'):
        BlockedSwiGLU(36, 24).init(key, x)


# -- under K-FAC -------------------------------------------------------------

def _captured(plain, build, cfg, seed=7):
    flat, batch = seeded(plain, cfg, seed=seed)
    model = build(cfg)
    variables = {'params': weights.unflatten(flat)}
    metas = capture.collect_layer_meta(model, variables, batch['input'])

    def loss_fn(logits):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch['label']).mean()
    _, _, grads, acts, gs, _ = jax.jit(
        lambda v: capture.value_and_grad_with_capture(
            model, loss_fn, v, batch['input'],
            mutable=(capture.COUNTERS,)))(variables)
    return flat, batch, metas, grads, acts, gs


def test_capture_sows_round_the_scan(plain, build):
    """The factored projections of a KDA layer lie outside the scan: their
    statistics and gradients are the reference's, whose cotangents came
    back through the token-by-token recurrence; ``g_b_proj`` has its bias
    column."""
    flat, batch, metas, grads, acts, gs = _captured(plain, build, CFG)
    plan = build_plan(metas, 1, 'pred')
    a_list, g_list = engine.compute_layer_stats(plan, acts, gs)
    names = [m.name for m in plan.metas]
    paths = [f'layer_1/self_attn/{n}' for n in (
        'v_proj', 'f_a_proj', 'b_proj', 'f_b_proj', 'g_b_proj', 'o_proj')]
    paths += ['layer_0/mlp/up_2', 'layer_0/mlp/down_1']
    shapes = {}
    jax.eval_shape(lambda p: plain.forward(CFG, p, batch, {}, jnp.float32,
                                           shapes=shapes), flat)
    taps = {p: jnp.zeros(*shapes[p]) for p in paths}
    (_, ref_acts), (ref_grads, ref_gs) = jax.jit(jax.value_and_grad(
        lambda p, t: plain.forward(CFG, p, batch, t, jnp.float32),
        argnums=(0, 1), has_aux=True))(flat, taps)
    for path in paths:
        i = names.index(path)
        a, g = ref_acts[path], 80 * ref_gs[path]
        mat = ref_grads[path + '/kernel'].T
        if path.endswith('g_b_proj'):
            a = jnp.concatenate([a, jnp.ones((80, 1))], axis=1)
            mat = jnp.concatenate(
                [mat, ref_grads[path + '/bias'][:, None]], axis=1)
        np.testing.assert_allclose(a_list[i], a.T @ a / 80, rtol=1e-4,
                                   atol=1e-6, err_msg=path)
        want_g = g.T @ g / 80
        np.testing.assert_allclose(
            g_list[i], want_g, rtol=2e-4,
            atol=1e-5 * float(jnp.abs(want_g).max()), err_msg=path)
        np.testing.assert_allclose(
            engine.layer_grad_matrix(plan.metas[i], grads), mat, rtol=3e-4,
            atol=1e-5 * float(jnp.abs(mat).max()), err_msg=path)


def test_kfac_decision_for_every_weight(plain, build):
    """Kronecker-factored: a KDA layer's nine projections (six of them one
    input group with one ``A``), a latent layer's four, the dense block's
    twelve blocks (``gate_j`` / ``up_j`` one group of six here), shared and
    held experts' three. Everything else is first-order."""
    flat, _, metas, _, _, _ = _captured(plain, build, CFG)
    want = set(l['path'] for l in plain.kfac_layers(CFG))
    assert set(metas) == want
    factored = {m.name for m in metas.values()}
    first_order = {p for p in flat if p.rsplit('/', 1)[0] not in factored
                   and '/experts/' not in p}
    assert {p.split('/', 2)[-1] for p in first_order
            if p.startswith('layer_1/self_attn/')} == {
        'q_conv/weight', 'k_conv/weight', 'v_conv/weight', 'A_log',
        'dt_bias', 'o_norm/scale'}
    groups = {}
    for name, m in metas.items():
        if m.input_group is not None:
            groups.setdefault(m.input_group, []).append(name)
    six = ['q_proj', 'k_proj', 'v_proj', 'f_a_proj', 'g_a_proj', 'b_proj']
    for i in (0, 1):
        assert groups[f'layer_{i}/self_attn/q_proj'] == [
            f'layer_{i}/self_attn/{n}' for n in six]
    assert groups['layer_2/self_attn/q_proj'] == [
        'layer_2/self_attn/q_proj', 'layer_2/self_attn/kv_a_proj_with_mqa']
    assert groups['layer_0/mlp/gate_0'] == [
        f'layer_0/mlp/{n}_{j}' for n in ('gate', 'up') for j in range(3)]
    assert metas['layer_0/mlp/down_1'].input_group is None
    assert metas['layer_1/self_attn/g_b_proj'].use_bias
    assert metas['layer_1/self_attn/g_b_proj'].in_dim == 8 + 1
    # 3 attention + 1 dense + 2 shared + 2 x 5 routed
    assert len(groups) == 16
    plan = build_plan(metas, 1, 'pred')
    record = pred_layout_record(plan)
    assert record['a_groups'] == 16
    assert record['a_rows_saved'] == 2 * 5 + 1 + 5 + 2 + 10
    # the six members of a KDA group read ONE stored A, and differ in G
    rows = {m.name: plan.layer_rows[i] for i, m in enumerate(plan.metas)}
    a_rows = {rows[f'layer_1/self_attn/{n}'][:2] for n in six}
    assert len(a_rows) == 1
    assert len({rows[f'layer_1/self_attn/{n}'][2:4] for n in six}) == 6
