"""The decoder whose layers differ in kind (``models.mixed_decoder_lm``:
window and full attention mixed, grouped-query, gated, QK-normed, four
norms a block, sigmoid-routed experts with one shared expert). Tiny sizes
on the CPU, seeded, float32 at ``highest``; the model is held against the
benchmark's plain reference (``benchmarks/reference/mixed_lm_plain.py``),
which imports nothing of the program."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kfac_pytorch_tpu import capture, engine, models
from kfac_pytorch_tpu.models import mixed_decoder as md
from kfac_pytorch_tpu.parallel.moe import RoutedExperts
from kfac_pytorch_tpu.plan import build_plan, pred_layout_record

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks')
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)
from harness import files, weights  # noqa: E402

jax.config.update('jax_default_matmul_precision', 'highest')

#: a small model's share: one dense layer, a window layer and a full layer
#: with experts; 4 of 8 query heads with 2 of 4 key/value heads, 5 of 8
#: experts; a window of 4 in sequences of 10, so the mask bites
CFG = dict(
    vocab_size=48, hidden_size=24, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=40, moe_intermediate_size=12,
    num_experts_published=8, num_experts_per_tok=3, num_shared_experts=1,
    route_scale=2.826, route_norm=True, head_dim=8,
    num_attention_heads_published=8, num_key_value_heads_published=4,
    q_head_ids=[2, 3, 6, 7], kv_head_ids=[1, 3],
    layer_types_held=['sliding_attention', 'sliding_attention',
                      'full_attention'],
    sliding_window=4, rope_theta=1e4, rms_norm_eps=1e-5, mup_enabled=True,
    expert_ids=[0, 2, 3, 5, 7], seq_len=10, tokens_per_step=20,
    expert_capacity=20)
TRAFFIC = dict(batch_per_chip=2, chips=1)


def build(cfg):
    return models.mixed_decoder_lm(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        layer_types=tuple(cfg['layer_types_held']),
        first_k_dense=cfg['first_k_dense_replace'],
        intermediate_size=cfg['intermediate_size'],
        expert_width=cfg['moe_intermediate_size'],
        n_routed_experts=cfg['num_experts_published'],
        experts_per_tok=cfg['num_experts_per_tok'],
        n_shared_experts=cfg['num_shared_experts'],
        routed_scale=cfg['route_scale'], norm_topk=cfg['route_norm'],
        head_dim=cfg['head_dim'],
        num_attention_heads=cfg['num_attention_heads_published'],
        num_key_value_heads=cfg['num_key_value_heads_published'],
        sliding_window=cfg['sliding_window'], rope_theta=cfg['rope_theta'],
        eps=cfg['rms_norm_eps'], mup_enabled=cfg['mup_enabled'],
        q_head_ids=tuple(cfg['q_head_ids']),
        kv_head_ids=tuple(cfg['kv_head_ids']),
        expert_ids=tuple(cfg['expert_ids']),
        expert_capacity=cfg['expert_capacity'])


@pytest.fixture(scope='module')
def plain():
    return files.load_module('reference', 'mixed_lm_plain')


def seeded(plain, cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    shapes = plain.param_shapes(cfg)
    flat = {p: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
            + (1.0 if p.endswith('/scale') else 0.0)
            for i, (p, s) in enumerate(sorted(shapes.items()))}
    batch = plain.make_batch(cfg, TRAFFIC, jax.random.fold_in(key, 999))
    return flat, batch


def test_block_is_the_plain_reference(plain):
    """Logits, loss and every leaf's gradient of a model with both kinds of
    layer, a window shorter than the sequence, float32 at highest."""
    flat, batch = seeded(plain, CFG)
    model = build(CFG)
    assert set(flat) == set(weights.flatten(capture.init(
        model, {'params': jax.random.PRNGKey(0)}, batch['input'])['params']))

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
    # the reference gives no logits: its loss from the program's
    logp = jax.nn.log_softmax(logits.reshape(-1, CFG['vocab_size']))
    np.testing.assert_allclose(-jnp.take_along_axis(
        logp, batch['label'].reshape(-1, 1), axis=-1).mean(), want,
        rtol=1e-5)
    for path in flat:
        np.testing.assert_allclose(
            grads[path], ref_grads[path], rtol=2e-4,
            atol=2e-5 * float(jnp.abs(ref_grads[path]).max()) + 1e-9,
            err_msg=path)
    # the bias of the choice gets no gradient; router and head norms do
    assert not np.any(grads['layer_1/mlp/e_score_correction_bias'])
    for path in ('layer_1/mlp/router/kernel', 'layer_0/self_attn/q_norm/scale',
                 'layer_2/self_attn/k_norm/scale'):
        assert np.any(grads[path]), path


def test_the_kinds_of_layer_differ_and_the_window_bites(plain):
    """A one-layer model: as a window layer a position past the window does
    not see token 0, as a full layer it does; the two kinds give different
    results (mask and rotary)."""
    cfg = dict(CFG, num_hidden_layers=1, first_k_dense_replace=1)
    flat, batch = seeded(plain, dict(cfg, layer_types_held=[md.SLIDING]),
                         seed=3)
    other = batch['input'].at[:, 0].set((batch['input'][:, 0] + 1) % 48)
    out = {}
    for kind in (md.SLIDING, md.FULL):
        model = build(dict(cfg, layer_types_held=[kind]))
        run = lambda ids: model.apply(     # noqa: E731
            {'params': weights.unflatten(flat)}, ids)
        out[kind] = (run(batch['input']), run(other))
    a, b = out[md.SLIDING]
    np.testing.assert_array_equal(a[:, 4:], b[:, 4:])   # 0 <= l - m < 4
    assert not np.allclose(a[:, 3], b[:, 3])
    a, b = out[md.FULL]
    assert not np.allclose(a[:, 9], b[:, 9])
    assert not np.allclose(out[md.SLIDING][0], out[md.FULL][0])
    # the reference's mask, written out
    mask = plain.attention_mask(6, 3)
    assert mask.sum() == 6 + 5 + 4 and mask[5, 3] and not mask[5, 2]
    assert plain.attention_mask(6, None).sum() == 21


def test_half_rotary_is_x_cos_plus_rotate_half_x_sin():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3, 2, 8))
    pos = jnp.arange(7)
    got = md.half_rotary(x, pos, 1e4)
    freq = 1e4 ** (-np.arange(0, 8, 2) / 8)
    ang = np.arange(7)[:, None] * freq
    ang = np.concatenate([ang, ang], -1)[None, :, None, None, :]
    xn = np.asarray(x)
    half = np.concatenate([-xn[..., 4:], xn[..., :4]], -1)
    np.testing.assert_allclose(got, xn * np.cos(ang) + half * np.sin(ang),
                               rtol=1e-5, atol=1e-6)
    # a rotation: q . k sees the distance alone
    q, k = x[:, :, 0, 0], x[:, :, 1, 0]
    a = jnp.einsum('bld,bmd->blm', md.half_rotary(q, pos, 1e4),
                   md.half_rotary(k, pos, 1e4))
    b = jnp.einsum('bld,bmd->blm', md.half_rotary(q, pos + 5, 1e4),
                   md.half_rotary(k, pos + 5, 1e4))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_held_layer_types_keep_whole_periods():
    assert md.held_layer_types(5) == (md.SLIDING,) * 4 + (md.FULL,)
    # uncut: the published pattern, every fourth layer full
    assert md.MixedDecoderConfig().layer_types == tuple(
        md.FULL if i % 4 == 3 else md.SLIDING for i in range(32))


# -- the share adds up -------------------------------------------------------

def _attention(q_ids, kv_ids, window):
    return md.GatedGroupedAttention(tuple(q_ids), tuple(kv_ids), 2,
                                    head_dim=8, window=window)


@pytest.mark.parametrize('window', [4, None], ids=['window', 'full'])
def test_head_shares_add_up_to_the_uncut_reference_layer(plain, window):
    """8 query heads over 4 key/value heads, split 4 ways (a whole group a
    share): the shares' outputs sum to the output with every head, and
    that is the plain reference's attention (its ``o_proj`` input holds
    every head's gated context)."""
    cfg = dict(CFG, num_hidden_layers=1, q_head_ids=list(range(8)),
               kv_head_ids=list(range(4)),
               layer_types_held=[md.SLIDING if window else md.FULL])
    flat, batch = seeded(plain, cfg, seed=5)
    p = weights.unflatten(flat)['layer_0']['self_attn']
    _, acts = plain.forward(cfg, flat, batch, {}, jnp.float32)
    u = acts['layer_0/self_attn/q_proj']        # the layer's normed input
    whole = _attention(range(8), range(4), window).apply(
        {'params': p}, u, 2, 10)
    np.testing.assert_allclose(
        whole, acts['layer_0/self_attn/o_proj'] @ p['o_proj']['kernel'],
        rtol=1e-4, atol=1e-5)
    total = 0.0
    for g in range(4):
        q_ids = (2 * g, 2 * g + 1)
        cols = np.concatenate([np.arange(8 * j, 8 * j + 8) for j in q_ids])
        kv = np.arange(8 * g, 8 * g + 8)
        mine = dict(
            p, q_proj={'kernel': p['q_proj']['kernel'][:, cols]},
            gate_proj={'kernel': p['gate_proj']['kernel'][:, cols]},
            k_proj={'kernel': p['k_proj']['kernel'][:, kv]},
            v_proj={'kernel': p['v_proj']['kernel'][:, kv]},
            o_proj={'kernel': p['o_proj']['kernel'][cols]})
        total = total + _attention(q_ids, (g,), window).apply(
            {'params': mine}, u, 2, 10)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)
    # a query head whose key/value head is not held, or held unevenly
    with pytest.raises(ValueError, match='key/value heads'):
        _attention((0, 1, 2), (0,), window).init(
            jax.random.PRNGKey(0), u, 2, 10)


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 routed experts split 16 ways, top-8, one shared expert: over all
    shares, with the shared expert counted once, the parts sum to what the
    layer gives holding all of them."""
    def layer(ids):
        return RoutedExperts(n_routed=16, top_k=8, expert_ids=tuple(ids),
                             expert_width=12, shared_width=12, capacity=20,
                             scale=2.826)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.fold_in(key, 1), (20, 24))
    params = layer(range(16)).init(key, x)['params']
    params = jax.tree.map(
        lambda p: 0.4 * jax.random.normal(jax.random.fold_in(key, p.size),
                                          p.shape), params)
    whole, counts = layer(range(16)).apply({'params': params}, x)
    shared = params['shared']
    shared_out = (jax.nn.silu(x @ shared['gate']['kernel'])
                  * (x @ shared['up']['kernel'])) @ shared['down']['kernel']
    total = shared_out
    for e in range(16):
        mine = dict(params, experts=jax.tree.map(
            lambda k: k[e:e + 1], params['experts']))
        part, _ = layer((e,)).apply({'params': mine}, x)
        total = total + (part - shared_out)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)
    assert float(counts['dropped']) == 0
    assert float(counts['rows_mean']) == 20 * 8 / 16


# -- under K-FAC -------------------------------------------------------------

def _captured(plain, cfg, seed=7):
    flat, batch = seeded(plain, cfg, seed=seed)
    model = build(cfg)
    variables = {'params': weights.unflatten(flat)}
    metas = capture.collect_layer_meta(model, variables, batch['input'])

    def loss_fn(logits):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch['label']).mean()
    _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
        model, loss_fn, variables, batch['input'],
        mutable=(capture.COUNTERS,))
    return flat, batch, metas, grads, acts, gs


def test_grouped_query_factors_and_gradient_are_the_references(plain):
    """``k_proj`` of a layer whose key/value heads each serve two query
    heads: its gradient and its ``G`` sum over the query heads of a group
    (autodiff through the reference's gather of keys), its ``A`` is its
    input's."""
    flat, batch, metas, grads, acts, gs = _captured(plain, CFG)
    plan = build_plan(metas, 1, 'pred')
    a_list, g_list = engine.compute_layer_stats(plan, acts, gs)
    names = [m.name for m in plan.metas]
    taps = {f'layer_{i}/self_attn/{n}': jnp.zeros((20, 16))
            for i in range(3) for n in ('k_proj', 'v_proj')}
    (_, ref_acts), (ref_grads, ref_gs) = jax.value_and_grad(
        lambda p, t: plain.forward(CFG, p, batch, t, jnp.float32),
        argnums=(0, 1), has_aux=True)(flat, taps)
    for layer in (0, 2):            # a window layer and the full one
        for proj in ('k_proj', 'v_proj'):
            path = f'layer_{layer}/self_attn/{proj}'
            i = names.index(path)
            a, g = ref_acts[path], 20 * ref_gs[path]
            np.testing.assert_allclose(a_list[i], a.T @ a / 20, rtol=1e-4,
                                       atol=1e-6, err_msg=path)
            np.testing.assert_allclose(g_list[i], g.T @ g / 20, rtol=1e-4,
                                       atol=1e-9, err_msg=path)
            np.testing.assert_allclose(
                engine.layer_grad_matrix(plan.metas[i], grads),
                ref_grads[path + '/kernel'].T, rtol=2e-4, atol=1e-7,
                err_msg=path)


def test_kfac_decision_for_every_weight(plain):
    """Kronecker-factored: the five attention projections, the dense and
    shared gate / up / down, every held expert's three; the projections
    that read one input are one group. Everything else is first-order."""
    _, _, metas, _, _, _ = _captured(plain, CFG)
    want = set(l['path'] for l in plain.kfac_layers(CFG))
    assert set(metas) == want
    groups = {}
    for name, m in metas.items():
        if m.input_group is not None:
            groups.setdefault(m.input_group, []).append(name)
    attn = [f'layer_{i}/self_attn/{n}' for i in range(3)
            for n in ('q_proj', 'k_proj', 'v_proj', 'gate_proj')]
    assert [groups[f'layer_{i}/self_attn/q_proj'] for i in range(3)] == [
        attn[4 * i:4 * i + 4] for i in range(3)]
    assert groups['layer_0/mlp/gate'] == ['layer_0/mlp/gate',
                                          'layer_0/mlp/up']
    assert groups['layer_2/mlp/shared/gate'] == [
        'layer_2/mlp/shared/gate', 'layer_2/mlp/shared/up']
    for e in range(5):
        assert groups[f'layer_1/mlp/experts/gate/{e}'] == [
            f'layer_1/mlp/experts/gate/{e}', f'layer_1/mlp/experts/up/{e}']
    # 3 attention + 1 dense + 2 shared + 2 x 5 routed
    assert len(groups) == 16
    assert all('o_proj' not in n and 'down' not in n
               for g in groups.values() for n in g)
    record = pred_layout_record(build_plan(metas, 1, 'pred'))
    assert record['a_groups'] == 16
    assert record['a_rows_saved'] == 3 * 3 + 1 + 2 + 10
    assert record['pred_operand_takes'] == 0
    assert record['stacked_layers'] == 2 * 3 * 5
