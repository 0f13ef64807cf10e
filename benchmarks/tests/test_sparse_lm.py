"""The sparse decoder's cell: its configuration file against the source's
published ``config.json``, the reducer of the grouped products' share of
the peak on a synthetic trace, and the rehearsal cell
``tiny-sparse-lm-freq10`` through ``run.py`` on the CPU (correct; both
controls fail; a buffer made too small gives ``correct`` false by
``moe/dropped``)."""

import json
import os

import numpy as np
import pytest

from harness import files
from test_run_cpu import by_phase, run_cell

#: kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, the keys that say
#: something about its shape
PUBLISHED = {
    'attention_bias': False, 'first_k_dense_replace': 1, 'head_dim': 64,
    'hidden_act': 'silu', 'hidden_size': 2048, 'intermediate_size': 6144,
    'kv_lora_rank': 512, 'max_position_embeddings': 32768,
    'model_type': 'deepseek_v3', 'moe_intermediate_size': 768,
    'moe_layer_freq': 1, 'n_group': 1, 'n_routed_experts': 128,
    'n_shared_experts': 2, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_experts_per_tok': 6,
    'num_hidden_layers': 48, 'num_key_value_heads': 32, 'q_lora_rank': None,
    'qk_head_dim': 192, 'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
    'rms_norm_eps': 1e-06, 'rope_interleave': True, 'rope_scaling': None,
    'rope_theta': 1000000, 'routed_scaling_factor': 2.448,
    'scoring_func': 'sigmoid', 'tie_word_embeddings': False,
    'topk_group': 1, 'topk_method': 'noaux_tc', 'v_head_dim': 128,
    'vocab_size': 128256}
HELD = {'num_hidden_layers': 5, 'n_routed_experts': 8,
        'num_attention_heads': 4, 'vocab_size': 16032}


@pytest.fixture(scope='module')
def config():
    return files.load_json('configs', 'kanana-2-30b-a3b-ep16')[0]


def test_every_published_key_is_held_unchanged_but_the_reduced(config):
    assert config['reduced'] == list(HELD)
    for key, value in PUBLISHED.items():
        want = HELD.get(key, value)
        assert config[key] == want, key              # as the driver reads it
        assert config['model'][key] == want, key     # as the cell runs it
    m = config['model']
    # the published counts beside the held ones, and which are held
    assert [m[k + '_published'] for k in HELD] == [PUBLISHED[k] for k in HELD]
    assert m['expert_ids'] == list(range(8)) and m['head_ids'] == [0, 1, 2, 3]
    assert '16 chips share each layer' in config['deployment']


def test_no_width_is_cut(config):
    widths = [k for k in PUBLISHED if k.endswith(('_dim', '_rank', '_size'))
              and k != 'vocab_size'] + ['num_experts_per_tok']
    assert len(widths) >= 9
    assert not set(widths) & set(config['reduced'])


def test_buffer_and_tokens_follow_from_the_traffic(config):
    m = config['model']
    traffic = files.load_json('traffic', 'b1-freq10')[0]
    assert m['tokens_per_step'] == traffic['batch_per_chip'] * m['seq_len']
    expected = m['tokens_per_step'] * m['num_experts_per_tok'] / 128
    assert expected == 192
    assert m['expert_capacity'] >= 2 * expected
    assert set(config['check']['limit_reasons']) == set(
        config['check']['limits'])
    assert 'moe/dropped' in config['check']['counters']


def test_sampled_layers_are_layers_of_the_plain_model(config):
    plain = files.load_module('reference', config['plain'])
    layers = {l['path']: l for l in plain.kfac_layers(config['model'])}
    assert len(layers) == 131
    assert sum(l['kind'] == 'rows' for l in layers.values()) == 96
    kinds = [layers[name]['kind']
             for name in config['check']['sampled_layers']]
    # dense layers alone: on the chip a held expert's rows differ between
    # program and reference by the near-ties of the top-6 (the file's own
    # reason for `factor_gap`); the rehearsal cell, float32 at highest,
    # keeps a routed expert among its three
    assert kinds == ['dense', 'dense', 'dense']
    assert 'mlp/shared/' in config['check']['sampled_layers'][0]
    tiny = files.load_json('configs', 'tiny-sparse-lm')[0]
    assert '/experts/down/' in tiny['check']['sampled_layers'][0]
    shapes = plain.param_shapes(config['model'])
    assert shapes['layer_1/mlp/experts/gate/kernel'] == (8, 2048, 768)
    assert shapes['layer_1/mlp/router/kernel'] == (2048, 128)
    assert sum(int(np.prod(s))
               for s in shapes.values()) == 314_860_544     # 315 M


def _trace(events, steps=2):
    return {'data': {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': events}]}]}, 'steps': steps}


def test_grouped_product_share_counts_the_products_it_times(
        config, monkeypatch):
    reducer = files.load_module('reducers', 'grouped_product_mxu_pct')
    flops = reducer.product_flops(config)
    m = config['model']
    assert flops == 2 * 8 * m['expert_capacity'] * 2048 * 768
    assert reducer.products_per_step(config) == 36
    monkeypatch.setattr(reducer, 'bf16_peak', lambda: 197e12)
    path = 'tf_op=jit(kfac_step_pred)/jvp(moe.experts)/experts/gate/'
    product = [path + 'einsum: hlo_category=convolution fusion']
    events, t = [], 0
    for _ in range(4):              # 4 products of 1 ms, 1 ms of the rest
        events.append(['fusion.1', t, 1_000_000, product[0]])
        t += 1_000_000
    events.append(['multiply.2', t, 1_000_000,
                   path + 'mul: hlo_category=non-fusion elementwise'])
    events.append(['fusion.9', t + 1_000_000, 5_000_000,
                   'tf_op=jit(x)/kfac.Precondition/e: '
                   'hlo_category=convolution fusion'])
    ctx = {'trace': _trace(events), 'config': config}
    got = reducer.reduce(ctx, scope='moe.experts')
    assert got == pytest.approx(100 * 4 * flops / (5e-3 * 197e12))
    # a program without the scope (the parent commit): nothing to read
    assert reducer.reduce({'trace': _trace(events[-1:]), 'config': config},
                          scope='moe.experts') is None
    assert reducer.reduce({'trace': None, 'config': config},
                          scope='moe.experts') is None
    # more products a step than the configuration has: not a share
    many = [['fusion.1', i, 1, product[0]] for i in range(80)]
    with pytest.raises(ValueError, match='split'):
        reducer.reduce({'trace': _trace(many), 'config': config},
                       scope='moe.experts')


def test_new_metrics_are_files_with_reducers():
    cell, _ = files.resolve_workload('kanana2-ep16-freq10')
    names = {m['name'] for m in cell['per_layer']}
    new = {'moe_route_ms_per_step', 'moe_experts_ms_per_step',
           'mla_ms_per_step', 'expert_factor_ms_per_step',
           'moe_experts_mxu_pct'}
    assert new <= names
    bert, _ = files.resolve_workload('bert-base-freq10')
    assert names - new == {m['name'] for m in bert['per_layer']}
    assert not new & {m['name'] for m in bert['per_layer']}
    for name in new:
        spec, _ = files.load_json('metrics', name)
        assert callable(files.load_module('reducers', spec['reducer']).reduce)


def test_lean_reference_gives_kfac_plains_numbers():
    """``kfac_plain_lean`` (the configuration's ``kfac_reference``: the
    same algebra within a one-chip host's memory) against ``kfac_plain``
    on the rehearsal configuration, to the last bit."""
    import jax
    from harness import weights
    cfg = files.load_json('configs', 'tiny-sparse-lm')[0]
    assert cfg['kfac_reference'] == 'kfac_plain_lean'
    traffic = dict(files.load_json('traffic', 'tiny-b4-sparse-freq10')[0],
                   chips=1, fac_update_freq=1, kfac_update_freq=2)
    plain = files.load_module('reference', cfg['plain'])
    key = jax.random.PRNGKey(3)
    outs = [files.load_module('reference', name).run(
        plain, cfg, traffic, weights.params_fn(cfg['init']), key,
        jax.random.fold_in(key, 1), 3,
        keep_factors=cfg['check']['sampled_layers'])
        for name in ('kfac_plain', 'kfac_plain_lean')]
    assert outs[0]['losses'] == outs[1]['losses']
    assert outs[0]['kl_scale'] == outs[1]['kl_scale']
    assert len(outs[0]['kl_scale']) == 3
    for number in ('first_update', 'param_change'):
        assert outs[0][number] == outs[1][number]
    for layer, pair in outs[0]['factors'].items():
        for fa, fb in zip(pair, outs[1]['factors'][layer]):
            np.testing.assert_array_equal(fa, fb)


# -- the rehearsal cell through run.py, on the CPU ---------------------------

def test_rehearsal_cell_is_correct_and_trains():
    proc, rows = run_cell('tiny-sparse-lm-freq10', seconds=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert last['correct'] is True and last['failed'] == 0
    assert last['check']['moe/dropped'] == {'value': 0, 'limit': 0}
    win, = by_phase(rows, 'window')
    assert win['compiles_in_window'] == 0
    assert win['loss_mean_last_period'] < win['loss_mean_first_period']
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert checks['factor_gap']['where'].startswith(
        ('layer_2/mlp/experts/down/3', 'layer_1/self_attn/kv_b_proj',
         'layer_0/mlp/gate'))


@pytest.mark.parametrize('mode', ['kfac', 'all'])
def test_rehearsal_controls_fail(mode):
    proc, rows = run_cell('tiny-sparse-lm-freq10', seconds=1,
                          extra=['--lower', mode])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rows[-1]['correct'] is False
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert not all(r['ok'] for r in checks.values())


def test_too_small_a_buffer_is_not_correct_by_the_dropped_rows():
    proc, rows = run_cell('tiny-sparse-lm-full-buffer-freq10', seconds=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert last['correct'] is False and last['failed'] >= 1
    assert last['check']['moe/dropped']['value'] > 0
    assert last['check']['bad_steps_window'] == {'value': 0, 'limit': 0}
