"""The mixed-attention decoder's cell: its configuration file against the
source's published ``config.json`` (the catalog row of ``arcee-ai/
Trinity-Mini``), what its metrics read, and the rehearsal cell
``tiny-mixed-lm-freq10`` through ``run.py`` on the CPU (correct, with a
routed expert and two layers that are not the first of their input group
sampled at ``highest``; both controls fail)."""

import numpy as np
import pytest

from harness import files
from test_run_cpu import by_phase, run_cell

#: arcee-ai/Trinity-Mini config.json, the keys that say something about its
#: shape (the catalog row beside the model-configs guide)
PUBLISHED = {
    'global_attn_every_n_layers': 4, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 2048, 'intermediate_size': 6144,
    'layer_types': (['sliding_attention'] * 3 + ['full_attention']) * 8,
    'load_balance_coeff': 0.001, 'max_position_embeddings': 131072,
    'model_type': 'afmoe', 'moe_intermediate_size': 1024,
    'mup_enabled': True, 'n_group': 1, 'num_attention_heads': 32,
    'num_dense_layers': 2, 'num_expert_groups': 1, 'num_experts': 128,
    'num_experts_per_tok': 8, 'num_hidden_layers': 32,
    'num_key_value_heads': 4, 'num_limited_groups': 1,
    'num_shared_experts': 1, 'rms_norm_eps': 1e-05, 'rope_scaling': None,
    'rope_theta': 10000, 'route_norm': True, 'route_scale': 2.826,
    'score_func': 'sigmoid', 'sliding_window': 2048,
    'tie_word_embeddings': False, 'topk_group': 1, 'use_grouped_mm': True,
    'vocab_size': 200192}
HELD = {'num_hidden_layers': 5, 'num_experts': 8, 'num_attention_heads': 8,
        'num_key_value_heads': 1, 'vocab_size': 25024}
CELL = 'trinity-mini-ep16-freq10'


@pytest.fixture(scope='module')
def trinity():
    return files.load_json('configs', 'trinity-mini-ep16')[0]


def test_trinity_every_published_key_is_held_unchanged_but_the_reduced(trinity):
    assert trinity['reduced'] == list(HELD)
    for key, value in PUBLISHED.items():
        want = HELD.get(key, value)
        assert trinity[key] == want, key              # as the driver reads it
        assert trinity['model'][key] == want, key     # as the cell runs it
    m = trinity['model']
    # the published counts beside the held ones, and which are held
    assert [m[k + '_published'] for k in HELD] == [PUBLISHED[k] for k in HELD]
    assert m['expert_ids'] == list(range(8))
    assert m['q_head_ids'] == list(range(8)) and m['kv_head_ids'] == [0]
    # one whole published group of query heads with its key/value head
    group = PUBLISHED['num_attention_heads'] // PUBLISHED['num_key_value_heads']
    assert {q // group for q in m['q_head_ids']} == set(m['kv_head_ids'])
    # published layer 1 (dense, window) and one whole period, 4-7
    held = m['layers_held_published_index']
    assert held == [1, 4, 5, 6, 7] and len(held) == m['num_hidden_layers']
    assert m['layer_types_held'] == [PUBLISHED['layer_types'][i]
                                     for i in held]
    assert m['first_k_dense_replace'] == sum(
        i < PUBLISHED['num_dense_layers'] for i in held) == 1
    assert '16 chips share each layer' in trinity['deployment']
    assert trinity['source'].endswith('arcee-ai/Trinity-Mini/blob/main/'
                                     'config.json')


def test_trinity_no_width_is_cut(trinity):
    widths = [k for k in PUBLISHED if k.endswith(('_dim', '_rank', '_size'))
              and k != 'vocab_size'] + ['num_experts_per_tok',
                                        'sliding_window']
    assert len(widths) >= 6
    assert not set(widths) & set(trinity['reduced'])
    for entry in files.benchmark_json()['configs']:
        if entry['name'] == trinity['name']:
            assert entry['reduced'] == trinity['reduced']
            assert entry['source'] == trinity['source']


def test_trinity_buffer_and_tokens_follow_from_the_traffic(trinity):
    m = trinity['model']
    traffic = files.load_json('traffic', 'b1-freq10')[0]
    assert m['tokens_per_step'] == traffic['batch_per_chip'] * m['seq_len']
    expected = m['tokens_per_step'] * m['num_experts_per_tok'] / 128
    assert expected == 256
    assert m['expert_capacity'] == m['tokens_per_step']   # every token
    assert m['seq_len'] == 2 * m['sliding_window']        # the mask bites
    assert set(trinity['check']['limit_reasons']) == set(
        trinity['check']['limits'])
    assert 'moe/dropped' in trinity['check']['counters']
    assert len(trinity['assumed']) >= 8


def test_trinity_sampled_layers_are_layers_of_the_plain_model(trinity):
    plain = files.load_module('reference', trinity['plain'])
    layers = {l['path']: l for l in plain.kfac_layers(trinity['model'])}
    assert len(layers) == 136
    assert sum(l['kind'] == 'rows' for l in layers.values()) == 96
    sampled = trinity['check']['sampled_layers']
    # dense layers alone on the chip (the kanana configuration's reason);
    # one of them a member of an input group that is not its first
    assert [layers[name]['kind'] for name in sampled] == ['dense'] * 3
    assert any(name.endswith(('/k_proj', '/v_proj', '/gate_proj', '/up'))
               for name in sampled)
    tiny = files.load_json('configs', 'tiny-mixed-lm')[0]
    assert '/experts/down/' in tiny['check']['sampled_layers'][0]
    shapes = plain.param_shapes(trinity['model'])
    assert shapes['layer_1/mlp/experts/gate/kernel'] == (8, 2048, 1024)
    assert shapes['layer_1/mlp/router/kernel'] == (2048, 128)
    assert shapes['layer_4/self_attn/k_proj/kernel'] == (2048, 128)
    assert shapes['layer_4/self_attn/gate_proj/kernel'] == (2048, 1024)
    assert shapes['layer_0/self_attn/q_norm/scale'] == (128,)
    assert sum(int(np.prod(s))
               for s in shapes.values()) == 401_911_552     # 402 M


def test_trinity_metrics_of_the_cell_are_files_with_reducers(trinity):
    cell, _ = files.resolve_workload(CELL)
    names = {m['name'] for m in cell['per_layer']}
    kanana, _ = files.resolve_workload('kanana2-ep16-freq10')
    new = {'attn_window_ms_per_step', 'attn_full_ms_per_step'}
    assert names - new == {m['name'] for m in kanana['per_layer']} - {
        'mla_ms_per_step'}
    assert new <= names
    assert {m['name'] for m in cell['end_to_end']} == {
        'samples_per_s', 'step_ms_p95', 'setup_s'}
    for name in new:
        spec, _ = files.load_json('metrics', name)
        assert spec['reducer'] == 'scope_device_ms'
        assert callable(files.load_module('reducers', spec['reducer']).reduce)
    # the grouped products' share reads this configuration's sizes
    reducer = files.load_module('reducers', 'grouped_product_mxu_pct')
    assert reducer.product_flops(trinity) == 2 * 8 * 4096 * 2048 * 1024
    assert reducer.products_per_step(trinity) == 36


# -- the rehearsal cell through run.py, on the CPU ---------------------------

def test_mixed_rehearsal_cell_is_correct():
    proc, rows = run_cell('tiny-mixed-lm-freq10', seconds=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert last['correct'] is True and last['failed'] == 0
    assert last['check']['moe/dropped'] == {'value': 0, 'limit': 0}
    win, = by_phase(rows, 'window')
    assert win['compiles_in_window'] == 0
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert checks['factor_gap']['where'].startswith(
        ('layer_2/mlp/experts/down/3', 'layer_1/self_attn/v_proj',
         'layer_0/mlp/up'))
    # float32 at highest: program and reference agree far inside the limits
    assert checks['first_update_norm_gap']['value'] < 1e-4
    assert checks['factor_gap']['value'] < 1e-4


@pytest.mark.parametrize('mode', ['kfac', 'all'])
def test_mixed_rehearsal_controls_fail(mode):
    proc, rows = run_cell('tiny-mixed-lm-freq10', seconds=1,
                          extra=['--lower', mode])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rows[-1]['correct'] is False
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert not all(r['ok'] for r in checks.values())
