"""The hybrid decoder's cell: its configuration file against the source's
published ``config.json`` (the catalog row of ``moonshotai/Kimi-Linear-48B-
A3B-Instruct``), what its metrics read, the chunked scan's roofline on a
made-up trace, and the rehearsal cell ``tiny-hybrid-lm-freq10`` through
``run.py`` on the CPU (correct, with a routed expert, a KDA member that is
not the first of its input group and a block of the dense layer sampled at
``highest``; both controls fail)."""

import numpy as np
import pytest

from harness import files
from test_run_cpu import by_phase, run_cell

#: moonshotai/Kimi-Linear-48B-A3B-Instruct config.json, the keys that say
#: something about its shape (the catalog row beside the model-configs
#: guide)
PUBLISHED = {
    'first_k_dense_replace': 1, 'head_dim': 72, 'hidden_act': 'silu',
    'hidden_size': 2304, 'intermediate_size': 9216, 'kv_lora_rank': 512,
    'linear_attn_config': {
        'full_attn_layers': [4, 8, 12, 16, 20, 24, 27], 'head_dim': 128,
        'kda_layers': [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        'num_heads': 32, 'short_conv_kernel_size': 4},
    'mla_use_nope': True, 'model_max_length': 1048576,
    'model_type': 'kimi_linear', 'moe_intermediate_size': 1024,
    'moe_layer_freq': 1, 'moe_renormalize': True,
    'moe_router_activation_func': 'sigmoid', 'num_attention_heads': 32,
    'num_expert_group': 1, 'num_experts': 256, 'num_experts_per_token': 8,
    'num_hidden_layers': 27, 'num_key_value_heads': 32,
    'num_nextn_predict_layers': 0, 'num_shared_experts': 1,
    'q_lora_rank': None, 'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
    'rms_norm_eps': 1e-05, 'rope_scaling': None, 'rope_theta': 10000,
    'routed_scaling_factor': 2.446, 'tie_word_embeddings': False,
    'topk_group': 1, 'use_grouped_topk': True, 'v_head_dim': 128,
    'vocab_size': 163840}
HELD = {'num_hidden_layers': 5, 'num_experts': 8, 'num_attention_heads': 8,
        'linear_attn_config': dict(PUBLISHED['linear_attn_config'],
                                   num_heads=8),
        'vocab_size': 20480}
CELL = 'kimi-linear-ep32-freq10'


@pytest.fixture(scope='module')
def kimi():
    return files.load_json('configs', 'kimi-linear-48b-a3b-ep32')[0]


def test_kimi_every_published_key_is_held_unchanged_but_the_reduced(kimi):
    assert kimi['reduced'] == list(HELD)
    for key, value in PUBLISHED.items():
        want = HELD.get(key, value)
        assert kimi[key] == want, key              # as the driver reads it
        assert kimi['model'][key] == want, key     # as the cell runs it
    m = kimi['model']
    # the published counts beside the held ones, and which are held
    assert [m[k + '_published'] for k in HELD if k != 'linear_attn_config'
            ] == [PUBLISHED[k] for k in HELD if k != 'linear_attn_config']
    assert m['linear_attn_num_heads_published'] == 32
    assert m['expert_ids'] == m['head_ids'] == m['kda_head_ids'] == list(
        range(8))
    # published layer 1 (KDA, dense) and one whole period, 5-8
    held = m['layers_held_published_index']
    assert held == [1, 5, 6, 7, 8] and len(held) == m['num_hidden_layers']
    lin = PUBLISHED['linear_attn_config']
    assert m['layer_kinds_held'] == [
        'latent' if i in lin['full_attn_layers'] else 'kda' for i in held]
    assert all((i in lin['kda_layers']) != (i in lin['full_attn_layers'])
               for i in held)
    assert m['first_k_dense_replace'] == sum(
        i <= PUBLISHED['first_k_dense_replace'] for i in held) == 1
    assert '32 chips share each layer' in kimi['deployment']
    assert kimi['source'].endswith(
        'moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json')


def test_kimi_no_width_is_cut(kimi):
    widths = [k for k in PUBLISHED if k.endswith(('_dim', '_rank', '_size'))
              and k != 'vocab_size'] + ['num_experts_per_token']
    assert len(widths) >= 8
    assert not set(widths) & set(kimi['reduced'])
    lin = kimi['model']['linear_attn_config']
    assert (lin['head_dim'], lin['short_conv_kernel_size']) == (128, 4)
    # the 9,216-wide block keeps its width: four blocks of K-FAC factors
    m = kimi['model']
    assert m['intermediate_size'] == 4 * m['ffn_block'] == 9216
    for entry in files.benchmark_json()['configs']:
        if entry['name'] == kimi['name']:
            assert entry['reduced'] == kimi['reduced']
            assert entry['source'] == kimi['source']


def test_kimi_buffer_and_tokens_follow_from_the_traffic(kimi):
    m = kimi['model']
    traffic = files.load_json('traffic', 'b1-freq10')[0]
    assert m['tokens_per_step'] == traffic['batch_per_chip'] * m['seq_len']
    expected = m['tokens_per_step'] * m['num_experts_per_token'] / 256
    assert expected == m['seq_len'] / 32
    assert m['expert_capacity'] == m['tokens_per_step']   # every token
    assert m['seq_len'] % m['kda_chunk'] == 0 and 16 <= m['kda_chunk'] <= 64
    assert set(kimi['check']['limit_reasons']) == set(
        kimi['check']['limits'])
    assert 'moe/dropped' in kimi['check']['counters']
    assert len(kimi['assumed']) >= 8
    # the centres of the two leaves that hold a distance
    assert m['kda_a_log_centre'] == pytest.approx(np.log(4.0), abs=1e-5)
    assert np.log1p(np.exp(m['kda_dt_bias_centre'])) == pytest.approx(
        0.01, rel=0.01)
    rules = {r[0]: r[1:] for r in kimi['init']}
    assert rules['/A_log$'] == ['normal', 0.8]
    assert rules['/dt_bias$'] == ['normal', 1.33]
    assert rules['_conv/weight$'] == ['normal_fan_in', 1.0]


def test_kimi_sampled_layers_are_layers_of_the_plain_model(kimi):
    plain = files.load_module('reference', kimi['plain'])
    layers = {l['path']: l for l in plain.kfac_layers(kimi['model'])}
    # 4 x 9 KDA + 4 latent + 12 blocks + 4 x (24 routed + 3 shared)
    assert len(layers) == 160
    assert sum(l['kind'] == 'rows' for l in layers.values()) == 96
    assert sum(l['bias'] for l in layers.values()) == 4
    sampled = kimi['check']['sampled_layers']
    assert [layers[name]['kind'] for name in sampled] == ['dense'] * 3
    assert sampled[0].endswith('self_attn/v_proj')      # not its group's first
    assert sampled[1].endswith('self_attn/f_b_proj')
    assert sampled[2].endswith('mlp/shared/down')
    tiny = files.load_json('configs', 'tiny-hybrid-lm')[0]
    assert '/experts/down/' in tiny['check']['sampled_layers'][0]
    assert tiny['check']['sampled_layers'][1:] == [
        'layer_0/self_attn/v_proj', 'layer_0/mlp/up_1']
    shapes = plain.param_shapes(kimi['model'])
    assert shapes['layer_1/mlp/experts/gate/kernel'] == (8, 2304, 1024)
    assert shapes['layer_1/mlp/router/kernel'] == (2304, 256)
    assert shapes['layer_0/mlp/gate_3/kernel'] == (2304, 2304)
    assert shapes['layer_0/mlp/down_0/kernel'] == (2304, 2304)
    assert shapes['layer_2/self_attn/b_proj/kernel'] == (2304, 8)
    assert shapes['layer_2/self_attn/g_b_proj/bias'] == (1024,)
    assert shapes['layer_2/self_attn/q_conv/weight'] == (4, 1024)
    assert shapes['layer_4/self_attn/q_proj/kernel'] == (2304, 8 * 192)
    assert shapes['layer_4/self_attn/kv_a_proj_with_mqa/kernel'] == (
        2304, 576)
    first_order = sum(
        int(np.prod(s)) for p, s in shapes.items()
        if not p.endswith(('/kernel', 'g_b_proj/bias')) or 'router' in p
        or 'lm_head' in p)
    assert sum(int(np.prod(s))
               for s in shapes.values()) == 464_825_120     # 465 M
    assert first_order == 96_811_808                        # 96.8 M


def test_kimi_metrics_of_the_cell_are_files_with_reducers(kimi):
    cell, _ = files.resolve_workload(CELL)
    names = {m['name'] for m in cell['per_layer']}
    kanana, _ = files.resolve_workload('kanana2-ep16-freq10')
    new = {'kda_ms_per_step', 'kda_scan_ms_per_step', 'kda_scan_roofline_pct'}
    assert names - new == {m['name'] for m in kanana['per_layer']}
    assert new <= names
    assert {m['name'] for m in cell['end_to_end']} == {
        'samples_per_s', 'step_ms_p95', 'setup_s'}
    for name in new:
        spec, _ = files.load_json('metrics', name)
        assert callable(files.load_module('reducers', spec['reducer']).reduce)
    assert files.load_json('metrics', 'kda_ms_per_step')[0]['args'][
        'scopes'] == ['kda.conv', 'kda.gates', 'kda.scan', 'kda.out']
    # no other cell reports them; the grouped products' share reads this
    # configuration's sizes
    for entry in files.benchmark_json()['per_layer']:
        if entry['name'] in new:
            assert entry['workloads'] == [CELL]
    reducer = files.load_module('reducers', 'grouped_product_mxu_pct')
    m = kimi['model']
    assert reducer.product_flops(kimi) == 2 * 8 * m['seq_len'] * 2304 * 1024
    assert reducer.products_per_step(kimi) == 36


def _trace(events, steps=2):
    return {'data': {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': events}]}]}, 'steps': steps}


def test_scan_roofline_counts_the_least_work_over_the_scope_s_time(
        kimi, monkeypatch):
    reducer = files.load_module('reducers', 'kda_scan_roofline_pct')
    # one chunk of one head: by hand at C = 64, dk = dv = 128
    assert reducer.chunk_flops(64, 128, 128) == 3 * (
        2 * 64 * 64 * 128 + 64 * 64 * 256 + 6 * 64 * 128 * 128
        + 64 * 64 * 128)
    assert reducer.chunk_bytes(64, 128, 128) == 2 * 4 * 64 * (
        4 * 128 + 1 + 128)
    m = kimi['model']
    traffic = dict(files.load_json('traffic', 'b1-freq10')[0], chips=1)
    units = reducer.units_per_step(kimi, traffic)
    assert units == (m['seq_len'] // m['kda_chunk']) * 8 * 4
    peaks = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    least, bound = reducer.least_seconds(kimi, traffic, peaks)
    c = m['kda_chunk']
    assert bound == 'bytes'
    assert least == pytest.approx(
        units * reducer.chunk_bytes(c, 128, 128) / 819e9)
    monkeypatch.setattr(reducer, 'chip_peaks', lambda: peaks)
    path = 'tf_op=jit(kfac_step)/jvp(kda.scan)/'
    events = [['fusion.1', 0, 3_000_000, path + 'exp: hlo_category=x'],
              ['while.2', 3_000_000, 5_000_000, path + 'while: x'],
              ['fusion.3', 3_500_000, 1_000_000, path + 'dot: x'],
              ['fusion.9', 9_000_000, 7_000_000,
               'tf_op=jit(kfac_step)/kda.out/mul: x']]
    ctx = {'trace': _trace(events), 'config': kimi, 'traffic': traffic}
    # 8 ms under the scope (the while's body counted once) over 2 steps
    assert reducer.reduce(ctx, scope='kda.scan') == pytest.approx(
        100 * least / 4e-3)
    assert reducer.reduce(ctx, scope='kda.scan') < 100
    # a program without the scope, or a run without a trace: nothing
    assert reducer.reduce(dict(ctx, trace=_trace(events[-1:])),
                          scope='kda.scan') is None
    assert reducer.reduce(dict(ctx, trace=None), scope='kda.scan') is None
    # a configuration without the recurrence (the parent's cells)
    other = files.load_json('configs', 'kanana-2-30b-a3b-ep16')[0]
    assert reducer.reduce(dict(ctx, config=other), scope='kda.scan') is None


# -- the rehearsal cell through run.py, on the CPU ---------------------------

def test_hybrid_rehearsal_cell_is_correct():
    proc, rows = run_cell('tiny-hybrid-lm-freq10', seconds=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = rows[-1]
    assert last['correct'] is True and last['failed'] == 0
    assert last['check']['moe/dropped'] == {'value': 0, 'limit': 0}
    win, = by_phase(rows, 'window')
    assert win['compiles_in_window'] == 0
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert checks['factor_gap']['where'].startswith(
        ('layer_1/mlp/experts/down/3', 'layer_0/self_attn/v_proj',
         'layer_0/mlp/up_1'))
    # float32 at highest: program and reference agree far inside the limits
    assert checks['first_update_norm_gap']['value'] < 1e-4
    assert checks['factor_gap']['value'] < 1e-4


@pytest.mark.parametrize('mode', ['kfac', 'all'])
def test_hybrid_rehearsal_controls_fail(mode):
    proc, rows = run_cell('tiny-hybrid-lm-freq10', seconds=1,
                          extra=['--lower', mode])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rows[-1]['correct'] is False
    checks = {r['check']: r for r in by_phase(rows, 'check') if 'check' in r}
    assert not all(r['ok'] for r in checks.values())
