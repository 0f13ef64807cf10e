"""bench.py output contract (the CPU smoke of the harness): one JSON
line, stable key set with explicit nulls for unmeasured legs, an
overrides marker on non-default configs, and no result line at all when
a leg raises."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE = dict(JAX_PLATFORMS='cpu',
             BENCH_MODEL='resnet20', BENCH_IMG='32', BENCH_BATCH='8',
             BENCH_ITERS='3')


def _run_bench(tmp_path, timeout, extra_env=()):
    # strip every BENCH_*/KFAC_* var from the inherited shell — the
    # repo's own workflow exports BENCH_FULL/BENCH_BREAKDOWN/
    # KFAC_EIGH_IMPL etc., and any of those leaking in changes the leg
    # set the contract assertions pin
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')
           and not k.startswith(('BENCH_', 'KFAC_'))}
    env.update(SMOKE, JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    env.update(extra_env)
    p = subprocess.run([sys.executable, 'bench.py'], cwd=REPO, env=env,
                       stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                       text=True, timeout=timeout)
    return p.returncode, p.stdout


@pytest.mark.slow
def test_bench_json_contract(tmp_path):
    rc, out = _run_bench(tmp_path, timeout=900)
    assert rc == 0, out
    lines = [l for l in out.splitlines() if l.strip()]
    assert len(lines) == 1, lines  # ONE JSON line on stdout
    d = json.loads(lines[0])
    assert d['metric'] == 'resnet50_imagenet_dpkfac_imgs_per_sec_per_chip'
    assert d['unit'] == 'imgs/s'
    assert d['value'] and d['value'] > 0
    assert d['vs_baseline'] and d['vs_baseline'] > 0
    extra = d['extra']
    # every leg key present — explicit null for unmeasured legs, so a
    # failed leg reads as null, never as an absent key
    for key in ('sgd_iter_s', 'inverse_dp_iter_s_freq1',
                'inverse_dp_iter_s_freq10',
                'inverse_dp_iter_s_freq1_warm_ns',
                'eigen_dp_iter_s_freq10', 'eigen_dp_iter_s_freq10_basis100',
                'eigen_dp_iter_s_freq10_warm_subspace',
                'kfac_overhead_vs_sgd_freq1', 'kfac_overhead_vs_sgd_freq10',
                'model_flops_per_iter', 'mfu_inverse_dp_freq1',
                'peak_flops', 'phase_breakdown_s', 'eigh_impl',
                'autotune', 'decomp'):
        assert key in extra, key
    # the analytic perf model's predictions ride along as the drift
    # block's other half, clearly labeled — and computed cleanly
    assert extra['predicted']['predicted_not_measured'] is True
    assert 'error' not in extra['predicted'], extra['predicted']
    # the obs.drift block pairs the measured legs with the prediction:
    # per-phase ratios present, and a CPU smoke run is advisory-only
    # (comparable: false) — it must never read as chip evidence
    dr = extra['drift']
    assert dr['measured_vs_predicted'] is True
    assert 'error' not in dr, dr
    assert dr['comparable'] is False
    assert dr['gate']['verdict'] == 'advisory'
    assert dr['phases']['Model']['measured_s'] > 0
    assert dr['phases']['Model']['ratio'] is not None
    assert extra['eigen_dp_iter_s_freq10'] is None  # BENCH_FULL unset
    # smoke config must be marked — a smoke run must never read as an
    # official resnet50 number, and a CPU has no MFU
    assert extra['overrides']['model'] == 'resnet20'
    assert extra['peak_flops'] is None
    assert extra['mfu_inverse_dp_freq1'] is None


@pytest.mark.slow
def test_bench_leg_that_raises_fails_the_run(tmp_path):
    # an unknown model makes the first leg raise: non-zero exit and NO
    # result line — nothing stands in for a measurement
    rc, out = _run_bench(tmp_path, timeout=300,
                         extra_env={'BENCH_MODEL': 'no-such-model'})
    assert rc != 0
    assert not [l for l in out.splitlines() if l.strip().startswith('{')]
