"""Pins the analytic perf model: the committed cost-analysis inputs,
the eigh-time fit, the scenario arithmetic, and the predicted block's
shape — so the `predicted` numbers a bench JSON carries are
reproducible and a silent change to any ingredient fails loudly here."""

import json
import os
import subprocess
import sys

import pytest

from kfac_pytorch_tpu import perfmodel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_inputs_are_official_resnet50():
    inputs = perfmodel.load_inputs()
    meta = inputs['meta']
    assert meta['official'] is True
    assert (meta['model'], meta['batch'], meta['img']) == ('resnet50', 32,
                                                           224)
    # all nine programs present with positive totals
    for tag in ('sgd', 'inverse_dp_base', 'inverse_dp_factor',
                'inverse_dp_full', 'eigen_dp_base', 'eigen_dp_factor',
                'eigen_dp_full', 'eigen_dp_refresh', 'ekfac_factor'):
        assert inputs['programs'][tag]['flops'] > 0, tag
        assert inputs['programs'][tag]['bytes'] > 0, tag
    # bucket table sane: ResNet-50's largest factor dim is 4608
    # (reference scripts/inverse_model.py:19-20); every bucket holds rows
    dims = [d for _, d in inputs['buckets']]
    assert max(dims) >= 4608
    assert all(r >= 1 for r, _ in inputs['buckets'])


def test_model_flops_sanity():
    """ResNet-50 fwd is ~4 GFLOPs/img at 224^2 (x3 for fwd+bwd, x32
    batch ~= 4e11); the counted sgd-program total must sit in that
    magnitude band — catches a units mixup or a silently-swapped inputs
    file."""
    inputs = perfmodel.load_inputs()
    sgd = inputs['programs']['sgd']['flops']
    assert 1.5e11 < sgd < 2.0e12, sgd


def test_eigh_fit_reproduces_fenced_points():
    _, _, fn = perfmodel.eigh_time_model()
    for rows, dim, secs in perfmodel.FENCED_EIGH_POINTS:
        assert abs(fn(rows, dim) - secs) / secs < 1e-6, (rows, dim)
    # monotone in both arguments (the fit must extrapolate sanely to
    # the 4608 bucket)
    assert fn(1, 4608) > fn(1, 2304) > fn(1, 512) > 0
    assert fn(8, 1024) > fn(4, 1024)


def test_phase_costs_nonnegative_and_ordered():
    inputs = perfmodel.load_inputs()
    ph = perfmodel.phase_costs(inputs)
    for name, (f, b) in ph.items():
        assert f >= 0 and b >= 0, (name, f, b)
    # the factor phase exists and the Cholesky phase is analytic > 0
    assert ph['factor'][0] > 0
    assert ph['inverse_chol'][0] > 0


def test_scenarios_ordered_and_variants_complete():
    pred = perfmodel.predict()
    variants = ('sgd', 'inverse_dp_freq1', 'inverse_dp_freq10',
                'eigen_dp_freq10_cold', 'eigen_dp_freq10_basis100',
                'ekfac_freq10_basis100')
    for v in variants:
        o = pred['optimistic'][v]['iter_s']
        c = pred['central'][v]['iter_s']
        k = pred['conservative'][v]['iter_s']
        assert 0 < o < c < k, (v, o, c, k)
        # vs_baseline arithmetic: imgs/s over the 0.487 s anchor's rate
        got = pred['central'][v]['vs_baseline']
        want = (perfmodel.BATCH / c) / (perfmodel.BATCH
                                        / perfmodel.BASELINE_ITER_S)
        assert abs(got - want) < 0.01 + 0.005 * want, (v, got, want)


def test_quantified_eigen_path_gap():
    """The model must reproduce the round-2 discovery AS A NUMBER: the
    reference's default variant (cold eigen_dp, its deployed freq-10
    cadence) is dominated by the fenced QDWH seconds-per-bucket term and
    cannot compete with the Cholesky flagship on this chip — in EVERY
    scenario, including optimistic."""
    pred = perfmodel.predict()
    for scen in perfmodel.SCENARIOS:
        cold = pred[scen]['eigen_dp_freq10_cold']['iter_s']
        chol = pred[scen]['inverse_dp_freq10']['iter_s']
        assert cold > 5 * chol, (scen, cold, chol)
        # and the amortized rescue recovers most of the gap
        rescued = pred[scen]['eigen_dp_freq10_basis100']['iter_s']
        assert rescued < cold / 2, (scen, rescued, cold)


def test_predict_block_shape():
    blk = perfmodel.predict_block()
    assert blk['predicted_not_measured'] is True
    assert 'error' not in blk, blk.get('error')
    assert blk['anchor']['reference_kfac_iter_s'] == 0.487
    assert blk['headline']['value'] == \
        blk['scenarios']['central']['inverse_dp_freq1']['imgs_per_s']
    # the assumptions block must disclose its own weakest points
    a = blk['assumptions']
    assert 'eigh_fit' in a and 'fenced_points' in a['eigh_fit']
    assert 'skinny_floor_datapoint' in a


@pytest.mark.slow
def test_derivation_script_smoke(tmp_path):
    """The derivation pipeline itself stays runnable: tiny-config run
    produces a structurally-valid inputs file that predict() accepts."""
    out = tmp_path / 'inputs.json'
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
    env.update(JAX_PLATFORMS='cpu', DERIVE_MODEL='resnet20',
               DERIVE_IMG='32', DERIVE_BATCH='8')
    subprocess.run([sys.executable, 'scripts/derive_perf_inputs.py',
                    '--out', str(out)], cwd=REPO, env=env, check=True,
                   timeout=900, stdout=subprocess.DEVNULL)
    inputs = json.loads(out.read_text())
    assert inputs['meta']['official'] is False
    pred = perfmodel.predict(inputs)  # arithmetic accepts the structure
    assert pred['central']['inverse_dp_freq1']['iter_s'] > 0
