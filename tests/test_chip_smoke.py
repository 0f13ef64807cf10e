"""CPU rehearsal of ``chip_smoke.py`` (on-chip-measurement guide §2,
rehearsals 1 and 2): the same script, end to end, at a tiny size with
the Pallas kernels interpreted, and its ``--chips 4`` path on four of
the virtual devices. The device check is swapped HERE, by the test — the
script has no option that lets it pass without a TPU — and so are its
size constants. Plus the compile-cache helper's placement contract.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from kfac_pytorch_tpu.utils import platform  # noqa: E402


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """chip_smoke at rehearsal size, its device check answered by the
    CPU; compile cache and outputs under tmp_path."""
    monkeypatch.setattr(chip_smoke, 'require_tpu', jax.devices)
    monkeypatch.setattr(chip_smoke, 'kernels_interpreted', lambda: True)
    monkeypatch.setattr(chip_smoke, 'RESNET_ARGS', [
        '--model', 'resnet20', '--img-size', '16', '--synthetic-size', '8',
        '--epochs', '1', '--kfac-cov-update-freq', '10',
        '--val-batch-size', '256'])
    monkeypatch.setattr(chip_smoke, 'RESNET_BATCH_PER_CHIP', 2)
    # two-image batches of a 16x16 ResNet-20: the loss is all noise
    monkeypatch.setattr(chip_smoke, 'MESH_LOSS_RTOL', 10.0)
    monkeypatch.setattr(chip_smoke, 'FENCE_SHAPE', (64, 2))
    monkeypatch.setattr(chip_smoke, 'KERNEL_SHAPES', {
        'batch': 2, 'hw56': 8, 'hw14': 4, 'hw224': 16,
        'bert_tokens': (2, 8), 'ef': (2, 16, 16),
        'attn_len': 256, 'attn_check_rows': 128})
    # set after jax was imported: JAX does not read it any more, and the
    # helper then sets no cache of its own -> the rehearsal compiles
    # without a persistent cache, whatever this worker ran before
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'cache'))
    return str(tmp_path / 'out')


def _rows(capsys):
    out = capsys.readouterr().out
    rows = [json.loads(l) for l in out.splitlines() if l.startswith('{')]
    assert rows, out[-2000:]
    return rows


def test_rehearsal_resnet_legs(tiny, capsys):
    chip_smoke.main(['--phases', 'resnet50:inverse_dp,resnet50:sgd',
                     '--out', tiny])
    rows = _rows(capsys)
    assert rows[-1]['ok'] is True and set(rows[-1]) == {'ok', 'device'}
    legs = {r['leg']: r for r in rows if r.get('phase') == 'resnet50'}
    kfac, sgd = legs['inverse_dp'], legs['sgd']
    assert len(kfac['losses']) == len(sgd['losses']) == 4
    # one factor+decomposition step, then plain precondition steps
    assert set(kfac['first_call_s']) == {'pred+stats+decomp', 'pred'}
    assert all(n == 1 for n in kfac['compilations'].values())
    assert kfac['decomp_populated_after_first_step'] is True
    assert kfac['health'] == {'skipped': 0, 'fallbacks': 0, 'rung': 0}
    cmp_ = legs['compare']
    assert cmp_['step0_loss']['inverse_dp'] == cmp_['step0_loss']['sgd']
    assert cmp_['param_rel_distance_to_sgd']['inverse_dp'] > 0


def test_rehearsal_fence_and_kernels(tiny, capsys):
    chip_smoke.main(['--phases', 'fence,kernels', '--out', tiny])
    rows = {r.get('phase'): r for r in _rows(capsys)}
    assert rows['kernels']['ok'] and rows['kernels']['interpret']
    names = [k['kernel'] for k in rows['kernels']['kernels']]
    assert len(names) == 11 and any('conv1' in n for n in names)
    assert {'block_until_ready_s', 'fetch_after_ready_s'} <= set(
        rows['fence'])


needs_four = pytest.mark.skipif(
    'len(jax.devices()) < 4', reason='needs 4 host devices (conftest)')


@needs_four
def test_rehearsal_four_chips_parity_and_last_line(tiny, capsys,
                                                   monkeypatch):
    # the ResNet legs rehearse in the next test (a minute of their own)
    monkeypatch.setattr(chip_smoke, 'mesh_resnet50', lambda *a: True)
    chip_smoke.main(['--chips', '4', '--out', tiny])
    rows = _rows(capsys)
    assert rows[-1] == {'ok': True, 'device': {
        'platform': 'cpu', 'kind': 'cpu', 'count': len(jax.devices())}}
    # only the mesh path and what it is compared with
    assert [r['phase'] for r in rows[:-1]] == ['start', 'mesh', 'end']
    assert rows[1]['leg'] == 'parity' and rows[1]['ok']


@needs_four
def test_rehearsal_four_chips_resnet_legs(tiny, capsys):
    os.makedirs(tiny)
    assert chip_smoke.mesh_resnet50(tiny, 'cpu', jax.devices()[:4],
                                    'inverse_dp')
    mesh, one = _rows(capsys)
    assert (mesh['devices'], one['devices']) == (4, 1)
    assert len(mesh['losses']) == len(one['losses']) == 4
    assert mesh['factors_on_all_devices'] and mesh['decomp_on_all_devices']
    assert all(n == 1 for n in mesh['compilations'].values())
    assert one['mesh_step0_loss'] == mesh['losses'][0]


def test_fails_without_a_tpu_and_prints_no_result():
    """As the driver runs it in the sandbox: non-zero, no result line."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run([sys.executable, os.path.join(REPO, 'chip_smoke.py')],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert 'needs a TPU' in p.stderr


def test_compile_cache_obeys_the_environment(monkeypatch):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/x')
    before = jax.config.jax_compilation_cache_dir
    assert platform.compile_cache_dir() == '/x'
    assert platform.enable_compile_cache() == '/x'
    # where the environment names one, code sets no other
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    path = platform.compile_cache_dir()
    assert path == os.path.join(REPO, '.jax_cache')
    assert path == platform.compile_cache_dir()      # no pid, no time
    ignored = open(os.path.join(REPO, '.gitignore')).read().split()
    assert '.jax_cache/' in ignored
