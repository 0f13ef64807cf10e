"""The step explains its own time: what ``training.step_fn`` writes into the
profiler's trace (host spans through ``obs.trace``), what the step programs
are called, and which device scopes their operations carry.

No live profiler session (the suite runs under xdist): the tests put a
recording stand-in in ``jax.profiler.TraceAnnotation``'s place.
"""

import re

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import nn as knn
from kfac_pytorch_tpu import training
from kfac_pytorch_tpu.obs import trace

from tests.helpers import TinyCNN


@pytest.fixture
def annotations(monkeypatch):
    """[(depth, name), ...] of every profiler annotation entered."""
    log, depth = [], [0]

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append((depth[0], self.name))
            depth[0] += 1

        def __exit__(self, *exc):
            depth[0] -= 1

    monkeypatch.setattr(jax.profiler, 'TraceAnnotation', Recording)
    return log


def _batch(n=4):
    rng = np.random.RandomState(0)
    return {'input': jnp.asarray(rng.randn(n, 8, 8, 3), jnp.float32),
            'label': jnp.asarray(rng.randint(0, 10, n))}


def _ce(outputs, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, batch['label']).mean()


class TinyMLP(linen.Module):
    """Dense capture only, as BERT's: no conv layer."""

    @linen.compact
    def __call__(self, x, train=True):
        x = linen.relu(knn.Dense(16, name='d1')(x.reshape(x.shape[0], -1)))
        return knn.Dense(10, name='d2')(x)


def _trainer(with_kfac=True, tracer=None, mesh=None, model=None, **kfac_kw):
    batch = _batch(8 if mesh is not None else 4)
    axis = 'batch' if mesh is not None else None
    precond = None
    if with_kfac:
        kw = dict(variant='inverse_dp', lr=0.05, damping=0.003,
                  fac_update_freq=2, kfac_update_freq=2,
                  num_devices=mesh.size if mesh is not None else 1,
                  axis_name=axis)
        kw.update(kfac_kw)
        precond = kfac.KFAC(**kw)
    model, tx = model or TinyCNN(), training.sgd(0.05)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(0), batch['input'])
    step = training.build_train_step(model, tx, precond, _ce, tracer=tracer,
                                     axis_name=axis, mesh=mesh)
    return step, state, batch


# -- obs.trace: one call, two sinks ------------------------------------------


def test_span_enters_the_profiler_without_a_recorder(annotations):
    trace.uninstall()
    with trace.span('kfac.anything', cat='kfac', step=3):
        pass
    assert annotations == [(0, 'kfac.anything')]


def test_span_enters_the_profiler_and_the_installed_recorder(annotations):
    rec = trace.install(recorder=trace.TraceRecorder(None))
    try:
        with trace.span('kfac.outer', step=3):
            with trace.span('kfac.inner'):
                pass
    finally:
        trace.uninstall()
    assert annotations == [(0, 'kfac.outer'), (1, 'kfac.inner')]
    spans = {e['name']: e for e in rec.events() if e['ph'] == 'X'}
    assert set(spans) == {'kfac.outer', 'kfac.inner'}
    assert spans['kfac.outer']['args'] == {'step': 3}


def test_recorder_span_annotate_names_and_mutes_the_profiler_side(
        annotations):
    rec = trace.TraceRecorder(None)
    with rec.span('kfac.dispatch', annotate='kfac.step.dispatch/pred'):
        with rec.span('kfac.Precondition', cat='kfac.sched',
                      annotate=False):
            pass
    # the profiler sees the span under its own name and not the drawing
    assert annotations == [(0, 'kfac.step.dispatch/pred')]
    assert [e['name'] for e in rec.events() if e['ph'] == 'X'] == [
        'kfac.Precondition', 'kfac.dispatch']
    # module level, no recorder: a muted span enters nothing at all
    trace.uninstall()
    with trace.span('kfac.Precondition', annotate=False):
        pass
    assert len(annotations) == 1


def test_annotation_passes_through_where_jax_is_not_loaded(monkeypatch):
    import sys
    monkeypatch.delitem(sys.modules, 'jax')
    with trace.annotation('kfac.step'):
        pass
    assert 'jax' not in sys.modules     # and it did not import it


# -- step_fn's host spans ------------------------------------------------------


def _steps(log):
    """Split the annotation log into one list per ``kfac.step``."""
    out = []
    for depth, name in log:
        if name == 'kfac.step':
            assert depth == 0
            out.append([])
        else:
            out[-1].append((depth, name))
    return out


@pytest.mark.parametrize('with_kfac', [True, False], ids=['kfac', 'sgd'])
def test_step_fn_emits_the_documented_spans(annotations, with_kfac):
    step, state, batch = _trainer(with_kfac)
    suffixes = []
    for _ in range(4):
        state, _ = step(state, batch)
        suffixes.append('+'.join(step.last_phases)
                        or ('none' if with_kfac else 'sgd'))
    per_step = _steps([a for a in annotations
                       if a[1].startswith('kfac.step')])
    assert len(per_step) == 4
    seen_builds = []
    for i, (spans, suffix) in enumerate(zip(per_step, suffixes)):
        names = [n for _, n in spans]
        # the read span only where the counter was read from the device:
        # the first call; after it the host counts
        head = ['kfac.step.read_step'] * (i == 0) + [
            'kfac.step.hooks', 'kfac.step.select']
        assert names[:len(head)] == head
        assert [d for d, _ in spans[:len(head)]] == [1] * len(head)
        assert names[-1] == 'kfac.step.dispatch/' + suffix
        builds = [n for n in names if n.startswith('kfac.step.build/')]
        # a build span only on a cache miss, open over the first call
        assert names[len(head):-1] == builds and len(builds) <= 1
        assert spans[-1][0] == (2 if builds else 1)
        seen_builds += builds
    if with_kfac:
        assert suffixes == ['pred+stats+decomp', 'pred'] * 2
        assert seen_builds == ['kfac.step.build/kfac_step_pred_stats_decomp',
                               'kfac.step.build/kfac_step_pred']
    else:
        assert suffixes == ['sgd'] * 4
        assert seen_builds == ['kfac.step.build/sgd_step']
    assert step.step_reads == 1


def test_recorder_keeps_getting_kfac_dispatch_and_nothing_new(annotations):
    rec = trace.TraceRecorder(None)
    step, state, batch = _trainer(tracer=rec)
    for _ in range(2):
        state, _ = step(state, batch)
    spans = [e for e in rec.events() if e['ph'] == 'X']
    # the recorder's side of the dispatch span keeps its name and args;
    # the step's other spans are the profiler's alone
    assert [s['name'] for s in spans] == ['kfac.dispatch'] * 2
    assert [s['args']['step'] for s in spans] == [0, 1]
    assert spans[0]['args']['phases'] == ['ComputeFactor', 'ComputeInverse',
                                          'Precondition']
    assert spans[1]['args']['phases'] == ['Precondition']
    dispatched = [n for _, n in annotations
                  if n.startswith('kfac.step.dispatch/')]
    assert dispatched == ['kfac.step.dispatch/pred+stats+decomp',
                          'kfac.step.dispatch/pred']
    assert 'kfac.dispatch' not in [n for _, n in annotations]


# -- step programs: names and device scopes -----------------------------------


def _hyper():
    return kfac.KFACHyperParams(lr=jnp.float32(0.05),
                                damping=jnp.float32(0.003))


def _op_names(fn, state, batch):
    text = fn.lower(state, batch, _hyper()).as_text(debug_info=True)
    return text, set(re.findall(r'loc\("([^"]*)"', text))


NAMES = [
    (dict(update_factors=False, update_inverse=False), 'kfac_step_pred'),
    (dict(update_factors=True, update_inverse=True),
     'kfac_step_pred_stats_decomp'),
    (dict(update_factors=True, update_inverse=False, factors_only=True),
     'kfac_step_stats'),
    (dict(update_factors=False, update_inverse=False, factors_only=True),
     'kfac_step_none'),
    (dict(update_factors=False, update_inverse=False, stagger_update=True),
     'kfac_step_pred_decomp_stagger'),
    (dict(update_factors=True, update_inverse=True, update_basis=False),
     'kfac_step_pred_stats_decomp_refresh'),
    (dict(update_factors=False, update_inverse=True, warm_basis=True),
     'kfac_step_pred_decomp_warm'),
    (dict(update_factors=True, update_inverse=True, prefetch=True),
     'kfac_step_pred_stats_decomp_prefetch'),
]


@pytest.mark.parametrize('static,name', NAMES, ids=[n for _, n in NAMES])
def test_step_program_is_named_after_what_it_does(static, name):
    step, _, _ = _trainer()
    # named when built; nothing is traced here
    assert step.make_variant(**static).__name__ == name


def test_gathering_variant_and_sgd_program_names():
    step, _, _ = _trainer(variant='eigen')
    assert step.make_variant(True, True).__name__ == (
        'kfac_step_pred_stats_decomp_gather')
    step, _, _ = _trainer(with_kfac=False)
    assert step.make_variant(False, False).__name__ == 'sgd_step'


@pytest.mark.parametrize('static,name', NAMES[:3],
                         ids=[n for _, n in NAMES[:3]])
def test_variant_carries_its_name_and_the_train_scopes(static, name):
    step, state, batch = _trainer()
    text, names = _op_names(step.make_variant(**static), state, batch)
    assert f'@jit_{name}' in text
    for scope in ('train.grad', 'train.optimizer', 'train.health_screen'):
        assert any(f'jit({name})/' in n and f'/{scope}/' in n
                   for n in names), scope
    # JAX's own path tells the passes apart inside train.grad
    grad = [n for n in names if '/train.grad/' in n]
    assert any('transpose(jvp(' in n for n in grad)
    assert any('jvp(' in n and 'transpose(' not in n for n in grad)
    # the new scopes sit beside the engine's, not inside them
    assert not any('kfac.' in n and 'train.' in n for n in names)


def test_sgd_and_mesh_variants_carry_the_train_scopes():
    step, state, batch = _trainer(with_kfac=False)
    text, names = _op_names(step.make_variant(False, False), state, batch)
    assert '@jit_sgd_step' in text
    assert any('/train.grad/' in n for n in names)
    assert any('/train.optimizer/' in n for n in names)
    # the gradient average exists only across devices
    assert not any('train.grad_reduce' in n for n in names)
    mesh = Mesh(np.array(jax.devices()[:4]), ('batch',))
    step, state, batch = _trainer(mesh=mesh)
    text, names = _op_names(step.make_variant(True, True), state, batch)
    assert '@jit_kfac_step_pred_stats_decomp' in text
    # (inside shard_map's body the paths start at the scope)
    for scope in ('train.grad', 'train.grad_reduce', 'train.optimizer',
                  'train.health_screen'):
        assert any(f'{scope}/' in n for n in names), scope


# -- conv statistics name the form they took (PR 26) ---------------------------


def _conv_a_scopes(names):
    return {m for n in names for m in re.findall(r'conv_a\.\w+', n)}


def test_conv_statistics_name_their_form_inside_compute_factor():
    step, state, batch = _trainer()
    _, names = _op_names(step.make_variant(True, True), state, batch)
    # TinyCNN: 3x3 kernels on 3 and 8 channels, both under the raw form's
    # channel bound
    assert _conv_a_scopes(names) == {'conv_a.raw'}
    assert all('kfac.ComputeFactor/conv_a.raw' in n for n in names
               if 'conv_a.' in n)
    # a step that takes no statistics builds no patches
    _, names = _op_names(step.make_variant(False, False), state, batch)
    assert not _conv_a_scopes(names)


def test_dense_only_update_step_holds_no_conv_scope():
    # a model without conv layers (BERT) runs none of the conv path: its
    # step programs are what they were before that path changed
    step, state, batch = _trainer(model=TinyMLP())
    text, names = _op_names(step.make_variant(True, True), state, batch)
    assert '@jit_kfac_step_pred_stats_decomp' in text
    assert any('kfac.ComputeFactor' in n for n in names)
    assert 'conv_a.' not in text
    assert 'stablehlo.convolution' not in text
