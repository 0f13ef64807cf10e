"""The yardstick's own arithmetic, on the CPU at toy sizes: how a run's
failures are tallied (``harness/window.tally``), which counters are read
(``harness/program.health_counters``), and ``reference/kfac_plain`` on a
layer whose weight is one slice of a stacked leaf and whose statistics
come from the rows routed to it. Collected into tier-1 through
``test_configs.py``."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import files, program, window


# -- the tally --------------------------------------------------------------

SETUP, WINDOW = 10, 40


def _mets(bad):
    """Metrics of SETUP + WINDOW steps; those in ``bad`` are refused (even
    index) or have a loss that is not finite (odd)."""
    good = {'loss': np.float32(5.9), 'health/ok': np.bool_(True)}
    refused = {'loss': np.float32(4.0), 'health/ok': np.bool_(False)}
    nan = {'loss': np.float32(np.nan), 'health/ok': np.bool_(True)}
    return [(nan if i % 2 else refused) if i in bad else good
            for i in range(SETUP + WINDOW)]


QUIET = {'health/skipped': 0.0, 'health/rung': 0.0, 'health/fallbacks': 0.0}


@pytest.mark.parametrize('bad,counters,compiles,failed,first', [
    ((), QUIET, 0, 0, -1),                                  # a sound run
    (range(2, 5), QUIET, 0, 1, 2),                          # set-up only
    (range(30, 50), QUIET, 0, 20, 30),                      # window only
    ((), dict(QUIET, **{'health/rung': 2.0}), 0, 1, -1),    # counters only
    ((), QUIET, 3, 1, -1),                                  # a compile only
    # more bad steps than the window has: every step from set-up on
    (range(0, 50), dict(QUIET, **{'health/skipped': 50.0}), 0, WINDOW, 0),
    # what the old sum made of PR 30's run: 614 of ~350
    (range(45, 50), dict(QUIET, **{'health/skipped': 609.0}), 0, 5, 45),
], ids=['sound', 'setup', 'window', 'counters', 'compile', 'every-step',
        'counter-above-steps'])
def test_tally_counts_over_the_windows_steps(bad, counters, compiles,
                                             failed, first):
    attempted, n_failed, parts = window.tally(
        _mets(set(bad)), SETUP, WINDOW, counters, compiles)
    assert attempted == WINDOW
    assert n_failed == failed and 0 <= n_failed <= attempted
    # 0 only where nothing at all is wrong: `correct` turns on it
    wrong = bool(bad) or compiles > 0 or any(counters.values())
    assert (n_failed > 0) == wrong
    assert parts['first_bad_step'] == first
    assert parts['bad_steps_setup'] == len([i for i in bad if i < SETUP])
    assert parts['bad_steps_window'] == len([i for i in bad if i >= SETUP])
    assert parts['compiles_in_window'] == compiles
    assert all(parts[k] == v for k, v in counters.items())
    # every part but the index reads 0 in a sound run, beside limit 0
    sound = {k: v for k, v in parts.items() if k != 'first_bad_step'}
    assert any(sound.values()) == wrong


def test_health_counters_by_the_configurations_names():
    mets = {'loss': np.float32(1.0), 'health/skipped': np.int32(2),
            'health/rung': np.int32(0), 'health/fallbacks': np.int32(0),
            'router/dropped_tokens': np.int32(7)}
    assert program.health_counters(mets) == {
        'health/skipped': 2.0, 'health/rung': 0.0, 'health/fallbacks': 0.0}
    assert program.health_counters(
        mets, ['health/skipped', 'router/dropped_tokens']) == {
        'health/skipped': 2.0, 'router/dropped_tokens': 7.0}
    # a program without a guard has none of the three; a name the
    # configuration asks for has to be there
    assert program.health_counters({'loss': np.float32(1.0)}) == {}
    with pytest.raises(KeyError):
        program.health_counters(mets, ['router/dropped'])


# -- kfac_plain: stacked leaves, statistics over routed rows -----------------

class Routed:
    """``tanh(x W + b)`` into E experts, row i to expert ``route[i]``; the
    loss is a mean over all T rows. ``stacked``: the experts are one leaf
    ``[E, h, o]``, each a ``rows`` layer over all T rows with its 0/1
    weight. Otherwise each expert is a leaf and a ``dense`` layer that sees
    its own rows alone; its tap is scaled by T over its rows, so that
    ``dense``'s "the loss is a mean over my rows" comes to the same G."""

    D_IN, HIDDEN, D_OUT = 6, 5, 4

    def __init__(self, stacked, route, experts=2):
        self.stacked, self.route, self.experts = stacked, route, experts

    def expert_path(self, e):
        return f'experts/{e}' if self.stacked else f'expert_{e}'

    def kfac_layers(self, cfg):
        layers = [dict(path='inp', kind='dense', bias=True,
                       kernel=(self.D_IN, self.HIDDEN))]
        for e in range(self.experts):
            layer = dict(path=self.expert_path(e), bias=False,
                         kernel=(self.HIDDEN, self.D_OUT))
            if self.stacked:
                layer.update(kind='rows', leaf='experts/kernel', index=e,
                             loss_rows=len(self.route))
            else:
                layer.update(kind='dense')
            layers.append(layer)
        return layers

    def param_shapes(self, cfg):
        shapes = {'inp/kernel': (self.D_IN, self.HIDDEN),
                  'inp/bias': (self.HIDDEN,)}
        if self.stacked:
            shapes['experts/kernel'] = (self.experts, self.HIDDEN, self.D_OUT)
        else:
            for e in range(self.experts):
                shapes[f'expert_{e}/kernel'] = (self.HIDDEN, self.D_OUT)
        return shapes

    def make_batch(self, cfg, traffic, key):
        kx, ky = jax.random.split(key)
        t = len(self.route)
        return {'x': jax.random.normal(kx, (t, self.D_IN)),
                'y': jax.random.normal(ky, (t, self.D_OUT))}

    def forward(self, cfg, params, batch, taps, dtype, rnd=lambda x: x,
                shapes=None):
        acts = {}

        def tapped(path, y, scale=1.0):
            if shapes is not None:
                shapes[path] = (y.shape, y.dtype)
            return y + scale * taps[path] if path in taps else y

        x = batch['x'].astype(dtype)
        acts['inp'] = x
        h = jnp.tanh(tapped('inp', x @ params['inp/kernel']
                            + params['inp/bias']))
        t = len(self.route)
        out = jnp.zeros((t, self.D_OUT), dtype)
        for e in range(self.experts):
            path = self.expert_path(e)
            mine = np.asarray(self.route) == e
            if self.stacked:
                w = jnp.asarray(mine, dtype)
                acts[path] = (h, w)
                y = tapped(path, h @ params['experts/kernel'][e])
                out = out + w[:, None] * y
            else:
                idx = np.flatnonzero(mine)
                acts[path] = h[idx]
                y = tapped(path, h[idx] @ params[f'expert_{e}/kernel'],
                           t / len(idx))
                out = out.at[idx].set(y)
        loss = 0.5 * jnp.sum(jnp.square(out - batch['y'])) / t
        return loss, acts


def _same_numbers(shapes, key):
    """Weights by the expert's name, so that the stacked leaf is the stack
    of the separate leaves."""
    def draw(path, shape):
        k = jax.random.fold_in(key, zlib.crc32(path.encode()))
        return 0.5 * jax.random.normal(k, shape, jnp.float32)
    return {path: (jnp.stack([draw(f'expert_{e}/kernel', shape[1:])
                              for e in range(shape[0])])
                   if path == 'experts/kernel' else draw(path, shape))
            for path, shape in shapes.items()}


def _run(model, name, steps=3, **kw):
    kfac_plain = files.load_module('reference', 'kfac_plain')
    cfg = {'name': name, 'model': {},
           'kfac': {'damping': 0.003, 'ema_new_weight': 0.95,
                    'kl_clip': 0.001},
           'optimizer': {'lr': 0.05, 'momentum': 0.9, 'weight_decay': 1e-4},
           'dtype': {'activations': 'float32',
                     'matmul_precision': 'highest'}}
    traffic = {'pool': 2, 'fac_update_freq': 1, 'kfac_update_freq': 1}
    key = jax.random.PRNGKey(38)
    paths = ['inp'] + [model.expert_path(e) for e in range(model.experts)]
    return kfac_plain.run(model, cfg, traffic, _same_numbers, key,
                          jax.random.fold_in(key, 1), steps,
                          keep_factors=paths, **kw)


ROUTE = (0, 1, 0, 0, 1, 1, 0, 0, 0, 1)


@pytest.fixture(scope='module')
def both_layouts():
    return (_run(Routed(True, ROUTE), 'routed-stacked'),
            _run(Routed(False, ROUTE), 'routed-separate'))


def test_rows_on_a_stacked_leaf_is_dense_on_separate_leaves(both_layouts):
    stacked, separate = both_layouts
    np.testing.assert_allclose(stacked['losses'], separate['losses'],
                               rtol=1e-6)
    assert stacked['losses'][-1] < stacked['losses'][0]
    for mine, theirs in (('inp', 'inp'), ('experts/0', 'expert_0'),
                         ('experts/1', 'expert_1')):
        for fa, fb in zip(stacked['factors'][mine],
                          separate['factors'][theirs]):
            assert not np.allclose(fa, np.eye(len(fa)))
            np.testing.assert_allclose(fa, fb, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize('number', ['first_update', 'param_change'])
def test_stacked_leaf_moves_as_its_slices_do(both_layouts, number):
    """The optimizer and the compared norms go by leaf: the stacked leaf's
    norm is that of its slices together."""
    stacked, separate = both_layouts
    for leaf in ('inp/kernel', 'inp/bias'):
        assert stacked[number][leaf] == pytest.approx(separate[number][leaf],
                                                      rel=1e-5)
    slices = [separate[number][f'expert_{e}/kernel'] for e in (0, 1)]
    assert all(s > 0 for s in slices)
    assert stacked[number]['experts/kernel'] == pytest.approx(
        float(np.hypot(*slices)), rel=1e-5)


def test_an_expert_that_gets_no_row_keeps_its_average():
    out = _run(Routed(True, (0,) * 10), 'routed-one-idle')
    assert np.all(np.isfinite(out['losses']))
    for factor in out['factors']['experts/1']:
        np.testing.assert_array_equal(factor, np.eye(len(factor)))
    assert not np.allclose(out['factors']['experts/0'][0], np.eye(5))
    # and the busy expert is what it is alone
    alone = _run(Routed(True, (0,) * 10, experts=1), 'routed-alone')
    np.testing.assert_allclose(out['losses'], alone['losses'], rtol=1e-6)
    for fa, fb in zip(out['factors']['experts/0'],
                      alone['factors']['experts/0']):
        np.testing.assert_allclose(fa, fb, rtol=1e-6)


def test_rows_with_every_row_is_dense_on_flat_tokens():
    rows = _run(Routed(True, (0,) * 10, experts=1), 'routed-alone')
    dense = _run(Routed(False, (0,) * 10, experts=1), 'routed-alone-dense')
    np.testing.assert_allclose(rows['losses'], dense['losses'], rtol=1e-6)
    for fa, fb in zip(rows['factors']['experts/0'],
                      dense['factors']['expert_0']):
        np.testing.assert_allclose(fa, fb, rtol=1e-6, atol=1e-8)
    assert rows['param_change']['experts/kernel'] == pytest.approx(
        dense['param_change']['expert_0/kernel'], rel=1e-5)


def test_a_slice_of_a_stacked_leaf_has_no_bias():
    model = Routed(True, ROUTE)
    layers = model.kfac_layers({})
    model.kfac_layers = lambda cfg: [dict(l, bias='leaf' in l)
                                     for l in layers]
    with pytest.raises(ValueError, match='has no bias'):
        _run(model, 'routed-biased', steps=1)
