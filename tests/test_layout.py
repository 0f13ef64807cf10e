"""The stacked-bucket layout as the apply consumes it (plan.build_plan →
engine.compute_pred_local / compute_pred_replicated).

Two halves, both in what ``build_plan`` lays down:

- bucket dims at the MXU tile (``default_bucket_fn``) with the odd bucket
  folded into its neighbour (``fold_buckets``) — pinned on ResNet-50's and
  BERT-base's layer dims, stated here as literals (no model is built);
- within a device's rows of a bucket, slots lie by (pred group, side,
  layer), so every group's rows are one contiguous run and the apply
  reads the stored decompositions in place (``PredGroup.run_starts``):
  contiguity on 1 and 4 devices, and the slice path bit-identical to the
  ``jnp.take`` form it replaces, take fallback included.
"""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kfac_pytorch_tpu import engine, ops
from kfac_pytorch_tpu import plan as plan_lib
from kfac_pytorch_tpu.capture import LayerMeta
from kfac_pytorch_tpu.obs import trace as obs_trace
from kfac_pytorch_tpu.plan import (build_plan, default_bucket_fn,
                                   fold_buckets, pred_layout_record)
from kfac_pytorch_tpu.preconditioner import KFAC

# (in_dim, out_dim) of every preconditioned layer, in the model's order:
# ResNet-50 (torchvision layout; conv in_dim = C·kh·kw, no bias; ``fc``
# 2,048 + 1 → 1,000) and BERT-base with the SQuAD span head (every dense
# layer carries the bias's homogeneous coordinate: 768 + 1, 3,072 + 1)


def _stage(c_in, planes, blocks):
    first = [(c_in, planes), (9 * planes, planes), (planes, 4 * planes),
             (c_in, 4 * planes)]
    rest = [(4 * planes, planes), (9 * planes, planes),
            (planes, 4 * planes)]
    return first + rest * (blocks - 1)


RESNET50 = ([(147, 64)] + _stage(64, 64, 3) + _stage(256, 128, 4)
            + _stage(512, 256, 6) + _stage(1024, 512, 3) + [(2049, 1000)])
BERT_BASE = (([(769, 768)] * 4 + [(769, 3072), (3073, 768)]) * 12
             + [(769, 2)])
MODELS = {'resnet50': RESNET50, 'bert-base': BERT_BASE}


def _metas(dims):
    return {f'l{i}': LayerMeta(name=f'l{i}', path=(f'l{i}',), kind='dense',
                               use_bias=False, in_dim=a, out_dim=g,
                               kernel_shape=(a, g))
            for i, (a, g) in enumerate(dims)}


@functools.lru_cache(maxsize=None)
def _plan(model, ndev=1, comm_mode='pred', assignment='round_robin'):
    return build_plan(_metas(MODELS[model]), ndev, comm_mode,
                      assignment=assignment)


def _old_ladder(dim):
    """The bucket rule this layout replaced: {128, 1.5·2^k, 2^k} to
    1,024, multiples of 256 above."""
    if dim <= 128:
        return 128
    if dim > 1024:
        return -(-dim // 256) * 256
    b = 128
    while True:
        if dim <= b:
            return b
        if dim <= b + b // 2:
            return b + b // 2
        b *= 2


def _decomp_cost(plan):
    return sum(b.n_rows * float(d) ** 3 for d, b in plan.buckets.items())


# ---------------------------------------------------------------------------
# the bucket rule and the fold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('dim,bucket', [
    (769, 896), (3073, 3200), (147, 256), (576, 640), (1152, 1152),
    (2049, 2176), (4608, 4608), (1000, 1024), (2, 128)])
def test_tile_rounding_of_the_benchmark_dims(dim, bucket):
    assert default_bucket_fn(dim) == bucket
    assert bucket % plan_lib.MXU_TILE == 0 and 0 <= bucket - dim < 128 \
        or bucket == 128


@pytest.mark.parametrize('rows_of,joins', [
    # one odd row a tile under six: joins (ResNet-50's fc input)
    ({2176: 1, 2304: 6}, {2176: 2304, 2304: 2304}),
    # two rows one tile under: still cheaper than a chain of their own
    ({896: 2, 1024: 5}, {896: 1024, 1024: 1024}),
    # many rows: padding them all costs more than the chain
    ({768: 60, 896: 61}, {768: 768, 896: 896}),
    ({3072: 12, 3200: 12}, {3072: 3072, 3200: 3200}),
    # one row, but the neighbour is far: 8x the work
    ({128: 1, 256: 4}, {128: 128, 256: 256}),
    ({640: 3, 1024: 15}, {640: 640, 1024: 1024}),
    # a chain of folds lands on its last link
    ({1024: 1, 1152: 1, 1280: 3}, {1024: 1280, 1152: 1280, 1280: 1280}),
    ({128: 7}, {128: 128}),
])
def test_fold_buckets(rows_of, joins):
    assert fold_buckets(rows_of) == joins


@pytest.mark.parametrize('model,buckets,max_sum_d', [
    ('resnet50', {128: 24, 256: 27, 512: 19, 640: 3, 1024: 15, 1152: 4,
                  2048: 6, 2304: 7, 4608: 3}, 13120),
    ('bert-base', {128: 1, 768: 60, 896: 61, 3072: 12, 3200: 12}, 8064),
])
def test_benchmark_models_bucket_set(model, buckets, max_sum_d):
    plan = _plan(model)
    assert {d: b.n_rows for d, b in plan.buckets.items()} == buckets
    assert sum(plan.bucket_dims) <= max_sum_d
    old = build_plan(_metas(MODELS[model]), 1, 'pred',
                     bucket_fn=_old_ladder)
    # never more buckets (sequential decomposition chains) nor more
    # decomposition work than the ladder this replaced
    assert len(plan.bucket_dims) <= len(old.bucket_dims)
    assert _decomp_cost(plan) <= _decomp_cost(old)


def test_resnet50_pays_no_new_chain_and_no_more_work():
    plan = _plan('resnet50')
    assert len(plan.bucket_dims) <= 10
    assert _decomp_cost(plan) <= 4.6e11
    # fc's 2,049 rides with the six 2,304s; 4,608 stays whole
    ba = plan.layer_rows[-1][0]
    assert ba == 2304 and plan.buckets[4608].n_rows == 3


def test_bert_base_sheds_the_ladders_padding():
    plan = _plan('bert-base')
    assert _decomp_cost(plan) <= 8.13e11          # 8.83e11 on the ladder
    assert [(pg.dg, pg.da, len(pg.layer_idx)) for pg in plan.pred_groups] \
        == [(128, 896, 1), (768, 896, 48), (768, 3200, 12),
            (3072, 896, 12)]


def test_same_buckets_on_every_world_size():
    # reshard_kfac_state moves whole decomposition rows between worlds:
    # the fold may not depend on the number of devices
    for model in MODELS:
        dims = {n: _plan(model, n).bucket_dims for n in (1, 2, 4, 8)}
        assert len({tuple(v) for v in dims.values()}) == 1, dims


def test_own_bucket_fn_is_taken_as_it_is():
    plan = build_plan(_metas(BERT_BASE), 1, 'pred', bucket_fn=_old_ladder)
    assert plan.bucket_dims == [128, 768, 1024, 3072, 3328]
    plan = build_plan(_metas([(5, 3), (7, 5)]), 1, 'pred',
                      bucket_fn=lambda d: d)
    assert plan.bucket_dims == [3, 5, 7]


# ---------------------------------------------------------------------------
# rows where the apply wants them
# ---------------------------------------------------------------------------

def _is_run(rows):
    rows = np.asarray(rows)
    return np.array_equal(rows, rows[0] + np.arange(len(rows)))


@pytest.mark.parametrize('assignment', ['round_robin', 'balanced'])
@pytest.mark.parametrize('ndev', [1, 4])
@pytest.mark.parametrize('model', sorted(MODELS))
def test_pred_group_rows_contiguous_per_device(model, ndev, assignment):
    plan = _plan(model, ndev, 'pred', assignment)
    for pg in plan.pred_groups:
        for d in range(ndev):
            n = int(pg.local_valid[d].sum())
            # a device's members sit in its first n slots, in the
            # group's own order, and their rows are one run
            assert pg.local_valid[d, :n].all()
            if n:
                assert (np.diff(pg.local_member[d, :n]) > 0).all()
                assert _is_run(pg.local_row_a[d, :n]), (pg.dg, pg.da, d)
                assert _is_run(pg.local_row_g[d, :n]), (pg.dg, pg.da, d)
    # the replicated layout: global rows, one run on one device
    rep = _plan(model, ndev, 'inverse', assignment)
    for pg in rep.pred_groups:
        per_dev_a = rep.buckets[pg.da].per_dev
        per_dev_g = rep.buckets[pg.dg].per_dev
        for d in range(ndev):
            ra = pg.row_a[pg.row_a // per_dev_a == d]
            rg = pg.row_g[pg.row_g // per_dev_g == d]
            assert len(ra) == 0 or _is_run(np.sort(ra))
            assert len(rg) == 0 or _is_run(np.sort(rg))
        if ndev == 1:
            assert _is_run(pg.row_a) and _is_run(pg.row_g)


@pytest.mark.parametrize('model,comm_mode,record', [
    ('resnet50', 'pred', dict(pred_operand_slices=38, pred_operand_takes=0,
                              pad_flop_share=1.0228)),
    ('resnet50', 'inverse', dict(pred_operand_slices=38,
                                 pred_operand_takes=0,
                                 pad_flop_share=1.0228)),
    ('bert-base', 'pred', dict(pred_operand_slices=8, pred_operand_takes=0,
                               pad_flop_share=1.1605)),
    ('bert-base', 'inverse', dict(pred_operand_slices=8,
                                  pred_operand_takes=0,
                                  pad_flop_share=1.1605)),
])
def test_layout_record_of_the_benchmark_models(model, comm_mode, record):
    # dense and conv layers only, every bucket inverted whole, every
    # layer of these lists alone on its input (tests/test_input_groups.py
    # has BERT-base's query / key / value on one)
    got = pred_layout_record(_plan(model, 1, comm_mode))
    # what the decomposition's device scopes are named after (PR 45):
    # every bucket whole ([n, n, D]), and sum n * D^3
    buckets, flop = got.pop('decomp_buckets'), got.pop('decomp_task_flop')
    # how each bucket gets from its Cholesky factor to its inverse (PR
    # 46): by blocks from 1,024 on, two dense solves under it
    route, spent = got.pop('decomp_route'), got.pop('decomp_route_flop')
    assert got == dict(record, stacked_layers=0, decomp_groups={},
                       a_groups=0, a_rows_saved=0)
    assert record['pad_flop_share'] <= 1.18
    assert buckets == {
        'resnet50': {'128': [24, 24, 128], '256': [27, 27, 256],
                     '512': [19, 19, 512], '640': [3, 3, 640],
                     '1024': [15, 15, 1024], '1152': [4, 4, 1152],
                     '2048': [6, 6, 2048], '2304': [7, 7, 2304],
                     '4608': [3, 3, 4608]},
        'bert-base': {'128': [1, 1, 128], '768': [60, 60, 768],
                      '896': [61, 61, 896], '3072': [12, 12, 3072],
                      '3200': [12, 12, 3200]}}[model]
    assert flop == {'resnet50': 456749219840,
                    'bert-base': 812168249344}[model]
    assert route == {d: 'structured' if int(d) >= 1024 else 'solves'
                     for d in buckets}
    assert sorted(d for d in route if route[d] == 'structured') == {
        'resnet50': ['1024', '1152', '2048', '2304', '4608'],
        'bert-base': ['3072', '3200']}[model]
    assert spent == sum(ops.inverse_route_flop(n, int(d))
                        for d, (n, _, _) in buckets.items())
    # the structured buckets are most of the task: the routes spend well
    # under the 7/3 of the task that two solves a bucket did
    assert flop < spent < 0.7 * (7 * flop // 3)


def test_layout_record_on_the_old_ladder_and_on_a_mesh():
    old = build_plan(_metas(BERT_BASE), 1, 'pred', bucket_fn=_old_ladder)
    assert pred_layout_record(old)['pad_flop_share'] == 1.3323
    # four devices, round robin: BERT's 73 layers leave the groups'
    # per-device runs of unequal length, so some reads fall back
    rec = pred_layout_record(_plan('bert-base', 4))
    assert rec['pred_operand_takes'] > 0
    assert rec['pred_operand_slices'] + rec['pred_operand_takes'] == 8
    # dummy slots are multiplied too
    assert rec['pad_flop_share'] > 1.1605


@pytest.mark.parametrize('model', sorted(MODELS))
def test_setup_records_the_layout_once(model, caplog):
    rec = obs_trace.install(None)
    try:
        pre = KFAC(variant='inverse_dp', num_devices=1)
        with caplog.at_level(logging.INFO,
                             logger='kfac_pytorch_tpu.preconditioner'):
            pre.setup(list(_metas(MODELS[model]).values()))
        events = [e for e in rec.events()
                  if e['name'] == 'kfac.precond.setup']
    finally:
        obs_trace.uninstall()
    assert len(events) == 1 and events[0]['ph'] == 'i'
    args = events[0]['args']
    want = pred_layout_record(pre.plan)
    assert {k: args[k] for k in want} == want
    assert args['pred_operand_takes'] == 0
    assert args['buckets'] == len(pre.plan.bucket_dims)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith('precond.setup')]
    assert len(lines) == 1
    assert 'pred_operand_takes 0' in lines[0]
    assert f"pad_flop_share {want['pad_flop_share']}" in lines[0]
    # ... and the route of every bucket with what the routes spend
    assert f"decomp_route {want['decomp_route']}" in lines[0]
    assert f"decomp_route_flop {want['decomp_route_flop']}" in lines[0]
    assert 'structured' in want['decomp_route'].values()


# ---------------------------------------------------------------------------
# parity of the two operand paths: slices against jnp.take, bit for bit
# ---------------------------------------------------------------------------

# small dims under an own bucket rule (multiples of 8): three groups
# that share buckets on both sides, so a bucket holds runs of several
# groups. Round robin over four devices deals EVEN's blocks of four out
# evenly (every device's run of a group has one length); UNEVEN's runs
# differ in length, which is the take fallback
EVEN = ([(9, 8)] * 4 + [(9, 16)] * 4 + [(17, 8)] * 4 + [(9, 8)] * 4)
UNEVEN = [(9, 8), (9, 8), (9, 16), (17, 8), (9, 8)] * 3
# one group dealt out unevenly ahead of an even one in the same bucket:
# the even group's runs start at another row on each device (one dynamic
# slice at the device's offset), the uneven group's reads are gathers
MIXED = [(9, 8)] * 5 + [(9, 16)] * 4


def _bucket8(dim):
    return -(-dim // 8) * 8


def _decomp(plan, method, seed=0):
    rng = np.random.RandomState(seed)
    out = {}
    for name, tail in (('invs', 2), ('evecs', 2), ('evals', 1)):
        if (name == 'invs') != (method == 'cholesky'):
            continue
        out[name] = {
            str(d): jnp.asarray(rng.standard_normal(
                (b.n_rows,) + (d,) * tail).astype(np.float32))
            + (1.0 if name == 'evals' else 0.0)
            for d, b in plan.buckets.items()}
    return out


def _grads(plan, seed=1):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.standard_normal(
        (m.out_dim, m.in_dim)).astype(np.float32)) for m in plan.metas]


def _apply(plan, decomp, grads, method, mesh):
    """The plan's apply: owner-local or replicated by its comm mode,
    under shard_map on a mesh."""
    local = plan.comm_mode == 'pred'

    def fn(decomp, grads):
        if local:
            return engine.compute_pred_local(
                plan, decomp, grads, 0.01, method,
                'batch' if mesh is not None else None)
        return engine.compute_pred_replicated(plan, decomp, grads, 0.01,
                                              method)
    if mesh is None:
        return jax.jit(fn)(decomp, grads)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P('batch') if local else P(), P()),
        out_specs=P(), check_vma=False))(decomp, grads)


@pytest.mark.parametrize('method', ['cholesky', 'eigh'])
@pytest.mark.parametrize('comm_mode', ['pred', 'inverse'])
@pytest.mark.parametrize('dims,ndev,local_reads', [
    (EVEN, 1, (6, 0)), (EVEN, 4, (6, 0)), (UNEVEN, 1, (6, 0)),
    (UNEVEN, 4, (0, 6)), (MIXED, 4, (2, 2))],
    ids=['even-1', 'even-4', 'uneven-1', 'uneven-4-take-fallback',
         'mixed-4-dynamic-slice'])
def test_slices_match_take_bit_for_bit(monkeypatch, dims, ndev, local_reads,
                                       comm_mode, method):
    plan = build_plan(_metas(dims), ndev, comm_mode, bucket_fn=_bucket8)
    rec = pred_layout_record(plan)
    if comm_mode == 'pred':
        # the case is what it says: (in place, gathered)
        assert (rec['pred_operand_slices'],
                rec['pred_operand_takes']) == local_reads
        if dims is MIXED:
            starts = plan.pred_groups[-1].run_starts('a', local=True)
            assert len(set(starts.tolist())) > 1      # the dynamic slice
    elif ndev == 1:
        assert rec['pred_operand_takes'] == 0
    mesh = (Mesh(np.array(jax.devices()[:ndev]), ('batch',))
            if ndev > 1 else None)
    decomp, grads = _decomp(plan, method), _grads(plan)
    got = _apply(plan, decomp, grads, method, mesh)
    # the form this replaced: every read a gather through the tables
    monkeypatch.setattr(plan_lib.PredGroup, 'run_starts',
                        lambda self, side, local: None)
    assert pred_layout_record(plan)['pred_operand_slices'] == 0
    want = _apply(plan, decomp, grads, method, mesh)
    assert len(got) == len(want) == len(dims)
    for g, w, (a, o) in zip(got, want, dims):
        assert g.shape == (o, a)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_slice_and_take_programs_differ_as_said():
    """The in-place path lowers to slices, the fallback to gathers: what
    the counter counts is what the program does."""
    plan = build_plan(_metas(EVEN), 1, 'pred', bucket_fn=_bucket8)
    decomp, grads = _decomp(plan, 'cholesky'), _grads(plan)
    text = jax.jit(lambda d, g: engine.compute_pred_local(
        plan, d, g, 0.01, 'cholesky', None)).lower(decomp, grads).as_text()
    assert 'gather' not in text
