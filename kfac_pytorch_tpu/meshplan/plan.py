"""``MeshFactorPlan``: the axis-aware layer over ``plan.FactorPlan``.

The base ``FactorPlan`` answers "which device of the K-FAC world owns
which factor row"; this layer answers the composed-mesh questions around
it — which mesh axes ARE the K-FAC world, which factor rows additionally
reduce over a tensor axis, and which axes the factor state varies over
(expert, pipeline) and therefore must never be crossed by a factor
collective.

Design invariant (the replan/transport contract): ``base`` is a plain
``FactorPlan`` built by ``plan.build_plan`` over the DATA world with the
same assignment inputs a dp-only run would use — every step-path
consumer (engine tables, cohorts, decomp shard, ``reshard_kfac_state``)
reads ``base`` and is untouched by mesh-awareness. With no non-data axes
the mesh plan degenerates to exactly the dp-only plan (bit-identical
programs, pinned by tests/test_meshplan.py). The extra tensor-axis
reduce enters the step through ONE seam: ``extra_reduce()`` tables
consumed by ``engine.update_factors``.

Per-axis communication accounting: ``comm_volume()`` extends
``FactorPlan.comm_volume`` to a ``{axis: {phase: bytes}}`` dict — the
``'data'`` entry is the base ledger over the (possibly multi-axis) data
world, each tensor axis prices its invariant-row pmean, and expert/
pipeline axes are all-zero BY CONSTRUCTION (the zero-comm trick on the
expert axis; stage-locality on the pipeline axis). scripts/comm_count.py
pins these numbers against the compiled HLO byte-for-byte, attributing
collectives to axes through their replica groups.
"""

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from kfac_pytorch_tpu import plan as base_plan
from kfac_pytorch_tpu.meshplan import axes as axes_mod
from kfac_pytorch_tpu.meshplan import rules as rules_mod
from kfac_pytorch_tpu.meshplan.axes import (AxisSpec, LayerAxisRule,
                                            match_rule)


@dataclasses.dataclass
class MeshFactorPlan:
    """Axis-aware factor layout for one composed mesh."""
    axes: Tuple[AxisSpec, ...]
    base: 'base_plan.FactorPlan'
    rules: Tuple[LayerAxisRule, ...]
    #: the K-FAC world (data + sequence axes), mesh order
    data_axes: Tuple[str, ...]
    tensor_axes: Tuple[str, ...]
    expert_axes: Tuple[str, ...]
    pipeline_axes: Tuple[str, ...]
    #: per layer (base.metas order): the matched rule, or None
    layer_rules: Tuple[Optional[LayerAxisRule], ...]
    #: per tensor axis: {bucket dim: sorted int32 global factor rows
    #: whose statistics pmean over that axis}
    tensor_rows: Dict[str, Dict[int, np.ndarray]]

    @property
    def world_size(self) -> int:
        return axes_mod.world_size(self.axes)

    @property
    def axis_name(self):
        """The K-FAC world's ``axis_name`` (str for one data axis, tuple
        for a multi-axis world) — what ``KFAC.step`` reduces over."""
        if len(self.data_axes) == 1:
            return self.data_axes[0]
        return self.data_axes

    @property
    def mesh_axis_names(self) -> Tuple[str, ...]:
        return tuple(a.name for a in self.axes)

    def spec(self) -> str:
        return axes_mod.format_mesh_spec(self.axes)

    def extra_reduce(self):
        """Static row tables of the tensor-axis statistics reduce, in the
        form ``engine.update_factors(extra_reduce=...)`` consumes:
        a tuple of ``(axis_name, {bucket_key: int32 rows})``.

        ``KFAC_MESH_TP_REDUCE=0`` disables the reduce (trace-time knob):
        tensor-replicated rows are mathematically identical across ranks
        when capture is exact, so the pmean is droppable where drift
        repair is not wanted — the comm ledger then prices zero tensor
        bytes (pass ``tensor_reduce=False`` to :meth:`comm_volume`).
        """
        if os.environ.get('KFAC_MESH_TP_REDUCE', '1') == '0':
            return ()
        out = []
        for ax in self.tensor_axes:
            rows_by_key = {str(bdim): idx
                           for bdim, idx in self.tensor_rows[ax].items()
                           if len(idx)}
            if rows_by_key:
                out.append((ax, rows_by_key))
        return tuple(out)

    def tensor_reduce_rows(self, ax: str) -> int:
        return sum(len(v) for v in self.tensor_rows.get(ax, {}).values())

    def comm_volume(self, *, stats_reduce, method, comm_precision='fp32',
                    comm_mode=None, decomp_shard=None,
                    tensor_reduce=True) -> Dict[str, Dict[str, int]]:
        """Per-axis wire bytes per device per factor-update step.

        Keys: ``'data'`` (the combined data world — the base
        ``FactorPlan.comm_volume`` ledger), each tensor axis name, each
        expert/pipeline axis name. Non-data axes carry only FactorComm;
        expert and pipeline axes are exactly zero in every phase.
        """
        from kfac_pytorch_tpu.parallel import collectives as coll
        zero = {'FactorComm': 0, 'InverseComm': 0, 'PredComm': 0,
                'DecompComm': 0}
        out = {'data': self.base.comm_volume(
            stats_reduce=stats_reduce, method=method,
            comm_precision=comm_precision, comm_mode=comm_mode,
            decomp_shard=decomp_shard)}
        reduce_wire = int(4 * coll.WIRE_COMPRESSION[
            coll.reduce_wire_dtype(comm_precision)])
        for ax in self.tensor_axes:
            v = dict(zero)
            if tensor_reduce:
                # one [k, D, D] all-reduce per bucket over the wire
                # dtype (collectives.pmean_wire); the rows reduced are
                # the SAME on every device (pre data-scatter), so the
                # per-device payload is the full marked-row set
                v['FactorComm'] = sum(
                    len(idx) * bdim * bdim * reduce_wire
                    for bdim, idx in self.tensor_rows[ax].items())
            out[ax] = v
        for ax in self.expert_axes + self.pipeline_axes:
            out[ax] = dict(zero)  # the zero-comm trick, by construction
        return out

    def describe(self) -> str:
        """Human-readable axis-role table (the README's source)."""
        lines = ['| Axis | Role | Size | K-FAC semantics |',
                 '|---|---|---|---|']
        sem = {
            'data': 'K-FAC world: stats reduce + row ownership',
            'sequence': 'K-FAC world (token sharding joins the batch)',
            'tensor': 'invariant factor rows pmean-reduced; slice rows '
                      'local (block-diagonal)',
            'expert': 'factors owner-local per expert — zero factor '
                      'bytes cross this axis',
            'pipeline': 'stage-local capture/ownership — zero factor '
                        'bytes cross this axis',
        }
        for a in self.axes:
            lines.append(f'| `{a.name}` | {a.role} | {a.size} '
                         f'| {sem[a.role]} |')
        return '\n'.join(lines)


def stage_partition(metas: Dict[str, 'base_plan.LayerMeta'],
                    num_stages: int, stage: int,
                    stage_of: Optional[Callable[[str], int]] = None
                    ) -> Dict[str, 'base_plan.LayerMeta']:
    """Stage-local slice of a GLOBAL layer-meta dict: the layers stage
    ``stage`` of ``num_stages`` captures/owns.

    The SPMD gpipe form (parallel/pipeline.py) needs no partition — each
    rank's ``stage_apply`` already traces only its own stage's layers.
    This helper covers harnesses holding the whole model's metas:
    ``stage_of(name) -> stage`` assigns explicitly; the default splits
    call order into ``num_stages`` contiguous chunks (the homogeneous-
    stage convention gpipe requires anyway).
    """
    if not 0 <= stage < num_stages:
        raise ValueError(f'stage {stage} out of range for '
                         f'{num_stages} stages')
    names = list(metas)
    if stage_of is None:
        L = len(names)
        per = -(-L // num_stages)  # ceil

        def stage_of(name, _names=names, _per=per):
            return _names.index(name) // _per
    picked = {n: m for n, m in metas.items() if stage_of(n) == stage}
    if not picked:
        raise ValueError(
            f'stage {stage}/{num_stages} owns no layers '
            f'({len(names)} total) — check the stage_of rule')
    return picked


def build_mesh_plan(metas, mesh_axes, *, comm_mode,
                    assignment='round_robin',
                    distribute_layer_factors=False,
                    bucket_fn=None,
                    rules=None) -> MeshFactorPlan:
    """Build the axis-aware plan: a plain data-world ``FactorPlan`` plus
    the per-axis role tables.

    ``mesh_axes``: a ``'dp2xtp2'`` spec string or parsed AxisSpec tuple.
    ``rules``: per-layer :class:`LayerAxisRule` tuple (default: the
    stock parallel/ families — ``meshplan.rules.default_rules``).
    ``metas`` must already be the LOCAL capture set of this rank's
    non-data position: the per-slice layers of its tensor rank, its own
    expert, its own pipeline stage (use :func:`stage_partition` to slice
    a global dict).
    """
    axes = axes_mod.parse_mesh_spec(mesh_axes)
    rules = tuple(rules) if rules is not None else rules_mod.default_rules()
    world = axes_mod.world_size(axes)
    base = base_plan.build_plan(
        metas, num_devices=world, comm_mode=comm_mode,
        assignment=assignment,
        distribute_layer_factors=distribute_layer_factors,
        bucket_fn=bucket_fn)

    tensor_axes = tuple(a.name for a in axes if a.role == 'tensor')
    expert_axes = tuple(a.name for a in axes if a.role == 'expert')
    pipeline_axes = tuple(a.name for a in axes if a.role == 'pipeline')

    layer_rules = tuple(match_rule(rules, m.name) for m in base.metas)

    # tensor-axis reduce rows: the tp-REPLICATED factor rows (column-A,
    # row-G) of every matched layer, as global stacked-bucket indices
    tensor_rows: Dict[str, Dict[int, list]] = {
        ax: {bdim: [] for bdim in base.bucket_dims} for ax in tensor_axes}
    for i, rule in enumerate(layer_rules):
        if rule is None:
            continue
        ba, ra, bg, rg, _owner = base.layer_rows[i]
        for ax in tensor_axes:
            if 'tensor' in rule.a_roles:
                tensor_rows[ax][ba].append(ra)
            if 'tensor' in rule.g_roles:
                tensor_rows[ax][bg].append(rg)
    tensor_tables = {
        ax: {bdim: np.asarray(sorted(rows), dtype=np.int32)
             for bdim, rows in by_bucket.items()}
        for ax, by_bucket in tensor_rows.items()}

    if expert_axes and not any(
            r is not None and 'expert' in r.local_roles
            for r in layer_rules):
        import warnings
        warnings.warn(
            f'mesh {axes_mod.format_mesh_spec(axes)} has an expert axis '
            f'but no captured layer matches an expert-local rule — the '
            'factors will be treated as expert-replicated state, which '
            'silently averages nothing and replicates everything; pass '
            'rules=moe.axis_rules(...) with your expert module names',
            stacklevel=2)

    return MeshFactorPlan(
        axes=axes, base=base, rules=rules,
        data_axes=axes_mod.data_axis_names(axes),
        tensor_axes=tensor_axes, expert_axes=expert_axes,
        pipeline_axes=pipeline_axes, layer_rules=layer_rules,
        tensor_rows=tensor_tables)
