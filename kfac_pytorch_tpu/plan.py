"""Static factor-distribution plan (host-side, built once at setup).

The reference's scheduling maps layers to ranks and branches per-rank at
runtime (``if rank == rank_a`` — kfac_preconditioner_inv_dp.py:80-90).
XLA wants one uniform program, so the plan instead fixes a *layout*:

- every Kronecker factor ("slot": one layer's A or G) is identity-padded to
  a bucket dim and stacked into one ``[rows, D, D]`` array per bucket;
- rows are ordered device-major (device d owns rows
  ``[d*per_dev, (d+1)*per_dev)``), so sharding axis 0 over the mesh puts
  each factor on its owner and batched eigh/inverse on the local shard *is*
  the distributed computation;
- preconditioning batches layers by their (G-bucket, A-bucket) pair so the
  per-layer triple matmuls run as batched einsums on the MXU;
- within a device's rows of a bucket, slots lie by (pred group, side,
  layer): each group's rows are one contiguous run, so the apply reads the
  stored decompositions where they lie (``PredGroup.run_starts``) instead
  of gathering a copy of them every step;
- dense layers that read one input (``LayerMeta.input_group``: ``q`` / ``k``
  / ``v``, a SwiGLU's ``gate`` / ``up``) keep ONE running-average ``A``
  between them, the first member's; every member still has a row of its
  own for the INVERSE of that ``A``, because the trace-split damping adds
  to it a multiple of the identity that goes by the member's own ``G``.
  Such rows hold no factor and lie last in their bucket
  (``Bucket.n_factor_rows``, ``Bucket.factor_row``).

Identity padding is numerically exact (see ops/linalg.py). The stacked
sharded-eigh layout is the TPU-idiomatic form of tcmm's multiBcast fused
compute+broadcast (reference: packages/tcmm/src/communicator.cpp:75-117).
"""

import collections
import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from kfac_pytorch_tpu.capture import LayerMeta
from kfac_pytorch_tpu.parallel.partition import (
    balanced_assign, round_robin_assign)


#: the MXU's tile edge: a bucket dim is a whole number of these
MXU_TILE = 128


def default_bucket_fn(dim, min_bucket=MXU_TILE):
    """Pad dim → bucket: the next multiple of the MXU tile (128), with
    ``min_bucket`` the floor. A tile is the finest step a TPU layout can
    take, so this is the least padding the stacked GEMMs and the batched
    decompositions can run at: a dense layer's ``in + 1`` (the bias's
    homogeneous coordinate) costs one tile, not the next rung of a ladder
    (BERT-base: 769 → 896, 3,073 → 3,200; ResNet-50's 4,608 stays 4,608).
    ``build_plan`` folds the odd bucket this leaves behind into its
    neighbour (:func:`fold_buckets`)."""
    return max(min_bucket, -(-dim // MXU_TILE) * MXU_TILE)


def fold_buckets(rows_of: Dict[int, int]) -> Dict[int, int]:
    """The default layout's second half: ``{bucket dim: dim it joins}``
    over ``rows_of`` (``{bucket dim: factor rows in it}``, all devices).

    Every bucket is one batched decomposition, and that is a sequential
    chain of 128-wide panels however few rows ride it: the model is
    ``cost(bucket) = (rows + 1) · D³``, one more row's work for the chain.
    Visiting buckets smallest first, a bucket joins the next larger one
    where the model's total falls, i.e. where padding its rows up costs
    less than the chain it saves: ``rows · (D'³ − D³) < D³`` — in practice
    one or two rows, a tile or so under a neighbour (ResNet-50's ``fc``
    input, 2,049 → 2,176, joins the six 2,304s; BERT-base's 60 × 768 stay
    out of 896). Dims and row counts alone: the same model has the same
    buckets on every world size, which ``reshard_kfac_state``'s whole-row
    transport of decompositions relies on."""
    dims = sorted(rows_of)
    rows = dict(rows_of)
    joins = {d: d for d in dims}
    for d, up in zip(dims, dims[1:]):
        if rows[d] * (_slot_cost(up) - _slot_cost(d)) < _slot_cost(d):
            joins[d] = up
            rows[up] += rows.pop(d)
    # a chain of folds lands on its last link
    for d in dims:
        while joins[joins[d]] != joins[d]:
            joins[d] = joins[joins[d]]
    return joins


def _run_start(rows):
    """First row of ``rows`` where they are one ascending run of step 1
    (what ``lax.slice_in_dim`` can read in place), else None."""
    rows = np.asarray(rows)
    if rows.size and np.array_equal(rows, rows[0] + np.arange(rows.size)):
        return int(rows[0])
    return None


@dataclasses.dataclass(frozen=True)
class Slot:
    layer_idx: int
    side: str        # 'A' | 'G'
    dim: int         # true (unpadded) dim
    owner: int
    # an ``A`` slot of a layer that reads another layer's input: the layer
    # whose running average it is inverted from (the slot holds an inverse
    # and no factor); None for a slot with a factor of its own
    factor_of: Optional[int] = None


@dataclasses.dataclass
class Bucket:
    """One stacked factor array: [n_rows, dim, dim], device-major rows."""
    dim: int
    per_dev: int
    n_rows: int
    slot_of_row: List[Optional[Slot]]       # None → dummy pad row
    true_dims: np.ndarray                   # [n_rows]; dummies get dim
    valid: np.ndarray                       # [n_rows] bool
    # the first ``n_factor_rows`` rows hold a running average and its
    # inverse; the rest (input groups, one device) an inverse alone, made
    # of the running average in row ``factor_row[r]``. The factor state is
    # ``[n_factor_rows, dim, dim]``, the decomposition ``[n_rows, ...]``.
    n_factor_rows: int
    factor_row: Optional[np.ndarray] = None  # [n_rows]; None: arange
    # pi-damping mate maps (cholesky variants; rank_a == rank_g layouts):
    # for each row: flat local index (concat over buckets, per device) of
    # the other factor of the same layer, plus dims and side sign.
    # (flat index: over the FACTOR rows of a device, buckets in order)
    mate_flat: Optional[np.ndarray] = None  # [P, per_dev]
    own_dim: Optional[np.ndarray] = None    # [P, per_dev]
    mate_dim: Optional[np.ndarray] = None   # [P, per_dev]
    side_is_a: Optional[np.ndarray] = None  # [P, per_dev] bool

    @property
    def factor_per_dev(self):
        """Factor rows a device (rows that hold an inverse alone are one
        device's)."""
        return self.per_dev - (self.n_rows - self.n_factor_rows)

    @property
    def factor_slots(self):
        return self.slot_of_row[:self.n_factor_rows]


@dataclasses.dataclass
class PredGroup:
    """Layers sharing (G-bucket, A-bucket): batched preconditioning unit."""
    dg: int
    da: int
    layer_idx: np.ndarray       # [M] global layer indices (static order)
    row_a: np.ndarray           # [M] global row in bucket da (inverse's)
    row_g: np.ndarray           # [M] global row in bucket dg
    # comm_pred (owner-computes) maps:
    k_per_dev: int = 0
    local_member: Optional[np.ndarray] = None   # [P, K] index into layer_idx
    local_valid: Optional[np.ndarray] = None    # [P, K] bool
    local_row_a: Optional[np.ndarray] = None    # [P, K] row in local da shard
    local_row_g: Optional[np.ndarray] = None    # [P, K] row in local dg shard
    gathered_row: Optional[np.ndarray] = None   # [M] row in all-gathered P*K

    def row_table(self, side, local):
        """The rows the apply reads for ``side`` ('a' | 'g'): global
        ``[M]`` rows of the bucket (replicated layout), or with ``local``
        the ``[P, K]`` rows of each device's shard — where
        ``side='member'`` names ``local_member``, the device's rows of
        the group's gradient stack."""
        if not local:
            return self.row_a if side == 'a' else self.row_g
        return {'a': self.local_row_a, 'g': self.local_row_g,
                'member': self.local_member}[side]

    def run_starts(self, side, local):
        """Where the apply reads :meth:`row_table`'s rows in place, or
        None where it has to gather them (``jnp.take``): the first row,
        if the replicated layout's rows are one run (always on one
        device); with ``local`` the ``[P]`` first rows of each device's K
        slots, if every slot of every device holds a member (runs of one
        length) and each device's are one run."""
        table = self.row_table(side, local)
        if not local:
            return _run_start(table)
        if not self.local_valid.all():
            return None
        starts = [_run_start(r) for r in table]
        return None if None in starts else np.asarray(starts, np.int32)


@dataclasses.dataclass
class FactorPlan:
    metas: List[LayerMeta]
    num_devices: int
    comm_mode: str                      # 'inverse' | 'pred'
    buckets: Dict[int, Bucket]
    # per layer: (bucket_a, row_a_global, bucket_g, row_g_global, owner);
    # row_a is the row of the layer's ``A`` FACTOR: in an input group the
    # first member's, for every member
    layer_rows: List[Tuple[int, int, int, int, int]]
    pred_groups: List[PredGroup]
    bucket_dims: List[int]              # sorted bucket keys (stable order)
    local_flat_offsets: Dict[int, int]  # bucket dim -> offset into the
                                        # per-device concatenated slot vector
    # the ownership rule this plan was built with — carried so
    # comm_volume can honestly price the OTHER comm mode's layout
    # (a pred plan re-derives whole-layer ownership from the same rule)
    assignment: str = 'round_robin'
    # per layer: the row of bucket_a that holds the inverse of its damped
    # ``A`` (``layer_rows``' row_a but for the later members of an input
    # group)
    inv_row_a: List[int] = dataclasses.field(default_factory=list)

    @property
    def num_layers(self):
        return len(self.metas)

    def a_leaders(self):
        """``[L]``: for every layer the first layer that reads its ``A``
        factor (itself, where it reads its input alone)."""
        first = {}
        return [first.setdefault((ba, ra), i)
                for i, (ba, ra, _, _, _) in enumerate(self.layer_rows)]

    def a_groups(self):
        """``[[layer index, ...], ...]``: the layers of every ``A``
        factor that more than one layer reads."""
        by_leader = {}
        for i, lead in enumerate(self.a_leaders()):
            by_leader.setdefault(lead, []).append(i)
        return [v for v in by_leader.values() if len(v) > 1]

    def comm_volume(self, *, stats_reduce, method, comm_precision='fp32',
                    comm_mode=None, decomp_shard=None):
        """Analytic per-phase collective payload bytes of ONE full
        factor+inverse K-FAC step under this layout — the model the
        HLO-level ledger (scripts/comm_count.py) measures, stated in
        closed form so ``scripts/comm_models.py`` and the drift gate can
        reason about wire-dtype compression without compiling anything.

        Returns ``{'FactorComm', 'InverseComm', 'PredComm',
        'DecompComm'}`` -> bytes:

        - FactorComm: the stats reduce-scatter result payload (MPD
          variants only — each device receives its own row block in the
          reduce wire dtype; int8 floors at bf16,
          collectives.reduce_wire_dtype, and backends without native
          bf16 reduction promote the wire to f32 — the model states the
          intended wire);
        - InverseComm: the decomposition gather (comm_inverse mode —
          eigenbasis + eigenvalues, or inverse factors, in the gather
          wire dtype; int8 adds the [rows] fp32 scale side channel);
        - PredComm: the preconditioned-gradient gather (comm_pred mode);
        - DecompComm: the mesh-sharded decomposition exchange
          (``decomp_shard``: a :class:`DecompShardPlan`) — per step, the
          damped-cohort gather (``P*R_b`` rows out) plus the result
          gather back (``P*S_b`` rows; eigh adds the eigenvalue
          vectors). 0 without a shard plan. Under ``decomp_shard`` the
          shard gathers REPLACE the staggered InverseComm merge gather
          (every shard collective carries the ``kfac.DecompComm`` named
          scope, which is how scripts/comm_count.py pins this number
          byte-for-byte against the compiled HLO).

        Cadence is the caller's: FactorComm recurs every
        ``fac_update_freq`` steps, InverseComm every
        ``kfac_update_freq`` (or 1/F of it per step under stagger);
        DecompComm is per-step (the staggered schedule decomposes one
        cohort every step).

        ``comm_mode`` overrides the plan's own mode (the autotuner's
        advisory comm-mode decision computes BOTH roads from one
        layout); default None = this plan's mode.
        """
        from kfac_pytorch_tpu.parallel import collectives as coll
        coll.check_wire_dtype(comm_precision)
        # one source of truth: payload widths from the collectives
        # layer's own constants (fp32 is 4 bytes; the reduce wire goes
        # through reduce_wire_dtype, which floors int8 at bf16)
        wire = int(4 * coll.WIRE_COMPRESSION[comm_precision])
        reduce_wire = int(4 * coll.WIRE_COMPRESSION[
            coll.reduce_wire_dtype(comm_precision)])
        scale_b = 4 if comm_precision == 'int8' else 0
        factor = inverse = pred = decomp = 0
        if stats_reduce == 'pmean':
            factor = sum(b.per_dev * b.dim * b.dim * reduce_wire
                         for b in self.buckets.values())
        if (comm_mode or self.comm_mode) == 'inverse':
            for b in self.buckets.values():
                inverse += b.n_rows * b.dim * b.dim * wire
                inverse += b.n_rows * scale_b
                if method == 'eigh':
                    inverse += b.n_rows * b.dim * wire + b.n_rows * scale_b
        else:
            pred_owners = None
            for pg in self.pred_groups:
                k = pg.k_per_dev
                if k == 0:
                    # this plan was built for comm_inverse, so the pred
                    # local tables were never laid out — but the OTHER
                    # road's price must still be honest (the autotuner's
                    # comm-mode prior asks for it via the comm_mode
                    # override): K is what the pred layout WOULD pad to.
                    # Re-derive the WHOLE-LAYER ownership a pred plan
                    # builds (pred never distributes factor-wise — a
                    # distributed plan's nominal A-owners clump on even
                    # ranks and would inflate K up to 2x)
                    if pred_owners is None:
                        if self.assignment == 'balanced':
                            costs = [_slot_cost(m.in_dim)
                                     + _slot_cost(m.out_dim)
                                     for m in self.metas]
                            pred_owners = [int(o) for o in
                                           balanced_assign(
                                               costs, self.num_devices)]
                        else:
                            pred_owners = [int(o) for o in
                                           round_robin_assign(
                                               len(self.metas),
                                               self.num_devices)]
                    owners = [pred_owners[int(i)] for i in pg.layer_idx]
                    k = max(1, max(owners.count(d)
                                   for d in range(self.num_devices)))
                rows = self.num_devices * k
                pred += rows * (pg.dg * pg.da * wire + scale_b)
        if decomp_shard is not None:
            # the shard exchange REPLACES the staggered InverseComm
            # merge gather in the compiled program — pricing both would
            # over-count a sharded step by the whole InverseComm payload
            inverse = 0
            P = self.num_devices
            for bdim in self.bucket_dims:
                r_b = decomp_shard.gather_rows(bdim)
                s_b = decomp_shard.shard_rows(bdim)
                # damped-cohort gather out: P*R_b matrices
                decomp += P * r_b * (bdim * bdim * wire + scale_b)
                # result gather back: P*S_b matrices (+ eigh evals)
                decomp += P * s_b * (bdim * bdim * wire + scale_b)
                if method == 'eigh':
                    decomp += P * s_b * (bdim * wire + scale_b)
        return {'FactorComm': factor, 'InverseComm': inverse,
                'PredComm': pred, 'DecompComm': decomp}


def pred_layout_record(plan: 'FactorPlan'):
    """What ``KFAC.setup`` records of the layout as the apply consumes it:

    - ``pred_operand_slices`` / ``pred_operand_takes``: of the apply's
      reads of stored decomposition rows (one per pred group and side),
      how many are served in place and how many by a gather;
    - ``pad_flop_share``: multiplied over needed flop of the apply's
      GEMMs, ``dg²·da + dg·da²`` multiply-adds a row at the bucket dims
      (dummy slots of the owner-local layout included) over the same at
      the layers' true dims;
    - ``stacked_layers``: how many of the layers are slices of a stacked
      leaf (``nn.StackedDense``: an expert each);
    - ``decomp_groups``: ``{str(bucket dim): [groups of rows, panels of
      columns]}`` for the buckets the Cholesky decomposition inverts tile
      by tile (``ops.inverse_tiling``), empty where every bucket goes
      whole;
    - ``decomp_buckets``: ``{str(bucket dim): [n, rows a group, columns a
      panel]}`` for EVERY bucket, ``n`` the matrices the bucket hands back
      an update on a device: what the decomposition's device scopes
      ``decomp.b<D>x<n>`` say (``engine.bucket_scope``), and how each is
      tiled (``[n, n, D]``: whole);
    - ``decomp_task_flop``: ``sum n * D^3`` over the buckets, one
      Cholesky factorisation, one triangular inverse and one triangular
      product at ``D^3 / 3`` flop each: the least any Cholesky-route
      inverse does, whatever implements it;
    - ``decomp_route``: ``{str(bucket dim): 'structured' | 'solves'}`` for
      every bucket: how the Cholesky decomposition gets from the factor to
      the inverse (``ops.inverse_route``: blocked triangular inverse and
      triangular product from a threshold dim on, two solves against a
      dense identity under it);
    - ``decomp_route_flop``: what those routes spend on the same buckets
      (``ops.inverse_route_flop``), to set beside ``decomp_task_flop``;
    - ``a_groups`` / ``a_rows_saved``: ``A`` factors that more than one
      layer reads (input groups), and the factor rows that saves.
    """
    from kfac_pytorch_tpu.ops.linalg import (inverse_route,
                                             inverse_route_flop,
                                             inverse_tiling)
    local = plan.comm_mode == 'pred'
    reads = [pg.run_starts(side, local) is not None
             for pg in plan.pred_groups for side in 'ag']
    padded = true = 0
    for pg in plan.pred_groups:
        rows = plan.num_devices * pg.k_per_dev if local else len(pg.layer_idx)
        padded += rows * (pg.dg ** 2 * pg.da + pg.dg * pg.da ** 2)
        for i in pg.layer_idx:
            m = plan.metas[int(i)]
            true += m.out_dim ** 2 * m.in_dim + m.out_dim * m.in_dim ** 2
    groups, tiles = {}, {}
    for bdim in plan.bucket_dims:
        rows = plan.buckets[bdim].per_dev
        size, width = inverse_tiling(rows, bdim)
        tiles[str(bdim)] = [rows, size, width]
        if (size, width) != (rows, bdim):
            groups[str(bdim)] = [-(-rows // size), bdim // width]
    shared = plan.a_groups()
    return {'pred_operand_slices': sum(reads),
            'pred_operand_takes': len(reads) - sum(reads),
            'pad_flop_share': round(padded / true, 4),
            'stacked_layers': sum(m.kind == 'stacked' for m in plan.metas),
            'decomp_groups': groups,
            'decomp_buckets': tiles,
            'decomp_task_flop': sum(n * int(d) ** 3
                                    for d, (n, _, _) in tiles.items()),
            'decomp_route': {d: inverse_route(int(d)) for d in tiles},
            'decomp_route_flop': sum(inverse_route_flop(n, int(d))
                                     for d, (n, _, _) in tiles.items()),
            'a_groups': len(shared),
            'a_rows_saved': sum(len(g) - 1 for g in shared)}


def _slot_cost(dim):
    # eigh/cholesky cost model ~ D^3 (reference fits a linear+cubic model,
    # scripts/inverse_model.py / comm_models.py:21-50; cubic term dominates)
    return float(dim) ** 3


@dataclasses.dataclass
class CohortPlan:
    """Staggered-refresh layout: every device's valid factor rows
    partitioned into ``num_cohorts`` cohorts, one refreshed per step.

    Instead of decomposing ALL rows every ``kfac_update_freq`` steps (the
    eigh spike), the staggered schedule decomposes cohort ``step % F``
    each step — same per-slot staleness contract (every slot refreshed
    once per F-step window), cost spread evenly. All tables are static
    host arrays indexed by a *traced* cohort scalar at runtime, so one
    compiled program covers every cohort (training.py's variant cache
    does not grow with F).

    Shapes are static per bucket: ``R_b = max over (cohort, device)`` of
    that bucket's cohort size, so off-peak cohorts decompose up to
    ``R_b - count`` padding rows (real factor rows whose results the
    merge discards) — the price of a single uniform program. Padding row
    indices are chosen OUTSIDE the cohort so scatter indices never
    collide with real updates (deterministic merge).
    """
    num_cohorts: int
    # per bucket dim, [F, P, R_b]: local row index (within the device's
    # per_dev rows) to decompose on cohort f / device p
    rows: Dict[int, np.ndarray]
    valid: Dict[int, np.ndarray]        # [F, P, R_b] bool (False = padding)
    # comm_inverse merge tables, flattened device-major to match
    # all_gather_rows output: [F, P*R_b] global row index / validity
    global_rows: Dict[int, np.ndarray]
    global_valid: Dict[int, np.ndarray]
    # cholesky pi-damping lookups for the selected rows, [F, P, R_b]:
    # flat local slot index of the row itself and of its mate factor
    own_flat: Dict[int, np.ndarray]
    mate_flat: Dict[int, np.ndarray]
    cohort_cost: np.ndarray             # [P, F] Σ bucket_dim³ per cohort
    cohort_count: np.ndarray            # [P, F] valid rows per cohort
    # per-bucket cadence overrides (ISSUE 14): the base refresh window
    # this layout was built for and the {bucket dim: stretch} overrides
    # applied on top of it — ``num_cohorts`` is the expanded table
    # window (base * lcm(stretches)); a bucket with stretch m refreshes
    # each of its rows every base*m steps instead of every base steps.
    # Carried so ``KFAC.rebase_cohorts`` can tell "same layout" apart
    # from "same cohort count by coincidence".
    base_freq: int = 0
    bucket_freq: Dict[int, int] = dataclasses.field(default_factory=dict)

    def max_rows_per_step(self):
        """Max over (device, cohort) of genuinely refreshed rows — the
        per-step decomposition row bound the bench records."""
        return int(self.cohort_count.max()) if self.cohort_count.size else 0

    def padded_rows_per_step(self):
        """Static per-device rows decomposed every step (Σ_b R_b) —
        includes the discarded padding rows of off-peak cohorts."""
        return int(sum(t.shape[2] for t in self.rows.values()))

    def total_rows(self):
        """Valid rows per device over a full window (= per-device slots)."""
        return int(self.cohort_count.sum(axis=1).max()) \
            if self.cohort_count.size else 0


def build_cohorts(plan: 'FactorPlan', num_cohorts: int,
                  bucket_freq: Optional[Dict[int, int]] = None) -> CohortPlan:
    """Partition each device's valid factor rows into ``num_cohorts``
    refresh cohorts, balanced by eigh cost ∝ D³.

    Per device: buckets are visited largest-dim first and every row goes
    to the cohort with the lexicographically least (row count, Σ D³) —
    counts stay within ±1 at all times, so the max refreshed rows per
    step is ceil(total_rows / F) (the bench's row budget), while the
    cost tiebreak round-robins each bucket's equal-cost rows over the
    cheapest cohorts (large buckets don't clump onto the step that also
    drew the small-bucket overflow).

    ``bucket_freq`` (ISSUE 14): per-bucket cadence overrides — a
    ``{bucket dim: stretch}`` map where a bucket with stretch ``m``
    refreshes each of its rows every ``num_cohorts * m`` steps instead
    of every ``num_cohorts``. The table window expands to
    ``W = lcm over buckets of num_cohorts * m`` and a row with stretch
    ``m`` appears in ``W / (num_cohorts * m)`` cohorts at stride
    ``num_cohorts * m`` — the greedy balances the SUM of (count, load)
    over a row's appearance set, so the per-step decomposition budget
    stays even while stretched (typically large-D) buckets buy their
    rows out of most steps. With no overrides (the default) this
    reduces bit-identically to the original single-appearance layout.
    """
    import math
    F = max(1, int(num_cohorts))
    P = plan.num_devices
    bucket_freq = {int(k): max(1, int(v))
                   for k, v in (bucket_freq or {}).items()}
    unknown = sorted(set(bucket_freq) - set(plan.bucket_dims))
    if unknown:
        raise ValueError(f'bucket_freq names unknown bucket dims '
                         f'{unknown} (plan has {plan.bucket_dims})')
    stretch = {b: bucket_freq.get(b, 1) for b in plan.bucket_dims}
    W = F
    for m in stretch.values():
        W = math.lcm(W, F * m)
    if W > 128 * F:
        # the tables are static traced constants replicated per cohort:
        # coprime stretches would lcm-explode them (231x for {3,7,11}).
        # KFAC.replan restricts stretches to powers of two <= 64; this
        # backstop keeps direct callers inside the same budget.
        raise ValueError(
            f'bucket_freq window {W} exceeds {128 * F} '
            f'(= 128 * base {F}): use power-of-two stretches '
            f'(got {bucket_freq})')

    def _appearances(bdim, c0):
        return range(c0, W, F * stretch[bdim])

    assign: Dict[int, np.ndarray] = {}
    cohort_cost = np.zeros((P, W), dtype=np.float64)
    cohort_count = np.zeros((P, W), dtype=np.int64)
    for bdim in plan.bucket_dims:
        b = plan.buckets[bdim]
        assign[bdim] = np.full((P, b.per_dev), -1, dtype=np.int64)
    for d in range(P):
        loads = np.zeros(W, dtype=np.float64)
        counts = np.zeros(W, dtype=np.int64)
        for bdim in sorted(plan.bucket_dims, reverse=True):
            b = plan.buckets[bdim]
            period = F * stretch[bdim]
            ks = [k for k in range(b.per_dev) if b.valid[d * b.per_dev + k]]
            for k in ks:
                # a stretched row appears at stride `period`: balance
                # the TOTAL count/load over its whole appearance set
                # (stretch 1 / W == F is exactly the original
                # (counts[c], loads[c], c) key)
                c = min(range(period), key=lambda c0: (
                    sum(counts[a] for a in _appearances(bdim, c0)),
                    sum(loads[a] for a in _appearances(bdim, c0)), c0))
                assign[bdim][d, k] = c
                # cost at the PADDED dim: that is what the batched
                # decomposition actually runs at
                for a in _appearances(bdim, c):
                    loads[a] += _slot_cost(bdim)
                    counts[a] += 1
        cohort_cost[d] = loads
        cohort_count[d] = counts

    def _in_cohort(bdim, c0, f):
        return c0 >= 0 and (f - c0) % (F * stretch[bdim]) == 0

    rows, valid, grows, gvalid, own_flat, mate_flat = {}, {}, {}, {}, {}, {}
    for bdim in plan.bucket_dims:
        b = plan.buckets[bdim]
        counts = np.zeros((W, P), dtype=np.int64)
        for d in range(P):
            for k in range(b.per_dev):
                c = assign[bdim][d, k]
                if c >= 0:
                    for a in _appearances(bdim, c):
                        counts[a, d] += 1
        R = max(1, int(counts.max()))
        r_tbl = np.zeros((W, P, R), dtype=np.int32)
        v_tbl = np.zeros((W, P, R), dtype=bool)
        for f in range(W):
            for d in range(P):
                members = [k for k in range(b.per_dev)
                           if _in_cohort(bdim, assign[bdim][d, k], f)]
                # padding points at a row OUTSIDE this cohort (always
                # exists whenever padding is needed: count < R ≤ per_dev)
                # so real updates and padding writes never collide
                spare = next((k for k in range(b.per_dev)
                              if assign[bdim][d, k] != f), 0)
                for j in range(R):
                    if j < len(members):
                        r_tbl[f, d, j] = members[j]
                        v_tbl[f, d, j] = True
                    else:
                        r_tbl[f, d, j] = spare
        rows[bdim] = r_tbl
        valid[bdim] = v_tbl
        dev_off = (np.arange(P, dtype=np.int32) * b.per_dev)[None, :, None]
        grows[bdim] = (r_tbl + dev_off).reshape(W, P * R)
        gvalid[bdim] = v_tbl.reshape(W, P * R)
        own_flat[bdim] = (r_tbl + plan.local_flat_offsets[bdim]).astype(
            np.int32)
        if b.mate_flat is not None:
            mate_flat[bdim] = np.take_along_axis(
                np.broadcast_to(b.mate_flat[None], (W,) + b.mate_flat.shape),
                r_tbl, axis=2).astype(np.int32)
        else:
            # factor-wise distributed layouts carry no mate maps (eigh
            # only there — the cholesky path never reads this table)
            mate_flat[bdim] = own_flat[bdim]
    return CohortPlan(num_cohorts=W, rows=rows, valid=valid,
                      global_rows=grows, global_valid=gvalid,
                      own_flat=own_flat, mate_flat=mate_flat,
                      cohort_cost=cohort_cost, cohort_count=cohort_count,
                      base_freq=F, bucket_freq=bucket_freq)


@dataclasses.dataclass
class DecompShardPlan:
    """Mesh-sharded decomposition layout: the active cohort's rows
    repartitioned across ALL ``P`` devices, cost-balanced by the same
    D³ model the cohorts use — so the most-loaded owner's cohort stops
    being the whole decomposition critical path while its peers idle.

    The work description is static, like the cohort tables: for cohort
    ``f`` the owners' damped cohort rows are all-gathered (device d's
    slot j of the gather sits at flat index ``d*R_b + j``), device p
    decomposes the ``S_b`` gathered slots named by ``src[f, p]``, the
    results are all-gathered back (device p's slot j at ``p*S_b + j``)
    and each stored row GATHERS its fresh value through ``res_slot`` —
    a pure gather-merge, so there are no scatter collisions to order.

    ``S_b = max over (cohort, device)`` of assigned rows, so the padded
    per-device decomposition work drops from ``Σ_b R_b·D³`` (owner-
    local: every device pays the most-loaded owner's static shape) to
    ``Σ_b S_b·D³ ≈ (1/P)·Σ_b total cohort rows·D³`` — the ~P× critical-
    path claim, bought for the two DecompComm gathers
    (``FactorPlan.comm_volume`` prices them; scripts/comm_count.py
    pins the price against the compiled HLO).
    """
    num_cohorts: int
    # per bucket, [F, P, S_b]: index into the flattened gathered cohort
    # array [P*R_b] that device p decomposes on cohort f
    src: Dict[int, np.ndarray]
    src_valid: Dict[int, np.ndarray]         # [F, P, S_b] bool
    # per bucket, [F, P, S_b]: the STORED global row each src slot
    # refreshes (valid slots only; padding points at row 0) — the warm-
    # seed lookup for the iterative kernels under comm_mode='inverse'
    src_global: Dict[int, np.ndarray]
    # merge gather tables, per bucket [F, n_rows]: where each stored
    # global row's fresh value sits in the result gather [P*S_b]
    # (comm_pred merges reshape to [F, P, per_dev] and take the local
    # block — global rows are device-major)
    res_slot: Dict[int, np.ndarray]
    res_valid: Dict[int, np.ndarray]         # [F, n_rows] bool
    shard_cost: np.ndarray                   # [F, P] Σ D³ assigned
    shard_count: np.ndarray                  # [F, P] valid rows assigned
    # per bucket: R_b, the per-device rows of the damped-cohort gather
    # (the cohort tables' static shape — carried for the byte model)
    cohort_rows: Dict[int, int] = dataclasses.field(default_factory=dict)

    def gather_rows(self, bdim):
        """R_b: per-device rows of the damped-cohort gather."""
        return self.cohort_rows[bdim]

    def shard_rows(self, bdim):
        """S_b: per-device rows decomposed (and gathered back)."""
        return self.src[bdim].shape[2]

    def max_rows_per_step(self):
        """Max over (cohort, device) of genuinely decomposed rows."""
        return int(self.shard_count.max()) if self.shard_count.size else 0

    def padded_rows_per_step(self):
        """Static per-device rows decomposed every step (Σ_b S_b)."""
        return int(sum(t.shape[2] for t in self.src.values()))


def build_decomp_shard(plan: 'FactorPlan',
                       cohorts: CohortPlan) -> DecompShardPlan:
    """Partition every cohort's valid rows across ALL devices — the
    cross-device extension of ``build_cohorts``' D³ cost model.

    The compiled shard program is UNIFORM: every device decomposes
    exactly ``S_b`` (padded) rows of bucket b per step, so the true
    per-device cost is ``Σ_b S_b·D³`` regardless of which rows are
    valid — minimizing the critical path means minimizing every
    ``S_b`` independently, and within a bucket all rows cost the same
    D³. The optimal assignment is therefore per-(cohort, bucket)
    round-robin: ``S_b = ceil(cohort rows of b / P)``, the information-
    theoretic floor, versus owner-local's ``R_b = max over owners`` —
    equal when ownership is balanced, up to P× smaller when one device
    owns the bucket (the real-world trigger: a model whose only large
    factors sit on one owner). A rotating start device spreads the
    remainder rows so per-device VALID row counts stay within 2× of
    the mean across the whole plan (pinned by
    tests/test_decomp_shard.py).
    """
    F, P = cohorts.num_cohorts, plan.num_devices
    shard_cost = np.zeros((F, P), dtype=np.float64)
    shard_count = np.zeros((F, P), dtype=np.int64)
    # (bucket -> per-cohort per-device assigned items)
    assigned: Dict[int, list] = {b: [[[] for _ in range(P)]
                                     for _ in range(F)]
                                 for b in plan.bucket_dims}
    for f in range(F):
        for b_idx, bdim in enumerate(plan.bucket_dims):
            b = plan.buckets[bdim]
            rows, valid = cohorts.rows[bdim][f], cohorts.valid[bdim][f]
            R = rows.shape[1]
            items = []  # (src_flat, global_row), owner-major order
            for d in range(P):
                for j in range(R):
                    if valid[d, j]:
                        items.append((d * R + j,
                                      d * b.per_dev + int(rows[d, j])))
            # rotate the start device per (cohort, bucket) so remainder
            # rows don't pile onto device 0 across buckets/cohorts
            start = (f + b_idx) % P
            for i, item in enumerate(items):
                p = (start + i) % P
                assigned[bdim][f][p].append(item)
                shard_cost[f, p] += _slot_cost(bdim)
                shard_count[f, p] += 1

    src, src_valid, src_global, res_slot, res_valid = {}, {}, {}, {}, {}
    for bdim in plan.bucket_dims:
        b = plan.buckets[bdim]
        S = max(1, max(len(assigned[bdim][f][p])
                       for f in range(F) for p in range(P)))
        s_tbl = np.zeros((F, P, S), dtype=np.int32)
        v_tbl = np.zeros((F, P, S), dtype=bool)
        g_tbl = np.zeros((F, P, S), dtype=np.int32)
        slot_tbl = np.zeros((F, b.n_rows), dtype=np.int32)
        rvalid_tbl = np.zeros((F, b.n_rows), dtype=bool)
        for f in range(F):
            for p in range(P):
                for j, (src_flat, grow) in enumerate(assigned[bdim][f][p]):
                    s_tbl[f, p, j] = src_flat
                    v_tbl[f, p, j] = True
                    g_tbl[f, p, j] = grow
                    slot_tbl[f, grow] = p * S + j
                    rvalid_tbl[f, grow] = True
                # padding slots keep src 0 (a real gathered matrix —
                # decomposable; the result is never gathered into any
                # stored row because no res_slot points at it)
        src[bdim] = s_tbl
        src_valid[bdim] = v_tbl
        src_global[bdim] = g_tbl
        res_slot[bdim] = slot_tbl
        res_valid[bdim] = rvalid_tbl
    return DecompShardPlan(
        num_cohorts=F, src=src, src_valid=src_valid,
        src_global=src_global, res_slot=res_slot, res_valid=res_valid,
        shard_cost=shard_cost, shard_count=shard_count,
        cohort_rows={b: cohorts.rows[b].shape[2]
                     for b in plan.bucket_dims})


def same_row_layout(plan_a: 'FactorPlan', plan_b: 'FactorPlan') -> bool:
    """True when the two plans place every factor row identically —
    same world size, same buckets (dims, per-device rows, validity) and
    the same per-layer row map. When this holds, a rebuilt plan's state
    arrays are layout-compatible with the old plan's and a replan can
    carry them VERBATIM (the applied comm-mode switch: only the traced
    programs change, not one byte of state). comm_mode itself is NOT
    part of the row layout — only ownership (which both plans derive
    from the same assignment inputs) is."""
    if plan_a.num_devices != plan_b.num_devices:
        return False
    if plan_a.bucket_dims != plan_b.bucket_dims:
        return False
    for bdim in plan_a.bucket_dims:
        a, b = plan_a.buckets[bdim], plan_b.buckets[bdim]
        if (a.per_dev, a.n_rows, a.n_factor_rows) != (
                b.per_dev, b.n_rows, b.n_factor_rows):
            return False
        if not np.array_equal(a.valid, b.valid):
            return False
        if not np.array_equal(a.true_dims, b.true_dims):
            return False
    return (plan_a.layer_rows == plan_b.layer_rows
            and plan_a.inv_row_a == plan_b.inv_row_a)


def build_plan(metas: Dict[str, LayerMeta], num_devices: int, comm_mode: str,
               assignment: str = 'round_robin',
               distribute_layer_factors: bool = False,
               bucket_fn: Optional[Callable[[int], int]] = None):
    """Build the static layout.

    Ownership parity: round-robin layer→rank (kfac_preconditioner_inv.py:
    62-77); with ``distribute_layer_factors`` (comm_mode='inverse' only) the
    interleaved A/G slot round-robin of eigen.py:75-94; 'balanced' uses the
    LPT scheduler (the dp_block_partition.py upgrade).

    ``bucket_fn=None`` is the default layout: :func:`default_bucket_fn`'s
    tile rounding, then :func:`fold_buckets`. A caller's own ``bucket_fn``
    states the buckets it wants and is taken as it is.

    Layers of one ``LayerMeta.input_group`` keep ONE ``A`` factor (in the
    slot of the first of them in ``metas``); the others' ``A`` slots hold
    the inverse alone (``Slot.factor_of``) and lie last in their bucket.
    On one device only: with more, ownership goes by layer and a group's
    slots would have to move together. Which configurations keep an ``A``
    a layer instead is ``KFAC.setup``'s to say (``KFAC._plan_metas``, the
    one place that strips the groups: :func:`without_input_groups`); here
    such metas are refused.
    """
    meta_list = list(metas.values())
    if (num_devices > 1 or distribute_layer_factors) and any(
            m.input_group is not None for m in meta_list):
        raise ValueError(
            'layers that read one input share an A factor on one device '
            'only: build this plan from without_input_groups(metas)')
    L = len(meta_list)
    P = num_devices
    # leader[i]: the layer whose ``A`` factor layer i reads (itself, alone)
    first_of: Dict[str, int] = {}
    leader = [i if m.input_group is None
              else first_of.setdefault(m.input_group, i)
              for i, m in enumerate(meta_list)]
    if comm_mode == 'pred' and distribute_layer_factors:
        raise ValueError(
            'factor-wise distribution requires communicating inverses '
            '(reference asserts rank_a == rank_g for comm_pred, '
            'kfac_preconditioner_inv.py:169)')

    # --- ownership ------------------------------------------------------
    if distribute_layer_factors:
        # interleaved slot sequence [A0, G0, A1, G1, ...]
        dims = []
        for m in meta_list:
            dims.extend([m.in_dim, m.out_dim])
        if assignment == 'balanced':
            owners = balanced_assign([_slot_cost(d) for d in dims], P)
        else:
            owners = round_robin_assign(2 * L, P)
        slot_owner = [(int(owners[2 * i]), int(owners[2 * i + 1]))
                      for i in range(L)]
        layer_owner = [a for a, _ in slot_owner]  # nominal (unused for pred)
    else:
        if assignment == 'balanced':
            costs = [_slot_cost(m.in_dim) + _slot_cost(m.out_dim)
                     for m in meta_list]
            owners = balanced_assign(costs, P)
        else:
            owners = round_robin_assign(L, P)
        layer_owner = [int(o) for o in owners]
        slot_owner = [(o, o) for o in layer_owner]

    # --- buckets --------------------------------------------------------
    slots: List[Slot] = []
    for i, m in enumerate(meta_list):
        oa, og = slot_owner[i]
        slots.append(Slot(i, 'A', m.in_dim, oa,
                          None if leader[i] == i else leader[i]))
        slots.append(Slot(i, 'G', m.out_dim, og))

    if bucket_fn is None:
        tiled = {s.dim: default_bucket_fn(s.dim) for s in slots}
        joins = fold_buckets(
            collections.Counter(tiled[s.dim] for s in slots))

        def bucket_fn(dim):
            return joins[tiled[dim]]

    # pred groups: layers sharing (G-bucket, A-bucket), in key order; a
    # layer whose ``A`` row holds an inverse alone (it lies with its like
    # at the bucket's end) in a group of such layers
    group_key = [(bucket_fn(m.out_dim), bucket_fn(m.in_dim),
                  leader[i] != i) for i, m in enumerate(meta_list)]
    group_idx = {k: g for g, k in enumerate(sorted(set(group_key)))}

    by_bucket: Dict[int, List[Slot]] = {}
    for s in slots:
        by_bucket.setdefault(bucket_fn(s.dim), []).append(s)
    # a device's rows of a bucket lie by (pred group, side, layer): every
    # group's rows are then one contiguous run, in the group's own member
    # order, and the apply can read them in place
    for members in by_bucket.values():
        members.sort(key=lambda s: (s.factor_of is not None,
                                    group_idx[group_key[s.layer_idx]],
                                    s.side, s.layer_idx))

    buckets: Dict[int, Bucket] = {}
    slot_row: Dict[Tuple[int, str], Tuple[int, int]] = {}  # → (bucket, row)
    for bdim in sorted(by_bucket):
        members = by_bucket[bdim]
        rows_by_dev: List[List[Slot]] = [[] for _ in range(P)]
        for s in members:
            rows_by_dev[s.owner].append(s)
        per_dev = max(1, max(len(r) for r in rows_by_dev))
        n_rows = P * per_dev
        slot_of_row: List[Optional[Slot]] = [None] * n_rows
        true_dims = np.full(n_rows, bdim, dtype=np.int32)
        valid = np.zeros(n_rows, dtype=bool)
        for d in range(P):
            for k, s in enumerate(rows_by_dev[d]):
                r = d * per_dev + k
                slot_of_row[r] = s
                true_dims[r] = s.dim
                valid[r] = True
                slot_row[(s.layer_idx, s.side)] = (bdim, r)
        buckets[bdim] = Bucket(
            dim=bdim, per_dev=per_dev, n_rows=n_rows,
            slot_of_row=slot_of_row, true_dims=true_dims, valid=valid,
            n_factor_rows=n_rows - sum(s.factor_of is not None
                                       for s in members))
    for b in buckets.values():
        if b.n_factor_rows != b.n_rows:
            b.factor_row = np.asarray(
                [r if s.factor_of is None else slot_row[(s.factor_of, 'A')][1]
                 for r, s in enumerate(b.slot_of_row)], np.int32)

    bucket_dims = sorted(buckets)
    # flat local-slot indexing: per device, concat of its local rows over
    # buckets in bucket_dims order
    local_flat_offsets = {}
    off = 0
    for bdim in bucket_dims:
        local_flat_offsets[bdim] = off
        off += buckets[bdim].factor_per_dev

    # --- pi-damping mate maps (only meaningful when rank_a == rank_g) ---
    if not distribute_layer_factors:
        for bdim in bucket_dims:
            b = buckets[bdim]
            mate_flat = np.zeros((P, b.per_dev), dtype=np.int32)
            own_dim = np.full((P, b.per_dev), bdim, dtype=np.int32)
            mate_dim = np.full((P, b.per_dev), bdim, dtype=np.int32)
            side_is_a = np.ones((P, b.per_dev), dtype=bool)
            for d in range(P):
                for k in range(b.per_dev):
                    r = d * b.per_dev + k
                    s = b.slot_of_row[r]
                    self_flat = local_flat_offsets[bdim] + k
                    if s is None:
                        mate_flat[d, k] = self_flat  # dummy: pi = 1
                        continue
                    # a layer's ``G`` is damped against the ``A`` factor
                    # it reads, an ``A`` inverse against the layer's ``G``
                    mb, mr = (slot_row[(s.layer_idx, 'G')] if s.side == 'A'
                              else slot_row[(leader[s.layer_idx], 'A')])
                    md = mr // buckets[mb].per_dev
                    assert md == d, 'mate slot must be co-located'
                    mate_flat[d, k] = (local_flat_offsets[mb]
                                       + mr - md * buckets[mb].per_dev)
                    own_dim[d, k] = s.dim
                    mate_dim[d, k] = buckets[mb].true_dims[mr]
                    side_is_a[d, k] = s.side == 'A'
            b.mate_flat, b.own_dim = mate_flat, own_dim
            b.mate_dim, b.side_is_a = mate_dim, side_is_a

    # --- per-layer row lookup ------------------------------------------
    layer_rows = []
    for i, m in enumerate(meta_list):
        ba, ra = slot_row[(leader[i], 'A')]
        bg, rg = slot_row[(i, 'G')]
        layer_rows.append((ba, ra, bg, rg, layer_owner[i]))
    inv_row_a = [slot_row[(i, 'A')][1] for i in range(L)]

    # --- pred groups ----------------------------------------------------
    groups: Dict[Tuple[int, int, bool], List[int]] = {}
    for i, key in enumerate(group_key):
        groups.setdefault(key, []).append(i)

    pred_groups = []
    for (dg, da, _), lidx in sorted(groups.items()):
        lidx = np.asarray(lidx, dtype=np.int32)
        row_a = np.asarray([slot_row[(i, 'A')][1] for i in lidx],
                           dtype=np.int32)
        row_g = np.asarray([layer_rows[i][3] for i in lidx], dtype=np.int32)
        pg = PredGroup(dg=dg, da=da, layer_idx=lidx, row_a=row_a, row_g=row_g)
        if comm_mode == 'pred':
            members_by_dev: List[List[int]] = [[] for _ in range(P)]
            for mpos, i in enumerate(lidx):
                members_by_dev[layer_rows[i][4]].append(mpos)
            K = max(1, max(len(v) for v in members_by_dev))
            local_member = np.zeros((P, K), dtype=np.int32)
            local_valid = np.zeros((P, K), dtype=bool)
            local_row_a = np.zeros((P, K), dtype=np.int32)
            local_row_g = np.zeros((P, K), dtype=np.int32)
            gathered_row = np.zeros(len(lidx), dtype=np.int32)
            for d in range(P):
                for k, mpos in enumerate(members_by_dev[d]):
                    i = int(lidx[mpos])
                    ba, _, bg, rg, owner = layer_rows[i]
                    ra = slot_row[(i, 'A')][1]
                    local_member[d, k] = mpos
                    local_valid[d, k] = True
                    local_row_a[d, k] = ra - d * buckets[ba].per_dev
                    local_row_g[d, k] = rg - d * buckets[bg].per_dev
                    gathered_row[mpos] = d * K + k
            pg.k_per_dev = K
            pg.local_member = local_member
            pg.local_valid = local_valid
            pg.local_row_a = local_row_a
            pg.local_row_g = local_row_g
            pg.gathered_row = gathered_row
        pred_groups.append(pg)

    return FactorPlan(metas=meta_list, num_devices=P, comm_mode=comm_mode,
                      buckets=buckets, layer_rows=layer_rows,
                      pred_groups=pred_groups, bucket_dims=bucket_dims,
                      local_flat_offsets=local_flat_offsets,
                      assignment=assignment, inv_row_a=inv_row_a)


def without_input_groups(metas):
    """``metas`` (a ``{name: LayerMeta}`` dict) with no layer in an input
    group: the plan built from it keeps an ``A`` a layer."""
    return {k: dataclasses.replace(m, input_group=None)
            if m.input_group is not None else m for k, m in metas.items()}
