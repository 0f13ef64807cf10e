"""Long-context causal-LM trainer: sequence-parallel ring attention + K-FAC.

Capability beyond the reference (SURVEY.md §5.7 — the reference has no
context/sequence parallelism and tops out at 384 tokens): trains
``models.TransformerLM`` with the *sequence* axis sharded over a mesh axis
(ring attention or Ulysses all-to-all, ``parallel/ring_attention.py``) and
an optional data axis — a ('data', 'seq') 2-D mesh. DP-KFAC factor
statistics stay owner-local per shard exactly as in the reference's DP
variants (kfac_preconditioner_inv_dp.py:75-90).

Dataset: a plain-text corpus via ``--data`` or a synthetic Markov corpus
so the entrypoint runs in a dataset-free container (same convention as
examples/wikitext_rnn.py).

Example (virtual mesh smoke):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/longcontext_lm.py \
      --seq-len 512 --seq-devices 4 --data-devices 2 --epochs 1

Composed-mesh form of the same run (meshplan grammar; axis-aware K-FAC
derives the data/sequence worlds from the spec):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/longcontext_lm.py \
      --seq-len 512 --kfac-mesh dp2xsp4 --epochs 1

``--model sparse-decoder`` trains ``models.sparse_decoder_lm`` instead: one
chip's share of a decoder with latent attention and sigmoid-routed experts
(kanana-2-30b-a3b's published widths; ``--n-layer``, ``--n-head`` heads
held, ``--experts-held``, the vocabulary and ``--d-model`` are what is cut
to fit), flat tokens, on one device; its log line adds the router's
counters (``moe/dropped`` must stay 0):
  python examples/longcontext_lm.py --model sparse-decoder --kfac-name \
      inverse_dp --seq-len 4096 --batch-size 1 --n-layer 5 --n-head 4 \
      --d-model 2048 --experts-held 8 --synthetic-vocab 16032 --epochs 1

``--model mixed-decoder`` trains ``models.mixed_decoder_lm``: one chip's
share of a decoder whose layers differ in kind (window and full attention
mixed, grouped-query, gated, QK-normed; four norms a block; sigmoid-routed
top-8 experts with one shared: Trinity-Mini's published widths). ``--n-layer``
layers held (one leading dense layer, then whole periods of three window
layers and one full), ``--n-head`` query heads held with the key/value
heads they read (8 query heads a key/value head), the rest as above:
  python examples/longcontext_lm.py --model mixed-decoder --kfac-name \
      inverse_dp --seq-len 4096 --batch-size 1 --n-layer 5 --n-head 8 \
      --d-model 2048 --experts-held 8 --synthetic-vocab 25024 --epochs 1

``--model hybrid-decoder`` trains ``models.hybrid_decoder_lm``: one chip's
share of a decoder whose layers are a recurrence or a softmax (Kimi Delta
Attention, a gated delta-rule linear attention run as a chunked scan, and
latent attention without positions, 3:1; sigmoid-routed top-8 experts of
256 with one shared: Kimi-Linear-48B-A3B's published widths). ``--n-layer``
layers held (one leading dense KDA layer, then whole periods of three KDA
layers and one latent), ``--n-head`` heads held of each attention,
``--kda-chunk`` tokens a chunk of the scan, ``--ffn-block`` the width of a
block of the dense layer's K-FAC factors. K-FAC's split of a
KDA layer: Kronecker-factored are its nine projections, all outside the
scan (``q_proj`` / ``k_proj`` / ``v_proj`` / ``f_a_proj`` / ``g_a_proj`` /
``b_proj`` share one ``A``; ``f_b_proj``, ``g_b_proj`` with its bias,
``o_proj``); first-order are the three short convolutions, ``A_log``,
``dt_bias`` and the output norm. The log line adds ``kda/log_decay_min`` and
``kda/state_absmax`` beside the router's counters:
  python examples/longcontext_lm.py --model hybrid-decoder --kfac-name \
      inverse_dp --seq-len 1024 --batch-size 1 --n-layer 5 --n-head 8 \
      --d-model 2304 --experts-held 8 --synthetic-vocab 20480 --epochs 1
"""

import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, models, training
from kfac_pytorch_tpu.utils import metrics


def parse_args():
    p = argparse.ArgumentParser(
        description='Long-context TransformerLM + DP-KFAC (TPU)')
    p.add_argument('--data', default=None)
    p.add_argument('--seq-len', type=int, default=2048)
    p.add_argument('--batch-size', type=int, default=4,
                   help='global batch (sequences per step)')
    p.add_argument('--epochs', type=int, default=3)
    p.add_argument('--steps-per-epoch', type=int, default=100)
    p.add_argument('--model', choices=['transformer', 'sparse-decoder',
                                       'mixed-decoder', 'hybrid-decoder'],
                   default='transformer',
                   help='transformer: models.transformer_lm; '
                        'sparse-decoder: models.sparse_decoder_lm (latent '
                        'attention, routed experts); mixed-decoder: '
                        'models.mixed_decoder_lm (window and full '
                        'attention mixed, grouped-query, gated; routed '
                        'experts); hybrid-decoder: models.hybrid_decoder_lm '
                        '(a gated delta-rule recurrence and latent '
                        'attention without positions mixed; routed '
                        'experts)')
    p.add_argument('--experts-held', type=int, default=8,
                   help='sparse-, mixed-, hybrid-decoder: routed experts '
                        'this chip holds (ids 0..n-1 of the published 128; '
                        'hybrid-decoder: of 256)')
    p.add_argument('--expert-capacity', type=int, default=None,
                   help='sparse-, mixed-, hybrid-decoder: rows of a held '
                        'expert\'s buffer (default: four times the '
                        'expected load)')
    p.add_argument('--kda-chunk', type=int, default=64,
                   help='hybrid-decoder: tokens a chunk of the gated '
                        'delta rule\'s scan (16-64)')
    p.add_argument('--ffn-block', type=int, default=2304,
                   help='hybrid-decoder: width of a block of the dense '
                        'SwiGLU\'s K-FAC factors; has to divide its 9,216')
    p.add_argument('--n-layer', type=int, default=4)
    p.add_argument('--n-head', type=int, default=8)
    p.add_argument('--d-model', type=int, default=256)
    p.add_argument('--seq-impl', choices=['ring', 'ulysses'],
                   default='ring')
    p.add_argument('--seq-devices', type=int, default=1,
                   help="size of the 'seq' mesh axis")
    p.add_argument('--data-devices', type=int, default=1,
                   help="size of the 'data' mesh axis")
    p.add_argument('--kfac-mesh',
                   default=os.environ.get('KFAC_MESH') or None,
                   metavar='SPEC',
                   help="composed-mesh spec in the meshplan grammar "
                        "('dp2xsp4', 'dp2xsp2xtp1', ...) — overrides "
                        "--data-devices/--seq-devices and routes K-FAC "
                        "through the axis-aware mesh plan "
                        "(parallel/mesh.make_composed_mesh). Axes beyond "
                        "data/sequence must be size 1 here: this workload "
                        "shards batch and sequence only")
    p.add_argument('--base-lr', type=float, default=3e-2)
    p.add_argument('--kfac-update-freq', type=int, default=10)
    p.add_argument('--kfac-basis-update-freq', type=int, default=0,
                   help='full eigendecomposition cadence; intermediate '
                        'inverse updates refresh eigenvalues in the '
                        'retained basis (0 = always full)')
    p.add_argument('--kfac-warm-start', action='store_true',
                   help='warm-start decompositions from the stored one: '
                        'eigen variants track the previous eigenbasis '
                        '(KFAC_EIGH_IMPL=subspace|auto|jacobi), Cholesky '
                        'variants Newton-Schulz-iterate the previous '
                        'inverse')
    p.add_argument('--kfac-comm-precision',
                   default=os.environ.get('KFAC_COMM_PRECISION', 'fp32'),
                   choices=['fp32', 'bf16', 'int8'],
                   help='wire dtype of the K-FAC factor collectives '
                        '(default from $KFAC_COMM_PRECISION): bf16 '
                        'halves, int8 quarters the gather payloads; '
                        'lossy stats reduces carry an error-feedback '
                        'residual; the gradient allreduce is never '
                        'compressed (see README "Communication '
                        'compression")')
    p.add_argument('--kfac-comm-mode',
                   default=os.environ.get('KFAC_COMM_MODE') or None,
                   choices=['inverse', 'pred'],
                   help='override the variant\'s comm mode (default from '
                        '$KFAC_COMM_MODE; unset = the variant default): '
                        "'inverse' gathers decompositions once per "
                        "refresh, 'pred' gathers preconditioned "
                        'gradients every step. A runtime knob since the '
                        'live replanning path — with --kfac-autotune the '
                        'controller probes the other mode and applies a '
                        'winning switch mid-run via KFAC.replan (see '
                        'README "Live replanning")')
    p.add_argument('--kfac-comm-prefetch', action='store_true',
                   help='comm_inverse variants only: publish each '
                        "inverse update's gathered decomposition for "
                        'the NEXT step so the gather overlaps the pred '
                        'einsums (one step of decomposition staleness)')
    p.add_argument('--kfac-capture-impl',
                   default=os.environ.get('KFAC_CAPTURE_IMPL') or None,
                   choices=['xla', 'pallas', 'auto'],
                   help='capture kernels (default from '
                        '$KFAC_CAPTURE_IMPL; unset = the legacy '
                        'capture path, hidden from the autotuner): '
                        'xla = patch-extract + factor GEMM + EMA as '
                        'separate XLA ops; pallas = the fused Pallas '
                        'kernels (no HBM patch matrix, EMA / wire-'
                        'quantize folded into the epilogues); auto = '
                        'the fused rung. An explicit value makes this '
                        'a live autotuner ladder rung (see README '
                        '"Capture hot path")')
    p.add_argument('--kfac-decomp-impl',
                   default=os.environ.get('KFAC_DECOMP_IMPL') or None,
                   choices=['xla', 'auto', 'jacobi', 'subspace',
                            'newton_schulz'],
                   help='decomposition kernel (default from '
                        '$KFAC_DECOMP_IMPL; unset = the legacy '
                        'KFAC_EIGH_IMPL env contract): xla = cold '
                        'QDWH eigh / Cholesky; subspace|jacobi (eigh '
                        'variants) and newton_schulz (Cholesky '
                        'variants) are warm iterative kernels that '
                        'replace the decomposition with GEMMs; auto '
                        'picks the warm kernel for the variant. An '
                        'explicit value makes this a live autotuner '
                        'ladder rung (see README "Attacking the '
                        'decomposition wall")')
    p.add_argument('--kfac-decomp-shard', action='store_true',
                   default=os.environ.get('KFAC_DECOMP_SHARD', '') == '1',
                   help='mesh-sharded decomposition: repartition each '
                        'refresh cohort cost-balanced across ALL '
                        'devices instead of owner-local (~P x shorter '
                        'decomposition critical path for two bounded '
                        'DecompComm gathers per step; implies '
                        '--kfac-stagger semantics)')
    p.add_argument('--kfac-autotune', action='store_true',
                   default=os.environ.get('KFAC_AUTOTUNE', '') == '1',
                   help='closed-loop autotuning: one online controller '
                        'hill-climbs kfac/fac_update_freq and the comm '
                        'wire dtype from measured step times through '
                        'the knob arbiter (defaults on when '
                        '$KFAC_AUTOTUNE=1; see README "Closed-loop '
                        'autotuning")')
    p.add_argument('--kfac-cov-update-freq', type=int, default=1)
    p.add_argument('--kfac-name', default='eigen_dp',
                   choices=list(kfac.KFAC_VARIANTS))
    p.add_argument('--damping', type=float, default=0.003)
    p.add_argument('--stat-decay', type=float, default=0.95)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--vocab-limit', type=int, default=8192)
    p.add_argument('--synthetic-vocab', type=int, default=512)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--speed', action='store_true')
    p.add_argument('--log-dir', default='./logs')
    p.add_argument('--tb-dir', default=None,
                   help='TensorBoard scalar summaries (rank 0)')
    # observability (kfac_pytorch_tpu/obs/)
    p.add_argument('--trace', default=None, metavar='DIR',
                   help='write Chrome-trace spans to DIR/trace-host<i>.'
                        'jsonl and epoch metric snapshots to DIR/'
                        'metrics.jsonl (defaults to $KFAC_TRACE_DIR '
                        'when set); merge with kfac-obs')
    p.add_argument('--prom-file',
                   default=os.environ.get('KFAC_PROM_FILE'),
                   metavar='PATH',
                   help='export the metrics registry as a Prometheus '
                        'textfile at PATH after every epoch (rank 0; '
                        'defaults to $KFAC_PROM_FILE — the training '
                        'service sets it per tenant job, and the path '
                        'is namespaced by tenant/job id either way)')
    return p.parse_args()


def load_corpus(args):
    if args.data and os.path.exists(args.data):
        with open(args.data) as f:
            words = f.read().split()
        from collections import Counter
        vocab = {w: i for i, (w, _) in enumerate(
            Counter(words).most_common(args.vocab_limit - 1))}
        vocab['<unk>'] = len(vocab)
        ids = np.asarray([vocab.get(w, vocab['<unk>']) for w in words],
                         np.int32)
        return ids, len(vocab)
    rng = np.random.RandomState(args.seed)
    V = args.synthetic_vocab
    trans = rng.dirichlet(np.ones(V) * 0.05, size=V)
    cum = trans.cumsum(axis=1)
    n = max(200000, args.batch_size * args.seq_len * 8)
    u = rng.rand(n)
    ids = np.zeros(n, np.int32)
    for i in range(1, n):  # inverse-CDF sampling: O(log V) per token
        ids[i] = np.searchsorted(cum[ids[i - 1]], u[i])
    return np.minimum(ids, V - 1), V


def sample_batches(ids, args, rng):
    L = args.seq_len
    for _ in range(args.steps_per_epoch):
        starts = rng.randint(0, len(ids) - L - 1, args.batch_size)
        toks = np.stack([ids[s:s + L] for s in starts])
        labs = np.stack([ids[s + 1:s + L + 1] for s in starts])
        yield {'input': jnp.asarray(toks), 'label': jnp.asarray(labs)}


def main():
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    args = parse_args()
    from kfac_pytorch_tpu.utils.runlog import setup_run_logging
    log, _ = setup_run_logging(
        args.log_dir, f'longctx_L{args.seq_len}', args.kfac_name,
        f'bs{args.batch_size}', f'sd{args.seq_devices}',
        f'dd{args.data_devices}')
    log.info('args: %s', vars(args))

    ids, vocab = load_corpus(args)
    split = int(len(ids) * 0.9)
    train_ids, val_ids = ids[:split], ids[split:]
    mesh_axes = None
    if args.kfac_mesh:
        from kfac_pytorch_tpu import meshplan
        mesh_axes = meshplan.parse_mesh_spec(args.kfac_mesh)
        bad = [a.name for a in mesh_axes
               if a.role not in ('data', 'sequence') and a.size > 1]
        if bad:
            raise SystemExit(
                f'--kfac-mesh: axes {bad} need model-level sharding this '
                'workload does not implement (batch/sequence only); use '
                'size-1 placeholders or drop them')
        dsz = [a.size for a in mesh_axes if a.role == 'data']
        ssz = [a.size for a in mesh_axes if a.role == 'sequence']
        if len([s for s in dsz if s > 1]) > 1 or \
                len([s for s in ssz if s > 1]) > 1:
            raise SystemExit('--kfac-mesh: at most one data and one '
                             'sequence axis of size > 1 here')
        nd = int(np.prod(dsz)) if dsz else 1
        ns = int(np.prod(ssz)) if ssz else 1
        args.data_devices, args.seq_devices = nd, ns
        log.info('composed mesh %s: data world %d x seq %d',
                 meshplan.format_mesh_spec(mesh_axes), nd, ns)
    else:
        nd, ns = args.data_devices, args.seq_devices
    ndev = nd * ns
    devices = jax.devices()
    assert len(devices) >= ndev, (len(devices), ndev)
    assert args.seq_len % max(ns, 1) == 0
    assert args.batch_size % max(nd, 1) == 0

    if mesh_axes is not None:
        seq_axis = next((a.name for a in mesh_axes
                         if a.role == 'sequence' and a.size > 1), None)
        data_axis = next((a.name for a in mesh_axes
                          if a.role == 'data' and a.size > 1), None)
    else:
        seq_axis = 'seq' if ns > 1 else None
        data_axis = 'data' if nd > 1 else None
    step_kw = {}
    if args.model == 'sparse-decoder':
        assert ndev == 1, 'the sparse decoder trains on one device here'
        tokens = args.batch_size * args.seq_len
        capacity = args.expert_capacity or -(-4 * tokens * 6 // 128)
        model = twin = models.sparse_decoder_lm(
            vocab_size=vocab, hidden_size=args.d_model,
            num_layers=args.n_layer, head_ids=tuple(range(args.n_head)),
            expert_ids=tuple(range(args.experts_held)),
            expert_capacity=capacity, dtype=jnp.bfloat16)
        # the model's counters ride in the state and in the step's metrics
        step_kw = dict(extra_mutable=(capture.COUNTERS,))
    elif args.model == 'mixed-decoder':
        assert ndev == 1, 'the mixed decoder trains on one device here'
        tokens = args.batch_size * args.seq_len
        capacity = args.expert_capacity or -(-4 * tokens * 8 // 128)
        # a key/value head serves 8 query heads (32 / 4 as published)
        model = twin = models.mixed_decoder_lm(
            vocab_size=vocab, hidden_size=args.d_model,
            layer_types=models.held_layer_types(args.n_layer),
            first_k_dense=1, q_head_ids=tuple(range(args.n_head)),
            kv_head_ids=tuple(range(-(-args.n_head // 8))),
            expert_ids=tuple(range(args.experts_held)),
            expert_capacity=capacity, dtype=jnp.bfloat16)
        step_kw = dict(extra_mutable=(capture.COUNTERS,))
    elif args.model == 'hybrid-decoder':
        assert ndev == 1, 'the hybrid decoder trains on one device here'
        tokens = args.batch_size * args.seq_len
        capacity = args.expert_capacity or -(-4 * tokens * 8 // 256)
        model = twin = models.hybrid_decoder_lm(
            vocab_size=vocab, hidden_size=args.d_model,
            layer_kinds=models.held_layer_kinds(args.n_layer),
            first_k_dense=1, ffn_block=args.ffn_block,
            kda_chunk=args.kda_chunk,
            kda_head_ids=tuple(range(args.n_head)),
            head_ids=tuple(range(args.n_head)),
            expert_ids=tuple(range(args.experts_held)),
            expert_capacity=capacity, dtype=jnp.bfloat16)
        step_kw = dict(extra_mutable=(capture.COUNTERS,))
    else:
        model = models.transformer_lm(
            vocab_size=vocab, n_layer=args.n_layer, n_head=args.n_head,
            d_model=args.d_model, max_len=args.seq_len, seq_axis=seq_axis,
            seq_impl=args.seq_impl)
        twin = models.transformer_lm(
            vocab_size=vocab, n_layer=args.n_layer, n_head=args.n_head,
            d_model=args.d_model, max_len=args.seq_len, seq_axis=None)

    # K-FAC distributes factor work over the flattened mesh when both
    # axes exist; with one axis it uses that axis directly. A composed
    # --kfac-mesh spec builds the mesh through the axis-aware plan
    # (size-1 extra axes are carried so the same spec string is valid
    # on chips that do shard them).
    if mesh_axes is not None and ndev > 1:
        from kfac_pytorch_tpu.parallel.mesh import make_composed_mesh
        mesh, _ = make_composed_mesh(mesh_axes)
        kfac_axis = tuple(a for a in (data_axis, seq_axis) if a)
        kfac_axis = kfac_axis if len(kfac_axis) > 1 else kfac_axis[0]
    elif ndev > 1:
        mesh = Mesh(np.array(devices[:ndev]).reshape(nd, ns),
                    ('data', 'seq'))
        kfac_axis = tuple(a for a, n in (('data', nd), ('seq', ns))
                          if n > 1)
        kfac_axis = kfac_axis if len(kfac_axis) > 1 else kfac_axis[0]
    else:
        mesh, kfac_axis, mesh_axes = None, None, None

    precond = None
    if args.kfac_update_freq > 0:
        # a composed spec hands the whole world derivation (num_devices
        # + axis_name from the data axes, per-layer axis roles for any
        # sharded-module axes) to the mesh plan
        world_kw = (dict(mesh_axes=mesh_axes)
                    if mesh_axes is not None
                    else dict(num_devices=ndev, axis_name=kfac_axis))
        precond = kfac.KFAC(
            variant=args.kfac_name, lr=args.base_lr, damping=args.damping,
            fac_update_freq=args.kfac_cov_update_freq,
            kfac_update_freq=args.kfac_update_freq,
            basis_update_freq=(args.kfac_basis_update_freq or None),
            warm_start_basis=args.kfac_warm_start,
            factor_decay=args.stat_decay, kl_clip=args.kl_clip,
            comm_precision=args.kfac_comm_precision,
            comm_mode=args.kfac_comm_mode,
            comm_prefetch=args.kfac_comm_prefetch,
            decomp_impl=args.kfac_decomp_impl,
            capture_impl=args.kfac_capture_impl,
            decomp_shard=args.kfac_decomp_shard,
            exclude_vocabulary_size=vocab, **world_kw)

    tx = training.sgd(args.base_lr, momentum=0.9)
    sample_local = jnp.zeros(
        (max(args.batch_size // max(nd, 1), 1),
         args.seq_len // max(ns, 1)), jnp.int32)
    state = training.init_train_state(twin, tx, precond,
                                      jax.random.PRNGKey(args.seed),
                                      sample_local)

    def ce(outputs, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, batch['label']).mean()

    # observability: trace recorder + metrics registry (epoch-line
    # suffixes render through the registry, byte-compatible with the
    # old hand-plumbed health_suffix) — same bootstrap as cifar/imagenet
    from kfac_pytorch_tpu import obs
    # closed-loop autotuner: proposes knob changes to the single knob
    # arbiter from measured step times
    from kfac_pytorch_tpu import autotune
    tuner = autotune.controller_from_args(
        precond, enabled=args.kfac_autotune, trace_dir=args.trace,
        log=log)
    tracer, reg = obs.setup_trainer(trace_dir=args.trace,
                                    prom_file=args.prom_file,
                                    tuner=tuner)

    bspec = P(data_axis, seq_axis)
    step = training.build_train_step(
        model, tx, precond, ce, axis_name=kfac_axis, mesh=mesh,
        batch_specs={'input': bspec, 'label': bspec}, tracer=tracer,
        autotune=tuner, **step_kw)

    def eval_loss_local(params, batch):
        out = model.apply({'params': params}, batch['input'], train=False)
        loss = ce(out, batch)
        if kfac_axis is not None:
            loss = jax.lax.pmean(loss, kfac_axis)
        return loss

    if mesh is not None:
        from kfac_pytorch_tpu.parallel.ring_attention import (
            interpreted_attention_active)
        eval_step = jax.jit(jax.shard_map(
            eval_loss_local, mesh=mesh,
            in_specs=(P(), {'input': bspec, 'label': bspec}),
            out_specs=P(),
            check_vma=not interpreted_attention_active()))
    else:
        eval_step = jax.jit(eval_loss_local)

    rng = np.random.RandomState(args.seed)
    from kfac_pytorch_tpu.utils.summary import maybe_writer
    tb = maybe_writer(args.tb_dir)
    if tb is not None:
        reg.add_exporter(obs.metrics.TensorBoardExporter(tb))
    monitor = metrics.HealthMonitor(log, state=state, registry=reg)
    if tuner is not None:
        # numerical-health gate for the tuner: a knob probe window that
        # skipped batches or fell back to raw SGD never commits, however
        # fast it looked (the decomp_impl ladder's accuracy backstop)
        tuner.quality_gate = monitor.quality_signal
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        loss_m = metrics.Metric('loss')
        iter_times = []
        for i, batch in enumerate(sample_batches(train_ids, args, rng)):
            ti = time.perf_counter()
            state, m = step(state, batch, lr=args.base_lr,
                            damping=args.damping)
            # float() pulls the loss to the host: the step is done
            loss_m.update(float(m['loss']))
            monitor.update(m, step=int(state.step) - 1)
            if args.speed:
                iter_times.append(time.perf_counter() - ti)
                if i >= 60:
                    break
        if args.speed:
            it = np.mean(iter_times[5:]), np.std(iter_times[5:])
            toks = args.batch_size * args.seq_len / it[0]
            log.info('SPEED: iter time %.4f +- %.4f s (tokens/sec %.1f)',
                     it[0], it[1], toks)
            break
        val_m = metrics.Metric('val_loss')
        vrng = np.random.RandomState(args.seed + 1)
        vargs = args
        for vb in list(sample_batches(val_ids, vargs, vrng))[:10]:
            val_m.update(float(eval_step(state.params, vb)))
        ppl = math.exp(min(loss_m.avg, 20))
        vppl = math.exp(min(val_m.avg, 20))
        # one registry call renders the health/resilience suffixes
        # byte-identically to the old hand-plumbed health_suffix
        moe = ''.join(f' {k} {float(v):g}' for k, v in m.items()
                      if k.startswith(('moe/', 'kda/')))
        log.info('epoch %d: train_ppl %.2f val_ppl %.2f (%.1fs)%s%s', epoch,
                 ppl, vppl, time.perf_counter() - t0,
                 reg.epoch_suffixes(), moe)
        monitor.epoch_flush()
        reg.export(step=epoch)
        if tracer is not None:
            tracer.flush()
        if tb is not None:
            tb.add_scalar('train/ppl', ppl, epoch)
            tb.add_scalar('val/ppl', vppl, epoch)
            tb.flush()
    reg.close()


if __name__ == '__main__':
    main()
