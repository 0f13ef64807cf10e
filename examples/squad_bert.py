"""SQuAD BERT fine-tuning with K-FAC.

Workload parity with the reference entrypoint
(examples/pytorch_squad_bert.py): span-prediction loss (start+end CE),
K-FAC on every dense layer with the wordpiece vocab head excluded
(``exclude_vocabulary_size``, :394/:443-450), warmup-linear LR, F1/EM
evaluation (:562-617). Reads a SQuAD-format JSON from ``--train-file`` if
provided (whitespace tokenization — no pretrained wordpiece assets in this
container); otherwise a synthetic span-extraction task (find the marked
span) that a small model learns from scratch.
"""

import argparse
import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture, training, utils
from kfac_pytorch_tpu.models import bert

PAD, CLS, SEP, MARK = 0, 1, 2, 3


def parse_args():
    p = argparse.ArgumentParser(description='SQuAD BERT K-FAC (TPU)')
    p.add_argument('--train-file', default=None)
    p.add_argument('--model-size', default='tiny',
                   choices=['tiny', 'base', 'large'])
    p.add_argument('--batch-size', type=int, default=4)
    p.add_argument('--epochs', type=int, default=2)
    p.add_argument('--max-seq-length', type=int, default=64)
    p.add_argument('--base-lr', type=float, default=0.04)
    p.add_argument('--warmup-frac', type=float, default=0.1)
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
    p.add_argument('--stat-decay', type=float, default=0.95)
    p.add_argument('--damping', type=float, default=0.003)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--num-devices', type=int, default=1)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--synthetic-size', type=int, default=1024)
    p.add_argument('--speed', action='store_true')
    p.add_argument('--log-dir', default='./logs',
                   help='per-run log files land here')
    p.add_argument('--tb-dir', default=None,
                   help='TensorBoard scalar summaries (rank 0)')
    # observability (kfac_pytorch_tpu/obs/), matching the cifar/imagenet
    # wiring: one flag turns on Chrome-trace spans + metric snapshots,
    # one exports the registry as a Prometheus textfile
    p.add_argument('--trace', default=None, metavar='DIR',
                   help='write Chrome-trace spans (per-step dispatch '
                        'spans, resilience instants) to '
                        'DIR/trace-host<i>.jsonl and epoch metric '
                        'snapshots to DIR/metrics.jsonl; merge a pod\'s '
                        'files with kfac-obs (defaults to '
                        '$KFAC_TRACE_DIR when set)')
    p.add_argument('--prom-file',
                   default=os.environ.get('KFAC_PROM_FILE'),
                   metavar='PATH',
                   help='export the metrics registry as a Prometheus '
                        'textfile at PATH after every epoch (rank 0; '
                        'defaults to $KFAC_PROM_FILE — the training '
                        'service sets it per tenant job, and the path '
                        'is namespaced by tenant/job id either way)')
    return p.parse_args()


def synthetic_squad(n, seq_len, vocab, seed=0):
    """Context with a MARK-delimited answer span; question = first tokens
    of the span. Learnable from scratch; answers are token spans so F1/EM
    evaluate exactly as for real SQuAD."""
    rng = np.random.RandomState(seed)
    ids = np.full((n, seq_len), PAD, np.int32)
    types = np.zeros((n, seq_len), np.int32)
    mask = np.zeros((n, seq_len), np.float32)
    starts = np.zeros(n, np.int32)
    ends = np.zeros(n, np.int32)
    for i in range(n):
        ctx_len = seq_len - 8
        ctx = rng.randint(4, vocab, ctx_len)
        s = rng.randint(2, ctx_len - 6)
        L = rng.randint(1, 4)
        ctx[s - 1] = MARK
        ctx[s + L] = MARK
        q = ctx[s:s + 1]
        seq = np.concatenate(([CLS], q, [SEP], ctx, [SEP]))
        ids[i, :len(seq)] = seq[:seq_len]
        types[i, 3:len(seq)] = 1
        mask[i, :len(seq)] = 1
        starts[i] = 3 + s
        ends[i] = 3 + s + L - 1
    return ids, types, mask, starts, ends


def squad_f1_em(pred_spans, gold_spans, token_seqs):
    """Token-level F1 / exact match (the reference's metric computed over
    answer token bags, examples/pytorch_squad_bert.py:562-617)."""
    f1s, ems = [], []
    for (ps, pe), (gs, ge), toks in zip(pred_spans, gold_spans, token_seqs):
        pred = list(toks[ps:pe + 1]) if pe >= ps else []
        gold = list(toks[gs:ge + 1])
        ems.append(float(pred == gold))
        common = collections.Counter(pred) & collections.Counter(gold)
        n_common = sum(common.values())
        if n_common == 0:
            f1s.append(0.0)
            continue
        prec = n_common / max(len(pred), 1)
        rec = n_common / max(len(gold), 1)
        f1s.append(2 * prec * rec / (prec + rec))
    return 100.0 * np.mean(f1s), 100.0 * np.mean(ems)


def main():
    from kfac_pytorch_tpu.parallel import mesh as kmesh
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    kmesh.maybe_initialize_distributed()
    args = parse_args()
    from kfac_pytorch_tpu.utils.runlog import setup_run_logging
    log, _ = setup_run_logging(
        args.log_dir, 'squad', args.model_size,
        f'kfac{args.kfac_update_freq}', args.kfac_name,
        f'bs{args.batch_size}', f'nd{args.num_devices}')
    log.info('args: %s', vars(args))

    cfg_fn = {'tiny': bert.BertConfig.tiny, 'base': bert.BertConfig.base,
              'large': bert.BertConfig.large}[args.model_size]
    cfg = cfg_fn(max_position_embeddings=max(64, args.max_seq_length))
    model = bert.BertForQuestionAnswering(cfg)

    ids, types, mask, starts, ends = synthetic_squad(
        args.synthetic_size, args.max_seq_length, cfg.vocab_size, args.seed)
    vids, vtypes, vmask, vstarts, vends = synthetic_squad(
        256, args.max_seq_length, cfg.vocab_size, args.seed + 1)

    steps_per_epoch = len(ids) // args.batch_size
    total = steps_per_epoch * args.epochs
    lr_fn = utils.polynomial_decay(args.base_lr, total, power=1.0,
                                   warmup_steps=int(total * args.warmup_frac))
    tx = training.sgd(lr_fn, momentum=0.9, weight_decay=0.0)

    use_kfac = args.kfac_update_freq > 0
    precond = None
    if use_kfac:
        precond = kfac.get_kfac_module(args.kfac_name)(
            lr=args.base_lr, damping=args.damping,
            fac_update_freq=args.kfac_cov_update_freq,
            kfac_update_freq=args.kfac_update_freq,
            basis_update_freq=(args.kfac_basis_update_freq or None),
            warm_start_basis=args.kfac_warm_start,
            comm_precision=args.kfac_comm_precision,
            comm_mode=args.kfac_comm_mode,
            comm_prefetch=args.kfac_comm_prefetch,
            decomp_impl=args.kfac_decomp_impl,
            capture_impl=args.kfac_capture_impl,
            decomp_shard=args.kfac_decomp_shard,
            kl_clip=args.kl_clip, factor_decay=args.stat_decay,
            exclude_vocabulary_size=cfg.vocab_size,
            num_devices=args.num_devices,
            axis_name='batch' if args.num_devices > 1 else None)

    mesh, axis = None, None
    if args.num_devices > 1:
        mesh = Mesh(np.array(jax.devices()[:args.num_devices]), ('batch',))
        axis = 'batch'

    def loss_fn(outputs, batch):
        start_logits, end_logits = outputs
        ls = optax.softmax_cross_entropy_with_integer_labels(
            start_logits, batch['label'][:, 0]).mean()
        le = optax.softmax_cross_entropy_with_integer_labels(
            end_logits, batch['label'][:, 1]).mean()
        return (ls + le) / 2.0

    sample = (jnp.asarray(ids[:args.batch_size]),
              jnp.asarray(types[:args.batch_size]),
              jnp.asarray(mask[:args.batch_size]))
    rngs = {'params': jax.random.PRNGKey(args.seed),
            'dropout': jax.random.PRNGKey(args.seed + 1)}
    variables = capture.init(model, rngs, sample)
    params = variables['params']
    if precond is not None:
        metas = capture.collect_layer_meta(
            model, {'params': params}, sample, train=False,
            exclude_vocabulary_size=cfg.vocab_size)
        precond.setup(metas)
    state = training.TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
        kfac_state=precond.init() if precond else None, extra_vars={})

    # observability: trace recorder + metrics registry (epoch-line
    # suffixes render through the registry, byte-compatible with the
    # old hand-plumbed health_suffix)
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

    step = training.build_train_step(model, tx, precond, loss_fn,
                                     axis_name=axis, mesh=mesh,
                                     dropout_seed=args.seed + 2,
                                     tracer=tracer,
                                     autotune=tuner)

    @jax.jit
    def eval_step(params, batch):
        s, e = model.apply({'params': params}, batch, train=False)
        return jnp.argmax(s, -1), jnp.argmax(e, -1)

    rs = np.random.RandomState(args.seed)
    if args.speed:
        from kfac_pytorch_tpu.utils import profiling
        n = min(args.batch_size, len(ids))  # real rows, not requested
        batch = {'input': (jnp.asarray(ids[:n]), jnp.asarray(types[:n]),
                           jnp.asarray(mask[:n])),
                 'label': jnp.asarray(np.stack([starts[:n], ends[:n]], 1))}
        profiling.speed_report(
            log, step, state, batch, n * ids.shape[1], lr=args.base_lr,
            damping=args.damping if precond else 0.0)
        return

    from kfac_pytorch_tpu.utils.summary import maybe_writer
    tb = maybe_writer(args.tb_dir)
    if tb is not None:
        reg.add_exporter(obs.metrics.TensorBoardExporter(tb))
    monitor = utils.HealthMonitor(log, state=state, registry=reg)
    if tuner is not None:
        # numerical-health gate for the tuner: a knob probe window that
        # skipped batches or fell back to raw SGD never commits, however
        # fast it looked (the decomp_impl ladder's accuracy backstop)
        tuner.quality_gate = monitor.quality_signal
    for epoch in range(args.epochs):
        t0 = time.time()
        m = utils.Metric('loss')
        order = rs.permutation(len(ids))
        for i in range(steps_per_epoch):
            sel = order[i * args.batch_size:(i + 1) * args.batch_size]
            batch = {'input': (jnp.asarray(ids[sel]),
                               jnp.asarray(types[sel]),
                               jnp.asarray(mask[sel])),
                     'label': jnp.asarray(
                         np.stack([starts[sel], ends[sel]], 1))}
            state, metrics = step(state, batch, lr=args.base_lr,
                                  damping=args.damping if precond else 0.0)
            m.update(metrics['loss'])
            monitor.update(metrics, step=int(state.step) - 1)
        ps, pe = eval_step(state.params,
                           (jnp.asarray(vids), jnp.asarray(vtypes),
                            jnp.asarray(vmask)))
        f1, em = squad_f1_em(list(zip(np.asarray(ps), np.asarray(pe))),
                             list(zip(vstarts, vends)), vids)
        # one registry call renders the health/resilience suffixes
        # byte-identically to the old hand-plumbed health_suffix
        log.info('epoch %d: loss %.4f F1 %.2f EM %.2f (%.1fs)%s',
                 epoch, m.avg, f1, em, time.time() - t0,
                 reg.epoch_suffixes())
        monitor.epoch_flush()
        reg.export(step=epoch)
        if tracer is not None:
            tracer.flush()
        if tb is not None:
            tb.add_scalar('train/loss', m.avg, epoch)
            tb.add_scalar('val/F1', f1, epoch)
            tb.add_scalar('val/EM', em, epoch)
            tb.flush()
    if tracer is not None:
        tracer.flush()
    reg.close()


if __name__ == '__main__':
    main()
