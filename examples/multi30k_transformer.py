"""Multi-30k de-en Transformer trainer with K-FAC.

Flag-surface parity with the reference entrypoint
(examples/pytorch_multi30k_transformer.py): Adam-vs-SGD+KFAC switch
(:277-286), tied-embedding pre-softmax layer excluded from K-FAC via
``exclude_vocabulary_size`` (:297), label smoothing, inverse-sqrt LR for
Adam / multistep for SGD, BLEU eval via greedy or beam-search decoding.

Data: reads whitespace-tokenized parallel files ``train.de``/``train.en``
(+ val) from ``--dir`` if present; otherwise a synthetic
sequence-transduction task (token-shifted reversal) that a 2-layer model
learns quickly — keeping the entrypoint runnable in a dataset-free
container.
"""

import argparse
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
from kfac_pytorch_tpu.models import transformer, translator

PAD, BOS, EOS = 1, 2, 3


def parse_args():
    p = argparse.ArgumentParser(description='Multi-30k Transformer (TPU)')
    p.add_argument('--dir', default=None)
    p.add_argument('--batch-size', type=int, default=128)
    p.add_argument('--epochs', type=int, default=100)
    p.add_argument('--d-model', type=int, default=512)
    p.add_argument('--d-inner', type=int, default=2048)
    p.add_argument('--n-layers', type=int, default=6)
    p.add_argument('--n-head', type=int, default=8)
    p.add_argument('--max-len', type=int, default=32)
    p.add_argument('--dropout', type=float, default=0.1)
    p.add_argument('--label-smoothing', type=float, default=0.1)
    # optimizer switch (reference :277-286)
    p.add_argument('--optimizer', default='sgd', choices=['sgd', 'adam'])
    p.add_argument('--base-lr', type=float, default=0.1)
    p.add_argument('--lr-mul', type=float, default=0.5)
    p.add_argument('--warmup-steps', type=int, default=4000)
    p.add_argument('--lr-decay', nargs='+', type=int, default=[40, 80])
    # K-FAC
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
    p.add_argument('--damping', type=float, default=0.03)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--exclude-parts', default='')
    p.add_argument('--num-devices', type=int, default=1)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--speed', action='store_true')
    p.add_argument('--beam-size', type=int, default=0,
                   help='>0 uses beam search for BLEU eval')
    p.add_argument('--synthetic-vocab', type=int, default=64)
    p.add_argument('--synthetic-size', type=int, default=2048)
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


def load_parallel(data_dir, split, max_len):
    """Whitespace-tokenized parallel files + shared vocab build."""
    src_path = os.path.join(data_dir, f'{split}.de')
    trg_path = os.path.join(data_dir, f'{split}.en')
    with open(src_path) as f:
        src = [l.split()[:max_len - 2] for l in f]
    with open(trg_path) as f:
        trg = [l.split()[:max_len - 2] for l in f]
    return src, trg


def build_vocab(sentences, min_freq=2):
    from collections import Counter
    c = Counter(w for s in sentences for w in s)
    vocab = {'<unk>': 0, '<pad>': PAD, '<bos>': BOS, '<eos>': EOS}
    for w, n in c.most_common():
        if n >= min_freq:
            vocab[w] = len(vocab)
    return vocab


def encode_corpus(src, trg, src_vocab, trg_vocab, max_len):
    def enc(sents, vocab):
        out = np.full((len(sents), max_len), PAD, np.int32)
        for i, s in enumerate(sents):
            ids = [BOS] + [vocab.get(w, 0) for w in s] + [EOS]
            out[i, :len(ids)] = ids[:max_len]
        return out
    return enc(src, src_vocab), enc(trg, trg_vocab)


def synthetic_translation(n, vocab, max_len, seed=0):
    """Reversal task: target = reversed source tokens (+4 offset)."""
    rng = np.random.RandomState(seed)
    src = np.full((n, max_len), PAD, np.int32)
    trg = np.full((n, max_len), PAD, np.int32)
    for i in range(n):
        L = rng.randint(4, max_len - 2)
        toks = rng.randint(4, vocab - 1, L)
        src[i, 0], src[i, 1:L + 1], src[i, L + 1] = BOS, toks, EOS
        trg[i, 0], trg[i, 1:L + 1], trg[i, L + 1] = BOS, toks[::-1], EOS
    return src, trg


def main():
    from kfac_pytorch_tpu.parallel import mesh as kmesh
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    kmesh.maybe_initialize_distributed()
    args = parse_args()
    from kfac_pytorch_tpu.utils.runlog import setup_run_logging
    log, _ = setup_run_logging(
        args.log_dir, 'multi30k', args.optimizer,
        f'kfac{args.kfac_update_freq}', args.kfac_name,
        f'bs{args.batch_size}', f'nd{args.num_devices}')
    log.info('args: %s', vars(args))

    if args.dir and os.path.exists(os.path.join(args.dir, 'train.de')):
        src_s, trg_s = load_parallel(args.dir, 'train', args.max_len)
        vsrc, vtrg = build_vocab(src_s), build_vocab(trg_s)
        train_src, train_trg = encode_corpus(src_s, trg_s, vsrc, vtrg,
                                             args.max_len)
        try:
            vs, vt = load_parallel(args.dir, 'val', args.max_len)
            val_src, val_trg = encode_corpus(vs, vt, vsrc, vtrg,
                                             args.max_len)
        except FileNotFoundError:
            val_src, val_trg = train_src[:256], train_trg[:256]
        n_src_vocab, n_trg_vocab = len(vsrc), len(vtrg)
        share = False  # separate vocabs
    else:
        n_src_vocab = n_trg_vocab = args.synthetic_vocab
        train_src, train_trg = synthetic_translation(
            args.synthetic_size, n_src_vocab, args.max_len, args.seed)
        val_src, val_trg = synthetic_translation(
            256, n_src_vocab, args.max_len, args.seed + 1)
        share = True

    model = transformer.Transformer(
        n_src_vocab=n_src_vocab, n_trg_vocab=n_trg_vocab,
        src_pad_idx=PAD, trg_pad_idx=PAD,
        d_word_vec=args.d_model, d_model=args.d_model,
        d_inner=args.d_inner, n_layers=args.n_layers, n_head=args.n_head,
        d_k=args.d_model // args.n_head, d_v=args.d_model // args.n_head,
        dropout=args.dropout, n_position=max(200, args.max_len),
        trg_emb_prj_weight_sharing=True)

    use_kfac = args.kfac_update_freq > 0 and args.optimizer == 'sgd'
    if args.optimizer == 'adam':
        lr_fn = utils.inverse_sqrt(args.d_model, args.warmup_steps,
                                   args.lr_mul)
        tx = optax.chain(optax.scale_by_adam(b1=0.9, b2=0.98, eps=1e-9),
                         optax.scale_by_learning_rate(lr_fn))
    else:
        steps_per_epoch = max(len(train_src) // args.batch_size, 1)
        lr_fn = utils.warmup_multistep(args.base_lr, steps_per_epoch, 5,
                                       args.lr_decay)
        tx = training.sgd(lr_fn, momentum=0.9, weight_decay=5e-4)

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
            exclude_vocabulary_size=n_trg_vocab,  # tied pre-softmax (:297)
            exclude_parts=args.exclude_parts,
            num_devices=args.num_devices,
            axis_name='batch' if args.num_devices > 1 else None)

    mesh, axis = None, None
    if args.num_devices > 1:
        mesh = Mesh(np.array(jax.devices()[:args.num_devices]), ('batch',))
        axis = 'batch'

    def loss_fn(outputs, batch):
        # shifted teacher forcing: predict trg[1:] from trg[:-1]
        # (pad-masked label-smoothed CE, reference :318-336)
        logits = outputs[:, :-1]
        target = batch['label'][:, 1:]
        mask = (target != PAD).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        V = logits.shape[-1]
        onehot = jax.nn.one_hot(target, V)
        sm = args.label_smoothing
        tgt = onehot * (1 - sm) + sm / V
        ll = -(tgt * logp).sum(-1)
        return (ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    import flax.linen as linen

    # model takes (src, trg) — adapt the trainer's single-input convention
    class Wrapped(linen.Module):
        inner: linen.Module

        @linen.compact
        def __call__(self, xs, train=True):
            return self.inner(xs[0], xs[1], train=train)

    wrapped = Wrapped(inner=model)

    sample = (jnp.asarray(train_src[:args.batch_size]),
              jnp.asarray(train_trg[:args.batch_size]))
    rngs = {'params': jax.random.PRNGKey(args.seed),
            'dropout': jax.random.PRNGKey(args.seed + 1)}
    variables = capture.init(wrapped, rngs, sample)
    params = variables['params']
    if precond is not None:
        metas = capture.collect_layer_meta(
            wrapped, {'params': params}, sample, train=False,
            exclude_vocabulary_size=n_trg_vocab)
        precond.setup(metas)

    kfac_state = precond.init() if precond is not None else None
    state = training.TrainState(step=jnp.zeros((), jnp.int32), params=params,
                                opt_state=tx.init(params),
                                kfac_state=kfac_state, extra_vars={})

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

    step = training.build_train_step(
        wrapped, tx, precond, loss_fn, axis_name=axis, mesh=mesh,
        dropout_seed=args.seed + 2, tracer=tracer,
        autotune=tuner)

    monitor = utils.HealthMonitor(log, state=state, registry=reg)
    if tuner is not None:
        # numerical-health gate for the tuner: a knob probe window that
        # skipped batches or fell back to raw SGD never commits, however
        # fast it looked (the decomp_impl ladder's accuracy backstop)
        tuner.quality_gate = monitor.quality_signal

    def run_epoch(state, epoch):
        m = utils.Metric('loss')
        n = len(train_src) // args.batch_size
        order = np.random.RandomState(epoch).permutation(len(train_src))
        for i in range(n):
            sel = order[i * args.batch_size:(i + 1) * args.batch_size]
            batch = {'input': (jnp.asarray(train_src[sel]),
                               jnp.asarray(train_trg[sel])),
                     'label': jnp.asarray(train_trg[sel])}
            state, metrics = step(state, batch, lr=args.base_lr,
                                  damping=args.damping if precond else 0.0)
            m.update(metrics['loss'])
            monitor.update(metrics, step=int(state.step) - 1)
        return state, m.avg

    if args.speed:
        # SPEED mode: steady-state iteration time, no eval (reference
        # transformer trainer's speed measurement convention). `sample`
        # is the already-built batch prefix — its REAL row count feeds
        # the tokens/sec (a small dataset silently truncates the batch).
        from kfac_pytorch_tpu.utils import profiling
        batch = {'input': sample, 'label': sample[1]}
        profiling.speed_report(
            log, step, state, batch,
            sample[0].shape[0] * args.max_len, lr=args.base_lr,
            damping=args.damping if precond else 0.0)
        return

    from kfac_pytorch_tpu.utils.summary import maybe_writer
    tb = maybe_writer(args.tb_dir)
    if tb is not None:
        reg.add_exporter(obs.metrics.TensorBoardExporter(tb))
    for epoch in range(args.epochs):
        t0 = time.time()
        state, train_loss = run_epoch(state, epoch)
        # eval: greedy-decode BLEU on a validation slice
        vars_eval = {'params': state.params['inner']}
        hyp = translator.greedy_decode(
            model, vars_eval, jnp.asarray(val_src[:128]), BOS, EOS,
            max_len=args.max_len)
        hyp = np.asarray(hyp)
        hyps, refs = [], []
        for h, r in zip(hyp, val_trg[:128]):
            h = h.tolist()
            h = h[:h.index(EOS)] if EOS in h else h
            r = [t for t in r.tolist()[1:] if t not in (PAD, EOS)]
            hyps.append(h)
            refs.append(r)
        score = translator.bleu(hyps, refs)
        # one registry call renders the health/resilience suffixes
        # byte-identically to the old hand-plumbed health_suffix
        log.info('epoch %d: train_loss %.4f BLEU %.2f (%.1fs)%s',
                 epoch, train_loss, score, time.time() - t0,
                 reg.epoch_suffixes())
        monitor.epoch_flush()
        reg.export(step=epoch)
        if tracer is not None:
            tracer.flush()
        if tb is not None:
            tb.add_scalar('train/loss', train_loss, epoch)
            tb.add_scalar('val/BLEU', score, epoch)
            tb.flush()
    if tracer is not None:
        tracer.flush()
    reg.close()


if __name__ == '__main__':
    main()
