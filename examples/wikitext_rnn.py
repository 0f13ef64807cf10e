"""WikiText-2 LSTM language-model trainer.

Workload parity with the reference entrypoint
(examples/pytorch_wikitext_rnn.py: 2-layer LSTM-650 LM, BPTT batching,
SGD with gradient clipping, per-epoch perplexity). The reference marks
the workload "does not work with K-FAC yet" (:6); here it DOES —
``--kfac-update-freq N`` (default 0 = reference-parity SGD) swaps in the
capture-aware LSTM cell (models/rnn.KFACLSTMCell) and preconditions the
recurrent ih/hh matmuls with any K-FAC variant; the pre-softmax decoder
stays vocab-excluded like every other trainer.

Reads a plain-text corpus from ``--data`` (one token stream, whitespace
tokenized, the wikitext-2 raw format) or synthesizes a Markov-chain
corpus so the entrypoint runs in a dataset-free container.
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

from kfac_pytorch_tpu import KFAC_VARIANTS, training, utils
from kfac_pytorch_tpu.models import rnn


def parse_args():
    p = argparse.ArgumentParser(description='WikiText LSTM LM (TPU)')
    p.add_argument('--data', default=None)
    p.add_argument('--batch-size', type=int, default=20)
    p.add_argument('--bptt', type=int, default=35)
    p.add_argument('--epochs', type=int, default=5)
    p.add_argument('--embed-dim', type=int, default=650)
    p.add_argument('--hidden-dim', type=int, default=650)
    p.add_argument('--num-layers', type=int, default=2)
    p.add_argument('--dropout', type=float, default=0.5)
    p.add_argument('--base-lr', type=float, default=20.0)
    p.add_argument('--clip', type=float, default=0.25)
    p.add_argument('--vocab-limit', type=int, default=10000)
    p.add_argument('--kfac-update-freq', type=int, default=0,
                   help='0 = SGD (reference-parity: its RNN K-FAC is '
                        'broken); N>0 preconditions the LSTM matmuls')
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
                   choices=list(KFAC_VARIANTS))
    p.add_argument('--damping', type=float, default=0.003)
    p.add_argument('--stat-decay', type=float, default=0.95)
    p.add_argument('--kl-clip', type=float, default=0.001)
    p.add_argument('--seed', type=int, default=42)
    p.add_argument('--synthetic-vocab', type=int, default=256)
    p.add_argument('--synthetic-tokens', type=int, default=100000)
    p.add_argument('--speed', action='store_true')
    p.add_argument('--log-dir', default='./logs',
                   help='per-run log files land here')
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
    # synthetic Markov chain (learnable structure -> ppl drops fast)
    rng = np.random.RandomState(args.seed)
    V = args.synthetic_vocab
    trans = rng.dirichlet(np.ones(V) * 0.05, size=V)
    ids = np.zeros(args.synthetic_tokens, np.int32)
    for i in range(1, len(ids)):
        ids[i] = rng.choice(V, p=trans[ids[i - 1]])
    return ids, V


def batchify(ids, batch_size):
    n = len(ids) // batch_size
    return ids[:n * batch_size].reshape(batch_size, n)


def main():
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    args = parse_args()
    from kfac_pytorch_tpu.utils.runlog import setup_run_logging
    log, _ = setup_run_logging(
        args.log_dir, 'wikitext', f'kfac{args.kfac_update_freq}',
        args.kfac_name if args.kfac_update_freq else 'sgd',
        f'bs{args.batch_size}')
    log.info('args: %s', vars(args))

    ids, vocab_size = load_corpus(args)
    split = int(len(ids) * 0.95)
    train_data = batchify(ids[:split], args.batch_size)
    val_data = batchify(ids[split:], args.batch_size)

    use_kfac = args.kfac_update_freq > 0
    model = rnn.wikitext_lstm(vocab_size, embed_dim=args.embed_dim,
                              hidden_dim=args.hidden_dim,
                              num_layers=args.num_layers,
                              dropout=args.dropout,
                              kfac_lstm=use_kfac)
    sample = jnp.asarray(train_data[:, :args.bptt])
    tx = optax.chain(optax.clip_by_global_norm(args.clip),
                     optax.sgd(args.base_lr))
    precond = None
    if use_kfac:
        import kfac_pytorch_tpu as kfac
        precond = kfac.KFAC(
            variant=args.kfac_name, lr=args.base_lr, damping=args.damping,
            fac_update_freq=args.kfac_cov_update_freq,
            kfac_update_freq=args.kfac_update_freq,
            factor_decay=args.stat_decay, kl_clip=args.kl_clip,
            comm_precision=args.kfac_comm_precision,
            comm_mode=args.kfac_comm_mode,
            comm_prefetch=args.kfac_comm_prefetch,
            decomp_impl=args.kfac_decomp_impl,
            capture_impl=args.kfac_capture_impl,
            decomp_shard=args.kfac_decomp_shard,
            num_devices=1, axis_name=None,
            exclude_vocabulary_size=vocab_size)
    state = training.init_train_state(model, tx, precond,
                                      jax.random.PRNGKey(args.seed), sample)

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

    step = training.build_train_step(model, tx, precond, ce,
                                     dropout_seed=args.seed + 1,
                                     tracer=tracer,
                                     autotune=tuner)

    @jax.jit
    def eval_step(params, x, y):
        logits = model.apply({'params': params}, x, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    n_steps = (train_data.shape[1] - 1) // args.bptt
    if args.speed:
        from kfac_pytorch_tpu.utils import profiling
        # clamp to the data actually available (the training path would
        # just run zero steps; a speed batch must still be well-formed)
        bptt = min(args.bptt, train_data.shape[1] - 1)
        batch = {'input': jnp.asarray(train_data[:, :bptt]),
                 'label': jnp.asarray(train_data[:, 1:bptt + 1])}
        profiling.speed_report(
            log, step, state, batch, train_data.shape[0] * bptt,
            lr=args.base_lr, damping=args.damping)
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
        for i in range(n_steps):
            s = i * args.bptt
            batch = {
                'input': jnp.asarray(train_data[:, s:s + args.bptt]),
                'label': jnp.asarray(train_data[:, s + 1:s + args.bptt + 1]),
            }
            state, metrics = step(state, batch, lr=args.base_lr,
                                  damping=args.damping)
            m.update(metrics['loss'])
            monitor.update(metrics, step=int(state.step) - 1)
        vm = utils.Metric('val')
        for i in range((val_data.shape[1] - 1) // args.bptt):
            s = i * args.bptt
            x = jnp.asarray(val_data[:, s:s + args.bptt])
            y = jnp.asarray(val_data[:, s + 1:s + args.bptt + 1])
            vm.update(eval_step(state.params, x, y))
        ppl = math.exp(min(m.avg, 20))
        vppl = math.exp(min(vm.avg, 20))
        # one registry call renders the health/resilience suffixes
        # byte-identically to the old hand-plumbed health_suffix
        log.info('epoch %d: train_ppl %.2f val_ppl %.2f (%.1fs)%s', epoch,
                 ppl, vppl, time.time() - t0, reg.epoch_suffixes())
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
