"""BERT with the SQuAD span head, built as ``examples/squad_bert.py``
builds it: the model zoo's encoder in float32, SGD with momentum, the mean
of the start and end cross-entropies, K-FAC on every dense layer (the
span head has no vocabulary-sized layer to exclude)."""

import jax
import jax.numpy as jnp
import optax

import kfac_pytorch_tpu
from kfac_pytorch_tpu import capture, health, training
from kfac_pytorch_tpu.models import bert


def build(config, traffic, kfac=True, axis_name=None):
    m, opt, k = config['model'], config['optimizer'], config['kfac']
    cfg = bert.BertConfig(
        vocab_size=m['vocab_size'], hidden_size=m['hidden_size'],
        num_hidden_layers=m['num_hidden_layers'],
        num_attention_heads=m['num_attention_heads'],
        intermediate_size=m['intermediate_size'],
        max_position_embeddings=m['max_position_embeddings'],
        type_vocab_size=m['type_vocab_size'],
        hidden_dropout_prob=m['hidden_dropout_prob'],
        attention_probs_dropout_prob=m['attention_probs_dropout_prob'],
        layer_norm_eps=m['layer_norm_eps'])
    model = bert.BertForQuestionAnswering(cfg)
    tx = training.sgd(opt['lr'], momentum=opt['momentum'],
                      weight_decay=opt['weight_decay'])
    precond = None
    if kfac:
        precond = kfac_pytorch_tpu.get_kfac_module(k['variant'])(
            lr=opt['lr'], damping=k['damping'],
            fac_update_freq=traffic['fac_update_freq'],
            kfac_update_freq=traffic['kfac_update_freq'],
            kl_clip=k['kl_clip'], factor_decay=k['ema_new_weight'],
            exclude_vocabulary_size=cfg.vocab_size,
            num_devices=traffic['chips'], axis_name=axis_name)

    def loss_fn(outputs, batch):
        start, end = outputs
        ls = optax.softmax_cross_entropy_with_integer_labels(
            start, batch['label'][:, 0]).mean()
        le = optax.softmax_cross_entropy_with_integer_labels(
            end, batch['label'][:, 1]).mean()
        return (ls + le) / 2.0

    n, length = traffic['batch_per_chip'] * traffic['chips'], m['seq_len']
    sample = (jnp.zeros((n, length), jnp.int32),
              jnp.zeros((n, length), jnp.int32),
              jnp.ones((n, length), jnp.float32))

    def init_state(rng):
        rngs = {'params': rng, 'dropout': jax.random.fold_in(rng, 1)}
        params = capture.init(model, rngs, sample)['params']
        if precond is not None and precond.plan is None:
            precond.setup(capture.collect_layer_meta(
                model, {'params': params}, sample, train=False,
                exclude_vocabulary_size=cfg.vocab_size))
        return training.TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=tx.init(params),
            kfac_state=precond.init() if precond else None, extra_vars={},
            # as init_train_state seeds it: the step would add it host-side
            # on its first call otherwise
            health=(health.HealthState.init()
                    if getattr(precond, 'health', None) is not None
                    else None))

    dropout = (m['hidden_dropout_prob'] > 0
               or m['attention_probs_dropout_prob'] > 0)
    return dict(model=model, tx=tx, precond=precond, loss_fn=loss_fn,
                init_state=init_state,
                step_kwargs=dict(dropout_seed=2 if dropout else None))
