"""One chip's share of a sparse decoder with window and full attention
mixed (``models.mixed_decoder_lm``: grouped-query, gated, QK-normed
attention, four norms a block, sigmoid-routed experts with a shared
expert), built as ``examples/longcontext_lm.py --model mixed-decoder``
builds it: the model zoo's block with bfloat16 activations, SGD with
momentum, the mean next-token cross-entropy over all tokens of the batch,
K-FAC on every projection but the router and the head, the model's
counters (``moe/dropped``...) handed to the step as a mutable collection."""

import jax.numpy as jnp
import optax

import kfac_pytorch_tpu
from kfac_pytorch_tpu import capture, health, models, training


def build(config, traffic, kfac=True, axis_name=None):
    m, opt, k = config['model'], config['optimizer'], config['kfac']
    model = models.mixed_decoder_lm(
        vocab_size=m['vocab_size'], hidden_size=m['hidden_size'],
        layer_types=tuple(m['layer_types_held']),
        first_k_dense=m['first_k_dense_replace'],
        intermediate_size=m['intermediate_size'],
        expert_width=m['moe_intermediate_size'],
        n_routed_experts=m['num_experts_published'],
        experts_per_tok=m['num_experts_per_tok'],
        n_shared_experts=m['num_shared_experts'],
        routed_scale=m['route_scale'], norm_topk=m['route_norm'],
        head_dim=m['head_dim'],
        num_attention_heads=m['num_attention_heads_published'],
        num_key_value_heads=m['num_key_value_heads_published'],
        sliding_window=m['sliding_window'],
        rope_theta=float(m['rope_theta']), eps=m['rms_norm_eps'],
        mup_enabled=m['mup_enabled'], q_head_ids=tuple(m['q_head_ids']),
        kv_head_ids=tuple(m['kv_head_ids']),
        expert_ids=tuple(m['expert_ids']),
        expert_capacity=m['expert_capacity'],
        dtype=jnp.dtype(config['dtype']['activations']))
    tx = training.sgd(opt['lr'], momentum=opt['momentum'],
                      weight_decay=opt['weight_decay'])
    precond = None
    if kfac:
        precond = kfac_pytorch_tpu.get_kfac_module(k['variant'])(
            lr=opt['lr'], damping=k['damping'],
            fac_update_freq=traffic['fac_update_freq'],
            kfac_update_freq=traffic['kfac_update_freq'],
            kl_clip=k['kl_clip'], factor_decay=k['ema_new_weight'],
            exclude_vocabulary_size=m['vocab_size'],
            num_devices=traffic['chips'], axis_name=axis_name)

    def loss_fn(logits, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch['label']).mean()

    n, length = traffic['batch_per_chip'] * traffic['chips'], m['seq_len']
    sample = jnp.zeros((n, length), jnp.int32)

    def init_state(rng):
        variables = capture.init(model, {'params': rng}, sample)
        params = variables.pop('params')
        if precond is not None and precond.plan is None:
            precond.setup(capture.collect_layer_meta(
                model, {'params': params, **variables}, sample,
                exclude_vocabulary_size=m['vocab_size']))
        return training.TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=tx.init(params),
            kfac_state=precond.init() if precond else None,
            extra_vars=variables,
            health=(health.HealthState.init()
                    if getattr(precond, 'health', None) is not None
                    else None))

    return dict(model=model, tx=tx, precond=precond, loss_fn=loss_fn,
                init_state=init_state,
                step_kwargs=dict(extra_mutable=(capture.COUNTERS,)))
