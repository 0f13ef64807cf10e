"""One chip's share of a sparse decoder whose layers are a recurrence or a
softmax (``models.hybrid_decoder_lm``: Kimi Delta Attention and latent
attention without positions, sigmoid-routed experts with a shared expert,
the wide dense block's K-FAC factors in blocks), built as
``examples/longcontext_lm.py --model hybrid-decoder`` builds it: the model
zoo's block with bfloat16 activations, SGD with momentum, the mean
next-token cross-entropy over all tokens of the batch, K-FAC on every
projection but the router and the head, the model's counters
(``moe/dropped``, ``kda/log_decay_min``...) handed to the step as a
mutable collection."""

import jax.numpy as jnp
import optax

import kfac_pytorch_tpu
from kfac_pytorch_tpu import capture, health, models, training


def build_model(m, dtype=None):
    """``models.hybrid_decoder_lm`` of a configuration's ``model`` keys."""
    lin = m['linear_attn_config']
    return models.hybrid_decoder_lm(
        vocab_size=m['vocab_size'], hidden_size=m['hidden_size'],
        layer_kinds=tuple(m['layer_kinds_held']),
        first_k_dense=m['first_k_dense_replace'],
        intermediate_size=m['intermediate_size'], ffn_block=m['ffn_block'],
        expert_width=m['moe_intermediate_size'],
        n_routed_experts=m['num_experts_published'],
        experts_per_tok=m['num_experts_per_token'],
        n_shared_experts=m['num_shared_experts'],
        routed_scale=m['routed_scaling_factor'],
        norm_topk=m['moe_renormalize'], kv_rank=m['kv_lora_rank'],
        qk_nope=m['qk_nope_head_dim'], qk_rope=m['qk_rope_head_dim'],
        v_dim=m['v_head_dim'], kda_head_dim=lin['head_dim'],
        kda_rank=m['kda_rank'],
        kda_conv_size=lin['short_conv_kernel_size'],
        kda_chunk=m['kda_chunk'], kda_a_log_centre=m['kda_a_log_centre'],
        kda_dt_bias_centre=m['kda_dt_bias_centre'], eps=m['rms_norm_eps'],
        kda_head_ids=tuple(m['kda_head_ids']),
        head_ids=tuple(m['head_ids']), expert_ids=tuple(m['expert_ids']),
        expert_capacity=m['expert_capacity'], dtype=dtype)


def build(config, traffic, kfac=True, axis_name=None):
    m, opt, k = config['model'], config['optimizer'], config['kfac']
    model = build_model(m, jnp.dtype(config['dtype']['activations']))
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
