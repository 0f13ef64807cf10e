"""One chip's share of a sparse decoder whose layers are a recurrence or a
softmax (Kimi Delta Attention and latent attention without positions, 3:1;
sigmoid-routed experts with a shared expert: the ``kimi_linear`` block as
``moonshotai/Kimi-Linear-48B-A3B-Instruct`` publishes it) in plain
jax.numpy: the reference's own forward pass, importing nothing of the
program.

Flat tokens: a batch of ``B`` sequences of ``L + 1`` ids gives ``T = B L``
rows (``input = ids[:, :-1]``, ``label = ids[:, 1:]``; no position is
masked), every projection sees ``[T, d]``, and the loss is the mean over
the ``T`` tokens of the next-token cross-entropy. Per held layer ``i``,
``x`` the residual stream, RMS norms in float32, no biases but the one
named: ``x += attn_i(norm(x))``, ``x += ffn_i(norm(x))``.

- ``layer_kinds_held[i] == 'kda'``, ``u = norm(x)``, ``h`` heads held of
  128, ``n = 128 h``: ``q~, k~, v~ = u W_q, u W_k, u W_v``; then in
  float32: a depthwise causal convolution of ``short_conv_kernel_size`` 4
  on each (own weights ``w [4, n]``, zero left padding, within the
  sequence, FOUR SHIFTED SUMS: ``c_t = sum_i w[i] z_{t-3+i}``) and SiLU;
  ``q_t = l2norm(q_t) 128^-1/2``, ``k_t = l2norm(k_t)`` over a head
  (``x / sqrt(sum x^2 + 1e-6)``); decay ``g_t = -exp(kda_a_log_centre +
  A_log[head]) softplus((u W_fa) W_fb + kda_dt_bias_centre + dt_bias)``
  per channel; ``beta_t = sigmoid(u W_b)`` per head; the recurrence TOKEN
  BY TOKEN (``lax.scan`` over ``t``, no chunks), per head and sequence
  from ``S = 0 [128, 128]``: ``S <- Diag(exp g_t) S``, ``S <- S + beta_t
  k_t (v_t - S' k_t)'``, ``o_t = S' q_t``, at ``highest``;
  ``y = [norm_128(o) * sigmoid((u W_ga) W_gb + b_g)] W_o`` (one 128-wide
  scale a layer).
- ``'latent'``: ``q = u W_q -> [T, h, 192]``, ``u W_kva -> 512 | 64``,
  ``kv = norm(c) W_kvb -> [T, h, 128 | 128]``; NO rotary (``mla_use_nope``):
  the 64 shared dimensions enter the score as they are; causal
  ``softmax(q k' / sqrt(192)) v`` as full scores, the softmax in float32;
  ``W_o``.
- the first ``first_k_dense_replace`` held layers: ``(silu(u W_g) * u W_u)
  W_d`` of ``intermediate_size``, written as its ``intermediate_size /
  ffn_block`` column blocks of ``W_g`` / ``W_u`` and row blocks of ``W_d``
  (the same sum; each block a K-FAC layer of its own, which is the
  configuration's decision for a 9,216-wide weight). The others: ``s =
  sigmoid(u W_r)`` over ALL ``num_experts_published`` outputs, float32 at
  ``highest``; top-k of ``s + b``; weights ``s_i / (sum over the chosen s +
  1e-20) * routed_scaling_factor`` (``moe_renormalize``); ``y = sum over
  chosen i in expert_ids of w_i expert_i(u) + shared(u)``, the experts as
  a loop over the held ones. What absent experts and heads would add is
  left out.
- final norm, untied head, float32 logits.

Departures from the published code, each on both sides: the score
correction bias is a constant, no balance loss, one chip's share of heads,
experts and vocabulary; ``A_log`` and ``dt_bias`` are leaves centred on
the configuration's ``kda_a_log_centre`` / ``kda_dt_bias_centre``.

K-FAC layers (``kfac_layers``), all ``dense`` on 2-D input but the held
experts' (``rows``): a KDA layer's ``q_proj, k_proj, v_proj, f_a_proj,
g_a_proj, b_proj, f_b_proj, g_b_proj`` (``bias``), ``o_proj``; a latent
layer's four; the dense block's twelve blocks ``gate_j, up_j, down_j``;
the shared ``gate, up, down``. A factor pair a layer: this file knows
nothing of layers that share an input. Convolution weights, ``A_log``,
``dt_bias``, every norm, router, ``e_score_correction_bias`` (no
gradient), embedding and head are first-order.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _kda_width(cfg):
    return len(cfg['kda_head_ids']) * cfg['linear_attn_config']['head_dim']


def _kda_shapes(cfg):
    d, n = cfg['hidden_size'], _kda_width(cfg)
    r, h = cfg['kda_rank'], len(cfg['kda_head_ids'])
    return {'q_proj': (d, n), 'k_proj': (d, n), 'v_proj': (d, n),
            'f_a_proj': (d, r), 'g_a_proj': (d, r), 'b_proj': (d, h),
            'f_b_proj': (r, n), 'g_b_proj': (r, n), 'o_proj': (n, d)}


def _latent_shapes(cfg):
    d, h = cfg['hidden_size'], len(cfg['head_ids'])
    nope, rope, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                      cfg['v_head_dim'])
    return {'q_proj': (d, h * (nope + rope)),
            'kv_a_proj_with_mqa': (d, cfg['kv_lora_rank'] + rope),
            'kv_b_proj': (cfg['kv_lora_rank'], h * (nope + vd)),
            'o_proj': (h * vd, d)}


def _ffn_shapes(d, width):
    return {'gate': (d, width), 'up': (d, width), 'down': (width, d)}


def _is_dense(cfg, i):
    return i < cfg['first_k_dense_replace']


def _blocks(cfg):
    return cfg['intermediate_size'] // cfg['ffn_block']


def kfac_layers(cfg):
    d = cfg['hidden_size']
    held = len(cfg['expert_ids'])
    layers = []

    def dense(path, kernel, bias=False):
        layers.append(dict(path=path, kind='dense', kernel=tuple(kernel),
                           bias=bias))
    for i, kind in enumerate(cfg['layer_kinds_held']):
        p = f'layer_{i}'
        attn = _kda_shapes(cfg) if kind == 'kda' else _latent_shapes(cfg)
        for name, shape in attn.items():
            dense(f'{p}/self_attn/{name}', shape, bias=name == 'g_b_proj')
        if _is_dense(cfg, i):
            for name, shape in _ffn_shapes(d, cfg['ffn_block']).items():
                for j in range(_blocks(cfg)):
                    dense(f'{p}/mlp/{name}_{j}', shape)
            continue
        for name, shape in _ffn_shapes(d, cfg['moe_intermediate_size']
                                       ).items():
            for e in range(held):
                layers.append(dict(
                    path=f'{p}/mlp/experts/{name}/{e}', kind='rows',
                    kernel=tuple(shape), bias=False,
                    leaf=f'{p}/mlp/experts/{name}/kernel', index=e,
                    loss_rows=cfg['tokens_per_step']))
        shared = cfg['num_shared_experts'] * cfg['moe_intermediate_size']
        for name, shape in _ffn_shapes(d, shared).items():
            dense(f'{p}/mlp/shared/{name}', shape)
    return layers


def param_shapes(cfg):
    d, held = cfg['hidden_size'], len(cfg['expert_ids'])
    shapes = {'embed_tokens/embedding': (cfg['vocab_size'], d),
              'norm/scale': (d,), 'lm_head/kernel': (d, cfg['vocab_size'])}
    for layer in kfac_layers(cfg):
        if 'leaf' in layer:
            shapes[layer['leaf']] = (held,) + tuple(layer['kernel'])
        else:
            shapes[layer['path'] + '/kernel'] = tuple(layer['kernel'])
            if layer['bias']:
                shapes[layer['path'] + '/bias'] = (layer['kernel'][-1],)
    lin = cfg['linear_attn_config']
    for i, kind in enumerate(cfg['layer_kinds_held']):
        p = f'layer_{i}'
        for name in ('input_layernorm', 'post_attention_layernorm'):
            shapes[f'{p}/{name}/scale'] = (d,)
        if kind == 'kda':
            n = _kda_width(cfg)
            for x in 'qkv':
                shapes[f'{p}/self_attn/{x}_conv/weight'] = (
                    lin['short_conv_kernel_size'], n)
            shapes[f'{p}/self_attn/A_log'] = (len(cfg['kda_head_ids']),)
            shapes[f'{p}/self_attn/dt_bias'] = (n,)
            shapes[f'{p}/self_attn/o_norm/scale'] = (lin['head_dim'],)
        else:
            shapes[f'{p}/self_attn/kv_a_layernorm/scale'] = (
                cfg['kv_lora_rank'],)
        if not _is_dense(cfg, i):
            n = cfg['num_experts_published']
            shapes[f'{p}/mlp/router/kernel'] = (d, n)
            shapes[f'{p}/mlp/e_score_correction_bias'] = (n,)
    return shapes


def make_batch(cfg, traffic, key):
    """One global batch from ``key``: ids i.i.d. Zipf(1) over the
    vocabulary slice, ``p(i) ~ 1 / (i + 1)``; ``seq_len + 1`` a sequence,
    cut into inputs and next-token labels."""
    n, length = traffic['batch_per_chip'] * traffic['chips'], cfg['seq_len']
    if n * length != cfg['tokens_per_step']:
        raise ValueError(f'traffic gives {n} x {length} tokens a step, the '
                         f'configuration states {cfg["tokens_per_step"]}')
    logits = -jnp.log(jnp.arange(1, cfg['vocab_size'] + 1,
                                 dtype=jnp.float32))
    ids = jax.random.categorical(key, logits, shape=(n, length + 1))
    ids = ids.astype(jnp.int32)
    return {'input': ids[:, :-1], 'label': ids[:, 1:]}


def _norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def short_conv(z, w):
    """``z [B, L, n]``, ``w [K, n]``: ``silu(sum_i w[i] z_{t-K+1+i})``,
    zeros before the sequence's start: ``K`` shifted sums."""
    size, length = w.shape[0], z.shape[1]
    out = jnp.zeros_like(z)
    for i in range(size):
        back = size - 1 - i         # how far behind t this tap reads
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :length - back]], axis=1)
        out = out + w[i] * shifted
    return jax.nn.silu(out)


@jax.checkpoint
def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token. ``q, k, g [B, L, h, dk]``,
    ``v [B, L, h, dv]``, ``beta [B, L, h]``, float32 -> ``(o [B, L, h,
    dv], S_L [B, h, dk, dv])``. The steps' states are made again in the
    backward pass (2,048 of them a layer otherwise kept)."""
    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        miss = v_t - jnp.einsum('bhk,bhkv->bhv', k_t, state)
        state = state + (b_t[..., None] * k_t)[..., :, None] * miss[
            ..., None, :]
        return state, jnp.einsum('bhk,bhkv->bhv', q_t, state)
    start = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:], jnp.float32)
    with jax.default_matmul_precision('highest'):
        end, o = jax.lax.scan(step, start, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), end


def route(cfg, u, kernel, bias):
    """-> (chosen [T, k] expert ids, w [T, k] weights), float32 at
    ``highest``."""
    with jax.default_matmul_precision('highest'):
        s = jax.nn.sigmoid(u.astype(jnp.float32) @ kernel)
    _, chosen = jax.lax.top_k(s + bias, cfg['num_experts_per_token'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg['moe_renormalize']:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg['routed_scaling_factor']


def forward(cfg, params, batch, taps, dtype, rnd=lambda x: x, shapes=None):
    """-> (loss, inputs); see ``resnet_plain.forward``."""
    acts = {}
    eps, f32 = cfg['rms_norm_eps'], jnp.float32
    nope, rope, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                      cfg['v_head_dim'])
    rank, h = cfg['kv_lora_rank'], len(cfg['head_ids'])
    hk, hd = len(cfg['kda_head_ids']), cfg['linear_attn_config']['head_dim']

    def tapped(path, y):
        if shapes is not None:
            shapes[path] = (y.shape, y.dtype)
        return rnd(y + taps[path]) if path in taps else rnd(y)

    def dense(path, x):
        x = rnd(x)
        acts[path] = x
        y = x @ params[path + '/kernel'].astype(dtype)
        if path + '/bias' in params:
            y = y + params[path + '/bias'].astype(dtype)
        return tapped(path, y)

    def swiglu(path, x):
        hid = jax.nn.silu(dense(f'{path}/gate', x)) * dense(f'{path}/up', x)
        return dense(f'{path}/down', hid)

    def kda(p, u):
        a = f'{p}/self_attn'
        q, k, v = (short_conv(
            dense(f'{a}/{x}_proj', u).astype(f32).reshape(n, length, -1),
            params[f'{a}/{x}_conv/weight']).reshape(n, length, hk, hd)
            for x in 'qkv')
        q, k = _l2norm(q) * hd ** -0.5, _l2norm(k)
        f = dense(f'{a}/f_b_proj', dense(f'{a}/f_a_proj', u))
        z = dense(f'{a}/g_b_proj', dense(f'{a}/g_a_proj', u))
        a_log = cfg['kda_a_log_centre'] + params[f'{a}/A_log']
        dt_bias = cfg['kda_dt_bias_centre'] + params[f'{a}/dt_bias']
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f.astype(f32) + dt_bias).reshape(n, length, hk, hd)
        beta = jax.nn.sigmoid(
            dense(f'{a}/b_proj', u).astype(f32)).reshape(n, length, hk)
        o, _ = delta_rule(q, k, v, g, beta)
        o = _norm(o, params[f'{a}/o_norm/scale'], eps).reshape(
            n * length, hk * hd)
        o = (o * jax.nn.sigmoid(z.astype(f32))).astype(dtype)
        return dense(f'{a}/o_proj', o)

    def latent(p, u):
        a = f'{p}/self_attn'
        q = dense(f'{a}/q_proj', u).reshape(n, length, h, nope + rope)
        ckv = dense(f'{a}/kv_a_proj_with_mqa', u)
        c = _norm(ckv[:, :rank], params[f'{a}/kv_a_layernorm/scale'], eps)
        kv = dense(f'{a}/kv_b_proj', c).reshape(n, length, h, nope + vd)
        shared = ckv[:, rank:].reshape(n, length, rope)
        s = (jnp.einsum('blhd,bmhd->bhlm', q[..., :nope], kv[..., :nope])
             + jnp.einsum('blhd,bmd->bhlm', q[..., nope:], shared))
        s = s.astype(f32) / np.sqrt(nope + rope)
        att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = jnp.einsum('bhlm,bmhd->blhd', att.astype(dtype), kv[..., nope:])
        return dense(f'{a}/o_proj', ctx.reshape(n * length, h * vd))

    ids = batch['input']
    n, length = ids.shape
    x = params['embed_tokens/embedding'].astype(dtype)[ids.reshape(-1)]
    causal = np.tril(np.ones((length, length), bool))
    for i, kind in enumerate(cfg['layer_kinds_held']):
        p = f'layer_{i}'
        u = _norm(x, params[f'{p}/input_layernorm/scale'], eps)
        x = x + (kda(p, u) if kind == 'kda' else latent(p, u))
        u = _norm(x, params[f'{p}/post_attention_layernorm/scale'], eps)
        if _is_dense(cfg, i):
            x = x + sum(dense(
                f'{p}/mlp/down_{j}',
                jax.nn.silu(dense(f'{p}/mlp/gate_{j}', u))
                * dense(f'{p}/mlp/up_{j}', u)) for j in range(_blocks(cfg)))
            continue
        chosen, w = route(cfg, u, params[f'{p}/mlp/router/kernel'],
                          params[f'{p}/mlp/e_score_correction_bias'])
        u_in = rnd(u)
        y = jnp.zeros(u.shape, f32)
        for e, expert in enumerate(cfg['expert_ids']):
            hit = chosen == expert                              # [T, k]
            came = hit.any(axis=-1).astype(f32)                 # 0 / 1
            weight = jnp.where(hit, w, 0.0).sum(axis=-1)        # [T]

            def proj(name, a):
                path = f'{p}/mlp/experts/{name}/{e}'
                acts[path] = (a, came)
                kernel = params[f'{p}/mlp/experts/{name}/kernel'][e]
                return tapped(path, a @ kernel.astype(dtype))
            hid = rnd(jax.nn.silu(proj('gate', u_in)) * proj('up', u_in))
            y = y + weight[:, None] * proj('down', hid).astype(f32)
        x = x + y.astype(dtype) + swiglu(f'{p}/mlp/shared', u)
    x = _norm(x, params['norm/scale'], eps)
    logits = (rnd(x) @ params['lm_head/kernel'].astype(dtype)).astype(f32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    label = batch['label'].reshape(-1)
    loss = -jnp.take_along_axis(logp, label[:, None], axis=-1).mean()
    return loss, acts
