"""One chip's share of a sparse decoder (latent attention, sigmoid-routed
experts with shared experts; DeepSeek-V3's block as
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` publishes it) in plain
jax.numpy: the reference's own forward pass, importing nothing of the
program.

Flat tokens: a batch of ``B`` sequences of ``L + 1`` ids gives ``T = B L``
rows (``input = ids[:, :-1]``, ``label = ids[:, 1:]``; no position is
masked), every projection sees ``[T, d]``, and the loss is the mean over
the ``T`` tokens of the next-token cross-entropy. Per layer, ``x`` the
residual stream, RMS norms in float32, no biases anywhere:

- ``u = norm(x)``; ``q = u W_q -> [T, h, nope + rope]``; ``u W_kva`` =
  ``c`` (``kv_lora_rank``) | ``k_rope`` (one for all heads);
  ``norm(c) W_kvb -> [T, h, nope + v]`` = ``k_nope`` | ``v``; rotary on
  ``q_rope`` and ``k_rope`` (interleaved pairs: the complex product of
  ``x_2i + j x_2i+1`` with ``exp(j pos theta^(-2i/rope))``, positions
  within the sequence); causal ``softmax(q k' / sqrt(nope + rope)) v``,
  the softmax in float32; ``x += . W_o``.
- ``u = norm(x)``; the first ``first_k_dense_replace`` layers:
  ``x += (silu(u W_g) * u W_u) W_d``. The others: ``s = sigmoid(u W_r)``
  over ALL ``n_routed_experts_published`` outputs, float32 at ``highest``
  on both sides; top-k of ``s + b``; weights ``s_i / (sum over the chosen
  s + 1e-20) * routed_scaling_factor``, the sum over all chosen wherever
  they live; ``x += sum over chosen i in expert_ids of w_i expert_i(u) +
  shared(u)``. What absent experts and heads would add is left out.
- final norm, untied head, float32 logits.

Every expert is computed on all ``T`` rows and masked (plain, not fast).
K-FAC layers (``kfac_layers``): the four attention projections and the
dense / shared ``gate`` / ``up`` / ``down`` as ``dense`` on 2-D input
(flat tokens: no sequence mean); each held expert's ``gate`` / ``up`` /
``down`` as ``rows`` of slice ``index`` of the stacked leaf, with the 0/1
row weight "token chose this expert" and ``loss_rows`` = T. Router,
``e_score_correction_bias`` (no gradient), norms, embedding and head are
first-order.
"""

import jax
import jax.numpy as jnp
import numpy as np

_ATTN = ('q_proj', 'kv_a_proj_with_mqa', 'kv_b_proj', 'o_proj')
_FFN = ('gate', 'up', 'down')


def _attn_shapes(cfg):
    h = len(cfg['head_ids'])
    d, r = cfg['hidden_size'], cfg['kv_lora_rank']
    nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                     cfg['v_head_dim'])
    return {'q_proj': (d, h * (nope + rope)),
            'kv_a_proj_with_mqa': (d, r + rope),
            'kv_b_proj': (r, h * (nope + v)), 'o_proj': (h * v, d)}


def _ffn_shapes(d, width):
    return {'gate': (d, width), 'up': (d, width), 'down': (width, d)}


def _is_dense(cfg, i):
    return i < cfg['first_k_dense_replace']


def kfac_layers(cfg):
    d = cfg['hidden_size']
    held = len(cfg['expert_ids'])
    layers = []

    def dense(path, kernel):
        layers.append(dict(path=path, kind='dense', kernel=tuple(kernel),
                           bias=False))
    for i in range(cfg['num_hidden_layers']):
        p = f'layer_{i}'
        for name, shape in _attn_shapes(cfg).items():
            dense(f'{p}/self_attn/{name}', shape)
        if _is_dense(cfg, i):
            for name, shape in _ffn_shapes(d, cfg['intermediate_size']
                                           ).items():
                dense(f'{p}/mlp/{name}', shape)
            continue
        for name, shape in _ffn_shapes(d, cfg['moe_intermediate_size']
                                       ).items():
            for e in range(held):
                layers.append(dict(
                    path=f'{p}/mlp/experts/{name}/{e}', kind='rows',
                    kernel=tuple(shape), bias=False,
                    leaf=f'{p}/mlp/experts/{name}/kernel', index=e,
                    loss_rows=cfg['tokens_per_step']))
        shared = cfg['n_shared_experts'] * cfg['moe_intermediate_size']
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
    for i in range(cfg['num_hidden_layers']):
        p = f'layer_{i}'
        shapes[f'{p}/input_layernorm/scale'] = (d,)
        shapes[f'{p}/post_attention_layernorm/scale'] = (d,)
        shapes[f'{p}/self_attn/kv_a_layernorm/scale'] = (
            cfg['kv_lora_rank'],)
        if not _is_dense(cfg, i):
            n = cfg['n_routed_experts_published']
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


def rotary(x, theta):
    """``x [B, L, ..., D]``: each pair ``(x_2i, x_2i+1)`` times
    ``exp(j l theta^(-2i/D))`` as a complex number, in float32."""
    d, length = x.shape[-1], x.shape[1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = np.arange(length, dtype=np.float32)[:, None] * freq
    angle = angle.reshape((1, length) + (1,) * (x.ndim - 3) + (d // 2,))
    xf = x.astype(jnp.float32)
    z = jax.lax.complex(xf[..., 0::2], xf[..., 1::2]) * jnp.exp(
        1j * jnp.asarray(angle)).astype(jnp.complex64)
    out = jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def route(cfg, u, kernel, bias):
    """-> (chosen [T, k] expert ids, w [T, k] weights), float32 at
    ``highest``."""
    with jax.default_matmul_precision('highest'):
        s = jax.nn.sigmoid(u.astype(jnp.float32) @ kernel)
    _, chosen = jax.lax.top_k(s + bias, cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg['norm_topk_prob']:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg['routed_scaling_factor']


def forward(cfg, params, batch, taps, dtype, rnd=lambda x: x, shapes=None):
    """-> (loss, inputs); see ``resnet_plain.forward``."""
    acts = {}
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    nope, rope, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                      cfg['v_head_dim'])
    rank, h = cfg['kv_lora_rank'], len(cfg['head_ids'])

    def tapped(path, y):
        if shapes is not None:
            shapes[path] = (y.shape, y.dtype)
        return rnd(y + taps[path]) if path in taps else rnd(y)

    def dense(path, x):
        x = rnd(x)
        acts[path] = x
        return tapped(path, x @ params[path + '/kernel'].astype(dtype))

    def swiglu(path, x):
        hid = jax.nn.silu(dense(f'{path}/gate', x)) * dense(f'{path}/up', x)
        return dense(f'{path}/down', hid)

    ids = batch['input']
    n, length = ids.shape
    x = params['embed_tokens/embedding'].astype(dtype)[ids.reshape(-1)]
    causal = np.tril(np.ones((length, length), bool))
    for i in range(cfg['num_hidden_layers']):
        p = f'layer_{i}'
        u = _norm(x, params[f'{p}/input_layernorm/scale'], eps)
        q = dense(f'{p}/self_attn/q_proj', u).reshape(n, length, h,
                                                      nope + rope)
        ckv = dense(f'{p}/self_attn/kv_a_proj_with_mqa', u)
        c = _norm(ckv[:, :rank], params[f'{p}/self_attn/kv_a_layernorm/scale'],
                  eps)
        kv = dense(f'{p}/self_attn/kv_b_proj', c).reshape(n, length, h,
                                                          nope + vd)
        q_rope = rotary(q[..., nope:], theta)
        k_rope = rotary(ckv[:, rank:].reshape(n, length, rope), theta)
        s = (jnp.einsum('blhd,bmhd->bhlm', q[..., :nope], kv[..., :nope])
             + jnp.einsum('blhd,bmd->bhlm', q_rope, k_rope))
        s = s.astype(jnp.float32) / np.sqrt(nope + rope)
        att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = jnp.einsum('bhlm,bmhd->blhd', att.astype(dtype), kv[..., nope:])
        x = x + dense(f'{p}/self_attn/o_proj',
                      ctx.reshape(n * length, h * vd))
        u = _norm(x, params[f'{p}/post_attention_layernorm/scale'], eps)
        if _is_dense(cfg, i):
            x = x + swiglu(f'{p}/mlp', u)
            continue
        chosen, w = route(cfg, u, params[f'{p}/mlp/router/kernel'],
                          params[f'{p}/mlp/e_score_correction_bias'])
        u_in = rnd(u)
        y = jnp.zeros(u.shape, jnp.float32)
        for e, expert in enumerate(cfg['expert_ids']):
            hit = chosen == expert                              # [T, k]
            came = hit.any(axis=-1).astype(jnp.float32)         # 0 / 1
            weight = jnp.where(hit, w, 0.0).sum(axis=-1)        # [T]

            def proj(name, a):
                path = f'{p}/mlp/experts/{name}/{e}'
                acts[path] = (a, came)
                kernel = params[f'{p}/mlp/experts/{name}/kernel'][e]
                return tapped(path, a @ kernel.astype(dtype))
            hid = rnd(jax.nn.silu(proj('gate', u_in)) * proj('up', u_in))
            y = y + weight[:, None] * proj('down', hid).astype(jnp.float32)
        x = x + y.astype(dtype) + swiglu(f'{p}/mlp/shared', u)
    x = _norm(x, params['norm/scale'], eps)
    logits = (rnd(x) @ params['lm_head/kernel'].astype(dtype)).astype(
        jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    label = batch['label'].reshape(-1)
    loss = -jnp.take_along_axis(logp, label[:, None], axis=-1).mean()
    return loss, acts
