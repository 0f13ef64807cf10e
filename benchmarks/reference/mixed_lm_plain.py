"""One chip's share of a sparse decoder whose layers differ in kind (window
and full attention mixed, grouped-query, gated, QK-normed; four norms a
block; sigmoid-routed experts with a shared expert: the ``afmoe`` block as
``arcee-ai/Trinity-Mini`` publishes it) in plain jax.numpy: the reference's
own forward pass, importing nothing of the program.

Flat tokens: a batch of ``B`` sequences of ``L + 1`` ids gives ``T = B L``
rows (``input = ids[:, :-1]``, ``label = ids[:, 1:]``; no position is
masked), every projection sees ``[T, d]``, and the loss is the mean over
the ``T`` tokens of the next-token cross-entropy. Per held layer ``i``,
``x`` the residual stream, RMS norms in float32, no biases anywhere:

- ``u = norm(x)``; ``q = u W_q -> [T, h, hd]``, ``k = u W_k -> [T, g,
  hd]``, ``v = u W_v -> [T, g, hd]``, ``z = u W_z -> [T, h hd]``; ``q`` and
  ``k`` RMS-normed over ``hd`` (one scale vector each a layer). Where
  ``layer_types_held[i]`` is ``sliding_attention``: rotary on ``q`` and
  ``k`` (halves: ``x cos + rotate_half(x) sin``, positions within the
  sequence) and ``l`` sees ``m`` with ``0 <= l - m < sliding_window``;
  where ``full_attention``: no rotary, ``m <= l``. Query head ``j`` (its
  published id) reads key/value head ``j // (published heads / published
  key/value heads)``; here every query head's keys are gathered, so the
  sum over a group's query heads in ``W_k``'s gradient is autodiff's.
  ``softmax(q k' / sqrt(hd)) v``, the softmax in float32;
  ``x += norm((. * sigmoid(z)) W_o)``, the gate's product in float32.
- ``u = norm(x)``; the first ``first_k_dense_replace`` held layers:
  ``y = (silu(u W_g) * u W_u) W_d``. The others: ``s = sigmoid(u W_r)``
  over ALL ``num_experts_published`` outputs, float32 at ``highest``; top-k
  of ``s + b``; weights ``s_i / (sum over the chosen s + 1e-20) *
  route_scale`` (``route_norm``), the sum over all chosen wherever they
  live; ``y = sum over chosen i in expert_ids of w_i expert_i(u) +
  shared(u)``. ``x += norm(y)``. What absent experts and heads would add
  is left out.
- the embedding's rows times ``sqrt(hidden_size)`` (``mup_enabled``); final
  norm, untied head, float32 logits.

Departures from the published code, each on both sides: the expert bias
``b`` is a constant (no update rule is published), no balance loss, one
chip's share of heads, experts and vocabulary.

Every expert is computed on all ``T`` rows and masked (plain, not fast).
K-FAC layers (``kfac_layers``): the five attention projections and the
dense / shared ``gate`` / ``up`` / ``down`` as ``dense`` on 2-D input; each
held expert's ``gate`` / ``up`` / ``down`` as ``rows`` of slice ``index`` of
the stacked leaf, with the 0/1 row weight "token chose this expert" and
``loss_rows`` = T. A factor pair a layer: this file knows nothing of layers
that share an input. Router, ``e_score_correction_bias`` (no gradient),
norms (the query/key norms too), embedding and head are first-order.
"""

import jax
import jax.numpy as jnp
import numpy as np

_FFN = ('gate', 'up', 'down')
_NORMS = ('input_layernorm', 'post_attention_layernorm', 'pre_mlp_layernorm',
          'post_mlp_layernorm')


def _attn_shapes(cfg):
    h, g = len(cfg['q_head_ids']), len(cfg['kv_head_ids'])
    d, hd = cfg['hidden_size'], cfg['head_dim']
    return {'q_proj': (d, h * hd), 'k_proj': (d, g * hd),
            'v_proj': (d, g * hd), 'gate_proj': (d, h * hd),
            'o_proj': (h * hd, d)}


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
    for i in range(cfg['num_hidden_layers']):
        p = f'layer_{i}'
        for name in _NORMS:
            shapes[f'{p}/{name}/scale'] = (d,)
        for name in ('q_norm', 'k_norm'):
            shapes[f'{p}/self_attn/{name}/scale'] = (cfg['head_dim'],)
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


def rotary(x, theta):
    """``x [B, L, H, D]``: ``x cos + rotate_half(x) sin`` with the angle
    ``l theta^(-2i/D)`` for both ``x_i`` and ``x_{i + D/2}``, in float32."""
    d, length = x.shape[-1], x.shape[1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = np.arange(length, dtype=np.float32)[:, None] * freq
    angle = np.concatenate([angle, angle], axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return (xf * np.cos(angle) + half * np.sin(angle)).astype(x.dtype)


def route(cfg, u, kernel, bias):
    """-> (chosen [T, k] expert ids, w [T, k] weights), float32 at
    ``highest``."""
    with jax.default_matmul_precision('highest'):
        s = jax.nn.sigmoid(u.astype(jnp.float32) @ kernel)
    _, chosen = jax.lax.top_k(s + bias, cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg['route_norm']:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg['route_scale']


def attention_mask(length, window):
    """``[L, L]`` bool: position ``l`` (rows) sees ``m`` (columns)."""
    gap = np.arange(length)[:, None] - np.arange(length)[None, :]
    return (gap >= 0) if window is None else (gap >= 0) & (gap < window)


def forward(cfg, params, batch, taps, dtype, rnd=lambda x: x, shapes=None):
    """-> (loss, inputs); see ``resnet_plain.forward``."""
    acts = {}
    eps, theta, hd = cfg['rms_norm_eps'], cfg['rope_theta'], cfg['head_dim']
    q_ids, kv_ids = list(cfg['q_head_ids']), list(cfg['kv_head_ids'])
    group = (cfg['num_attention_heads_published']
             // cfg['num_key_value_heads_published'])
    # the held key/value head each held query head reads
    reads = np.asarray([kv_ids.index(j // group) for j in q_ids])
    h, g = len(q_ids), len(kv_ids)

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
    if cfg['mup_enabled']:
        x = x * jnp.asarray(np.sqrt(cfg['hidden_size']), dtype)
    for i in range(cfg['num_hidden_layers']):
        p = f'layer_{i}'
        window = (cfg['sliding_window']
                  if cfg['layer_types_held'][i] == 'sliding_attention'
                  else None)
        u = _norm(x, params[f'{p}/input_layernorm/scale'], eps)
        q = dense(f'{p}/self_attn/q_proj', u).reshape(n, length, h, hd)
        k = dense(f'{p}/self_attn/k_proj', u).reshape(n, length, g, hd)
        v = dense(f'{p}/self_attn/v_proj', u).reshape(n, length, g, hd)
        z = dense(f'{p}/self_attn/gate_proj', u)
        q = _norm(q, params[f'{p}/self_attn/q_norm/scale'], eps)
        k = _norm(k, params[f'{p}/self_attn/k_norm/scale'], eps)
        if window is not None:
            q, k = rotary(q, theta), rotary(k, theta)
        s = jnp.einsum('blhd,bmhd->bhlm', q, k[:, :, reads])
        s = s.astype(jnp.float32) / np.sqrt(hd)
        att = jax.nn.softmax(
            jnp.where(attention_mask(length, window), s, -jnp.inf), axis=-1)
        ctx = jnp.einsum('bhlm,bmhd->blhd', att.astype(dtype),
                         v[:, :, reads]).reshape(n * length, h * hd)
        ctx = (ctx.astype(jnp.float32)
               * jax.nn.sigmoid(z.astype(jnp.float32))).astype(dtype)
        x = x + _norm(dense(f'{p}/self_attn/o_proj', ctx),
                      params[f'{p}/post_attention_layernorm/scale'], eps)
        u = _norm(x, params[f'{p}/pre_mlp_layernorm/scale'], eps)
        if _is_dense(cfg, i):
            y = swiglu(f'{p}/mlp', u)
        else:
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
                hid = rnd(jax.nn.silu(proj('gate', u_in))
                          * proj('up', u_in))
                y = y + weight[:, None] * proj('down', hid).astype(
                    jnp.float32)
            y = y.astype(dtype) + swiglu(f'{p}/mlp/shared', u)
        x = x + _norm(y, params[f'{p}/post_mlp_layernorm/scale'], eps)
    x = _norm(x, params['norm/scale'], eps)
    logits = (rnd(x) @ params['lm_head/kernel'].astype(dtype)).astype(
        jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    label = batch['label'].reshape(-1)
    loss = -jnp.take_along_axis(logp, label[:, None], axis=-1).mean()
    return loss, acts
