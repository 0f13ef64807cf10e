"""BERT encoder with a SQuAD span head in plain jax.numpy.

The reference's own forward pass (Devlin et al. 2018; sizes from the
published ``config.json``): imports nothing of the program. Post-norm
encoder, learned positions, exact (erf) GELU, layer norm in float32,
softmax attention with an additive mask, span head ``hidden -> 2``; the
loss is the mean of the start and end cross-entropies. Dropout is off (the
configuration file says so under ``reduced``), which is what lets a second
implementation follow the step at all.

Every dense layer of the encoder and the span head is a K-FAC layer: its
input is recorded and a zero ``tap`` is added to its output.
"""

import jax
import jax.numpy as jnp
import numpy as np

_DENSE = ('attention/query', 'attention/key', 'attention/value',
          'attention/output', 'intermediate', 'ffn_output')


def kfac_layers(cfg):
    h, f = cfg['hidden_size'], cfg['intermediate_size']
    layers = []
    for i in range(cfg['num_hidden_layers']):
        for name in _DENSE:
            d_in = f if name == 'ffn_output' else h
            d_out = f if name == 'intermediate' else h
            layers.append(dict(path=f'bert/layer_{i}/{name}', kind='dense',
                               kernel=(d_in, d_out), bias=True))
    layers.append(dict(path='qa_outputs', kind='dense', kernel=(h, 2),
                       bias=True))
    return layers


def param_shapes(cfg):
    h = cfg['hidden_size']
    shapes = {
        'bert/word_emb/embedding': (cfg['vocab_size'], h),
        'bert/pos_emb/embedding': (cfg['max_position_embeddings'], h),
        'bert/type_emb/embedding': (cfg['type_vocab_size'], h),
        'bert/emb_ln/scale': (h,), 'bert/emb_ln/bias': (h,),
    }
    for layer in kfac_layers(cfg):
        shapes[layer['path'] + '/kernel'] = tuple(layer['kernel'])
        shapes[layer['path'] + '/bias'] = (layer['kernel'][1],)
    for i in range(cfg['num_hidden_layers']):
        for ln in (f'bert/layer_{i}/attention/ln', f'bert/layer_{i}/ln'):
            shapes[ln + '/scale'] = (h,)
            shapes[ln + '/bias'] = (h,)
    return shapes


def make_batch(cfg, traffic, key):
    """One global batch from ``key``: uniform token ids, a question /
    context split of the segment ids, no padding, uniform span labels."""
    n, length = traffic['batch_per_chip'] * traffic['chips'], cfg['seq_len']
    k1, k2, k3 = jax.random.split(key, 3)
    ids = jax.random.randint(k1, (n, length), 0, cfg['vocab_size'])
    split = jax.random.randint(k2, (n, 1), 8, length // 4)
    types = (jnp.arange(length)[None] >= split).astype(jnp.int32)
    mask = jnp.ones((n, length), jnp.float32)
    label = jax.random.randint(k3, (n, 2), 0, length)
    return {'input': (ids, types, mask), 'label': label}


def _ln(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = jnp.maximum((xf * xf).mean(-1, keepdims=True) - mean * mean, 0.0)
    y = (xf - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias
    return y.astype(x.dtype)


def forward(cfg, params, batch, taps, dtype, rnd=lambda x: x, shapes=None):
    """-> (loss, inputs); see ``resnet_plain.forward``."""
    acts = {}
    eps = cfg['layer_norm_eps']
    heads = cfg['num_attention_heads']

    def dense(path, x):
        x = rnd(x)
        acts[path] = x
        y = x @ params[path + '/kernel'].astype(dtype)
        y = y + params[path + '/bias'].astype(dtype)
        if shapes is not None:
            shapes[path] = (y.shape, y.dtype)
        return rnd(y + taps[path]) if path in taps else rnd(y)

    def ln(path, x):
        return _ln(x, params[path + '/scale'], params[path + '/bias'], eps)

    ids, types, mask = batch['input']
    n, length = ids.shape
    x = (params['bert/word_emb/embedding'][ids]
         + params['bert/pos_emb/embedding'][jnp.arange(length)][None]
         + params['bert/type_emb/embedding'][types]).astype(dtype)
    x = ln('bert/emb_ln', x)
    d = cfg['hidden_size'] // heads
    for i in range(cfg['num_hidden_layers']):
        p = f'bert/layer_{i}'

        def split(t):
            return t.reshape(n, length, heads, d).transpose(0, 2, 1, 3)
        q = split(dense(f'{p}/attention/query', x))
        k = split(dense(f'{p}/attention/key', x))
        v = split(dense(f'{p}/attention/value', x))
        att = jnp.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(d)
        att = att + ((1.0 - mask[:, None, None, :]) * -1e9).astype(att.dtype)
        att = jax.nn.softmax(att, axis=-1)
        ctx = jnp.einsum('bhqk,bhkd->bhqd', att, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, length, heads * d)
        x = ln(f'{p}/attention/ln', dense(f'{p}/attention/output', ctx) + x)
        hid = jax.nn.gelu(dense(f'{p}/intermediate', x), approximate=False)
        x = ln(f'{p}/ln', dense(f'{p}/ffn_output', hid) + x)
    logits = dense('qa_outputs', x)

    def xent(lg, lab):
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1).mean()
    loss = (xent(logits[..., 0], batch['label'][:, 0])
            + xent(logits[..., 1], batch['label'][:, 1])) / 2.0
    return loss, acts
