"""ImageNet ResNet (He et al. 2015, torchvision layout) in plain jax.numpy.

The reference's own forward pass: imports nothing of the program. Layers
are named by the path of their parameters (``layer2_0/conv1``), which is
the only thing shared with the system under test. Batch norm runs in
training mode (batch statistics; statistics in float32, result cast to the
activation dtype), the stem is 7x7/2 + 3x3/2 max-pool, blocks are
bottlenecks with the stride on the 3x3 (v1.5), shortcuts are 1x1
projections, the head is global average pooling and one dense layer, the
loss is cross-entropy against a label-smoothed target.

A K-FAC layer's input is recorded and a zero ``tap`` is added to its
output, so that differentiating with respect to the taps gives the
output's cotangent.
"""

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def _blocks(cfg):
    """(name, in_planes, planes, stride, has_projection) per block."""
    out, in_planes = [], cfg['stem_features']
    exp = cfg['expansion']
    for stage, (planes, n) in enumerate(zip(cfg['stage_planes'],
                                            cfg['stage_blocks'])):
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            proj = stride != 1 or in_planes != planes * exp
            out.append((f'layer{stage + 1}_{i}', in_planes, planes, stride,
                        proj))
            in_planes = planes * exp
    return out, in_planes


def kfac_layers(cfg):
    """K-FAC layers in forward order: dict(path, kind, kernel, stride,
    pad, bias)."""
    def conv(path, k, cin, cout, stride, pad):
        return dict(path=path, kind='conv', kernel=(k, k, cin, cout),
                    stride=stride, pad=pad, bias=False)
    layers = [conv('conv1', 7, cfg['in_channels'], cfg['stem_features'],
                   2, 3)]
    blocks, feat = _blocks(cfg)
    exp = cfg['expansion']
    for name, cin, planes, stride, proj in blocks:
        layers.append(conv(f'{name}/conv1', 1, cin, planes, 1, 0))
        layers.append(conv(f'{name}/conv2', 3, planes, planes, stride, 1))
        layers.append(conv(f'{name}/conv3', 1, planes, planes * exp, 1, 0))
        if proj:
            layers.append(conv(f'{name}/ds_conv', 1, cin, planes * exp,
                               stride, 0))
    layers.append(dict(path='fc', kind='dense',
                       kernel=(feat, cfg['num_classes']), bias=True))
    return layers


def param_shapes(cfg):
    """{path: shape} of every trainable parameter."""
    shapes = {}
    for layer in kfac_layers(cfg):
        shapes[layer['path'] + '/kernel'] = tuple(layer['kernel'])
        if layer['bias']:
            shapes[layer['path'] + '/bias'] = (layer['kernel'][-1],)
        if layer['kind'] == 'conv':
            # every conv is followed by a batch norm of its width
            bn = layer['path'].replace('ds_conv', 'ds_bn').replace(
                'conv', 'bn')
            for leaf in ('scale', 'bias'):
                shapes[f'{bn}/{leaf}'] = (layer['kernel'][-1],)
    return shapes


def make_batch(cfg, traffic, key):
    """One global batch from ``key``: normal images, uniform labels."""
    n, s = traffic['batch_per_chip'] * traffic['chips'], cfg['image_size']
    k1, k2 = jax.random.split(key)
    return {
        'input': jax.random.normal(k1, (n, s, s, cfg['in_channels']),
                                   jnp.dtype(cfg['input_dtype'])),
        'label': jax.random.randint(k2, (n,), 0, cfg['num_classes']),
    }


def _bn(x, scale, bias, dtype):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=(0, 1, 2))
    var = jnp.maximum((xf * xf).mean(axis=(0, 1, 2)) - mean * mean, 0.0)
    y = (xf - mean) * (lax.rsqrt(var + BN_EPS) * scale) + bias
    return y.astype(dtype)


def forward(cfg, params, batch, taps, dtype, rnd=lambda x: x, shapes=None):
    """-> (loss, inputs): the loss of ``batch`` and each K-FAC layer's
    input, keyed by path. ``taps[path]`` is added to that layer's
    output. ``rnd`` rounds a K-FAC layer's input and output (and their
    cotangents): the identity, or the lower precision of a control.
    With ``taps`` empty and ``shapes`` a dict, the output shapes are
    written into it (for building the taps)."""
    acts = {}

    def tapped(path, y):
        if shapes is not None:
            shapes[path] = (y.shape, y.dtype)
        return rnd(y + taps[path]) if path in taps else rnd(y)

    def conv(path, x, stride, pad):
        x = rnd(x)
        acts[path] = x
        k = params[path + '/kernel'].astype(dtype)
        y = lax.conv_general_dilated(
            x, k, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        return tapped(path, y)

    def bn(path, x):
        return _bn(x, params[path + '/scale'], params[path + '/bias'],
                   dtype)

    x = batch['input'].astype(dtype)
    x = jax.nn.relu(bn('bn1', conv('conv1', x, 2, 3)))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for name, _, _, stride, proj in _blocks(cfg)[0]:
        idt = x
        out = jax.nn.relu(bn(f'{name}/bn1', conv(f'{name}/conv1', x, 1, 0)))
        out = jax.nn.relu(bn(f'{name}/bn2',
                             conv(f'{name}/conv2', out, stride, 1)))
        out = bn(f'{name}/bn3', conv(f'{name}/conv3', out, 1, 0))
        if proj:
            idt = bn(f'{name}/ds_bn',
                     conv(f'{name}/ds_conv', x, stride, 0))
        x = jax.nn.relu(out + idt)
    x = rnd(x.mean(axis=(1, 2)))
    acts['fc'] = x
    logits = x @ params['fc/kernel'].astype(dtype)
    logits = tapped('fc', logits + params['fc/bias'].astype(dtype))

    classes, eps = cfg['num_classes'], cfg['label_smoothing']
    logp = jax.nn.log_softmax(logits, axis=-1)
    target = (jax.nn.one_hot(batch['label'], classes) * (1.0 - eps)
              + eps / classes)
    return -(target * logp).sum(axis=-1).mean(), acts
