"""ImageNet ResNets, built as ``examples/imagenet_resnet.py`` builds them:
the model zoo's torchvision-layout ResNet in the configuration's
activation dtype, SGD with momentum and weight decay, label-smoothed
cross-entropy, batch statistics as the extra mutable collection, and the
K-FAC variant and hyper-parameters of the configuration file."""

import jax.numpy as jnp

import kfac_pytorch_tpu
from kfac_pytorch_tpu import training, utils
from kfac_pytorch_tpu.models import imagenet_resnet


def build(config, traffic, kfac=True, axis_name=None):
    m, opt, k = config['model'], config['optimizer'], config['kfac']
    if list(m['stage_planes']) != [64, 128, 256, 512] or m['expansion'] != 4:
        raise ValueError('the model zoo fixes the stage widths')
    dtype = jnp.dtype(config['dtype']['activations'])
    model = imagenet_resnet.ResNet(
        block=imagenet_resnet.Bottleneck, layers=tuple(m['stage_blocks']),
        num_classes=m['num_classes'], dtype=dtype)
    tx = training.sgd(opt['lr'], momentum=opt['momentum'],
                      weight_decay=opt['weight_decay'])
    precond = None
    if kfac:
        precond = kfac_pytorch_tpu.get_kfac_module(k['variant'])(
            lr=opt['lr'], damping=k['damping'],
            fac_update_freq=traffic['fac_update_freq'],
            kfac_update_freq=traffic['kfac_update_freq'],
            kl_clip=k['kl_clip'], factor_decay=k['ema_new_weight'],
            num_devices=traffic['chips'], axis_name=axis_name,
            assignment=k['assignment'])

    def loss_fn(outputs, batch):
        return utils.label_smoothing_cross_entropy(
            outputs, batch['label'], smoothing=m['label_smoothing'])

    sample = jnp.zeros((traffic['batch_per_chip'] * traffic['chips'],
                        m['image_size'], m['image_size'], m['in_channels']),
                       dtype)

    def init_state(rng):
        return training.init_train_state(model, tx, precond, rng, sample)

    return dict(model=model, tx=tx, precond=precond, loss_fn=loss_fn,
                init_state=init_state,
                step_kwargs=dict(extra_mutable=('batch_stats',)))

