"""Model zoo — the reference's example models rebuilt in Flax/NHWC with
KFAC-aware layers (reference zoo: examples/cifar_resnet.py,
cifar_vgg.py, cifar_wide_resnet.py, imagenet_resnet.py,
imagenet_inceptionv4.py, examples/transformer/, wikitext_models.py), and
beyond it the language models the trainers build by name: ``transformer_lm``
(LayerNorm/GELU decoder), ``sparse_decoder_lm`` (latent attention,
sigmoid-routed experts: one chip's share), ``mixed_decoder_lm`` (window
and full attention mixed, grouped-query, gated, QK-normed; routed experts:
one chip's share) and ``hybrid_decoder_lm`` (a gated delta-rule recurrence
and latent attention without positions mixed; routed experts: one chip's
share)."""

from kfac_pytorch_tpu.models.cifar_resnet import (
    resnet20, resnet32, resnet44, resnet56, resnet110)
from kfac_pytorch_tpu.models.cifar_vgg import vgg11, vgg13, vgg16, vgg19
from kfac_pytorch_tpu.models.cifar_wide_resnet import wrn_28_10
from kfac_pytorch_tpu.models.imagenet_resnet import (
    resnet18, resnet34, resnet50, resnet101, resnet152,
    resnext50_32x4d, resnext101_32x8d)
from kfac_pytorch_tpu.models.densenet import (
    densenet121, densenet169, densenet201)
from kfac_pytorch_tpu.models.inception_v4 import inception_v4
from kfac_pytorch_tpu.models.rnn import wikitext_lstm
from kfac_pytorch_tpu.models.gpt import TransformerLM, transformer_lm
from kfac_pytorch_tpu.models.sparse_decoder import (
    SparseDecoderConfig, SparseDecoderLM, sparse_decoder_lm)
from kfac_pytorch_tpu.models.mixed_decoder import (
    MixedDecoderConfig, MixedDecoderLM, held_layer_types, mixed_decoder_lm)
from kfac_pytorch_tpu.models.hybrid_decoder import (
    HybridDecoderConfig, HybridDecoderLM, held_layer_kinds,
    hybrid_decoder_lm)


def get_model(name, num_classes=10, **kw):
    """Name-based factory mirroring the ``--model`` flag surface of the
    reference entrypoints (examples/pytorch_cifar10_resnet.py:203-217)."""
    registry = {
        'resnet20': resnet20, 'resnet32': resnet32, 'resnet44': resnet44,
        'resnet56': resnet56, 'resnet110': resnet110,
        'vgg11': vgg11, 'vgg13': vgg13, 'vgg16': vgg16, 'vgg19': vgg19,
        'wrn-28-10': wrn_28_10, 'wideresnet': wrn_28_10,
        'resnet18': resnet18, 'resnet34': resnet34, 'resnet50': resnet50,
        'resnet101': resnet101, 'resnet152': resnet152,
        'resnext50': resnext50_32x4d, 'resnext101': resnext101_32x8d,
        'inceptionv4': inception_v4, 'inception-v4': inception_v4,
        'densenet121': densenet121, 'densenet169': densenet169,
        'densenet201': densenet201,
    }
    if name not in registry:
        raise KeyError(f'unknown model {name!r}')
    return registry[name](num_classes=num_classes, **kw)
