"""Share of the chip's bf16 peak that the routed experts' grouped products
reach: their multiply-adds AS THE DEVICE RUNS THEM over the device time
under ``scope`` times the peak, in %.

A held expert's buffer has ``expert_capacity`` rows whatever came to it,
so one grouped product (``[E, C, d] x [E, d, w]``, or either of its two
backward products, which contract the same three sizes) is ``2 E C d w``
operations, ``d`` the hidden size and ``w`` the expert's width:
:func:`product_flops`, from the configuration alone. A step runs nine of
them an expert layer (``gate``, ``up``, ``down``: forward, input
cotangent, weight gradient). Only the products that the trace shows under
the scope are counted: the operations there whose ``hlo_category`` is
``convolution fusion`` (fusions change owner across scope borders, PERF.md
section 6; a product the compiler moved elsewhere is neither timed nor
counted). The time is everything of its own under the scope, the
activation between the products too, so the share understates the MXU's.
More such operations a step than nine a layer means the compiler split the
products and one no longer holds ``2 E C d w``: an error, not a share.
No trace, or no operation under the scope (a program without it): None.
"""

import json
import os

from harness import files, tracefile

_PRODUCT = 'hlo_category=convolution fusion'


def product_flops(config):
    """Operations of one grouped product of the routed experts' buffers."""
    m = config['model']
    return (2 * len(m['expert_ids']) * m['expert_capacity']
            * m['hidden_size'] * m['moe_intermediate_size'])


def products_per_step(config):
    m = config['model']
    return 9 * (m['num_hidden_layers'] - m['first_k_dense_replace'])


def bf16_peak():
    import jax
    with open(os.path.join(files.BENCH, 'peaks.json')) as f:
        peaks = json.load(f)
    return peaks[jax.devices()[0].device_kind]['bf16_flops_per_s']


def reduce(ctx, scope):
    trace = ctx.get('trace')
    if not trace:
        return None
    per_device = tracefile.device_ops(trace['data'])
    if not per_device:
        return None
    own_ns = products = 0
    for events in per_device.values():
        under = set(map(id, tracefile.matching(events, [scope])))
        if not under:
            return None     # the program has no such scope
        own_ns += sum(own for e, own in zip(events, tracefile.self_ns(events))
                      if id(e) in under)
        products += sum(id(e) in under and _PRODUCT in e[3] for e in events)
    if not own_ns or not products:
        return None
    config = ctx['config']
    per_step = products / len(per_device) / trace['steps']
    if per_step > products_per_step(config) + 1e-9:
        raise ValueError(
            f'{per_step} products a step under {scope}, the configuration '
            f'has {products_per_step(config)}: the compiler split them')
    flops = products / len(per_device) * product_flops(config)
    return 100.0 * flops / (own_ns / len(per_device) / 1e9 * bf16_peak())
