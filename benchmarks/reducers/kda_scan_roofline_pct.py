"""Share of its roofline that the chunked gated delta rule reaches: the
least time the chip could take for the step's chunks, ``max(operations /
bf16 peak, bytes / HBM bandwidth)``, over the device time under ``scope``
(``kda.scan``: ``reducers/scope_device_ms.py``'s reading), in %.

One chunk of one head is the unit (:func:`chunk_flops`, :func:`chunk_bytes`:
from ``model.kda_chunk`` = ``C`` and the head's ``dk = dv`` =
``linear_attn_config.head_dim`` alone). Counted is the least the
mathematics needs, so that the share under-reads:

- operations, forward: the two decayed Gram triangles ``k_t' Diag(e^{G_t -
  G_i}) k_i`` (``i < t``) and ``q_t' Diag(.) k_i`` (``i <= t``), ``C^2 dk``
  each as ``C^2 / 2`` pairs of ``dk`` multiply-adds; the unit triangular
  solve against ``dk + dv`` columns, ``C^2 (dk + dv)``; the chunk's four
  products with a ``dk x dv`` state or a ``C x C`` triangle: ``S_0' (e^G
  k)``, ``S_0' (e^G q)``, the state's update, ``6 C dk dv`` together, and
  ``A_qk u``, ``C^2 dv``. No exponential, mask or cumulative sum is
  counted. Backward: twice the forward's (every product has two
  cotangent products); what the backward pass computes AGAIN of the
  forward runs under the scope and is not counted.
- bytes: ``q, k, v, g`` (``C (3 dk + dv)``), ``beta`` (``C``) in and ``o``
  (``C dv``) out, float32, and once more for their cotangents. The state
  carried between chunks, the Gram triangles and ``u`` count as if they
  never left the chip's fast memory.

A step runs ``batch x ceil(seq_len / C) x held heads x KDA layers`` such
units. No trace, or no operation under the scope (a program without it):
None.
"""

import json
import os

from harness import files


def chunk_flops(chunk, dk, dv):
    """Operations of one chunk of one head, forward and backward."""
    forward = (2 * chunk * chunk * dk + chunk * chunk * (dk + dv)
               + 6 * chunk * dk * dv + chunk * chunk * dv)
    return 3 * forward


def chunk_bytes(chunk, dk, dv):
    """Bytes one chunk of one head has to move, forward and backward."""
    return 2 * 4 * chunk * (3 * dk + dv + 1 + dv)


def units_per_step(config, traffic):
    """Chunks of one head a step runs: sequences x chunks x heads x KDA
    layers held."""
    m = config['model']
    chunks = -(-m['seq_len'] // m['kda_chunk'])
    return (traffic['batch_per_chip'] * traffic['chips'] * chunks
            * len(m['kda_head_ids']) * m['layer_kinds_held'].count('kda'))


def least_seconds(config, traffic, peaks):
    """The roofline's time for one step's chunks, and which side bounds
    it."""
    m = config['model']
    dk = dv = m['linear_attn_config']['head_dim']
    units = units_per_step(config, traffic)
    by_ops = units * chunk_flops(m['kda_chunk'], dk, dv) / peaks[
        'bf16_flops_per_s']
    by_bytes = units * chunk_bytes(m['kda_chunk'], dk, dv) / peaks[
        'hbm_bytes_per_s']
    return max(by_ops, by_bytes), 'flops' if by_ops > by_bytes else 'bytes'


def chip_peaks():
    import jax
    with open(os.path.join(files.BENCH, 'peaks.json')) as f:
        peaks = json.load(f)
    return peaks[jax.devices()[0].device_kind]


def reduce(ctx, scope):
    if 'kda_chunk' not in ctx['config']['model']:
        return None
    step_ms = files.load_module('reducers', 'scope_device_ms').reduce(
        ctx, scopes=[scope])
    if not step_ms:
        return None     # no trace, or the program has no such scope
    least, _ = least_seconds(ctx['config'], ctx['traffic'], chip_peaks())
    return 100.0 * least / (step_ms / 1e3)
