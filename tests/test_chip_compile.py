"""Does the chip's compiler take the kernels of the main path?

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is DESCRIBED, not attached: each case lowers one Pallas kernel
at a ResNet-50 / BERT-base / long-context shape for one device of a
``v5e:2x2`` topology and asserts the kernel is in the executable
(``tpu_custom_call``) under the name its ``pallas_call`` gives it (what a
profiler trace of the chip shows, and what the benchmark's
``kernel_ms_per_step`` looks for). Interpret-mode tests cannot see what
this sees —
a strided slice Mosaic refuses, a tile over VMEM. Nothing runs; a
compile that passes is not a chip run (``chip_smoke.py`` is).

The only file in the repo that describes a topology, and only inside a
fixture: libtpu is loaded by the one xdist worker that runs this file,
after collection.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kfac_pytorch_tpu.ops import factors
from kfac_pytorch_tpu.ops import pallas_attention, pallas_capture as pc


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', True)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, BF16 = jnp.float32, jnp.bfloat16
N = 32          # train_imagenet.sh batch per chip

# (id, kernel under its ResNet-50 / BERT-base call, operand shapes)
CAPTURE_CASES = [
    ('a_conv-3x3s1-C64',
     lambda a: pc.compute_a_conv(a, (3, 3), (1, 1), ((1, 1), (1, 1)),
                                 False),
     [((N, 56, 56, 64), BF16)]),
    ('a_conv-1x1s2-C256-downsample',
     lambda a: pc.compute_a_conv(a, (1, 1), (2, 2), 'VALID', False),
     [((N, 56, 56, 256), BF16)]),
    ('a_conv-1x1s2-C1024-downsample-f32',
     lambda a: pc.compute_a_conv(a, (1, 1), (2, 2), 'VALID', False),
     [((N, 14, 14, 1024), F32)]),
    ('a_conv-3x3s2-C64',
     lambda a: pc.compute_a_conv(a, (3, 3), (2, 2), ((1, 1), (1, 1)),
                                 False),
     [((N, 56, 56, 64), BF16)]),
    ('g_conv-C256',
     lambda g: pc.compute_g_conv(g, True), [((N, 56, 56, 256), BF16)]),
    ('g_dense-1000',
     lambda g: pc.compute_g_dense(g, True), [((N, 1000), BF16)]),
    ('a_dense-768+bias-f32',
     lambda a: pc.compute_a_dense(a, True), [((4, 384, 768), F32)]),
    ('a_dense-768+bias-bf16',
     lambda a: pc.compute_a_dense(a, True), [((4, 384, 768), BF16)]),
    ('g_conv-C1024-fused-ema',
     lambda g, cur: pc.compute_g_conv(g, True, ema=(cur, 0.95)),
     [((N, 14, 14, 1024), BF16), ((1024, 1024), F32)]),
    ('a_conv-1x1-C1024-fused-ema',
     lambda a, cur: pc.compute_a_conv(a, (1, 1), (1, 1), 'VALID', False,
                                      ema=(cur, 0.95)),
     [((N, 14, 14, 1024), BF16), ((1024, 1024), F32)]),
    ('ef_quantize',
     lambda x, r: pc.ef_quantize(x, r),
     [((8, 512, 512), F32), ((8, 512, 512), F32)]),
]


def _kernel_of(case_id):
    """The ``pallas_call`` name a capture case compiles to."""
    if case_id.startswith('a_conv'):
        return 'kfac_conv_a'
    if case_id == 'ef_quantize':
        return 'kfac_ef_quantize'
    return 'kfac_stat_rows'     # dense A / G and conv G: _stat_rows


@pytest.mark.parametrize('fn,shapes,kernel',
                         [c[1:] + (_kernel_of(c[0]),)
                          for c in CAPTURE_CASES],
                         ids=[c[0] for c in CAPTURE_CASES])
def test_capture_kernel_compiles_for_v5e(one_chip, fn, shapes, kernel):
    text = _compile(one_chip, fn, *shapes)
    assert 'tpu_custom_call' in text
    # the instruction and its op_name path both carry the kernel's name
    assert f'%{kernel}' in text and f'/{kernel}/pallas_call' in text


def test_conv1_is_routed_to_xla_and_says_so(one_chip, capsys):
    """ResNet-50's conv1 (7x7/2 on 3 channels) pads 3 -> 128 lanes in
    VMEM and cannot be tiled per image: it is routed to the XLA
    reference EXPLICITLY — the program compiles with no kernel in it and
    the routing is reported, never silent (ROADMAP S4)."""
    pc._WARNED.clear()
    text = _compile(
        one_chip,
        lambda a: pc.compute_a_conv(a, (7, 7), (2, 2), ((3, 3), (3, 3)),
                                    False),
        ((N, 224, 224, 3), BF16))
    assert 'tpu_custom_call' not in text
    assert 'stays on the XLA path' in capsys.readouterr().err


# (id, activation shape at the benchmark's batch, kernel, strides, padding,
#  the form the shape rule takes, patch tensors the compiler may plan)
XLA_CONV_A_CASES = [
    ('conv1-7x7s2-C3', (128, 224, 224, 3), (7, 7), (2, 2),
     ((3, 3), (3, 3)), 'raw', 1),
    ('layer1-3x3s1-C64', (128, 56, 56, 64), (3, 3), (1, 1),
     ((1, 1), (1, 1)), 'taps', 1),
    ('layer2-3x3s2-C128', (128, 56, 56, 128), (3, 3), (2, 2),
     ((1, 1), (1, 1)), 'taps', 1),
    ('layer2-1x1s2-C256-downsample', (128, 56, 56, 256), (1, 1), (2, 2),
     ((0, 0), (0, 0)), '1x1', 1),
    ('layer1-1x1s1-C256', (128, 56, 56, 256), (1, 1), (1, 1),
     ((0, 0), (0, 0)), '1x1', 0),
]


@pytest.mark.parametrize('shape,kernel,strides,padding,form,patch_tensors',
                         [c[1:] for c in XLA_CONV_A_CASES],
                         ids=[c[0] for c in XLA_CONV_A_CASES])
def test_xla_conv_a_plans_one_patch_tensor_on_v5e(
        one_chip, shape, kernel, strides, padding, form, patch_tensors):
    """The statistic ResNet-50's cells run (no kernel: ``capture_impl`` is
    None): the chip's compiler keeps at most one patch tensor of
    temporaries at the benchmark's batch. The row-scaled form it replaced
    planned four to five (conv1 2,476 MB against 488 MB; PERF.md, PR 26)."""
    assert factors._conv_a_form(kernel, shape[-1]) == form
    a = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    compiled = jax.jit(lambda x: factors.compute_a_conv(
        x, kernel, strides, padding, False)).lower(a).compile()
    oh = (shape[1] + sum(padding[0]) - kernel[0]) // strides[0] + 1
    ow = (shape[2] + sum(padding[1]) - kernel[1]) // strides[1] + 1
    patch_bytes = 2 * shape[0] * oh * ow * kernel[0] * kernel[1] * shape[3]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.1 * patch_tensors * patch_bytes + (1 << 20), (
        temp / patch_bytes)


def test_apply_reads_bert_base_inverses_in_place_on_v5e(one_chip):
    """``kfac.Precondition`` as ``bert-base-freq10`` runs it
    (``compute_pred_local``, one device, stored inverses of BERT-base's
    plan): every pred group's rows are one static slice of the stored
    ``[rows, D, D]`` bucket, so the compiler plans no gather and builds no
    copy of an inverse stack (the ``jnp.take`` form planned 1.54 GB of
    temporaries for 1.72 GB of arguments: chains of
    ``dynamic-update-slice`` into fresh ``f32[12,3328,3328]``,
    ``[48,1024,1024]``... buffers; PERF.md, PR 31). What is left under 0.6
    GB is the padded gradient stacks. A compile, not a timing."""
    import re

    from kfac_pytorch_tpu import engine
    from kfac_pytorch_tpu.capture import LayerMeta
    from kfac_pytorch_tpu.plan import build_plan, pred_layout_record
    dims = (([(769, 768)] * 4 + [(769, 3072), (3073, 768)]) * 12
            + [(769, 2)])
    plan = build_plan(
        {f'l{i}': LayerMeta(name=f'l{i}', path=(f'l{i}',), kind='dense',
                            use_bias=True, in_dim=a, out_dim=g,
                            kernel_shape=(a - 1, g))
         for i, (a, g) in enumerate(dims)}, 1, 'pred')
    assert pred_layout_record(plan)['pred_operand_takes'] == 0

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    invs = {str(d): spec(plan.buckets[d].n_rows, d, d)
            for d in plan.bucket_dims}
    grads = [spec(m.out_dim, m.in_dim) for m in plan.metas]
    compiled = jax.jit(lambda invs, grads: engine.compute_pred_local(
        plan, {'invs': invs}, grads, 0.003, 'cholesky', None)
    ).lower(invs, grads).compile()
    text = compiled.as_text()
    assert not re.search(r'\bgather\(', text)
    # no [rows, D, D] stack is assembled row by row
    square = re.findall(
        r'= f32\[\d+,(\d+),(\d+)\]\S* dynamic-update-slice\(', text)
    assert not [d for d in square if d[0] == d[1]], square[:4]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.6e9, mem.temp_size_in_bytes
    # two GEMMs a group, over the three large groups and the span head
    assert len(re.findall(r'kind=kOutput', text)) == 2 * len(
        plan.pred_groups)


def test_guard_flags_ride_the_writers_on_v5e(one_chip):
    """The health guard on a healthy update, at BERT-base's largest
    bucket (12 x 3,200^2): (1) the running average and its rows'
    ``isfinite`` flags are ONE fusion (it writes the rows and reduces over
    them; no pass reads the bucket for the flag alone), and the repair
    loop behind it holds no second bucket; (2) a fresh inverse is settled
    from its diagonal tiles (under a twentieth of its bytes), the stored
    bucket is read only inside the loop over rows at fault, and nothing
    the size of the bucket is selected or copied. A compile, not a
    timing."""
    import re

    from kfac_pytorch_tpu import engine
    rows, d = 12, 3200
    bucket = ((rows, d, d), F32)

    def update(stat, old, ok):
        return engine.settle_factor_rows(
            old * 0.05 + stat * 0.95, old, [ok], guard=True)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip)
            for s, t in (bucket, bucket, ((rows,), jnp.bool_))]
    compiled = jax.jit(update, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    entry = text[text.index('ENTRY'):]
    whole = re.findall(
        r'= (\(?[^=]*?\)?) (fusion|copy|select)\(', entry)
    whole = [(shape, op) for shape, op in whole
             if f'f32[{rows},{d},{d}]' in shape]
    # one operation writes the bucket: the fusion that also gives the flags
    assert len(whole) == 1 and whole[0][1] == 'fusion', whole
    assert f'pred[{rows}]' in whole[0][0], whole
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20

    from kfac_pytorch_tpu import ops

    def settle(inv, stored):
        return ops.settle_inverse_rows(inv, stored, guard=True)[0]
    args = [jax.ShapeDtypeStruct(*bucket, sharding=one_chip)] * 2
    compiled = jax.jit(settle, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    entry = text[text.index('ENTRY'):]
    assert not re.search(
        rf'= f32\[{rows},{d},{d}\]\S* (fusion|select|copy)\(', entry), entry
    # the flags' fusions read the diagonal's tiles, not the bucket
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize('length', [2048, 32768])
def test_flash_block_attn_fwd_bwd_compiles_for_v5e(one_chip, length,
                                                   monkeypatch):
    # the fused backward, whatever the auto policy picks at this length
    monkeypatch.setenv('KFAC_ATTN_BWD_IMPL', 'pallas')
    bh, d = 8, 64
    scale = d ** -0.5

    def loss(q, k, v, mask):
        starts = jnp.zeros((2,), jnp.int32)
        _, l, pv = pallas_attention.flash_block_attn(
            q, k, v, mask, starts, scale, True, False)
        return (l ** 2).sum() + (pv.astype(F32) ** 2).sum()

    qkv = ((bh, length, d), BF16)
    text = _compile(one_chip,
                    jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    qkv, qkv, qkv, ((bh, length), F32))
    # forward + dq + dkv kernels, each under its name (autodiff wraps
    # it: jvp(kfac_flash_fwd), transpose(jvp(kfac_flash_bwd_dq)))
    assert text.count('tpu_custom_call') >= 3
    for kernel in ('kfac_flash_fwd', 'kfac_flash_bwd_dq',
                   'kfac_flash_bwd_dkv'):
        assert f'({kernel})' in text, kernel
