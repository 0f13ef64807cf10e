#!/usr/bin/env python3
"""Does the K-FAC train step start on the chip? One process, one TPU.

    python chip_smoke.py                   # what the driver runs
    python chip_smoke.py --all             # + eigen_dp and dense: needs a host
                                           #   with well over 40 GiB of RAM
    python chip_smoke.py --phases kernels,dense
    python chip_smoke.py --chips 4         # the data-parallel mesh, builder-run

Drives the trainers themselves (``examples/imagenet_resnet.py`` /
``examples/squad_bert.py`` ``main()``, existing flags only) at full model
width on synthetic data from a seed, wraps the step they build to read
back every loss, timing and the final state, and checks what comes out
by the repo's own means. Each phase prints one JSON line; the LAST line
of stdout is ``{"ok": true, "device": {...}}`` and is printed only when
every phase passed on a TPU. No accelerator, or no repo beside this
file: non-zero exit, no result line.
"""

import argparse
import glob
import importlib.util
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the run the driver makes. ResNet-50 ``eigen_dp`` (the trainers'
#: default) is NOT in it: compiling its full-update step (one ``eigh``
#: per bucket size up to 4,608) takes ~20 minutes and more host memory
#: than a one-chip machine has (killed at 40 GiB, PERF.md PR 21); the
#: Cholesky variant's compiles in under four minutes.
DEFAULT_PHASES = ('fence', 'resnet50:inverse_dp', 'resnet50:sgd', 'kernels')
#: ``--all``: every phase, the long compiles last
ALL_PHASES = DEFAULT_PHASES + ('resnet50:eigen_dp', 'dense')

#: train_imagenet.sh's configuration at the reference's deployed K-FAC
#: cadence; 128 synthetic images = four train steps at batch 32, so the
#: steps cross one factor+decomposition step and three plain
#: precondition steps
RESNET_ARGS = ['--model', 'resnet50', '--img-size', '224',
               '--synthetic-size', '128', '--epochs', '1',
               '--kfac-cov-update-freq', '10']
RESNET_BATCH_PER_CHIP = 32
#: train_squad.sh's configuration at the S1 sequence length
DENSE_ARGS = ['--model-size', 'base', '--batch-size', '4',
              '--max-seq-length', '384', '--synthetic-size', '16',
              '--epochs', '1', '--base-lr', '0.04',
              '--kfac-update-freq', '1', '--kfac-cov-update-freq', '1',
              '--kfac-name', 'eigen_dp', '--damping', '0.003']
#: K-FAC variant of the four-chip ResNet-50 leg (``--all``: eigen_dp)
MESH_VARIANT = 'inverse_dp'

#: (matrix size, chained matmuls) of the fence probe: ~1.1 TFLOP each
FENCE_SHAPE = (8192, 400)
#: sizes of the kernels phase (ResNet-50 batch and feature maps, BERT
#: tokens, the long-context length); the rehearsal test shrinks them
KERNEL_SHAPES = {
    'batch': 32, 'hw56': 56, 'hw14': 14, 'hw224': 224,
    'bert_tokens': (4, 384), 'ef': (8, 512, 512),
    'attn_len': 32768, 'attn_check_rows': 512,
}

#: step-0 losses of the legs share seed, data and forward; the programs
#: differ (capture taps change XLA's bf16 fusion), hence a band
LOSS_RTOL = 5e-3
#: four-chip step-0 loss vs the one-chip leg's: different batch (128 vs
#: 32 images of the same draw), same random init -> both ~ln(1000)
MESH_LOSS_RTOL = 0.25
#: normalized max error |kernel - reference| / max|reference| of a
#: compiled Pallas kernel against ops/factors.py / XLA block attention
KERNEL_TOL = 2e-2


def emit(**row):
    print(json.dumps(row), flush=True)


def require_tpu():
    """The device this run is about; anything but a TPU is a failure."""
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        sys.exit(f'chip_smoke: needs a TPU, JAX found '
                 f'{devices[0].platform} x{len(devices)}')
    return devices


def load_trainer(name):
    spec = importlib.util.spec_from_file_location(
        f'chip_smoke_{name}', os.path.join(ROOT, 'examples', name + '.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class StepRecorder:
    """Stands in for ``training.build_train_step`` while a trainer's
    ``main()`` runs: the step it returns is the real one, wrapped to
    fence and time every call and keep the newest state."""

    def __init__(self, build):
        self._build = build
        self.step = None
        self.state = None
        self.rows = []
        self.decomp_after_first = None

    def build(self, *args, **kw):
        self.step = self._build(*args, **kw)
        return self

    def __getattr__(self, name):       # last_phases, variants, ...
        return getattr(self.step, name)

    def __call__(self, state, batch, **kw):
        import jax
        import numpy as np
        t0 = time.perf_counter()
        state, m = self.step(state, batch, **kw)
        t1 = time.perf_counter()
        jax.block_until_ready((state, m))
        t2 = time.perf_counter()
        # a host fetch AFTER the fence: ~0 s iff the fence held
        loss = float(np.asarray(m['loss']))
        t3 = time.perf_counter()
        self.rows.append({
            'phases': '+'.join(self.step.last_phases) or 'sgd',
            'dispatch_s': t1 - t0, 'ready_s': t2 - t1,
            'fetch_s': t3 - t2, 'loss': loss})
        if self.decomp_after_first is None:
            self.decomp_after_first = decomp_populated(state)
        self.state = state
        return state, m


def decomp_populated(state):
    import jax
    import jax.numpy as jnp
    if state.kfac_state is None:
        return False
    return any(bool(jnp.any(x != 0))
               for x in jax.tree.leaves(state.kfac_state.decomp))


def run_trainer(mod, argv):
    """``mod.main()`` under ``argv`` with its step recorded."""
    rec = StepRecorder(mod.training.build_train_step)
    old_argv = sys.argv
    sys.argv = [mod.__file__] + argv
    mod.training.build_train_step = rec.build
    try:
        mod.main()
    finally:
        sys.argv = old_argv
        mod.training.build_train_step = rec._build
    return rec


def peak_hbm():
    import jax
    return [(d.memory_stats() or {}).get('peak_bytes_in_use')
            for d in jax.local_devices()]


def summarize(rec, log_dir):
    """Numbers and hard checks of one recorded trainer run."""
    import numpy as np
    rows = rec.rows
    failures = []
    losses = [r['loss'] for r in rows]
    if not rows or not all(np.isfinite(losses)):
        failures.append(f'non-finite or missing losses: {losses}')
    first, steady = {}, {}
    for r in rows:
        wall = r['dispatch_s'] + r['ready_s'] + r['fetch_s']
        if r['phases'] in first:
            steady.setdefault(r['phases'], []).append(wall)
        else:
            first[r['phases']] = wall
    state = rec.state
    health = None
    if state is not None and state.health is not None:
        health = {k: int(getattr(state.health, k))
                  for k in ('skipped', 'fallbacks', 'rung')}
        if any(health.values()):
            failures.append(f'health guard fired: {health}')
    logs = ''.join(open(p).read()
                   for p in glob.glob(os.path.join(log_dir, '*.log')))
    epoch = re.search(r'epoch 0: (?:train_)?loss ([0-9.naninf-]+)', logs)
    if epoch is None or not np.isfinite(float(epoch.group(1))):
        failures.append('run log has no finite epoch-0 loss line')
    if '[health:' in logs:
        failures.append('run log carries a [health: ...] suffix')
    compilations = {'|'.join(map(str, k)): fn._cache_size()
                    for k, fn in rec.step.variants.items()}
    heaviest = max(rows, key=lambda r: r['ready_s'] + r['fetch_s'],
                   default=None)
    return {
        'losses': losses,
        'first_call_s': first, 'steady_s': steady,
        'compilations': compilations,
        'health': health,
        'log_epoch_loss': epoch and float(epoch.group(1)),
        'peak_hbm_bytes': peak_hbm(),
        # the step with the longest device wait: block_until_ready took
        # ready_s, a host fetch right after it took fetch_s
        'fence': heaviest and {k: heaviest[k] for k in
                               ('phases', 'dispatch_s', 'ready_s',
                                'fetch_s')},
    }, failures


def flat_params(state):
    import jax
    import numpy as np
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(state.params)])


def resnet_leg(trainer, variant, out, num_devices=1):
    """Four ResNet-50 steps through examples/imagenet_resnet.py."""
    import jax
    leg = f'{variant}-nd{num_devices}'
    log_dir = os.path.join(out, leg, 'logs')
    ckpt = os.path.join(out, leg, 'checkpoints')
    argv = list(RESNET_ARGS) + [
        '--batch-size', str(RESNET_BATCH_PER_CHIP * num_devices),
        '--num-devices', str(num_devices),
        '--log-dir', log_dir, '--checkpoint-format', ckpt]
    if num_devices > 1:
        # the loader feeds the global batch; same four steps
        i = argv.index('--synthetic-size')
        argv[i + 1] = str(int(argv[i + 1]) * num_devices)
    if variant == 'sgd':
        argv += ['--kfac-update-freq', '0']
    else:
        argv += ['--kfac-update-freq', '10', '--kfac-name', variant]
    t0 = time.perf_counter()
    rec = run_trainer(trainer, argv)
    row, failures = summarize(rec, log_dir)
    row['wall_s'] = time.perf_counter() - t0
    kfac_on = variant != 'sgd'
    if kfac_on:
        row['decomp_populated_after_first_step'] = rec.decomp_after_first
        if not rec.decomp_after_first:
            failures.append('decomposition empty after the first '
                            'inverse update')
        if row['health'] is None:
            failures.append('K-FAC leg carries no health counters')
        if num_devices > 1:
            devices = jax.devices()[:num_devices]
            kstate = rec.state.kfac_state
            row['factors_on_all_devices'] = shard_spread(kstate.factors,
                                                         devices)
            row['decomp_on_all_devices'] = shard_spread(kstate.decomp,
                                                        devices)
            if not (row['factors_on_all_devices']
                    and row['decomp_on_all_devices']):
                failures.append('K-FAC state is not spread over all '
                                'devices')
    params = flat_params(rec.state)
    shutil.rmtree(ckpt, ignore_errors=True)   # ~GBs; the logs stay
    rec.state = None
    jax.clear_caches()                        # executables hold HBM too
    return row, failures, params


def phase_resnet50(trainer, variants, out, kind):
    """The one-chip ResNet-50 legs and the checks that span them."""
    import numpy as np
    ok = True
    step0, params = {}, {}
    for variant in variants:
        row, failures, params[variant] = resnet_leg(trainer, variant, out)
        step0[variant] = row['losses'][0] if row['losses'] else float('nan')
        emit(phase='resnet50', leg=variant, device_kind=kind,
             ok=not failures, failures=failures, **row)
        ok = ok and not failures
    failures = []
    ref = next(iter(step0.values()))
    for variant, loss in step0.items():
        if not abs(loss - ref) <= LOSS_RTOL * abs(ref):
            failures.append(f'step-0 loss of {variant} {loss} != {ref}')
    dist = {}
    if 'sgd' in params:
        for variant, p in params.items():
            if variant == 'sgd':
                continue
            d = float(np.linalg.norm(p - params['sgd'])
                      / np.linalg.norm(params['sgd']))
            dist[variant] = d
            if not (np.isfinite(d) and d > 0):
                failures.append(f'{variant} parameters equal the SGD '
                                f'leg\'s (rel. distance {d})')
    else:
        failures.append('no SGD leg to compare the K-FAC parameters with')
    emit(phase='resnet50', leg='compare', device_kind=kind,
         ok=not failures, failures=failures, step0_loss=step0,
         loss_rtol=LOSS_RTOL, param_rel_distance_to_sgd=dist)
    return ok and not failures


def phase_dense(out, kind):
    """A few BERT-base steps through examples/squad_bert.py."""
    import jax
    trainer = load_trainer('squad_bert')
    log_dir = os.path.join(out, 'dense', 'logs')
    t0 = time.perf_counter()
    rec = run_trainer(trainer, list(DENSE_ARGS) + ['--log-dir', log_dir])
    row, failures = summarize(rec, log_dir)
    row['wall_s'] = time.perf_counter() - t0
    row['decomp_populated_after_first_step'] = rec.decomp_after_first
    if not rec.decomp_after_first:
        failures.append('decomposition empty after the first inverse '
                        'update')
    rec.state = None
    jax.clear_caches()
    emit(phase='dense', device_kind=kind, ok=not failures,
         failures=failures, **row)
    return not failures


def phase_fence(kind):
    """Does ``jax.block_until_ready`` wait for a multi-second program?
    Times it against a host fetch on a chain of bf16 matmuls (the
    eigh-bearing K-FAC steps report the same pair in their own lines)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, iters = FENCE_SHAPE

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, iters, lambda i, a: a @ a, x)

    x = jnp.eye(n, dtype=jnp.bfloat16)
    np.asarray(chain(x)[:1, :1])              # compile + warm both
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    jax.block_until_ready(y)
    t2 = time.perf_counter()
    np.asarray(y[:1, :1])
    t3 = time.perf_counter()
    y = chain(x)
    np.asarray(y[:1, :1])
    t4 = time.perf_counter()
    row = {'dispatch_s': t1 - t0, 'block_until_ready_s': t2 - t1,
           'fetch_after_ready_s': t3 - t2, 'fetch_alone_s': t4 - t3}
    # it fences iff the wait is in block_until_ready, not in the fetch
    fences = row['fetch_after_ready_s'] < 0.1 * row['fetch_alone_s']
    emit(phase='fence', device_kind=kind, ok=True,
         block_until_ready_fences=fences, **row)
    return True


def _nerr(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-30))


def kernels_interpreted():
    """Part of the device check: on the chip the kernels run compiled."""
    from kfac_pytorch_tpu.ops import pallas_capture as pc
    if pc.interpret_default():
        sys.exit('chip_smoke: pallas_capture.interpret_default() is True '
                 '— the kernels would run interpreted, not on the chip')
    return False


def phase_kernels(kind):
    """One compiled call of each Pallas kernel against its reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from kfac_pytorch_tpu.ops import factors as ref
    from kfac_pytorch_tpu.ops import pallas_attention as pa
    from kfac_pytorch_tpu.ops import pallas_capture as pc
    # the package re-exports the ring_attention FUNCTION under this name
    ring = importlib.import_module(
        'kfac_pytorch_tpu.parallel.ring_attention')

    interpret = kernels_interpreted()
    expect_compiled = not interpret
    ks = KERNEL_SHAPES
    n, bf16 = ks['batch'], jnp.bfloat16
    rng = np.random.RandomState(0)

    def rand(shape, dtype):
        return jnp.asarray(rng.randn(*shape), dtype)

    cur = rand((1024, 1024), jnp.float32)
    cases = []   # name, kernel fn, reference fn, args, expect a kernel

    def conv_a(name, shape, kernel, strides, padding, fused=True):
        cases.append((
            name,
            lambda a: pc.compute_a_conv(a, kernel, strides, padding,
                                        False, interpret=interpret),
            lambda a: ref.compute_a_conv(a, kernel, strides, padding,
                                         False),
            (rand(shape, bf16),), fused))

    conv_a('a_conv 3x3/1 C=64', (n, ks['hw56'], ks['hw56'], 64),
           (3, 3), (1, 1), ((1, 1), (1, 1)))
    conv_a('a_conv 1x1/2 C=256 (downsample)',
           (n, ks['hw56'], ks['hw56'], 256), (1, 1), (2, 2), 'VALID')
    # conv1: C=3 pads to 128 lanes in VMEM -> routed to XLA, on record
    conv_a('a_conv 7x7/2 C=3 (conv1, XLA-routed)',
           (n, ks['hw224'], ks['hw224'], 3), (7, 7), (2, 2),
           ((3, 3), (3, 3)), fused=ks['hw224'] < 64)
    cases.append((
        'g_conv C=256',
        lambda g: pc.compute_g_conv(g, True, interpret=interpret),
        lambda g: ref.compute_g_conv(g, True),
        (rand((n, ks['hw56'], ks['hw56'], 256), bf16) / n,), True))
    cases.append((
        'g_conv C=1024 + fused EMA',
        lambda g, c: pc.compute_g_conv(g, True, ema=(c, 0.95),
                                       interpret=interpret),
        lambda g, c: ref.update_running_avg(ref.compute_g_conv(g, True),
                                            c, 0.95),
        (rand((n, ks['hw14'], ks['hw14'], 1024), bf16) / n, cur), True))
    for dtype in (jnp.float32, bf16):
        cases.append((
            f'a_dense 768+bias {jnp.dtype(dtype).name}',
            lambda a: pc.compute_a_dense(a, True, interpret=interpret),
            lambda a: ref.compute_a_dense(a, True),
            (rand(ks['bert_tokens'] + (768,), dtype),), True))
    cases.append((
        'g_dense 1000',
        lambda g: pc.compute_g_dense(g, True, interpret=interpret),
        lambda g: ref.compute_g_dense(g, True),
        (rand((n, 1000), bf16) / n,), True))

    def ef_ref(x, r):
        # collectives.pmean_scatter_ef's two-pass algebra. NOT
        # xc - f32(bf16(xc)): under jit on the TPU, XLA folds that
        # round trip away and the "reference" residual is exactly 0
        # (PERF.md, PR 21) — the kernel was right, the reference wrong
        xc = x + r
        rounded = jax.lax.reduce_precision(xc, exponent_bits=8,
                                           mantissa_bits=7)
        return rounded.astype(jnp.bfloat16), xc - rounded

    cases.append((
        'ef_quantize',
        lambda x, r: pc.ef_quantize(x, r, interpret=interpret), ef_ref,
        (rand(ks['ef'], jnp.float32), rand(ks['ef'], jnp.float32) * 1e-3),
        True))

    results, failures = [], []

    def check(name, got, want, compiled, fused, **more):
        errs = [_nerr(g, w) for g, w in
                zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        err = max(errs)
        results.append({'kernel': name, 'compiled_kernel': compiled,
                        'nerr': err, 'nerr_per_output': errs, **more})
        if not err <= KERNEL_TOL:
            failures.append(f'{name}: nerr {err} > {KERNEL_TOL}')
        if expect_compiled and compiled != fused:
            failures.append(f'{name}: tpu_custom_call present={compiled}, '
                            f'expected {fused}')

    for name, fn, ref_fn, args, fused in cases:
        compiled = jax.jit(fn).lower(*args).compile()
        with jax.default_matmul_precision('highest'):
            want = jax.jit(ref_fn)(*args)
        check(name, compiled(*args), want,
              'tpu_custom_call' in compiled.as_text(), fused)

    # flash attention block, forward + backward, at the long-context
    # length: forward against the XLA block path on the last query rows
    # (every query row is independent), the fused backward against the
    # repo's blockwise-recompute backward
    L, rows, D, BH = ks['attn_len'], ks['attn_check_rows'], 64, 2
    scale = D ** -0.5
    q, k, v = (rand((BH, L, D), bf16) for _ in range(3))
    mask = jnp.ones((BH, L), jnp.float32)
    starts = jnp.zeros((2,), jnp.int32)

    def fwd(q, k, v):
        return pa.flash_block_attn(q, k, v, mask, starts, scale, True,
                                   interpret)

    fwd_c = jax.jit(fwd).lower(q, k, v).compile()
    m, l, pv = fwd_c(q, k, v)

    @jax.jit
    def fwd_ref(q, k, v):
        bias = ring._bias_for_block(L - rows, 0, rows, L, True, None)
        m_, l_, pv_ = ring._block_attn(q[None, :, L - rows:], k[None],
                                       v[None], bias, scale)
        return m_[0], l_[0], pv_[0]

    rm, rl, rpv = fwd_ref(q, k, v)
    check(f'flash_block_attn fwd L={L}',
          (m[:, L - rows:], l[:, L - rows:], pv[:, L - rows:]),
          (rm, rl, rpv), 'tpu_custom_call' in fwd_c.as_text(), True)

    def loss(q, k, v):
        _, l_, pv_ = fwd(q, k, v)
        return (jnp.log(l_) ** 2).sum() + (pv_.astype(jnp.float32) ** 2
                                           ).sum()

    grads = {}
    for impl in ('pallas', 'recompute'):
        os.environ['KFAC_ATTN_BWD_IMPL'] = impl   # trace-time knob
        try:
            g_c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                q, k, v).compile()
        finally:
            del os.environ['KFAC_ATTN_BWD_IMPL']
        grads[impl] = (g_c(q, k, v), g_c.as_text().count('tpu_custom_call'))
    check(f'flash_block_attn bwd L={L}', grads['pallas'][0],
          grads['recompute'][0],
          grads['pallas'][1] > grads['recompute'][1], True,
          custom_calls={k: v[1] for k, v in grads.items()})

    emit(phase='kernels', device_kind=kind, ok=not failures,
         failures=failures, tolerance=KERNEL_TOL, interpret=interpret,
         kernels=results)
    return not failures


def shard_spread(tree, devices):
    """Do the leaves' addressable shards sit on every device?"""
    import jax
    want = set(devices)
    return all({s.device for s in x.addressable_shards} == want
               for x in jax.tree.leaves(tree))


def mesh_parity(kind, devices):
    """The comparison __graft_entry__._dryrun_parity makes on a CPU
    mesh, on the chips: MPD eigen, BatchNorm off, same global batch on
    the mesh and on one device. fp32 matmuls as in the CPU tests — the
    TPU's default single-pass bf16 would blur an exact comparison."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh

    import __graft_entry__ as graft

    def ce(outputs, b):
        return optax.softmax_cross_entropy_with_integer_labels(
            outputs, b['label']).mean()

    t0 = time.perf_counter()
    failures = []
    try:
        with jax.default_matmul_precision('highest'):
            graft._dryrun_parity(len(devices),
                                 Mesh(np.array(devices), ('batch',)), ce)
    except AssertionError as e:
        failures.append(f'parity: {e}')
    emit(phase='mesh', leg='parity', device_kind=kind, ok=not failures,
         failures=failures, wall_s=time.perf_counter() - t0)
    jax.clear_caches()
    return not failures


def mesh_resnet50(out, kind, devices, variant):
    """ResNet-50 over the mesh, then the same four steps on one chip."""
    n = len(devices)
    ok = True
    trainer = load_trainer('imagenet_resnet')
    row, failures, _ = resnet_leg(trainer, variant, out, num_devices=n)
    peaks = [p for p in row['peak_hbm_bytes'] if p]
    if peaks and max(peaks) > 1.5 * min(peaks):
        failures.append(f'per-device peak HBM uneven: {peaks}')
    emit(phase='mesh', leg=f'resnet50:{variant}', devices=n,
         device_kind=kind, ok=not failures, failures=failures, **row)
    ok = ok and not failures
    mesh_loss = row['losses'][0] if row['losses'] else float('nan')

    row, failures, _ = resnet_leg(trainer, variant, out)
    one_loss = row['losses'][0] if row['losses'] else float('nan')
    if not abs(mesh_loss - one_loss) <= MESH_LOSS_RTOL * abs(one_loss):
        failures.append(f'step-0 loss over the mesh {mesh_loss} outside '
                        f'{MESH_LOSS_RTOL:.0%} of one chip\'s {one_loss}')
    emit(phase='mesh', leg=f'resnet50:{variant}', devices=1,
         device_kind=kind, ok=not failures, failures=failures,
         mesh_step0_loss=mesh_loss, loss_rtol=MESH_LOSS_RTOL, **row)
    return ok and not failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4),
                    help='4: only the data-parallel mesh path and its '
                         'one-chip comparison (builder-run)')
    ap.add_argument('--all', action='store_true',
                    help='also the phases whose cold compile fits neither '
                         'the default run\'s time nor a one-chip host\'s '
                         '40 GiB (ResNet-50 eigen_dp, dense); with '
                         '--chips 4, eigen_dp over the mesh')
    ap.add_argument('--phases', default=None,
                    help='comma-separated subset, e.g. kernels,dense')
    ap.add_argument('--out', default=os.path.join(ROOT, 'chip_smoke_out'),
                    help='logs and checkpoints of this run (emptied first)')
    args = ap.parse_args(argv)

    devices = require_tpu()
    import jax
    if len(devices) < args.chips:
        sys.exit(f'chip_smoke: --chips {args.chips} needs {args.chips} '
                 f'devices, JAX found {len(devices)}')
    devices = devices[:args.chips]
    kind = devices[0].device_kind

    sys.path.insert(0, ROOT)
    from kfac_pytorch_tpu.utils.platform import enable_compile_cache
    cache = enable_compile_cache()
    # a rehearsal's checkpoint would be auto-resumed by the next run
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    emit(phase='start', device_kind=kind, devices=len(jax.devices()),
         compile_cache_dir=cache,
         compile_cache_entries=(len(os.listdir(cache))
                                if os.path.isdir(cache) else 0),
         jax=jax.__version__)

    t0 = time.perf_counter()
    if args.chips > 1:
        # the four-chip path and what it is compared with, nothing else
        ok = mesh_parity(kind, devices)
        ok = mesh_resnet50(args.out, kind, devices,
                           'eigen_dp' if args.all else MESH_VARIANT) and ok
    else:
        phases = (args.phases.split(',') if args.phases
                  else ALL_PHASES if args.all else DEFAULT_PHASES)
        unknown = [p for p in phases if p not in ALL_PHASES]
        if unknown:
            sys.exit(f'chip_smoke: unknown phases {unknown}; '
                     f'choose from {ALL_PHASES}')
        ok = True
        variants = [p.split(':')[1] for p in phases
                    if p.startswith('resnet50:')]
        if 'fence' in phases:
            ok = phase_fence(kind) and ok
        if variants:
            ok = phase_resnet50(load_trainer('imagenet_resnet'), variants,
                                args.out, kind) and ok
        if 'kernels' in phases:
            ok = phase_kernels(kind) and ok
        if 'dense' in phases:
            ok = phase_dense(args.out, kind) and ok
    emit(phase='end', ok=ok, wall_s=time.perf_counter() - t0,
         peak_hbm_bytes=peak_hbm())
    if not ok:
        sys.exit(1)
    print(json.dumps({'ok': True, 'device': {
        'platform': devices[0].platform, 'kind': kind,
        'count': len(jax.devices())}}), flush=True)


if __name__ == '__main__':
    main()
