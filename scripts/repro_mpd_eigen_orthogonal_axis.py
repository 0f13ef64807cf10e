"""RESOLVED case study (round 3): an apparent MPD-'eigen' nd>=2
divergence under an ORTHOGONAL varying mesh axis ('expert') that was NOT
an engine bug. Kept as a postmortem because both failure modes are easy
to hit again:

1. The K-FAC capture convention is a LOCAL-mean loss. A globally
   psum-normalized loss leaves grads and A factors equal but makes the
   engine's G-factor scale shard-size-dependent (local cotangents x
   local-batch scaling), so cross-mesh comparisons diverge in exactly
   the preconditioned output while every input looks equal.
2. `check_vma=False` on a shard_map disables vma autodiff's AUTOMATIC
   cross-axis gradient psum — debug probes taken under it show grads
   missing their reductions and will send the investigation sideways.

With the convention respected the full nd=2 cross-mesh invariance
passes: tests/test_moe.py::test_moe_kfac_dp_ep_invariance.

Usage: [NOKL=1] [VARIANT=eigen|eigen_dp] python scripts/repro_mpd_eigen_orthogonal_axis.py
"""
import sys; sys.path.insert(0, 'tests'); sys.path.insert(0, '.')
print('=' * 72)
print('POSTMORTEM REPRODUCER: the harness below DELIBERATELY commits the')
print('two mistakes the docstring describes (global-psum loss and')
print('check_vma=False probes) — divergent numbers in this output are the')
print('EXPECTED broken-harness signature, NOT an engine bug. The correct-')
print('convention invariance passes in tests/test_moe.py.')
print('=' * 72)
import os
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault(
    'XLA_FLAGS', '--xla_force_host_platform_device_count=8')
print('importing test_moe', flush=True)
import test_moe as m
print('imported', flush=True)
import numpy as np, jax, jax.numpy as jnp, functools
from jax.sharding import Mesh, PartitionSpec as P
import kfac_pytorch_tpu as kfac
from kfac_pytorch_tpu import capture
from kfac_pytorch_tpu.parallel.moe import SwitchMoE
NE2, ND = 2, 2
TL, D, DH = m.TL, m.D, m.DH
T = NE2 * TL
x = jnp.asarray(np.random.RandomState(5).randn(ND*T, D), jnp.float32)
y = jnp.asarray(np.random.RandomState(6).randn(ND*T, D), jnp.float32)
gate, experts, stacked = m._params(11)
gate = {'kernel': gate['kernel'][:, :NE2], 'bias': gate['bias'][:NE2]}
stacked2 = jax.tree.map(lambda a: a[:NE2], stacked)
local = SwitchMoE(D, DH, capacity=T, axis=None)
especs = jax.tree.map(lambda _: P('expert'), stacked2)
params = {'gate': gate, 'expert': stacked2}

def make_pre(nd, axis):
    import os
    KL = None if os.environ.get('NOKL') else 0.001
    import os as _os
    VAR = _os.environ.get('VARIANT', 'eigen')
    pre = kfac.KFAC(variant=VAR, lr=0.1, damping=0.01, kl_clip=KL,
                    fac_update_freq=1, kfac_update_freq=1,
                    num_devices=nd, axis_name=axis)
    xs = x[:T]
    variables = capture.init(local, jax.random.PRNGKey(0), xs)
    pre.setup(capture.collect_layer_meta(local, variables, xs))
    return pre

def run(mesh, axes, kfac_axis, nd, cap):
    moe = SwitchMoE(D, DH, capacity=cap, axis='expert')
    pre = make_pre(nd, kfac_axis)
    kstate = jax.tree.map(lambda a: jnp.stack([a]*NE2), pre.init())
    inner = (pre.state_pspecs(kfac_axis) if kfac_axis
             else jax.tree.map(lambda _: P(), pre.state_pspecs(None)))
    kspecs = jax.tree.map(lambda s: P('expert', *s), inner,
                          is_leaf=lambda v: isinstance(v, P))
    pre1 = make_pre(1, None)
    kstate1 = jax.tree.map(lambda a: jnp.stack([a]*NE2), pre1.init())
    ks1 = jax.tree.map(lambda s: P('expert', *s),
                       jax.tree.map(lambda _: P(), pre1.state_pspecs(None)),
                       is_leaf=lambda v: isinstance(v, P))
    oes = jax.tree.map(lambda _: P('expert'), especs)
    @functools.partial(jax.shard_map, mesh=mesh,
        in_specs=({'gate': P(), 'expert': especs}, kspecs, P(axes), P(axes)),
        out_specs=(especs, especs), check_vma=False)
    def step(params, kstate, x, y):
        kstate1_ = jax.tree.map(lambda a: a, kstate1)
        local_p = {'gate': params['gate'],
                   'expert': jax.tree.map(lambda a: a[0], params['expert'])}
        all_axes = (('data', 'expert') if kfac_axis else 'expert')
        def gm(o):
            s = ((o[0] - y) ** 2).sum() / (ND * T * D)
            return jax.lax.psum(s, all_axes)
        _, _, grads, acts, gs, _ = capture.value_and_grad_with_capture(
            moe, gm, {'params': local_p}, x, axis_name=all_axes)
        k = jax.tree.map(lambda a: a[0], kstate)
        ng, _ = pre.step(k, grads, acts, gs, axis_name=kfac_axis)
        if kfac_axis:
            # the SAME captures through an nd=1 world-of-one engine: the
            # distributed result must match it exactly
            k1 = jax.tree.map(lambda a: a[0], kstate1)
            ng1, _ = pre1.step(k1, grads, acts, gs, axis_name=None)
        else:
            ng1 = ng
        return (jax.tree.map(lambda a: a[None], ng['expert']),
                jax.tree.map(lambda a: a[None], ng1['expert']))
    return step(params, kstate, x, y)

total = ND * T
mesh_dp = Mesh(np.array(jax.devices()[:ND*NE2]).reshape(ND, NE2), ('data','expert'))
print("running dp+ep (nd=2)...", flush=True)
got = run(mesh_dp, ('data','expert'), 'data', ND, cap=total // (ND*NE2))
mesh_e = Mesh(np.array(jax.devices()[:NE2]), ('expert',))
print("running expert-only...", flush=True)
want = run(mesh_e, 'expert', None, 1, cap=total // NE2)
def flat(t):
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_leaves_with_path(t)}
gd, g1 = flat(got[0]), flat(got[1])
print('=== nd=2 engine vs in-program nd=1 engine, same captures:')
for kk in gd:
    print(kk, float(np.abs(np.asarray(gd[kk], np.float64)
                           - np.asarray(g1[kk], np.float64)).max()))
import sys; sys.exit(0)
for name, a, b in (('A', got[0], want[0]), ('G', got[1], want[1])):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    print(name, 'shape', a.shape, 'maxdiff', float(np.abs(a - b).max()),
          'scale', float(np.abs(b).max()))
    print(name, 'ratio sample', (a.reshape(2, -1)[:, :3] /
                                 np.where(b.reshape(2, -1)[:, :3] == 0, 1,
                                          b.reshape(2, -1)[:, :3])))
