"""Pure-functional math ops for K-FAC on TPU (MXU-batched, fp32 factors).

The fused capture kernels (``ops.pallas_capture``: patch-extract +
factor GEMM + EMA / wire-quantize epilogues, ISSUE 19) are deliberately
NOT imported here — like ``ops.pallas_attention`` they pull in Pallas,
which the reference capture path never needs; consumers import the
submodule lazily (engine._capture_backend, collectives.pmean_scatter_ef).
"""

from kfac_pytorch_tpu.ops.factors import (
    extract_patches,
    compute_a_dense,
    compute_a_conv,
    compute_g_dense,
    compute_g_conv,
    layer_rows_dense,
    layer_rows_conv,
    ekfac_scales,
    update_running_avg,
)
from kfac_pytorch_tpu.ops.linalg import (
    psd_inverse,
    damped_psd_inverse,
    settle_inverse_rows,
    inverse_rows_finite,
    diagonal_finite,
    heal_rows,
    rows_finite,
    tile_diagonal,
    inverse_tiling,
    inverse_route,
    inverse_route_flop,
    sym_eig,
    jacobi_eigh,
    subspace_eigh,
    newton_schulz_inverse,
    warm_inverse,
    clamp_eigvals,
    add_scaled_identity,
    masked_trace,
    identity_pad,
)

__all__ = [
    'extract_patches', 'compute_a_dense', 'compute_a_conv',
    'compute_g_dense', 'compute_g_conv', 'layer_rows_dense',
    'layer_rows_conv', 'ekfac_scales', 'update_running_avg',
    'psd_inverse', 'damped_psd_inverse', 'settle_inverse_rows',
    'inverse_rows_finite', 'diagonal_finite', 'heal_rows', 'rows_finite', 'tile_diagonal',
    'inverse_tiling', 'inverse_route', 'inverse_route_flop',
    'sym_eig', 'jacobi_eigh', 'subspace_eigh',
    'newton_schulz_inverse', 'warm_inverse',
    'clamp_eigvals', 'add_scaled_identity',
    'masked_trace', 'identity_pad',
]
