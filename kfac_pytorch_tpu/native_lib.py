"""ctypes bindings for the native runtime library (native/kfac_native.cc).

Built from the committed source: the shared object is keyed by a hash of
``kfac_native.cc`` (``native/libkfac_native-<sha>.so``, git-ignored), so
a checkout never loads a binary built from another source — edit the
source and the next import builds anew (no pybind11 in this image; plain
C linkage + ctypes). Every entry point has a NumPy twin in pure Python,
used when — and only when — the machine has no C++ compiler (mirrors how
the reference keeps tcmm optional, kfac/utils.py:7); a build or load
that FAILS raises.
"""

import ctypes
import hashlib
import os
import shutil
import subprocess
import sys

import numpy as np

_DIR = os.path.join(os.path.dirname(__file__), '..', 'native')
_SRC = os.path.join(_DIR, 'kfac_native.cc')
_lib = None
_tried = False


def _lib_path():
    with open(_SRC, 'rb') as f:
        sha = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f'libkfac_native-{sha}.so')


def _build(path):
    # build beside the target and rename: concurrent importers (xdist
    # workers) each publish a complete file or none
    tmp = f'{path}.{os.getpid()}.tmp'
    try:
        subprocess.run(['c++', '-O2', '-shared', '-fPIC', '-o', tmp, _SRC],
                       check=True, capture_output=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def get_lib():
    """The native library, built if this source was never built here;
    None (NumPy paths) only where no C++ compiler exists."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    path = _lib_path()
    how = 'loaded'
    if not os.path.exists(path):
        if shutil.which('c++') is None:
            print('kfac_pytorch_tpu: no C++ compiler — native/ stays '
                  'unbuilt, NumPy paths in use', file=sys.stderr)
            return None
        _build(path)
        how = 'built'
    lib = ctypes.CDLL(path)
    print(f'kfac_pytorch_tpu: native library {how}: '
          f'{os.path.relpath(path)}', file=sys.stderr)
    lib.block_partition.restype = ctypes.c_double
    lib.block_partition.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.lpt_assign.restype = ctypes.c_double
    lib.lpt_assign.argtypes = lib.block_partition.argtypes
    lib.augment_crop_flip.restype = None
    lib.augment_crop_flip.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_float)]
    _lib = lib
    return _lib


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def block_partition(costs, num_devices):
    lib = get_lib()
    costs = np.ascontiguousarray(costs, np.float64)
    owners = np.zeros(len(costs), np.int64)
    if lib is None:
        from kfac_pytorch_tpu.parallel import partition
        return partition.block_partition(costs, num_devices)
    lib.block_partition(_ptr(costs, ctypes.c_double), len(costs),
                        num_devices, _ptr(owners, ctypes.c_int64))
    return owners


def lpt_assign(costs, num_devices):
    lib = get_lib()
    costs = np.ascontiguousarray(costs, np.float64)
    owners = np.zeros(len(costs), np.int64)
    if lib is None:
        from kfac_pytorch_tpu.parallel import partition
        return partition.balanced_assign(costs, num_devices)
    lib.lpt_assign(_ptr(costs, ctypes.c_double), len(costs), num_devices,
                   _ptr(owners, ctypes.c_int64))
    return owners


def augment_crop_flip(x, offs, flips, pad=4):
    """Native batched pad-crop-flip; x: [N,H,W,C] float32."""
    lib = get_lib()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, np.float32)
    offs = np.ascontiguousarray(offs, np.int32)
    flips = np.ascontiguousarray(flips, np.uint8)
    out = np.empty_like(x)
    n, h, w, c = x.shape
    lib.augment_crop_flip(_ptr(x, ctypes.c_float), n, h, w, c, pad,
                          _ptr(offs, ctypes.c_int32),
                          _ptr(flips, ctypes.c_uint8),
                          _ptr(out, ctypes.c_float))
    return out
