"""Native runtime components (C++ via ctypes).

The reference's runtime leaned on native code in two places of its own
(SURVEY.md §2.9): runtime-compiled PyCUDA kernels in the exchanger (on TPU
those became Pallas kernels — ``theanompi_tpu/ops/compress.py``) and the
parallel-loader child process that augmented batches on CPU and pushed them
into the GPU over CUDA IPC (§2.8).  The CPU half of that loader is this
module: the crop/mirror gather on uint8 (the cast and the mean belong to the
step program, ``ModelBase.stage_input``), ``loader.cc`` compiled at first
use with the system ``g++`` (mirroring the reference's
compile-on-first-run PyCUDA habit) and called through ctypes.  No pybind11 in
this environment; the C ABI + ctypes keeps the binding dependency-free.

``augment_batch`` is the public entry; it transparently falls back to a
NumPy implementation when no compiler is available, and both paths are
bit-identical (tested in ``tests/test_native_loader.py``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "loader.cc")
# No -march=native: the chip tool copies the working tree to another
# machine, and a library tuned to the build host's CPU may not run there.
_CXXFLAGS = ("-O3", "-shared", "-fPIC", "-pthread", "-std=c++17")

_lock = threading.Lock()
_lib = None
_lib_tried = False

DEFAULT_THREADS = min(16, os.cpu_count() or 1)


def _so_path() -> str:
    """The built library's path, keyed on a hash of ``loader.cc`` and the
    compiler flags: a ``.so`` built from other source or with other flags
    has another name and is never picked up."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_HERE, f"_loader-{h.hexdigest()[:16]}.so")


def _build() -> Optional[str]:
    """Compile loader.cc → ``_loader-<hash>.so`` if absent. Returns the
    path, or None when there is no compiler or the build fails.

    Compiles to a per-process temp name and installs with an atomic
    ``os.replace`` so concurrent first-use across processes (pytest-xdist, a
    multi-process host) can't interleave writes into one file — worst case
    both compile and the last install wins, both valid.
    """
    try:
        so = _so_path()
    except OSError:
        return None
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        r = subprocess.run(["g++", *_CXXFLAGS, _SRC, "-o", tmp],
                           capture_output=True)
        if r.returncode != 0:
            return None
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError):
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def get_lib():
    """The loaded native library, or None (then callers use the NumPy path).
    Set ``TMPI_NO_NATIVE=1`` to force the fallback."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    with _lock:
        if _lib_tried:
            return _lib
        if os.environ.get("TMPI_NO_NATIVE"):
            _lib_tried = True
            return None
        so = _build()
        if so is not None:
            try:
                lib = ctypes.CDLL(so)
                lib.tmpi_augment_u8.restype = None
                lib.tmpi_augment_u8.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p,          # in, out
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,  # n, h, w
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,  # c, crop, nchw
                    ctypes.c_void_p, ctypes.c_void_p,          # oy, ox
                    ctypes.c_void_p, ctypes.c_int,             # flip, threads
                ]
                lib.tmpi_loader_abi_version.restype = ctypes.c_int
                assert lib.tmpi_loader_abi_version() == 2
                _lib = lib
            except (OSError, AssertionError):
                _lib = None
                try:            # don't let a corrupt .so poison future runs
                    os.remove(so)
                except OSError:
                    pass
        _lib_tried = True
        return _lib


def native_available() -> bool:
    return get_lib() is not None


def is_nchw(x: np.ndarray) -> bool:
    """Layout heuristic for 4-D image batches, shared by the native and
    NumPy augment paths and the .hkl readers: channels-first iff dim 1 looks
    like a channel count and the trailing dim doesn't."""
    return x.ndim == 4 and x.shape[1] in (1, 3) and x.shape[-1] not in (1, 3)


def _augment_numpy(x, oy, ox, flip, crop):
    n = x.shape[0]
    if is_nchw(x):
        x = x.transpose(0, 2, 3, 1)
    out = np.empty((n, crop, crop, x.shape[-1]), np.uint8)
    for i in range(n):
        win = x[i, oy[i]:oy[i] + crop, ox[i]:ox[i] + crop, :]
        out[i] = win[:, ::-1, :] if flip[i] else win
    return out


def augment_batch(x: np.ndarray, oy, ox, flip, crop: int,
                  n_threads: Optional[int] = None) -> np.ndarray:
    """Crop + mirror in one pass: uint8 batch → uint8 NHWC crops.

    ``x``: uint8 ``[n,h,w,c]`` (NHWC) or ``[n,c,h,w]`` (NCHW — the
    reference's bc01 batch files); ``oy``/``ox``/``flip``: per-image crop
    offsets and mirror flags (scalars and one-element arrays broadcast).
    """
    assert x.dtype == np.uint8 and x.ndim == 4, (x.dtype, x.shape)
    n = x.shape[0]
    oy = np.broadcast_to(np.asarray(oy, np.int32), (n,))
    ox = np.broadcast_to(np.asarray(ox, np.int32), (n,))
    flip = np.broadcast_to(np.asarray(flip, np.uint8), (n,))
    nchw = is_nchw(x)
    c = x.shape[1] if nchw else x.shape[-1]
    h, w = (x.shape[2], x.shape[3]) if nchw else (x.shape[1], x.shape[2])
    if n and (oy.min() < 0 or ox.min() < 0 or oy.max() + crop > h
              or ox.max() + crop > w):
        # the native pass reads what the offsets say: never outside x
        raise ValueError(f"crop windows of side {crop} at offsets up to "
                         f"({oy.max()},{ox.max()}) leave the {h}x{w} images")

    lib = get_lib()
    if lib is None:
        return _augment_numpy(x, oy, ox, flip, crop)

    x = np.ascontiguousarray(x)
    oy = np.ascontiguousarray(oy)
    ox = np.ascontiguousarray(ox)
    flip = np.ascontiguousarray(flip)
    out = np.empty((n, crop, crop, c), np.uint8)
    lib.tmpi_augment_u8(
        x.ctypes.data, out.ctypes.data, n, h, w, c, crop, int(nchw),
        oy.ctypes.data, ox.ctypes.data, flip.ctypes.data,
        n_threads if n_threads is not None else DEFAULT_THREADS)
    return out
