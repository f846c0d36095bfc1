"""ctypes binding to the native C++ data pipeline (native/ddim_data.cc).

The reference parallelizes decode with 8 DataLoader worker *processes* per
rank (multi_gpu_trainer.py:63); the TPU-native runtime keeps one process per
host and moves the per-image work (libjpeg/libpng decode, torch-convention
resize, cold degradation, batch assembly) into a C++ thread pool that fills
numpy-owned float32 buffers — no Python, no GIL in the hot path.

The library is built lazily on first use (``g++`` one-liner, cached as
``native/libddim_data.so``); every entry point degrades gracefully to the
PIL/numpy path (datasets.py / resize.py), so the native layer is a pure
accelerator, never a dependency. Set ``DDIM_COLD_NO_NATIVE=1`` to disable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from ddim_cold_tpu.obs import spans

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libddim_data.so")

#: formats the native decoder handles; everything else goes through PIL.
NATIVE_EXTS = {".jpg", ".jpeg", ".png"}

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def _build() -> bool:
    """Compile ``native/ddim_data.cc`` (≈ 10 s), as a ``data/native/build``
    layer span (``ok``): it exists only in a process that ran the build."""
    src = os.path.join(_NATIVE_DIR, "ddim_data.cc")
    if not os.path.isfile(src):
        return False
    # compile to a per-process temp name, then atomically rename: concurrent
    # processes (multi-host on a shared fs, pytest-xdist) must never dlopen a
    # half-written .so.
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    with spans.layer("data/native/build") as span:
        try:
            subprocess.run(
                ["g++", "-O3", "-fPIC", "-std=c++17", "-ffp-contract=off", "-shared",
                 src, "-o", tmp, "-ljpeg", "-lpng", "-lpthread"],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp, _SO_PATH)
            ok = True
        except (subprocess.SubprocessError, OSError):  # compile failed / no g++
            try:
                os.unlink(tmp)
            except OSError:
                pass
            ok = False
        span.set(ok=ok)
    return ok


def _open_library() -> Optional[ctypes.CDLL]:
    """dlopen the decoder, building it first when it is missing or older than
    its source, as one ``data/native/load`` layer span (``built``) with the
    build's span inside it; child of whatever span is open on the thread —
    the dataset's ``data/dataset/open``, or a process's first decode."""
    with spans.layer("data/native/load") as span:
        src = os.path.join(_NATIVE_DIR, "ddim_data.cc")
        stale = (os.path.isfile(_SO_PATH) and os.path.isfile(src)
                 and os.path.getmtime(src) > os.path.getmtime(_SO_PATH))
        span.set(built=(not os.path.isfile(_SO_PATH) or stale) and _build())
        # a stale-but-present .so still loads (new entry points are
        # hasattr-guarded); only a missing library is fatal here
        if not os.path.isfile(_SO_PATH):
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            return None
        f32p = ctypes.POINTER(ctypes.c_float)
        i32p = ctypes.POINTER(ctypes.c_int32)
        charpp = ctypes.POINTER(ctypes.c_char_p)
        lib.ddim_load_base.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int, f32p]
        lib.ddim_load_base.restype = ctypes.c_int
        lib.ddim_cold_degrade.argtypes = [f32p, ctypes.c_int, ctypes.c_int,
                                          ctypes.c_int, f32p]
        lib.ddim_cold_degrade.restype = None
        lib.ddim_cold_item.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int, f32p, f32p]
        lib.ddim_cold_item.restype = ctypes.c_int
        lib.ddim_cold_batch.argtypes = [charpp, i32p, ctypes.c_int, ctypes.c_int,
                                        ctypes.c_int, ctypes.c_int, f32p, f32p, i32p]
        lib.ddim_cold_batch.restype = ctypes.c_int
        lib.ddim_base_batch.argtypes = [charpp, ctypes.c_int, ctypes.c_int,
                                        ctypes.c_int, ctypes.c_int, f32p, i32p]
        lib.ddim_base_batch.restype = ctypes.c_int
        try:
            lib.ddim_cold_pair_batch.argtypes = [f32p, i32p, ctypes.c_int,
                                                 ctypes.c_int, ctypes.c_int,
                                                 ctypes.c_int, f32p, f32p]
            lib.ddim_cold_pair_batch.restype = None
        except AttributeError:  # stale .so from before this entry point
            pass
        try:
            u8p = ctypes.POINTER(ctypes.c_uint8)
            lib.ddim_decode_batch.argtypes = [charpp, ctypes.c_int, ctypes.c_int,
                                              ctypes.c_int, ctypes.c_int, u8p,
                                              i32p]
            lib.ddim_decode_batch.restype = ctypes.c_int
        except AttributeError:  # stale .so from before this entry point
            pass
        return lib


def _load() -> Optional[ctypes.CDLL]:
    """The library, opened once a process (``_open_library``: the
    ``data/native/load`` span), or ``None`` when it is switched off or
    cannot be built or opened."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        if not os.environ.get("DDIM_COLD_NO_NATIVE"):
            _lib = _open_library()
        _lib_failed = _lib is None
        return _lib


def available() -> bool:
    """True when the native library is loaded (building it if needed)."""
    return _load() is not None


def has_decode_batch() -> bool:
    """True when the raw-u8 decode entry point exists (a stale .so built
    before it would silently force the float path — callers gate the uint8
    transfer mode on this so dtype never depends on which tier happened to
    fill a batch)."""
    lib = _load()
    return lib is not None and hasattr(lib, "ddim_decode_batch")


def supports(path: str) -> bool:
    return os.path.splitext(path)[1].lower() in NATIVE_EXTS


def _f32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _paths_array(paths: Sequence[str]):
    arr = (ctypes.c_char_p * len(paths))()
    arr[:] = [p.encode() for p in paths]
    return arr


def load_base(path: str, out_hw: tuple[int, int]) -> Optional[np.ndarray]:
    """decode → [0,1] → bilinear resize → [−1,1]; None on decode failure."""
    lib = _load()
    if lib is None or not supports(path):
        return None
    h, w = out_hw
    out = np.empty((h, w, 3), np.float32)
    if lib.ddim_load_base(path.encode(), h, w, _f32(out)):
        return None
    return out


def cold_degrade(img: np.ndarray, level_scale: int) -> Optional[np.ndarray]:
    """Native D(x, s) for a square (S, S, C) float32 array; None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    img = np.ascontiguousarray(img, np.float32)
    size, _, c = img.shape
    out = np.empty_like(img)
    lib.ddim_cold_degrade(_f32(img), size, c, int(level_scale), _f32(out))
    return out


def cold_item(path: str, size: int, t: int, chain: bool):
    """(D(x,t), target) for one file; None on failure → caller uses PIL."""
    lib = _load()
    if lib is None or not supports(path):
        return None
    noisy = np.empty((size, size, 3), np.float32)
    target = np.empty((size, size, 3), np.float32)
    if lib.ddim_cold_item(path.encode(), size, int(t), int(chain), _f32(noisy),
                          _f32(target)):
        return None
    return noisy, target


def cold_batch(paths: Sequence[str], ts: Sequence[int], size: int, chain: bool,
               num_threads: int = 8):
    """Assemble a whole (noisy, target) batch in C++ threads, straight into
    the final buffers — the C layer sniffs magic bytes itself, so unsupported
    or corrupt files just set their slot in ``failed_mask`` for the caller's
    PIL redo. Returns ``(noisy, target, failed_mask)`` or None when the
    library is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    n = len(paths)
    noisy = np.empty((n, size, size, 3), np.float32)
    target = np.empty((n, size, size, 3), np.float32)
    failed = np.zeros(n, np.int32)
    ts_arr = np.asarray(ts, np.int32)
    lib.ddim_cold_batch(
        _paths_array(paths), ts_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, size, int(chain), int(num_threads), _f32(noisy), _f32(target),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return noisy, target, failed.astype(bool)


def cold_pair_batch(bases: np.ndarray, ts: Sequence[int], chain: bool,
                    num_threads: int = 8):
    """(D(x,t), target) pairs from already-decoded (n, S, S, 3) base images —
    the cache's warm-epoch path (no file IO, degrade in C++ threads). Returns
    ``(noisy, target)`` or None when the library (or entry point) is missing."""
    lib = _load()
    if lib is None or not hasattr(lib, "ddim_cold_pair_batch"):
        return None
    bases = np.ascontiguousarray(bases, np.float32)
    n, size = bases.shape[0], bases.shape[1]
    noisy = np.empty_like(bases)
    target = np.empty_like(bases)
    ts_arr = np.asarray(ts, np.int32)
    lib.ddim_cold_pair_batch(
        _f32(bases), ts_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, size, int(chain), int(num_threads), _f32(noisy), _f32(target),
    )
    return noisy, target


def decode_batch(paths: Sequence[str], out_hw: tuple[int, int], num_threads: int = 8):
    """Raw RGB8 batch for the uint8 transfer path: a slot succeeds only when
    the file decodes at exactly ``out_hw`` (no resize — the bytes are the
    pre-normalization pixels). Returns ``(u8_batch, failed_mask)`` or None
    when the library (or entry point) is unavailable; failed slots go through
    the float path."""
    lib = _load()
    if lib is None or not hasattr(lib, "ddim_decode_batch"):
        return None
    n = len(paths)
    h, w = out_hw
    out = np.empty((n, h, w, 3), np.uint8)
    failed = np.zeros(n, np.int32)
    lib.ddim_decode_batch(
        _paths_array(paths), n, h, w, int(num_threads),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out, failed.astype(bool)


def base_batch(paths: Sequence[str], out_hw: tuple[int, int], num_threads: int = 8):
    """Batch of [−1,1] base images (Gaussian dataset front half); returns
    ``(base, failed_mask)`` or None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(paths)
    h, w = out_hw
    out = np.empty((n, h, w, 3), np.float32)
    failed = np.zeros(n, np.int32)
    lib.ddim_base_batch(
        _paths_array(paths), n, h, w, int(num_threads), _f32(out),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out, failed.astype(bool)
