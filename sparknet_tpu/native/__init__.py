"""Native pipeline library: build-on-first-use + ctypes bindings.

The reference shipped its native engine as a cmake-built libccaffe.so loaded
via JNA (CaffeLibrary.java:9); here the native surface is the host data
pipeline only (XLA owns device kernels), compiled lazily with g++ and loaded
via ctypes. Everything has a numpy path — ``available()`` says which is
active. The numpy path is for machines without a compiler: where g++
exists and the build or load fails, that is an error, not a fallback.

The binary is git-ignored and ``-march=native``, so it is only ever valid
for the source and the CPU that built it: its file name carries a digest
of pipeline.cpp, the flags and this machine's CPU flags. A tree copied to
another machine (or with an edited source) finds no binary under its own
name and builds one — never an mtime comparison, which a copy resets.
"""

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "pipeline.cpp")
_FLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]
_ABI = 3

_lock = threading.Lock()
_lib = None
_tried = False


def _cpu_flags():
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return ""


def lib_path():
    """The binary this source builds to on this machine."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_flags().encode())
    return os.path.join(_DIR,
                        f"libsparknet_native.{h.hexdigest()[:12]}.so")


def build():
    """Compile pipeline.cpp to lib_path(), replacing whatever is there.
    Raises on failure."""
    # Compile to a per-pid temp file and rename atomically: concurrent
    # builders (pytest workers, multi-host on a shared FS) must never dlopen
    # a partially written .so, and rename() makes the publish atomic.
    so = lib_path()
    tmp = f"{so}.build.{os.getpid()}"
    base = ["g++"] + _FLAGS
    try:
        try:
            subprocess.run(base + ["-fopenmp", _SRC, "-o", tmp], check=True,
                           capture_output=True)
        except subprocess.CalledProcessError:   # no libgomp: single-threaded
            try:
                subprocess.run(base + [_SRC, "-o", tmp], check=True,
                               capture_output=True)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    "g++ failed on native/pipeline.cpp:\n"
                    + e.stderr.decode("utf-8", "replace")[-4000:]) from e
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = lib_path()
        if not os.path.exists(so):
            if shutil.which("g++") is None:
                return None              # no compiler here: numpy path
            build()
        lib = ctypes.CDLL(so)
        if lib.native_abi_version() != _ABI:
            raise RuntimeError(
                f"{so} reports ABI {lib.native_abi_version()}, the "
                f"bindings expect {_ABI}: bump _ABI with pipeline.cpp")
        _bind(lib)
        _lib = lib
        return _lib


def _bind(lib):
    i64, i32p = ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.transform_batch.argtypes = [
        u8p, i64, i64, i64, i64, i64, i32p, i32p, u8p, f32p,
        ctypes.c_int, ctypes.c_float, f32p]
    lib.transform_batch.restype = None
    lib.decode_cifar_records.argtypes = [u8p, i64, i64, u8p, i32p]
    lib.decode_cifar_records.restype = None
    lib.accumulate_sum.argtypes = [u8p, i64, i64, i64p]
    lib.accumulate_sum.restype = None
    lib.crc32c_update.argtypes = [u8p, i64, ctypes.c_uint32]
    lib.crc32c_update.restype = ctypes.c_uint32
    lib.snappy_uncompress.argtypes = [u8p, i64, u8p, i64]
    lib.snappy_uncompress.restype = i64


def available():
    return _load() is not None


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def transform_batch(images, crop, ys=None, xs=None, mirror=None, mean=None,
                    scale=1.0, full_mean=False):
    """uint8 (N,C,H,W) -> float32 (N,C,crop,crop); native when possible.

    mean: None | (C,) per-channel | (C,crop,crop) cropped mean image
    (subtracted after the mirror) | with full_mean=True a (C,H,W)
    source-size mean image subtracted at the crop-window source index
    before the mirror — the exact reference mean_file semantics
    (data_transformer.cpp:42-51).
    ys/xs: per-image int32 crop offsets (None -> 0: top-left/no crop).
    mirror: per-image uint8 flags (None -> no flips).
    """
    lib = _load()
    images = np.ascontiguousarray(images, np.uint8)
    n, c, h, w = images.shape
    if mean is not None:
        mean = np.ascontiguousarray(mean, np.float32)
        if mean.ndim == 1:
            mean_kind = 1
        elif full_mean:
            mean_kind = 3
            if mean.shape != (c, h, w):
                raise ValueError(
                    f"full mean shape {mean.shape} != {(c, h, w)}")
        else:
            mean_kind = 2
            if mean.shape != (c, crop, crop):
                raise ValueError(
                    f"mean shape {mean.shape} != {(c, crop, crop)}")
    else:
        mean_kind = 0
    if lib is not None:
        out = np.empty((n, c, crop, crop), np.float32)
        ys_a = np.ascontiguousarray(ys, np.int32) if ys is not None else None
        xs_a = np.ascontiguousarray(xs, np.int32) if xs is not None else None
        mir = np.ascontiguousarray(mirror, np.uint8) \
            if mirror is not None else None
        lib.transform_batch(
            _ptr(images, ctypes.c_uint8), n, c, h, w, crop,
            _ptr(ys_a, ctypes.c_int32) if ys_a is not None else None,
            _ptr(xs_a, ctypes.c_int32) if xs_a is not None else None,
            _ptr(mir, ctypes.c_uint8) if mir is not None else None,
            _ptr(mean, ctypes.c_float) if mean is not None else None,
            mean_kind, ctypes.c_float(scale), _ptr(out, ctypes.c_float))
        return out
    # numpy fallback
    out = np.empty((n, c, crop, crop), np.float32)
    for i in range(n):
        y0 = int(ys[i]) if ys is not None else 0
        x0 = int(xs[i]) if xs is not None else 0
        win = images[i, :, y0:y0 + crop, x0:x0 + crop].astype(np.float32)
        if mean_kind == 3:  # source-indexed subtract, then mirror
            win = win - mean[:, y0:y0 + crop, x0:x0 + crop]
        if mirror is not None and mirror[i]:
            win = win[:, :, ::-1]
        out[i] = win
    if mean_kind == 1:
        out -= mean.reshape(1, c, 1, 1)
    elif mean_kind == 2:
        out -= mean
    if scale != 1.0:
        out *= scale
    return out


def decode_cifar_records(raw, record):
    """Packed records -> (images uint8 (N, record-1), labels int32)."""
    raw = np.ascontiguousarray(raw, np.uint8)
    n = raw.size // record
    lib = _load()
    if lib is not None:
        images = np.empty((n, record - 1), np.uint8)
        labels = np.empty(n, np.int32)
        lib.decode_cifar_records(_ptr(raw, ctypes.c_uint8), n, record,
                                 _ptr(images, ctypes.c_uint8),
                                 _ptr(labels, ctypes.c_int32))
        return images, labels
    recs = raw[:n * record].reshape(n, record)
    return np.ascontiguousarray(recs[:, 1:]), recs[:, 0].astype(np.int32)


def accumulate_sum(images, acc):
    """Add sum-over-batch of uint8 (N,...) into int64 acc (...)."""
    images = np.ascontiguousarray(images, np.uint8)
    lib = _load()
    if lib is not None and acc.flags.c_contiguous:
        n = images.shape[0]
        chw = images.size // max(n, 1)
        if n:
            lib.accumulate_sum(_ptr(images, ctypes.c_uint8), n, chw,
                               _ptr(acc, ctypes.c_int64))
        return acc
    acc += images.astype(np.int64).sum(axis=0)
    return acc


def crc32c(data, crc=0):
    """Native crc32c (Castagnoli) with the leveldb.py (data, crc)
    semantics, or None when the native lib is unavailable."""
    lib = _load()
    if lib is None:
        return None
    if not len(data):
        return crc & 0xffffffff      # xor-in/xor-out cancel on empty input
    buf = np.frombuffer(data, np.uint8)      # zero-copy for bytes-likes
    return int(lib.crc32c_update(_ptr(buf, ctypes.c_uint8), len(buf), crc))


def snappy_uncompress(data, declared_len):
    """Decode a raw-Snappy payload to bytes via the native decoder.
    Returns None when the lib is unavailable OR the decode fails — the
    caller's pure-Python decoder is both the fallback and the error
    path with the descriptive diagnostics."""
    lib = _load()
    if lib is None:
        return None
    # a corrupt preamble could claim terabytes: max snappy expansion is
    # ~64/3 bytes out per byte in (a 3-byte copy-2 element emitting 64),
    # so anything past 24x + slack cannot be a valid stream
    if declared_len < 0 or declared_len > len(data) * 24 + 64:
        return None
    src = np.frombuffer(data, np.uint8)      # zero-copy for bytes-likes
    out = np.empty(declared_len, np.uint8)
    got = lib.snappy_uncompress(
        _ptr(src, ctypes.c_uint8), len(src),
        _ptr(out, ctypes.c_uint8), declared_len)
    if got != declared_len:
        return None
    return out.tobytes()
