"""ctypes loader for the native host library (native/druid_native.cpp).

The reference's storage hot path rides JVM-native mechanics (lz4-java block
codec, off-heap ByteBuffers — reference:
processing/.../segment/data/CompressionStrategy.java:48). Here it is a real
C++ shared library, ALWAYS built from the tracked source with g++ the first
time it is needed: the binary is never committed, and it is named after a
hash of the source, so a stale build can never be chosen over the source (a
copy of the tree preserves content, not mtimes). Callers that can live
without it check `available()` and fall back to zlib/numpy paths; callers
that cannot (chip_smoke.py — the pure-python LZ4 codec at 100M rows looks
like a hang) call `require()`, which raises with the compiler's message.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC = os.path.join(_NATIVE_DIR, "druid_native.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_error: Optional[str] = None


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_NATIVE_DIR, f"libdruid_native-{digest}.so")


def _build(so: str) -> None:
    """Compile the source to `so`. Builds beside the target and renames, so
    a concurrent process (peons fork beside the server) never loads a
    half-written library; earlier sources' builds are swept."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-pthread",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_NATIVE_DIR, "libdruid_native*.so")):
        if stale != so:
            os.unlink(stale)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _error
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _so_path()
            if not os.path.exists(so):
                _build(so)
            lib = ctypes.CDLL(so)
        except subprocess.CalledProcessError as e:
            _error = f"g++ failed: {e.stderr.decode(errors='replace')[-2000:]}"
            return None
        except (OSError, subprocess.SubprocessError) as e:
            # no source / no toolchain / unloadable: callers fall back to
            # numpy paths, or fail loudly through require()
            _error = f"{type(e).__name__}: {e}"
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.druid_lz4_compress_bound.restype = ctypes.c_int64
        lib.druid_lz4_compress_bound.argtypes = [ctypes.c_int64]
        lib.druid_lz4_compress.restype = ctypes.c_int64
        lib.druid_lz4_compress.argtypes = [u8p, ctypes.c_int64, u8p,
                                           ctypes.c_int64]
        lib.druid_lz4_decompress.restype = ctypes.c_int64
        lib.druid_lz4_decompress.argtypes = [u8p, ctypes.c_int64, u8p,
                                             ctypes.c_int64]
        lib.druid_lz4_decompress_batch.restype = ctypes.c_int64
        lib.druid_lz4_decompress_batch.argtypes = [
            u8p, i64p, i64p, u8p, i64p, i64p, ctypes.c_int64, ctypes.c_int64]
        lib.druid_unpack_bits.restype = None
        lib.druid_unpack_bits.argtypes = [u8p, ctypes.c_int64, u8p]
        lib.druid_pack_keys.restype = None
        lib.druid_pack_keys.argtypes = [
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)), i64p,
            ctypes.c_int64, ctypes.c_int64, i64p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def require() -> None:
    """Raise unless the native library built and loaded — for paths where
    the pure-python fallbacks are not an option."""
    if _load() is None:
        raise RuntimeError(
            f"native library unavailable (built from {_SRC}): {_error}")


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def lz4_compress(data: bytes | np.ndarray) -> bytes:
    lib = _load()
    assert lib is not None
    src = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray, memoryview)) \
        else np.ascontiguousarray(data).view(np.uint8).ravel()
    n = src.shape[0]
    dst = np.empty(int(lib.druid_lz4_compress_bound(n)), dtype=np.uint8)
    got = lib.druid_lz4_compress(_u8(src), n, _u8(dst), dst.shape[0])
    if got < 0:
        raise ValueError("lz4 compression overflow")
    return dst[:got].tobytes()


def lz4_decompress(data, decompressed_size: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    src = np.frombuffer(data, dtype=np.uint8)
    dst = np.empty(decompressed_size, dtype=np.uint8)
    got = lib.druid_lz4_decompress(_u8(src), src.shape[0], _u8(dst),
                                   decompressed_size)
    if got != decompressed_size:
        raise ValueError(f"lz4 malformed block (got {got}, "
                         f"want {decompressed_size})")
    return dst


def lz4_decompress_batch(blob, src_offsets: np.ndarray, src_sizes: np.ndarray,
                         dst_offsets: np.ndarray, dst_sizes: np.ndarray,
                         total_out: int, n_threads: int = 0) -> np.ndarray:
    """Decompress many blocks from one blob into one contiguous buffer,
    multi-threaded in native code (the analog of the reference decompressing
    column chunks on the processing pool)."""
    lib = _load()
    assert lib is not None
    src = np.frombuffer(blob, dtype=np.uint8)
    dst = np.empty(total_out, dtype=np.uint8)
    if n_threads <= 0:
        n_threads = min(8, os.cpu_count() or 1)
    so = np.ascontiguousarray(src_offsets, dtype=np.int64)
    ss = np.ascontiguousarray(src_sizes, dtype=np.int64)
    do = np.ascontiguousarray(dst_offsets, dtype=np.int64)
    ds = np.ascontiguousarray(dst_sizes, dtype=np.int64)
    rc = lib.druid_lz4_decompress_batch(
        _u8(src), _i64(so), _i64(ss), _u8(dst), _i64(do), _i64(ds),
        len(so), n_threads)
    if rc != 0:
        raise ValueError(f"lz4 batch decompression failed at block {-rc - 1}")
    return dst


def unpack_bits(words: np.ndarray, n_rows: int) -> np.ndarray:
    lib = _load()
    if lib is None:
        return np.unpackbits(words, count=n_rows)
    words = np.ascontiguousarray(words, dtype=np.uint8)
    out = np.empty(n_rows, dtype=np.uint8)
    lib.druid_unpack_bits(_u8(words), n_rows, out.ctypes.data_as(
        ctypes.POINTER(ctypes.c_uint8)))
    return out


def pack_keys(cols, cards) -> np.ndarray:
    """Fused group key = horner-scheme pack of int32 id columns."""
    lib = _load()
    n_rows = cols[0].shape[0] if cols else 0
    if lib is None:
        out = np.zeros(n_rows, dtype=np.int64)
        for col, card in zip(cols, cards):
            out = out * int(card) + col.astype(np.int64)
        return out
    cols = [np.ascontiguousarray(c, dtype=np.int32) for c in cols]
    arr_type = ctypes.POINTER(ctypes.c_int32) * len(cols)
    ptrs = arr_type(*[c.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
                      for c in cols])
    cards_a = np.asarray(list(cards), dtype=np.int64)
    out = np.empty(n_rows, dtype=np.int64)
    lib.druid_pack_keys(ptrs, _i64(cards_a), len(cols), n_rows, _i64(out))
    return out
