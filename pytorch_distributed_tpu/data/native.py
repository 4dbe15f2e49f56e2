"""ctypes bridge to the C++ recordio core (csrc/recordio.cpp).

Compiles the shared library on first use with g++ (the image has no
pybind11; the C ABI + ctypes keeps the binding dependency-free). Falls back
gracefully: ``available()`` returns False when no toolchain is present and
the pure-Python reader takes over.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Sequence

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc")
_SRC = os.path.join(_CSRC, "recordio.cpp")
_BUILD_DIR = os.path.join(_CSRC, "build")

_lock = threading.Lock()
_lib = None
_lib_error: str | None = None


def _so_path() -> str:
    """The library's path, keyed on the source's content: a library is
    reused only if it was built from exactly this ``recordio.cpp``. (An
    mtime comparison is not that test — a copy of the tree can carry a
    binary newer than a source it was never built from.)"""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"librecordio-{digest}.so")


def _build() -> str | None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so = _so_path()
    if os.path.exists(so):
        return so
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread", _SRC,
           "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        global _lib_error
        _lib_error = f"native recordio build failed: {e}"
        return None
    os.replace(tmp, so)
    return so


def _load():
    global _lib, _lib_error
    with _lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.tpr_open.restype = ctypes.c_void_p
        lib.tpr_open.argtypes = [ctypes.c_char_p]
        lib.tpr_close.argtypes = [ctypes.c_void_p]
        lib.tpr_count.restype = ctypes.c_int64
        lib.tpr_count.argtypes = [ctypes.c_void_p]
        lib.tpr_size.restype = ctypes.c_int64
        lib.tpr_size.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.tpr_read.restype = ctypes.c_int64
        lib.tpr_read.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.tpr_read_batch.restype = ctypes.c_int64
        lib.tpr_read_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int,
        ]
        lib.tpr_crop_batch.restype = ctypes.c_int64
        lib.tpr_crop_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class SizeMismatch(IOError):
    """A raw record's stored (h, w) differs from what the caller planned
    crop coordinates for — fall back to the per-record-size path."""


class NativeReader:
    def __init__(self, path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError(_lib_error or "native recordio unavailable")
        self._lib = lib
        self._h = lib.tpr_open(path.encode())
        if not self._h:
            raise IOError(f"tpr_open failed for {path}")
        self.n = int(lib.tpr_count(self._h))

    def size(self, i: int) -> int:
        return int(self._lib.tpr_size(self._h, i))

    def read(self, i: int, verify_crc: bool = True) -> bytes:
        size = self.size(i)
        if size < 0:
            raise IndexError(i)
        buf = ctypes.create_string_buffer(size)
        status = self._lib.tpr_read(self._h, i, buf, int(verify_crc))
        if status == -2:
            raise IOError(f"crc mismatch in record {i}")
        if status < 0:
            raise IOError(f"read failed for record {i}")
        return buf.raw[:size]

    def read_batch(self, indices: Sequence[int], verify_crc: bool = True) -> list[bytes]:
        idx = np.asarray(indices, np.uint64)
        sizes = np.asarray([self.size(int(i)) for i in idx], np.int64)
        if (sizes < 0).any():
            raise IndexError("index out of range in batch")
        offsets = np.concatenate([[0], np.cumsum(sizes[:-1])]).astype(np.uint64)
        total = int(sizes.sum())
        buf = ctypes.create_string_buffer(total)
        status = self._lib.tpr_read_batch(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(idx),
            buf,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            int(verify_crc),
        )
        if status == -2:
            raise IOError("crc mismatch in batch read")
        if status < 0:
            raise IOError("batch read failed")
        raw = buf.raw
        return [
            raw[int(o) : int(o) + int(s)] for o, s in zip(offsets, sizes)
        ]

    def crop_batch(
        self,
        indices: Sequence[int],
        tops: Sequence[int],
        lefts: Sequence[int],
        flips: Sequence[bool],
        crop: int,
        expect_h: int,
        expect_w: int,
        n_threads: int = 0,
    ):
        """Read RAW image records (data/raw.py layout) and return
        (images [B, crop, crop, 3] uint8, labels [B] int32) with the crop
        windows and horizontal flips applied in C — one copy, no GIL.

        ``expect_h``/``expect_w`` pin the stored size the crop coordinates
        were drawn for; a record whose header disagrees raises
        ``SizeMismatch`` (caller falls back to the per-record-size path).
        """
        idx = np.ascontiguousarray(indices, np.uint64)
        t = np.ascontiguousarray(tops, np.int32)
        l = np.ascontiguousarray(lefts, np.int32)
        f = np.ascontiguousarray(flips, np.uint8)
        b = len(idx)
        images = np.empty((b, crop, crop, 3), np.uint8)
        labels = np.empty((b,), np.int32)
        if n_threads <= 0:
            n_threads = min(os.cpu_count() or 1, 8)
        status = self._lib.tpr_crop_batch(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            b,
            t.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            l.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            f.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            crop,
            expect_h,
            expect_w,
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_threads,
        )
        if status == -3:
            raise SizeMismatch(
                f"record size differs from expected {expect_h}x{expect_w}"
            )
        if status < 0:
            raise IOError(
                "native crop_batch failed (bad index, truncated record, or "
                "crop window out of bounds)"
            )
        return images, labels

    def close(self):
        if self._h:
            self._lib.tpr_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
