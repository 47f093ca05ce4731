"""Native (C++) acceleration library: build-on-demand + ctypes bindings.

The reference ships its IO hot path in C++ (dmlc RecordIOReader +
``src/io`` image pipeline [unverified]); here ``src/librecordio.cc`` is
compiled once per checkout into a cached ``.so`` (under the git-ignored
``.mxtpu_cache/`` next to the package) and bound via ctypes. Every
entry point has a pure-Python fallback — the native path is an
acceleration, never a requirement (machines without g++/libjpeg still
work)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

_LOCK = threading.Lock()
_LIB = None
_TRIED = False

_SRC = os.path.join(os.path.dirname(__file__), "..", "src", "librecordio.cc")


def _build() -> Optional[str]:
    from .base import GENERATED_DIR

    os.makedirs(GENERATED_DIR, exist_ok=True)
    so = os.path.join(GENERATED_DIR, "libmxtpu_io.so")
    src = os.path.abspath(_SRC)
    if not os.path.exists(src):
        return None
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return so
    try:
        # compile to a per-process temp name and rename into place: rename
        # is atomic on the same filesystem, so a concurrent process (the
        # multi-worker launcher) can never dlopen a half-written .so
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src, "-ljpeg"],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so)
        return so
    except Exception:  # noqa: BLE001 - no compiler / no libjpeg: fallback
        return None


def lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("MXNET_TPU_NO_NATIVE"):
            return None
        so = _build()
        if so is None:
            return None
        try:
            L = ctypes.CDLL(so)
        except OSError:
            return None
        if L.mxtpu_io_abi_version() != 1:
            return None
        L.mxtpu_rio_open.restype = ctypes.c_void_p
        L.mxtpu_rio_open.argtypes = [ctypes.c_char_p]
        L.mxtpu_rio_count.restype = ctypes.c_longlong
        L.mxtpu_rio_count.argtypes = [ctypes.c_void_p]
        L.mxtpu_rio_size.restype = ctypes.c_longlong
        L.mxtpu_rio_size.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        L.mxtpu_rio_offset.restype = ctypes.c_longlong
        L.mxtpu_rio_offset.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        L.mxtpu_rio_end.restype = ctypes.c_longlong
        L.mxtpu_rio_end.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        L.mxtpu_rio_read.restype = ctypes.c_longlong
        L.mxtpu_rio_read.argtypes = [ctypes.c_void_p, ctypes.c_longlong,
                                     ctypes.c_char_p, ctypes.c_longlong]
        L.mxtpu_rio_read_at.restype = ctypes.c_longlong
        L.mxtpu_rio_read_at.argtypes = [ctypes.c_void_p, ctypes.c_longlong,
                                        ctypes.c_char_p, ctypes.c_longlong]
        L.mxtpu_rio_close.argtypes = [ctypes.c_void_p]
        L.mxtpu_jpeg_probe.restype = ctypes.c_int
        L.mxtpu_jpeg_probe.argtypes = [
            ctypes.c_char_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        L.mxtpu_jpeg_decode.restype = ctypes.c_int
        L.mxtpu_jpeg_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_longlong, ctypes.c_char_p,
            ctypes.c_longlong,
        ]
        _LIB = L
        return _LIB


def available() -> bool:
    return lib() is not None


class NativeRecordReader:
    """Random/sequential reader over one .rec file via the C++ scanner.

    The constructor scans the full framing into an offset index in native
    code (no Python per-record overhead); reads copy straight into bytes.
    """

    def __init__(self, path: str):
        L = lib()
        if L is None:
            raise RuntimeError("native IO library unavailable")
        self._L = L
        self._h = L.mxtpu_rio_open(path.encode())
        if not self._h:
            raise RuntimeError(f"cannot open/scan {path}")
        self._by_offset = None

    def __len__(self):
        return int(self._L.mxtpu_rio_count(self._h))

    def read(self, i: int) -> bytes:
        size = self._L.mxtpu_rio_size(self._h, i)
        if size < 0:
            raise IndexError(i)
        buf = ctypes.create_string_buffer(int(size))
        got = self._L.mxtpu_rio_read(self._h, i, buf, size)
        if got != size:
            raise RuntimeError(f"short read on record {i}")
        return buf.raw

    def read_at(self, offset: int):
        """-> (payload, end_offset) for the record starting at ``offset``;
        end_offset is where a sequential reader would stand afterwards."""
        if self._by_offset is None:
            self._by_offset = {
                int(self._L.mxtpu_rio_offset(self._h, i)): i
                for i in range(len(self))
            }
        i = self._by_offset.get(int(offset))
        if i is None:
            raise KeyError(f"no record at offset {offset}")
        return self.read(i), int(self._L.mxtpu_rio_end(self._h, i))

    def close(self):
        if self._h:
            self._L.mxtpu_rio_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


def jpeg_decode(img_bytes: bytes):
    """Decode a JPEG to an HWC uint8 BGR numpy array; None if the native
    path is unavailable or the payload is not a decodable JPEG."""
    import numpy as np

    L = lib()
    if L is None:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    c = ctypes.c_int()
    if L.mxtpu_jpeg_probe(img_bytes, len(img_bytes), ctypes.byref(w),
                          ctypes.byref(h), ctypes.byref(c)) != 0:
        return None
    out = np.empty((h.value, w.value, 3), np.uint8)
    rc = L.mxtpu_jpeg_decode(
        img_bytes, len(img_bytes),
        out.ctypes.data_as(ctypes.c_char_p), out.nbytes,
    )
    return out if rc == 0 else None
