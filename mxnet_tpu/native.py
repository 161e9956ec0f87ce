"""ctypes binding for the native IO library (``native/libmxtpu_io.so``).

The runtime around the XLA compute path is native where the reference's is
(reference: src/io/ C++ iterators behind the C API): RecordIO parsing,
zero-copy record access and background prefetch live in
``native/recordio.cc``. The library is built on first use with the
in-image toolchain (``make -C native``); every consumer falls back to the
pure-Python implementation when the toolchain or build is unavailable.

Freshness is decided by CONTENT: a stamp beside the library holds the
sha256 of the sources that produced it, and a library whose stamp does
not match the tree's sources is rebuilt, never loaded — file times say
nothing in a copied or freshly checked-out tree.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

__all__ = ["get_lib", "NativeRecordReader", "available"]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libmxtpu_io.so")
_STAMP_PATH = _LIB_PATH + ".stamp"
_SOURCES = ("recordio.cc", "Makefile")

_lock = threading.Lock()
_lib = None
_tried = False


def _source_hash():
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _needs_build():
    """True unless the library exists AND its stamp names exactly the
    sources in the tree."""
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip() != _source_hash()
    except OSError:
        return True


def _build():
    """Rebuild the library multi-process safely.

    Spawn DataLoader workers all import this module and may race the
    rebuild; a worker that dlopens a half-written .so
    segfaults. So: (1) an ``fcntl.flock`` file lock serializes builders
    across processes, (2) the compiler writes to a temp file in the
    same directory which is ``os.rename``d into place — rename is
    atomic on POSIX, so a concurrent ``CDLL`` sees either the complete
    old library or the complete new one, never a torn write, and (3)
    the freshness check re-runs under the lock so waiters don't rebuild
    what the winner just produced."""
    import fcntl
    import tempfile
    lock_path = _LIB_PATH + ".lock"
    with open(lock_path, "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            if not _needs_build():
                return
            built_from = _source_hash()
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_NATIVE_DIR)
            os.close(fd)
            # make must CREATE the target — the empty mkstemp file
            # would register as up to date and get renamed as-is.
            # Reusing the reserved name is safe under the flock.
            os.unlink(tmp)
            try:
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR, "-s",
                     f"SO={os.path.basename(tmp)}",
                     os.path.basename(tmp)],
                    check=True, capture_output=True)
                os.rename(tmp, _LIB_PATH)
                # stamp AFTER the library is in place: a death between
                # the two leaves a library with a stale stamp, which
                # the next process rebuilds
                with open(_STAMP_PATH + ".tmp", "w") as f:
                    f.write(built_from)
                os.replace(_STAMP_PATH + ".tmp", _STAMP_PATH)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


def get_lib():
    """Load (building if needed) the native library, or None."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            # rebuild BEFORE the first dlopen when the sources changed —
            # relinking an already-mapped .so truncates live code pages,
            # and a second CDLL on the same inode returns the stale
            # handle anyway. _build serializes across processes (flock)
            # and renames atomically, so spawn workers racing here each
            # end up dlopening a complete library.
            if _needs_build():
                _build()
            lib = ctypes.CDLL(_LIB_PATH)
        except (OSError, subprocess.CalledProcessError):
            # no toolchain / no libjpeg / unloadable: consumers run the
            # pure-Python path. A library these sources did not produce
            # is not an alternative
            return None
        lib.rio_open.restype = ctypes.c_void_p
        lib.rio_open.argtypes = [ctypes.c_char_p]
        lib.rio_count.restype = ctypes.c_int64
        lib.rio_count.argtypes = [ctypes.c_void_p]
        lib.rio_record_len.restype = ctypes.c_int64
        lib.rio_record_len.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.rio_record_ptr.restype = ctypes.c_void_p
        lib.rio_record_ptr.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.rio_record_copy.restype = ctypes.c_int
        lib.rio_record_copy.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_void_p]
        lib.rio_record_offset.restype = ctypes.c_int64
        lib.rio_record_offset.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.rio_error.restype = ctypes.c_char_p
        lib.rio_error.argtypes = [ctypes.c_void_p]
        lib.rio_prefetch_start.restype = ctypes.c_int
        lib.rio_prefetch_start.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64]
        lib.rio_prefetch_next.restype = ctypes.c_int64
        lib.rio_prefetch_next.argtypes = [ctypes.c_void_p]
        lib.rio_prefetch_stop.argtypes = [ctypes.c_void_p]
        lib.rio_close.argtypes = [ctypes.c_void_p]
        # in-native JPEG decode + augment (iter_image_recordio_2.cc:727
        # analog); absent in pre-r5 builds — probe before binding
        if hasattr(lib, "rio_decode_batch"):
            lib.rio_decode_record.restype = ctypes.c_int
            lib.rio_decode_record.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_uint64, ctypes.c_void_p]
            lib.rio_decode_batch.restype = ctypes.c_int
            lib.rio_decode_batch.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_void_p,
                ctypes.c_int]
            lib.rio_record_label.restype = ctypes.c_int
            lib.rio_record_label.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_float), ctypes.c_int]
        if hasattr(lib, "rio_record_offsets"):
            lib.rio_record_offsets.restype = ctypes.c_int64
            lib.rio_record_offsets.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


class NativeRecordReader:
    """Random-access RecordIO reader over the native library.

    Indexes the whole file once (mmap, O(n) scan), then serves records
    by ordinal with zero-copy for single-segment records. ``prefetch``
    starts the C++ readahead thread over an epoch's access order
    (reference analog: iter_prefetcher.h + dmlc::ThreadedIter)."""

    def __init__(self, path):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self._h = lib.rio_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")
        err = lib.rio_error(self._h)
        if err:
            msg = err.decode()
            if msg:
                lib.rio_close(self._h)
                self._h = None
                raise IOError(f"{path}: {msg}")

    def __len__(self):
        return int(self._lib.rio_count(self._h))

    def offset(self, idx) -> int:
        """Byte offset of record ``idx``'s header (for .idx files)."""
        off = self._lib.rio_record_offset(self._h, idx)
        if off < 0:
            raise IndexError(idx)
        return int(off)

    def read(self, idx) -> bytes:
        n = self._lib.rio_record_len(self._h, idx)
        if n < 0:
            raise IndexError(idx)
        ptr = self._lib.rio_record_ptr(self._h, idx)
        if ptr:
            return ctypes.string_at(ptr, n)
        buf = ctypes.create_string_buffer(int(n))
        if self._lib.rio_record_copy(self._h, idx, buf) != 0:
            raise IndexError(idx)
        return buf.raw

    def prefetch(self, order, capacity=64):
        arr = (ctypes.c_int64 * len(order))(*order)
        if self._lib.rio_prefetch_start(self._h, arr, len(order),
                                        capacity) != 0:
            raise RuntimeError("prefetch already running")

    def prefetch_next(self) -> Optional[int]:
        idx = self._lib.rio_prefetch_next(self._h)
        return None if idx < 0 else int(idx)

    def prefetch_stop(self):
        self._lib.rio_prefetch_stop(self._h)

    def close(self):
        if self._h:
            self._lib.rio_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
