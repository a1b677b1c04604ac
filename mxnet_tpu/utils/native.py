"""ctypes bindings + lazy build of the native IO library (libmxtpu_io).

Parity role: MXNet's C++ data plane (src/io/*, dmlc recordio) — the one
host-side hot path XLA does not cover (SURVEY.md §7.1).  The library is
compiled from the committed ``mxnet_tpu/native/src/mxtpu_io.cc`` with g++
on first use (no pybind — plain C ABI) and cached next to the source.
``MXNET_TPU_NO_NATIVE=1`` selects the pure-Python plane; otherwise a
build or load failure raises to whoever asked for the library — it never
turns into the Python path behind the caller's back.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as onp

from ..analysis.lockwitness import named_lock as _named_lock
from ..base import MXNetError

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_SRC = os.path.abspath(os.path.join(_NATIVE_DIR, "src", "mxtpu_io.cc"))
_LIB = os.path.abspath(os.path.join(_NATIVE_DIR, "libmxtpu_io.so"))

_lock = _named_lock("native.build", "one-shot native lib build")
_lib = None
_build_error: Optional[str] = None   # a failed build is not retried


def _build() -> Optional[str]:
    """Compile the library; the compiler's complaint on failure."""
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", _LIB, "-ljpeg"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=300)
    except subprocess.CalledProcessError as e:
        return f"{' '.join(cmd)} exited {e.returncode}: {e.stderr[-2000:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{' '.join(cmd)}: {e}"
    return None


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, built from the committed source when
    missing or older than it.  None only under ``MXNET_TPU_NO_NATIVE``;
    raises :class:`MXNetError` when it cannot be built or loaded."""
    global _lib, _build_error
    if os.environ.get("MXNET_TPU_NO_NATIVE"):
        return None
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is None and not (
                os.path.exists(_LIB) and
                os.path.getmtime(_LIB) >= os.path.getmtime(_SRC)):
            _build_error = _build()
        if _build_error is not None:
            raise MXNetError(
                "native IO library (libmxtpu_io.so) failed to build — fix "
                "the toolchain or set MXNET_TPU_NO_NATIVE=1 for the "
                f"pure-Python data plane: {_build_error}")
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as e:
            raise MXNetError(f"native IO library failed to load: {e}") \
                from e
        lib.mxio_writer_open.restype = ctypes.c_void_p
        lib.mxio_writer_open.argtypes = [ctypes.c_char_p]
        lib.mxio_writer_tell.restype = ctypes.c_int64
        lib.mxio_writer_tell.argtypes = [ctypes.c_void_p]
        lib.mxio_writer_write.restype = ctypes.c_int
        lib.mxio_writer_write.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.mxio_writer_close.argtypes = [ctypes.c_void_p]
        lib.mxio_scan.restype = ctypes.c_int64
        lib.mxio_scan.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64))]
        lib.mxio_free.argtypes = [ctypes.c_void_p]
        lib.mxio_pipe_open.restype = ctypes.c_void_p
        lib.mxio_pipe_open.argtypes = [
            ctypes.c_char_p,
            onp.ctypeslib.ndpointer(onp.uint64, flags="C_CONTIGUOUS"),
            onp.ctypeslib.ndpointer(onp.uint64, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            onp.ctypeslib.ndpointer(onp.float32, flags="C_CONTIGUOUS"),
            onp.ctypeslib.ndpointer(onp.float32, flags="C_CONTIGUOUS"),
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
        lib.mxio_pipe_schedule.argtypes = [
            ctypes.c_void_p,
            onp.ctypeslib.ndpointer(onp.int64, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_uint64]
        lib.mxio_pipe_next.restype = ctypes.c_int64
        lib.mxio_pipe_next.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            onp.ctypeslib.ndpointer(onp.float32, flags="C_CONTIGUOUS"),
            onp.ctypeslib.ndpointer(onp.float32, flags="C_CONTIGUOUS"),
            onp.ctypeslib.ndpointer(onp.uint8, flags="C_CONTIGUOUS")]
        lib.mxio_pipe_close.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


def available() -> bool:
    """False only when ``MXNET_TPU_NO_NATIVE`` opts out (see get_lib)."""
    return get_lib() is not None


# ------------------------------------------------------------------ API


def scan_record_offsets(path):
    """(offsets, lengths) uint64 arrays of LOGICAL records, natively
    scanned; None if the library is opted out or the scan fails.

    Single-frame records: (payload offset, payload length).  Multipart
    records (dmlc cflag chains): bit 63 of the length is set, the offset
    points at the FIRST FRAME HEADER and the length (bit 63 masked off)
    spans every frame through the last frame's payload — reassemble with
    mxnet_tpu.recordio.reassemble_span.
    """
    lib = get_lib()
    if lib is None:
        return None
    po = ctypes.POINTER(ctypes.c_uint64)()
    pl = ctypes.POINTER(ctypes.c_uint64)()
    n = lib.mxio_scan(path.encode(), ctypes.byref(po), ctypes.byref(pl))
    if n < 0:
        return None
    offs = onp.ctypeslib.as_array(po, shape=(n,)).copy()
    lens = onp.ctypeslib.as_array(pl, shape=(n,)).copy()
    lib.mxio_free(po)
    lib.mxio_free(pl)
    return offs, lens


class NativeRecordWriter:
    """Sequential RecordIO writer running in C (same framing as
    mx.recordio.MXRecordIO)."""

    def __init__(self, path):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self._h = lib.mxio_writer_open(path.encode())
        if not self._h:
            raise OSError(f"cannot open {path}")

    def tell(self):
        return self._lib.mxio_writer_tell(self._h)

    def write(self, buf: bytes):
        if self._lib.mxio_writer_write(self._h, buf, len(buf)):
            raise OSError("record write failed")

    def close(self):
        if self._h:
            self._lib.mxio_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class NativeImagePipeline:
    """Threaded pread + JPEG decode + augment pipeline over a RecordIO
    file (parity: src/io/iter_image_recordio_2.cc).  Yields NCHW float32
    batches in deterministic schedule order; records the C side could not
    decode are flagged so the caller can re-decode them in Python."""

    def __init__(self, path, offsets, lengths, data_shape, resize=-1,
                 rand_crop=False, rand_mirror=False,
                 mean=(0., 0., 0.), std=(1., 1., 1.), seed=0,
                 label_width=1, threads=4, capacity=None):
        if capacity is None:   # MXNET_TPU_PREFETCH: decoded-sample buffer
            capacity = int(os.environ.get("MXNET_TPU_PREFETCH", 256))
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        c, h, w = data_shape
        if c != 3:
            raise ValueError("native pipeline is RGB-only (C=3)")
        self._lib = lib
        self._shape = (3, h, w)
        self._label_width = label_width
        offs = onp.ascontiguousarray(offsets, onp.uint64)
        lens = onp.ascontiguousarray(lengths, onp.uint64)
        self._seed = int(seed) & (2 ** 64 - 1)
        self._epoch = 0
        self._h = lib.mxio_pipe_open(
            path.encode(), offs, lens, len(offs), int(threads), h, w,
            int(resize), int(bool(rand_crop)), int(bool(rand_mirror)),
            onp.asarray(mean, onp.float32), onp.asarray(std, onp.float32),
            self._seed, int(label_width), int(capacity))
        if not self._h:
            raise OSError(f"cannot open {path}")

    def schedule(self, order, seed=None):
        order = onp.ascontiguousarray(order, onp.int64)
        self._epoch += 1
        if seed is None:
            seed = (self._seed + 0x10001 * self._epoch) & (2 ** 64 - 1)
        self._lib.mxio_pipe_schedule(self._h, order, len(order), seed)

    def next_batch(self, batch_size):
        """(data (B,3,H,W) f32, labels (B,label_width) f32, ok (B,) bool,
        n_filled)."""
        c, h, w = self._shape
        data = onp.empty((batch_size, c, h, w), onp.float32)
        labels = onp.empty((batch_size, self._label_width), onp.float32)
        ok = onp.empty((batch_size,), onp.uint8)
        n = self._lib.mxio_pipe_next(self._h, batch_size, data, labels, ok)
        return data, labels, ok.astype(bool), int(n)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxio_pipe_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
