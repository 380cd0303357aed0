"""Native-runtime bindings: record IO, data loader, channels, logging.

Reference parity: the C++ IO layer — `BinFileReader/Writer`
(src/io/binfile_{reader,writer}.cc), image transforms
(src/io/image_transformer.cc), metric `Channel`s
(src/utils/channel.cc) and glog-style logging
(src/utils/logging.cc) — bound via ctypes instead of SWIG
(src/api/*.i). The shared library lives in native/ and is built on
demand with `make` (g++ only, no cmake required; CMakeLists.txt exists
for integrators).

The `Loader` is the TPU-era redesign of `ImageBatchIter`
(python/singa/data.py): record indexing, per-epoch shuffling,
rank/world sharding and prefetch all happen in native worker threads;
Python only sees ready (key, bytes) pairs.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Iterator, Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libsinga_tpu_rt.so")
_lib = None
_lib_lock = threading.Lock()


def _load() -> ctypes.CDLL:
    """Build and load the native runtime. `make` runs on every first
    load, not only when the library is absent: it is incremental (a
    no-op when `native/src` is unchanged), and it is what makes the
    loaded `.so` the product of the tracked sources rather than a
    stale file left on this disk."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        made = subprocess.run(["make"], cwd=_NATIVE_DIR,
                              capture_output=True, text=True)
        if made.returncode != 0:
            raise RuntimeError(
                f"building {_LIB_PATH} failed (make exit "
                f"{made.returncode}):\n{made.stderr}")
        lib = ctypes.CDLL(_LIB_PATH)
        lib.st_writer_open.restype = ctypes.c_void_p
        lib.st_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.st_writer_write.restype = ctypes.c_int
        lib.st_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_char_p, ctypes.c_uint64]
        lib.st_writer_close.argtypes = [ctypes.c_void_p]
        lib.st_reader_open.restype = ctypes.c_void_p
        lib.st_reader_open.argtypes = [ctypes.c_char_p]
        lib.st_reader_next.restype = ctypes.c_int
        lib.st_reader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.st_reader_close.argtypes = [ctypes.c_void_p]
        lib.st_loader_open.restype = ctypes.c_void_p
        lib.st_loader_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.st_loader_size.restype = ctypes.c_uint64
        lib.st_loader_size.argtypes = [ctypes.c_void_p]
        lib.st_loader_next.restype = ctypes.c_int
        lib.st_loader_next.argtypes = lib.st_reader_next.argtypes
        lib.st_loader_close.argtypes = [ctypes.c_void_p]
        lib.st_crc32.restype = ctypes.c_uint32
        lib.st_crc32.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.st_log.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
                               ctypes.c_char_p]
        lib.st_set_log_level.argtypes = [ctypes.c_int]
        lib.st_set_log_file.argtypes = [ctypes.c_char_p]
        lib.st_now_ns.restype = ctypes.c_uint64
        lib.st_channel_get.restype = ctypes.c_void_p
        lib.st_channel_get.argtypes = [ctypes.c_char_p]
        lib.st_channel_send.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.st_channel_stderr.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.st_channel_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.st_image_crop.restype = ctypes.c_int
        lib.st_image_hflip.restype = ctypes.c_int
        lib.st_image_normalize.restype = ctypes.c_int
        _lib = lib
        return lib


def _read_pair(fn, handle) -> Optional[Tuple[str, bytes]]:
    key = ctypes.c_char_p()
    klen = ctypes.c_uint32()
    val = ctypes.c_void_p()
    vlen = ctypes.c_uint64()
    if not fn(handle, ctypes.byref(key), ctypes.byref(klen),
              ctypes.byref(val), ctypes.byref(vlen)):
        return None
    k = ctypes.string_at(key, klen.value).decode()
    v = ctypes.string_at(val, vlen.value)
    return k, v


class _Handle:
    """Shared lifecycle for native-handle wrappers: closed-handle use
    raises instead of passing NULL into C (which would segfault), and
    GC closes leaked handles (worker threads/fds are native resources
    the interpreter can't reclaim)."""

    _close_fn: str

    def _check(self):
        if not self._h:
            raise ValueError(f"{type(self).__name__} is closed")
        return self._h

    def close(self) -> None:
        if getattr(self, "_h", None):
            getattr(self._lib, self._close_fn)(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class BinFileWriter(_Handle):
    """Reference: `singa::io::BinFileWriter`."""

    _close_fn = "st_writer_close"

    def __init__(self, path: str, mode: str = "w"):
        self._lib = _load()
        self._h = self._lib.st_writer_open(path.encode(), mode.encode())
        if not self._h:
            raise IOError(f"cannot open {path} for writing")

    def write(self, key: str, value: bytes) -> None:
        if not self._lib.st_writer_write(self._check(), key.encode(), value,
                                         len(value)):
            raise IOError(f"write failed for key {key}")


class BinFileReader(_Handle):
    """Reference: `singa::io::BinFileReader` — sequential (key, bytes)."""

    _close_fn = "st_reader_close"

    def __init__(self, path: str):
        self._lib = _load()
        self._h = self._lib.st_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path} (missing or bad magic)")

    def read(self) -> Optional[Tuple[str, bytes]]:
        return _read_pair(self._lib.st_reader_next, self._check())

    def __iter__(self) -> Iterator[Tuple[str, bytes]]:
        while True:
            pair = self.read()
            if pair is None:
                return
            yield pair


class Loader(_Handle):
    """Native threaded prefetch loader (see module docstring).

    epochs < 0 streams forever; rank/world shard the record set for
    multi-controller data parallelism (rank must be in [0, world)).
    """

    _close_fn = "st_loader_close"

    def __init__(self, path: str, prefetch: int = 16, shuffle: bool = True,
                 seed: int = 0, rank: int = 0, world: int = 1,
                 epochs: int = 1):
        if not 0 <= rank < world:
            raise ValueError(f"rank {rank} not in [0, {world})")
        self._lib = _load()
        self._h = self._lib.st_loader_open(
            path.encode(), prefetch, int(shuffle), seed, rank, world, epochs)
        if not self._h:
            raise IOError(f"cannot open {path}")

    def __len__(self) -> int:
        return self._lib.st_loader_size(self._check())

    def __iter__(self) -> Iterator[Tuple[str, bytes]]:
        while True:
            pair = _read_pair(self._lib.st_loader_next, self._check())
            if pair is None:
                return
            yield pair


class Channel:
    """Reference: `singa::Channel` — named metric output stream."""

    def __init__(self, name: str):
        self._lib = _load()
        self._h = self._lib.st_channel_get(name.encode())
        self.name = name

    def enable_dest_stderr(self, flag: bool) -> None:
        self._lib.st_channel_stderr(self._h, int(flag))

    def enable_dest_file(self, path: str) -> None:
        self._lib.st_channel_file(self._h, path.encode())

    def disable_dest_file(self) -> None:
        self._lib.st_channel_file(self._h, b"")

    def send(self, message: str) -> None:
        self._lib.st_channel_send(self._h, message.encode())


def get_channel(name: str) -> Channel:
    return Channel(name)


def crc32(data: bytes) -> int:
    return _load().st_crc32(data, len(data))


def log(severity: int, message: str) -> None:
    _load().st_log(severity, b"python", 0, message.encode())


def set_log_level(level: int) -> None:
    _load().st_set_log_level(level)


def set_log_file(path: str) -> None:
    _load().st_set_log_file(path.encode())


def now_ns() -> int:
    return _load().st_now_ns()


# ---------------------------------------------------------------------------
# Image transforms (reference: src/io/image_transformer.cc) on float32
# CHW arrays, executed in native code.
# ---------------------------------------------------------------------------
def _f32(a):
    return np.ascontiguousarray(a, dtype=np.float32)


def image_crop(img: np.ndarray, y0: int, x0: int, oh: int,
               ow: int) -> np.ndarray:
    lib = _load()
    img = _f32(img)
    c, h, w = img.shape
    out = np.empty((c, oh, ow), np.float32)
    ok = lib.st_image_crop(
        img.ctypes.data_as(ctypes.c_void_p), c, h, w, y0, x0, oh, ow,
        out.ctypes.data_as(ctypes.c_void_p))
    if not ok:
        raise ValueError(f"crop ({y0},{x0},{oh},{ow}) out of bounds for "
                         f"{img.shape}")
    return out


def image_hflip(img: np.ndarray) -> np.ndarray:
    lib = _load()
    img = _f32(img)
    c, h, w = img.shape
    out = np.empty_like(img)
    lib.st_image_hflip(img.ctypes.data_as(ctypes.c_void_p), c, h, w,
                       out.ctypes.data_as(ctypes.c_void_p))
    return out


def image_normalize(img: np.ndarray, mean, std) -> np.ndarray:
    lib = _load()
    img = _f32(img)
    c, h, w = img.shape
    mean = _f32(np.broadcast_to(np.asarray(mean, np.float32), (c,)))
    std = _f32(np.broadcast_to(np.asarray(std, np.float32), (c,)))
    out = np.empty_like(img)
    lib.st_image_normalize(
        img.ctypes.data_as(ctypes.c_void_p), c, h, w,
        mean.ctypes.data_as(ctypes.c_void_p),
        std.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p))
    return out


# ---------------------------------------------------------------------------
# Text-file record IO (reference: src/io/textfile_{reader,writer}.cc,
# SURVEY.md N18 — value = one line, key = line number).
# ---------------------------------------------------------------------------
def _load_text_syms(lib):
    if getattr(lib, "_text_ready", False):
        return lib
    lib.st_text_writer_open.restype = ctypes.c_void_p
    lib.st_text_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.st_text_writer_write.restype = ctypes.c_int
    lib.st_text_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.st_text_writer_flush.restype = ctypes.c_int
    lib.st_text_writer_flush.argtypes = [ctypes.c_void_p]
    lib.st_text_writer_close.argtypes = [ctypes.c_void_p]
    lib.st_text_reader_open.restype = ctypes.c_void_p
    lib.st_text_reader_open.argtypes = [ctypes.c_char_p]
    lib.st_text_reader_next.restype = ctypes.c_int
    lib.st_text_reader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64)]
    lib.st_text_reader_close.argtypes = [ctypes.c_void_p]
    lib.st_csv_decode.restype = ctypes.c_int64
    lib.st_csv_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int)]
    lib.st_csv_encode.restype = ctypes.c_int64
    lib.st_csv_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int64]
    lib._text_ready = True
    return lib


class TextFileWriter(_Handle):
    """Reference: `singa::io::TextFileWriter` — one record per line."""

    _close_fn = "st_text_writer_close"

    def __init__(self, path: str, mode: str = "w"):
        self._lib = _load_text_syms(_load())
        self._h = self._lib.st_text_writer_open(path.encode(),
                                                mode.encode())
        if not self._h:
            raise IOError(f"cannot open {path} for writing")

    def write(self, line: str) -> None:
        if "\n" in line or "\0" in line:
            # an embedded newline would split one record into two
            # (shifting every later line-number key); NUL would be
            # truncated by the C string boundary
            raise ValueError(
                "TextFileWriter records must not contain '\\n' or NUL")
        if not self._lib.st_text_writer_write(self._check(),
                                              line.encode()):
            raise IOError("text write failed")

    def flush(self) -> None:
        self._lib.st_text_writer_flush(self._check())


class TextFileReader(_Handle):
    """Reference: `singa::io::TextFileReader` — yields
    (line_number, line) with newline stripped."""

    _close_fn = "st_text_reader_close"

    def __init__(self, path: str):
        self._lib = _load_text_syms(_load())
        self._h = self._lib.st_text_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def read(self) -> Optional[Tuple[int, str]]:
        key = ctypes.c_uint64()
        val = ctypes.c_char_p()
        vlen = ctypes.c_uint64()
        if not self._lib.st_text_reader_next(
                self._check(), ctypes.byref(key), ctypes.byref(val),
                ctypes.byref(vlen)):
            return None
        return key.value, ctypes.string_at(val, vlen.value).decode()

    def __iter__(self) -> Iterator[Tuple[int, str]]:
        while True:
            pair = self.read()
            if pair is None:
                return
            yield pair


# ---------------------------------------------------------------------------
# CSV record codec (reference: src/io/csv_{encoder,decoder}.cc, N19 —
# "label,f0,f1,..." <-> (label, float vector)).
# ---------------------------------------------------------------------------
def csv_decode(line: str, has_label: bool = True,
               max_features: int = 1 << 16):
    """Parse a CSV line into (label, np.float32 vector); label is None
    when has_label is False."""
    lib = _load_text_syms(_load())
    out = np.empty(max_features, np.float32)
    label = ctypes.c_int()
    n = lib.st_csv_decode(line.encode(),
                          out.ctypes.data_as(ctypes.c_void_p),
                          max_features, int(has_label),
                          ctypes.byref(label))
    if n < 0:
        raise ValueError(f"malformed CSV line: {line!r}")
    if n > max_features:
        raise ValueError(f"CSV line has {n} features "
                         f"(> max_features={max_features})")
    return (label.value if has_label else None), out[:n].copy()


def csv_encode(values, label: Optional[int] = None) -> str:
    """Encode a float vector (optionally label-prefixed) as one CSV
    line."""
    lib = _load_text_syms(_load())
    vals = np.ascontiguousarray(values, np.float32).ravel()
    buf_len = 32 * (len(vals) + 2)
    buf = ctypes.create_string_buffer(buf_len)
    n = lib.st_csv_encode(vals.ctypes.data_as(ctypes.c_void_p),
                          len(vals),
                          0 if label is None else int(label),
                          int(label is not None), buf, buf_len)
    if n < 0:
        raise ValueError("csv_encode buffer overflow")
    return buf.raw[:n].decode()
