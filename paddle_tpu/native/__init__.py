"""ctypes bindings for the native runtime (csrc/libpaddle_tpu_native.so).

Builds on demand with make/g++ (no pybind11 in this image). Components:
RecordIO (csrc/recordio.cc), coordination KV/barrier service
(csrc/coord.cc), host arena allocator (csrc/arena.cc), host profiler
(csrc/profiler.cc).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Iterator, List, Optional

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc")
_LIB_PATH = os.path.abspath(os.path.join(_CSRC, "libpaddle_tpu_native.so"))

_lib: Optional[ctypes.CDLL] = None


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    # Always invoke make: its dependency check rebuilds when csrc/ changed
    # and is a no-op otherwise (the .so is never committed; see
    # .gitignore). A failed build is an error even when an older .so is
    # lying around: loading it would run code built from other sources.
    try:
        subprocess.run(
            ["make", "-C", os.path.abspath(_CSRC)],
            check=True, capture_output=True, text=True,
        )
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {_LIB_PATH} failed (make exit {e.returncode}):\n"
            f"{e.stderr[-2000:]}") from e
    lib = ctypes.CDLL(_LIB_PATH)
    # recordio
    lib.rio_writer_open.restype = ctypes.c_void_p
    lib.rio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.rio_writer_write.restype = ctypes.c_int
    lib.rio_writer_write.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32]
    lib.rio_writer_close.restype = ctypes.c_int
    lib.rio_writer_close.argtypes = [ctypes.c_void_p]
    lib.rio_scanner_open.restype = ctypes.c_void_p
    lib.rio_scanner_open.argtypes = [ctypes.c_char_p]
    lib.rio_scanner_next.restype = ctypes.c_int
    lib.rio_scanner_next.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.rio_scanner_close.argtypes = [ctypes.c_void_p]
    # coord
    lib.coord_server_start.restype = ctypes.c_void_p
    lib.coord_server_start.argtypes = [ctypes.c_int]
    lib.coord_server_stop.argtypes = [ctypes.c_void_p]
    lib.coord_client_connect.restype = ctypes.c_void_p
    lib.coord_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.coord_client_close.argtypes = [ctypes.c_void_p]
    lib.coord_put.restype = ctypes.c_int
    lib.coord_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32]
    lib.coord_get.restype = ctypes.c_int
    lib.coord_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                              ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint32]
    lib.coord_barrier.restype = ctypes.c_int
    lib.coord_barrier.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.coord_heartbeat.restype = ctypes.c_int
    lib.coord_heartbeat.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.coord_del.restype = ctypes.c_int
    lib.coord_del.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.coord_dead_peers.restype = ctypes.c_int
    lib.coord_dead_peers.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.c_char_p, ctypes.c_uint32]
    # arena
    lib.arena_create.restype = ctypes.c_void_p
    lib.arena_create.argtypes = [ctypes.c_uint64]
    lib.arena_destroy.argtypes = [ctypes.c_void_p]
    lib.arena_alloc.restype = ctypes.c_void_p
    lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.arena_free.restype = ctypes.c_int
    lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.arena_in_use.restype = ctypes.c_uint64
    lib.arena_in_use.argtypes = [ctypes.c_void_p]
    lib.arena_peak.restype = ctypes.c_uint64
    lib.arena_peak.argtypes = [ctypes.c_void_p]
    # profiler
    lib.prof_enable.restype = None
    lib.prof_disable.restype = None
    lib.prof_is_enabled.restype = ctypes.c_int
    lib.prof_begin.argtypes = [ctypes.c_char_p]
    lib.prof_end.restype = None
    lib.prof_dump.restype = ctypes.c_int
    lib.prof_dump.argtypes = [ctypes.c_char_p]
    _lib = lib
    return lib


def available() -> bool:
    """Whether the native runtime can be used here: False without a
    toolchain (no ``make``) or a loadable library. A build that FAILS
    raises — broken sources are not "unavailable"."""
    try:
        _load()
        return True
    except OSError:  # FileNotFoundError: no make; OSError: CDLL refused
        return False


# --- RecordIO ---


class RecordIOWriter:
    """Chunked CRC'd record file (native; csrc/recordio.cc)."""

    def __init__(self, path: str, compressor: str = "none"):
        lib = _load()
        comp = {"none": 0, "zlib": 1}[compressor]
        self._h = lib.rio_writer_open(path.encode(), comp)
        if not self._h:
            raise IOError(f"cannot open {path}")
        self._lib = lib

    def write(self, data: bytes):
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
        rc = self._lib.rio_writer_write(self._h, buf, len(data))
        if rc != 0:
            raise IOError("recordio write failed")

    def close(self):
        if self._h:
            rc = self._lib.rio_writer_close(self._h)
            self._h = None
            if rc != 0:
                raise IOError("recordio flush failed")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class RecordIOScanner:
    def __init__(self, path: str):
        lib = _load()
        self._h = lib.rio_scanner_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")
        self._lib = lib

    def __iter__(self) -> Iterator[bytes]:
        data = ctypes.POINTER(ctypes.c_uint8)()
        length = ctypes.c_uint32()
        while True:
            rc = self._lib.rio_scanner_next(
                self._h, ctypes.byref(data), ctypes.byref(length))
            if rc == 0:
                return
            if rc < 0:
                raise IOError("corrupt recordio record")
            yield ctypes.string_at(data, length.value)

    def close(self):
        if self._h:
            self._lib.rio_scanner_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# --- Coordination service ---


class CoordServer:
    """KV + barrier + heartbeat server (native; csrc/coord.cc)."""

    def __init__(self, port: int):
        lib = _load()
        self._h = lib.coord_server_start(port)
        if not self._h:
            raise OSError(f"cannot bind port {port}")
        self._lib = lib

    def stop(self):
        if self._h:
            self._lib.coord_server_stop(self._h)
            self._h = None


class CoordClient:
    def __init__(self, host: str, port: int):
        lib = _load()
        self._h = lib.coord_client_connect(host.encode(), port)
        if not self._h:
            raise OSError(f"cannot connect {host}:{port}")
        self._lib = lib

    def put(self, key: str, value: bytes):
        buf = (ctypes.c_uint8 * len(value)).from_buffer_copy(value)
        if self._lib.coord_put(self._h, key.encode(), buf, len(value)) != 0:
            raise OSError("coord put failed")

    def get(self, key: str, timeout_ms: int = -1, max_len: int = 1 << 20) -> bytes:
        out = (ctypes.c_uint8 * max_len)()
        n = self._lib.coord_get(self._h, key.encode(), timeout_ms, out, max_len)
        if n == -1:
            raise TimeoutError(f"coord get {key!r} timed out / absent")
        if n == -2:
            raise OSError("coord connection failed")
        if n < -2:  # value exists but exceeds max_len; retry with the size
            needed = -n - 3
            if needed <= max_len:
                raise OSError("coord get protocol error")
            return self.get(key, timeout_ms, max_len=needed)
        return bytes(out[:n])

    def barrier(self, name: str, count: int):
        if self._lib.coord_barrier(self._h, name.encode(), count) != 0:
            raise OSError("coord barrier failed")

    def heartbeat(self, worker_id: str):
        if self._lib.coord_heartbeat(self._h, worker_id.encode()) != 0:
            raise OSError("heartbeat failed")

    def delete(self, key: str):
        if self._lib.coord_del(self._h, key.encode()) != 0:
            raise OSError("coord delete failed")

    def dead_peers(self, max_age_ms: int) -> List[str]:
        out = ctypes.create_string_buffer(1 << 16)
        n = self._lib.coord_dead_peers(self._h, max_age_ms, out, 1 << 16)
        if n < 0:
            raise OSError("liveness query failed")
        s = out.value.decode()
        return [x for x in s.split(",") if x]

    def close(self):
        if self._h:
            self._lib.coord_client_close(self._h)
            self._h = None


# --- Arena allocator ---


class Arena:
    """Best-fit host staging arena (native; csrc/arena.cc)."""

    def __init__(self, capacity: int):
        lib = _load()
        self._h = lib.arena_create(capacity)
        if not self._h:
            raise MemoryError("arena allocation failed")
        self._lib = lib

    def _handle(self):
        if not self._h:
            raise ValueError("arena already destroyed")
        return self._h

    def alloc(self, size: int) -> int:
        p = self._lib.arena_alloc(self._handle(), size)
        if not p:
            raise MemoryError(f"arena exhausted (requested {size})")
        return p

    def free(self, ptr: int):
        if self._lib.arena_free(self._handle(), ptr) != 0:
            raise ValueError("pointer not owned by arena")

    @property
    def in_use(self) -> int:
        return self._lib.arena_in_use(self._handle())

    @property
    def peak(self) -> int:
        return self._lib.arena_peak(self._handle())

    def destroy(self):
        if self._h:
            self._lib.arena_destroy(self._h)
            self._h = None


# --- Profiler ---


def profiler_enable():
    _load().prof_enable()


def profiler_disable():
    _load().prof_disable()


def profiler_begin(name: str):
    _load().prof_begin(name.encode())


def profiler_end():
    _load().prof_end()


def profiler_dump(path: str) -> int:
    return _load().prof_dump(path.encode())
