"""TCPStore — Python binding over the native C++ store.

Reference counterpart: ``TCPStore``/``MasterDaemon`` in
``paddle/fluid/distributed/store/tcp_store.cc`` (SURVEY.md §2.2): rank 0
hosts the daemon; every rank connects as a client; used for bootstrap
(coordinator discovery), barriers (ADD + WAIT on counter keys), and small
control-plane blobs. The server/client live in
``native/tcp_store.cpp`` (single poll-driven daemon thread, length-prefixed
binary protocol), loaded here via ctypes; blocking waits happen in native
code with the GIL released.
"""

from __future__ import annotations

import ctypes
import glob
import os
import subprocess
from typing import Optional

__all__ = ["TCPStore", "load_native"]

_LIB = None


def _lib_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "lib", "libpaddle_tpu_native.so")


def _needs_build(path: str, native_dir: str) -> bool:
    """True when the library is missing or older than a ``native/*.cpp``:
    the library is not tracked, so one left by another build of the tree
    must not outlive a change to its sources."""
    if not os.path.exists(path):
        return True
    built = os.path.getmtime(path)
    return any(os.path.getmtime(src) > built
               for src in glob.glob(os.path.join(native_dir, "*.cpp")))


def load_native() -> ctypes.CDLL:
    """Load (building if necessary) the native runtime library."""
    global _LIB
    if _LIB is not None:
        return _LIB
    path = _lib_path()
    native_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
        "native")
    if _needs_build(path, native_dir):
        subprocess.run(["make", "-C", native_dir], check=True,
                       capture_output=True)
    lib = ctypes.CDLL(path)
    lib.tcp_store_server_start.restype = ctypes.c_void_p
    lib.tcp_store_server_start.argtypes = [ctypes.c_int]
    lib.tcp_store_server_port.restype = ctypes.c_int
    lib.tcp_store_server_port.argtypes = [ctypes.c_void_p]
    lib.tcp_store_server_stop.argtypes = [ctypes.c_void_p]
    lib.tcp_store_client_connect.restype = ctypes.c_void_p
    lib.tcp_store_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                             ctypes.c_int]
    lib.tcp_store_client_close.argtypes = [ctypes.c_void_p]
    lib.tcp_store_set.restype = ctypes.c_int
    lib.tcp_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_char_p, ctypes.c_int]
    lib.tcp_store_get.restype = ctypes.c_int
    lib.tcp_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.tcp_store_add.restype = ctypes.c_longlong
    lib.tcp_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_longlong]
    lib.tcp_store_wait.restype = ctypes.c_int
    lib.tcp_store_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int]
    lib.tcp_store_delete.restype = ctypes.c_int
    lib.tcp_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.tcp_store_num_keys.restype = ctypes.c_longlong
    lib.tcp_store_num_keys.argtypes = [ctypes.c_void_p]
    # data-loader queue
    lib.dl_queue_create.restype = ctypes.c_void_p
    lib.dl_queue_create.argtypes = [ctypes.c_int]
    lib.dl_queue_push.restype = ctypes.c_int
    lib.dl_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int, ctypes.c_int]
    lib.dl_queue_pop.restype = ctypes.c_int
    lib.dl_queue_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_int, ctypes.c_int]
    lib.dl_queue_size.restype = ctypes.c_int
    lib.dl_queue_size.argtypes = [ctypes.c_void_p]
    lib.dl_queue_close.argtypes = [ctypes.c_void_p]
    lib.dl_queue_destroy.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


class TCPStore:
    """``TCPStore(host, port, is_master, world_size, timeout)`` matching the
    reference's constructor shape."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 is_master: bool = False, world_size: int = 1,
                 timeout: float = 300.0):
        self._lib = load_native()
        self._server = None
        self.host = host
        self.is_master = is_master
        self.world_size = world_size
        self._timeout_ms = int(timeout * 1000)
        if is_master:
            self._server = self._lib.tcp_store_server_start(port)
            if not self._server:
                raise RuntimeError(f"TCPStore: cannot bind port {port}")
            port = self._lib.tcp_store_server_port(self._server)
        self.port = port
        self._client = self._lib.tcp_store_client_connect(
            host.encode(), port, self._timeout_ms)
        if not self._client:
            if self._server:
                self._lib.tcp_store_server_stop(self._server)
            raise RuntimeError(f"TCPStore: cannot connect to {host}:{port}")

    def set(self, key: str, value) -> None:
        data = value if isinstance(value, bytes) else str(value).encode()
        rc = self._lib.tcp_store_set(self._client, key.encode(), data, len(data))
        if rc != 0:
            raise RuntimeError(f"TCPStore.set({key!r}) failed: {rc}")

    def get(self, key: str, timeout_ms: Optional[int] = None) -> bytes:
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.tcp_store_get(
                self._client, key.encode(),
                self._timeout_ms if timeout_ms is None else timeout_ms,
                buf, cap)
            if n == -1:
                raise TimeoutError(f"TCPStore.get({key!r}) timed out")
            if n < 0:
                raise RuntimeError(f"TCPStore.get({key!r}) failed: {n}")
            if n <= cap:
                return buf.raw[:n]
            cap = n  # value larger than buffer: retry sized

    def add(self, key: str, amount: int = 1) -> int:
        ret = self._lib.tcp_store_add(self._client, key.encode(), amount)
        if ret < 0 and ret in (-2,):
            raise RuntimeError(f"TCPStore.add({key!r}) io error")
        return int(ret)

    def wait(self, key: str, timeout_ms: Optional[int] = None) -> None:
        rc = self._lib.tcp_store_wait(
            self._client, key.encode(),
            self._timeout_ms if timeout_ms is None else timeout_ms)
        if rc == -1:
            raise TimeoutError(f"TCPStore.wait({key!r}) timed out")
        if rc != 0:
            raise RuntimeError(f"TCPStore.wait({key!r}) failed: {rc}")

    def delete_key(self, key: str) -> bool:
        return self._lib.tcp_store_delete(self._client, key.encode()) == 1

    def num_keys(self) -> int:
        return int(self._lib.tcp_store_num_keys(self._client))

    def barrier(self, name: str = "barrier", timeout_ms: Optional[int] = None):
        """All-rank barrier: ADD a counter; WAIT for the release key the
        last arriver sets (the reference's store-based barrier)."""
        n = self.add(f"{name}/count")
        if n == self.world_size:
            self.set(f"{name}/release", b"1")
        self.wait(f"{name}/release", timeout_ms)

    def close(self):
        if getattr(self, "_client", None):
            self._lib.tcp_store_client_close(self._client)
            self._client = None
        if getattr(self, "_server", None):
            self._lib.tcp_store_server_stop(self._server)
            self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
