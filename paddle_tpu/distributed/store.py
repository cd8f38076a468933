"""TCPStore Python binding.

ref: paddle/phi/core/distributed/store/tcp_store.h:117 (pybind'd in the
reference; here ctypes over the C ABI of csrc/tcp_store.cc — pybind11 is
not in this image). The native library is built on first use with g++.
"""
import ctypes
import os
import threading

from ..utils.cpp_extension import build_if_stale

_LIB = None
_BUILD_LOCK = threading.Lock()


def _lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        src = os.path.join(here, "csrc", "tcp_store.cc")
        so = os.path.join(here, "csrc", "libtcpstore.so")
        lib = ctypes.CDLL(build_if_stale(src, so, opt="-O2"))
        lib.pts_server_start.restype = ctypes.c_void_p
        lib.pts_server_start.argtypes = [ctypes.c_int]
        lib.pts_server_port.restype = ctypes.c_int
        lib.pts_server_port.argtypes = [ctypes.c_void_p]
        lib.pts_server_stop.argtypes = [ctypes.c_void_p]
        lib.pts_client_connect.restype = ctypes.c_void_p
        lib.pts_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                           ctypes.c_int]
        lib.pts_client_close.argtypes = [ctypes.c_void_p]
        lib.pts_set.restype = ctypes.c_int
        lib.pts_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_char_p, ctypes.c_int]
        lib.pts_get.restype = ctypes.c_int
        lib.pts_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_char_p, ctypes.c_int]
        lib.pts_add.restype = ctypes.c_longlong
        lib.pts_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_longlong]
        lib.pts_wait.restype = ctypes.c_int
        lib.pts_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_longlong]
        lib.pts_delete.restype = ctypes.c_int
        lib.pts_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pts_num_keys.restype = ctypes.c_longlong
        lib.pts_num_keys.argtypes = [ctypes.c_void_p]
        _LIB = lib
    return _LIB


class TCPStore:
    """API mirrors the reference's TCPStore: rank 0 hosts, all ranks connect.

    TCPStore(host, port, is_master, world_size, timeout_s)
    """

    def __init__(self, host="127.0.0.1", port=0, is_master=False,
                 world_size=1, timeout=120):
        lib = _lib()
        self._server = None
        self.host = host
        if is_master:
            self._server = lib.pts_server_start(port)
            if not self._server:
                raise RuntimeError(f"TCPStore: cannot bind port {port}")
            port = lib.pts_server_port(self._server)
        self.port = port
        self._client = lib.pts_client_connect(host.encode(), port,
                                              int(timeout * 1000))
        if not self._client:
            self._shutdown_server()
            raise RuntimeError(f"TCPStore: cannot connect {host}:{port}")

    def set(self, key, value):
        if isinstance(value, str):
            value = value.encode()
        rc = _lib().pts_set(self._client, key.encode(), value, len(value))
        if rc != 0:
            raise RuntimeError("TCPStore.set failed")

    def get(self, key, wait=True, timeout_ms=-1):
        lib = _lib()
        if wait:
            st = lib.pts_wait(self._client, key.encode(), timeout_ms)
            if st != 0:
                raise TimeoutError(f"TCPStore.wait({key!r}) timed out")
        # pts_get returns -3 when the caller buffer is too small and
        # reports the REQUIRED size in the buffer's first 8 bytes, so
        # a value bigger than the initial 1 MB (a fleet worker's
        # resume ledger under many long prompts) costs exactly one
        # retry with an exact-size buffer — each attempt transfers the
        # whole value, so doubling blindly would re-download it per
        # step (a stale .so that doesn't report the size falls back to
        # doubling)
        bufsize = 1 << 20
        while True:
            buf = ctypes.create_string_buffer(bufsize)
            n = lib.pts_get(self._client, key.encode(), buf, len(buf))
            if n == -1:
                raise KeyError(key)
            if n == -3:
                need = int.from_bytes(buf.raw[:8], "little")
                if need > (1 << 28) or bufsize >= (1 << 28):
                    raise RuntimeError(
                        f"TCPStore.get({key!r}): value exceeds 256 MB")
                bufsize = need if need > bufsize else bufsize * 2
                continue
            if n < 0:
                raise RuntimeError(f"TCPStore.get error {n}")
            return buf.raw[:n]

    def add(self, key, amount=1):
        return int(_lib().pts_add(self._client, key.encode(), amount))

    def wait(self, keys, timeout_ms=-1):
        if isinstance(keys, str):
            keys = [keys]
        for k in keys:
            st = _lib().pts_wait(self._client, k.encode(), timeout_ms)
            if st != 0:
                raise TimeoutError(f"TCPStore.wait({k!r}) timed out")

    def delete_key(self, key):
        return _lib().pts_delete(self._client, key.encode()) == 0

    def num_keys(self):
        return int(_lib().pts_num_keys(self._client))

    def barrier(self, name, world_size, timeout_ms=60000):
        """Counter barrier (the reference's bootstrap barrier pattern)."""
        n = self.add(f"__barrier/{name}", 1)
        if n == world_size:
            self.set(f"__barrier/{name}/done", b"1")
        self.wait([f"__barrier/{name}/done"], timeout_ms)

    def _shutdown_server(self):
        if self._server:
            _lib().pts_server_stop(self._server)
            self._server = None

    def __del__(self):
        try:
            if getattr(self, "_client", None):
                _lib().pts_client_close(self._client)
            self._shutdown_server()
        except Exception:
            pass
