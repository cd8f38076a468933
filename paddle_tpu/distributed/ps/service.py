"""Parameter-server client/server Python bindings (ctypes over csrc/ps_service.cc).

TPU-native rebuild of the reference's the-one-PS service layer
(ref: paddle/fluid/distributed/ps/service/brpc_ps_client.h BrpcPsClient,
 brpc_ps_server.h BrpcPsServer; python/paddle/distributed/ps/the_one_ps.py).
brpc is replaced by the in-repo TCP protocol; the C++ server hosts
CTR-style sparse tables ([show, click, g2sum, w...]) and dense tables with
server-side SGD/Adagrad/Adam rules (ref: ps/table/sparse_sgd_rule.h).

`PsCluster` shards keys across multiple servers by `key % num_servers`
(ref: BrpcPsClient::PullSparse request fan-out per shard).
"""
import ctypes
import os
import threading

import numpy as np

from ...utils.cpp_extension import build_if_stale

_LIB = None
_BUILD_LOCK = threading.Lock()

OPTIMIZERS = {"sgd": 0, "adagrad": 1, "adam": 2}


def _lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        here = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        src = os.path.join(here, "csrc", "ps_service.cc")
        so = os.path.join(here, "csrc", "libps.so")
        lib = ctypes.CDLL(build_if_stale(src, so))
        u64p = ctypes.POINTER(ctypes.c_uint64)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.ps_server_start.restype = ctypes.c_void_p
        lib.ps_server_start.argtypes = [ctypes.c_int]
        lib.ps_server_port.restype = ctypes.c_int
        lib.ps_server_port.argtypes = [ctypes.c_void_p]
        lib.ps_server_stop.argtypes = [ctypes.c_void_p]
        lib.ps_client_connect.restype = ctypes.c_int
        lib.ps_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.ps_client_close.argtypes = [ctypes.c_int]
        lib.ps_create_table.restype = ctypes.c_int
        lib.ps_create_table.argtypes = [
            ctypes.c_int, ctypes.c_uint32, ctypes.c_uint8, ctypes.c_uint8,
            ctypes.c_uint32, ctypes.c_float, ctypes.c_float,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint8,
            ctypes.c_float, ctypes.c_float, ctypes.c_float]
        lib.ps_pull_sparse.restype = ctypes.c_int
        lib.ps_pull_sparse.argtypes = [
            ctypes.c_int, ctypes.c_uint32, u64p, ctypes.c_uint32,
            ctypes.c_uint32, f32p, ctypes.c_uint8]
        lib.ps_push_sparse.restype = ctypes.c_int
        lib.ps_push_sparse.argtypes = [
            ctypes.c_int, ctypes.c_uint32, u64p, ctypes.c_uint32,
            ctypes.c_uint32, f32p, f32p, f32p]
        lib.ps_pull_dense.restype = ctypes.c_int
        lib.ps_pull_dense.argtypes = [ctypes.c_int, ctypes.c_uint32, f32p,
                                      ctypes.c_uint32]
        lib.ps_push_dense.restype = ctypes.c_int
        lib.ps_push_dense.argtypes = [ctypes.c_int, ctypes.c_uint32, f32p,
                                      ctypes.c_uint32, ctypes.c_uint8]
        lib.ps_save.restype = ctypes.c_int
        lib.ps_save.argtypes = [ctypes.c_int, ctypes.c_uint32, ctypes.c_char_p]
        lib.ps_load.restype = ctypes.c_int
        lib.ps_load.argtypes = [ctypes.c_int, ctypes.c_uint32, ctypes.c_char_p]
        lib.ps_shrink.restype = ctypes.c_longlong
        lib.ps_shrink.argtypes = [ctypes.c_int, ctypes.c_uint32,
                                  ctypes.c_float, ctypes.c_float]
        lib.ps_stat.restype = ctypes.c_longlong
        lib.ps_stat.argtypes = [ctypes.c_int, ctypes.c_uint32,
                                ctypes.POINTER(ctypes.c_ulonglong)]
        lib.ps_barrier.restype = ctypes.c_int
        lib.ps_barrier.argtypes = [ctypes.c_int, ctypes.c_uint32]
        lib.ps_clear.restype = ctypes.c_int
        lib.ps_clear.argtypes = [ctypes.c_int, ctypes.c_uint32]
        _LIB = lib
    return _LIB


class SparseTableConfig:
    """Per-table config (ref: the_one_ps.py Table/Accessor protobuf config)."""

    def __init__(self, table_id, dim, optimizer="adagrad", lr=0.05,
                 init_range=0.01, is_dense=False, max_mem_rows=0,
                 spill_path=None, accessor="direct", nonclk_coeff=0.1,
                 click_coeff=1.0, embedx_threshold=10.0):
        self.table_id = int(table_id)
        self.dim = int(dim)
        self.optimizer = optimizer
        self.lr = float(lr)
        self.init_range = float(init_range)
        self.is_dense = bool(is_dense)
        # durability tier (ref: ps/table/ssd_sparse_table.h): rows beyond
        # max_mem_rows spill to disk and fault back in on access; 0 keeps
        # the table fully resident
        self.max_mem_rows = int(max_mem_rows)
        self.spill_path = spill_path
        # CTR accessor (ref: ps/table/ctr_accessor.h, the fork's feature-
        # value accessor): dim = 1 embed_w + embedx; embedx dormant until
        # score(show, click) >= embedx_threshold
        if accessor not in ("direct", "ctr"):
            raise ValueError(f"accessor must be direct/ctr, got {accessor}")
        self.accessor = accessor
        self.nonclk_coeff = float(nonclk_coeff)
        self.click_coeff = float(click_coeff)
        self.embedx_threshold = float(embedx_threshold)


class PsServer:
    """In-process PS server (ref: BrpcPsServer; here one thread pool inside
    the C++ library — start() returns immediately, serving on `port`)."""

    def __init__(self, port=0):
        self._h = _lib().ps_server_start(port)
        if not self._h:
            raise RuntimeError(f"PsServer: cannot bind port {port}")
        self.port = _lib().ps_server_port(self._h)

    def stop(self):
        if self._h:
            _lib().ps_server_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class PsClient:
    """Connection to one PS endpoint."""

    def __init__(self, host="127.0.0.1", port=0):
        self._fd = _lib().ps_client_connect(host.encode(), port)
        if self._fd < 0:
            raise RuntimeError(f"PsClient: cannot connect {host}:{port}")
        self._lock = threading.Lock()

    def close(self):
        if self._fd >= 0:
            _lib().ps_client_close(self._fd)
            self._fd = -1

    def create_table(self, cfg: SparseTableConfig):
        with self._lock:
            st = _lib().ps_create_table(
                self._fd, cfg.table_id, 1 if cfg.is_dense else 0,
                OPTIMIZERS[cfg.optimizer], cfg.dim, cfg.lr, cfg.init_range,
                cfg.max_mem_rows,
                cfg.spill_path.encode() if cfg.spill_path else None,
                1 if cfg.accessor == "ctr" else 0, cfg.nonclk_coeff,
                cfg.click_coeff, cfg.embedx_threshold)
        if st == 3:
            raise RuntimeError(
                f"table {cfg.table_id} already exists on the server with a "
                f"different config (dim/optimizer/kind) — pick a distinct "
                f"table_id per DistributedEmbedding")
        if st != 0:
            raise RuntimeError(f"create_table failed: status {st}")

    def pull_sparse(self, table_id, keys, dim, init_missing=True):
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.zeros((keys.size, dim), dtype=np.float32)
        with self._lock:
            st = _lib().ps_pull_sparse(
                self._fd, table_id,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                keys.size, dim,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                1 if init_missing else 0)
        if st != 0:
            raise RuntimeError(
                f"pull_sparse failed: status {st} "
                f"(1=no such table, 4=dim mismatch with server table)")
        return out

    def push_sparse(self, table_id, keys, grads, shows=None, clicks=None):
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        grads = np.ascontiguousarray(grads, dtype=np.float32)
        f32p = ctypes.POINTER(ctypes.c_float)
        sp = cp = None
        if shows is not None:
            shows = np.ascontiguousarray(shows, dtype=np.float32)
            clicks = np.ascontiguousarray(clicks, dtype=np.float32)
            sp = shows.ctypes.data_as(f32p)
            cp = clicks.ctypes.data_as(f32p)
        with self._lock:
            st = _lib().ps_push_sparse(
                self._fd, table_id,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                keys.size, grads.shape[-1] if grads.ndim > 1 else grads.size,
                grads.ctypes.data_as(f32p), sp, cp)
        if st != 0:
            raise RuntimeError(
                f"push_sparse failed: status {st} "
                f"(1=no such table, 4=dim mismatch with server table)")

    def pull_dense(self, table_id, n):
        out = np.zeros(n, dtype=np.float32)
        with self._lock:
            st = _lib().ps_pull_dense(
                self._fd, table_id,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n)
        if st != 0:
            raise RuntimeError(
                f"pull_dense failed: status {st} (1=no such table)")
        return out

    def push_dense(self, table_id, vals, is_param=False):
        vals = np.ascontiguousarray(vals, dtype=np.float32)
        with self._lock:
            st = _lib().ps_push_dense(
                self._fd, table_id,
                vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                vals.size, 1 if is_param else 0)
        if st != 0:
            raise RuntimeError("push_dense failed")

    def save(self, table_id, path):
        with self._lock:
            if _lib().ps_save(self._fd, table_id, path.encode()) != 0:
                raise RuntimeError("save failed")

    def load(self, table_id, path):
        with self._lock:
            if _lib().ps_load(self._fd, table_id, path.encode()) != 0:
                raise RuntimeError("load failed")

    def shrink(self, table_id, threshold=1.0, decay=0.98):
        """Decay shows and evict cold rows (ref: memory_sparse_table Shrink
        + ctr_accessor show_decay_rate). Returns rows dropped."""
        with self._lock:
            return _lib().ps_shrink(self._fd, table_id, threshold, decay)

    def stat(self, table_id):
        nf = ctypes.c_ulonglong(0)
        with self._lock:
            nrows = _lib().ps_stat(self._fd, table_id, ctypes.byref(nf))
        return {"rows": int(nrows), "floats": int(nf.value)}

    def barrier(self, world_size):
        with self._lock:
            if _lib().ps_barrier(self._fd, world_size) != 0:
                raise RuntimeError("barrier failed")

    def clear(self, table_id):
        with self._lock:
            _lib().ps_clear(self._fd, table_id)


class PsCluster:
    """Client view of N PS shards; keys are routed `key % N`
    (ref: BrpcPsClient per-shard request fan-out, the_one_ps.py
    server_endpoints)."""

    def __init__(self, endpoints):
        # endpoints: list of "host:port"
        self.clients = []
        for ep in endpoints:
            host, port = ep.rsplit(":", 1)
            self.clients.append(PsClient(host, int(port)))
        self.n = len(self.clients)
        self._tables = {}

    def close(self):
        for c in self.clients:
            c.close()

    def create_table(self, cfg: SparseTableConfig):
        for c in self.clients:
            c.create_table(cfg)
        self._tables[cfg.table_id] = cfg

    def _route(self, keys):
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        owner = (keys % np.uint64(self.n)).astype(np.int64)
        return keys, owner

    def _table_cfg(self, table_id):
        if table_id not in self._tables:
            raise KeyError(
                f"table {table_id} not registered on this cluster; call "
                f"create_table(SparseTableConfig({table_id}, dim)) first "
                f"(known tables: {sorted(self._tables)})")
        return self._tables[table_id]

    def pull_sparse(self, table_id, keys, init_missing=True):
        dim = self._table_cfg(table_id).dim
        keys, owner = self._route(keys)
        out = np.zeros((keys.size, dim), dtype=np.float32)
        for s in range(self.n):
            idx = np.nonzero(owner == s)[0]
            if idx.size:
                out[idx] = self.clients[s].pull_sparse(
                    table_id, keys[idx], dim, init_missing)
        return out

    def push_sparse(self, table_id, keys, grads, shows=None, clicks=None):
        keys, owner = self._route(keys)
        grads = np.ascontiguousarray(grads, dtype=np.float32)
        for s in range(self.n):
            idx = np.nonzero(owner == s)[0]
            if idx.size:
                self.clients[s].push_sparse(
                    table_id, keys[idx], grads[idx],
                    None if shows is None else shows[idx],
                    None if clicks is None else clicks[idx])

    def pull_dense(self, table_id, n):
        return self.clients[0].pull_dense(table_id, n)

    def push_dense(self, table_id, vals, is_param=False):
        self.clients[0].push_dense(table_id, vals, is_param)

    def save(self, table_id, dirname):
        os.makedirs(dirname, exist_ok=True)
        for s, c in enumerate(self.clients):
            c.save(table_id, os.path.join(dirname, f"shard_{s}.bin"))

    def load(self, table_id, dirname):
        for s, c in enumerate(self.clients):
            c.load(table_id, os.path.join(dirname, f"shard_{s}.bin"))

    def shrink(self, table_id, threshold=1.0, decay=0.98):
        return sum(c.shrink(table_id, threshold, decay) for c in self.clients)

    def stat(self, table_id):
        stats = [c.stat(table_id) for c in self.clients]
        return {"rows": sum(s["rows"] for s in stats),
                "floats": sum(s["floats"] for s in stats)}
