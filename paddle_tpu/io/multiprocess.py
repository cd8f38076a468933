"""Multiprocess DataLoader iterator.

ref: python/paddle/fluid/dataloader/dataloader_iter.py
(_DataLoaderIterMultiProcess, 871 LoC) + dataloader/worker.py: worker
PROCESSES (not threads) prepare batches so a fast accelerator step is
never starved by Python-GIL preprocessing; large arrays travel through
POSIX shared memory instead of being pickled through the queue
(ref: use_shared_memory / _shared_memory tensors).

Shape:
  - one index queue per worker, one shared result queue;
  - batches are dispatched round-robin with sequence numbers and
    re-assembled IN ORDER by the parent (the reference's _order outputs);
  - `prefetch_factor * num_workers` batches stay in flight;
  - arrays >= SHM_THRESHOLD bytes are handed over via
    multiprocessing.shared_memory (name + dtype + shape over the queue),
    attached zero-copy in the parent and unlinked after use;
  - workers are daemonic fork children; a sentinel per worker ends the
    epoch, join with timeout then terminate (watchdog semantics of
    _DataLoaderIterMultiProcess._shutdown).
"""
import atexit
import multiprocessing as mp
import queue as _queue
from multiprocessing import shared_memory

import numpy as np

SHM_THRESHOLD = 1 << 16  # 64 KiB: below this, pickling is cheaper


def _pack(obj, shms, threshold=SHM_THRESHOLD):
    """Replace large ndarrays with shm descriptors ('shm', name, shape,
    dtype); small leaves pass through pickled."""
    if isinstance(obj, np.ndarray) and obj.nbytes >= threshold:
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        dst = np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
        dst[...] = obj
        shms.append(shm)
        return ("__shm__", shm.name, obj.shape, str(obj.dtype))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_pack(o, shms, threshold) for o in obj)
    if isinstance(obj, dict):
        return {k: _pack(v, shms, threshold) for k, v in obj.items()}
    return obj


def _unpack(obj, owned):
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        shm = shared_memory.SharedMemory(name=obj[1])
        arr = np.ndarray(obj[2], np.dtype(obj[3]), buffer=shm.buf).copy()
        shm.close()
        owned.append(obj[1])
        return arr
    if isinstance(obj, (list, tuple)):
        return type(obj)(_unpack(o, owned) for o in obj)
    if isinstance(obj, dict):
        return {k: _unpack(v, owned) for k, v in obj.items()}
    return obj


def _numpy_collate(batch):
    """Default collate for workers: pure numpy stacking — workers must
    NEVER touch the accelerator (creating jax arrays would initialize the
    TPU backend inside every worker; the parent owns the device)."""
    first = batch[0]
    if isinstance(first, (list, tuple)):
        return type(first)(_numpy_collate([b[i] for b in batch])
                           for i in range(len(first)))
    if isinstance(first, dict):
        return {k: _numpy_collate([b[k] for b in batch]) for k in first}
    return np.stack([np.asarray(b) for b in batch])


def _worker_loop(dataset, collate_fn, index_q, result_q, wid,
                 worker_init_fn, iterable_slices,
                 shm_threshold=SHM_THRESHOLD):
    """ref: dataloader/worker.py _worker_loop."""
    import os
    # data workers are CPU-only: a chip belongs to one process, and that
    # process is the trainer, never one of its data workers
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    if worker_init_fn is not None:
        worker_init_fn(wid)
    try:
        while True:
            job = index_q.get()
            if job is None:
                break
            seq, idxs = job
            try:
                if iterable_slices:
                    batch = idxs  # already materialized items
                else:
                    batch = [dataset[i] for i in idxs]
                out = collate_fn(batch)
                out = _to_numpy_tree(out)
                shms = []
                payload = _pack(out, shms, shm_threshold)
                result_q.put((seq, payload, None))
                for shm in shms:
                    shm.close()  # parent unlinks
            except Exception as e:  # surface worker errors to the parent
                import traceback
                result_q.put((seq, None, f"{e}\n{traceback.format_exc()}"))
    except (KeyboardInterrupt, EOFError):
        pass


def _to_numpy_tree(obj):
    from ..tensor.tensor import Tensor
    if isinstance(obj, Tensor):
        return np.asarray(obj.data)
    if hasattr(obj, "__array__") and not isinstance(obj, np.ndarray):
        return np.asarray(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_numpy_tree(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_numpy_tree(v) for k, v in obj.items()}
    return obj


class MultiprocessIter:
    def __init__(self, loader):
        self.loader = loader
        self.num_workers = loader.num_workers
        self.prefetch = loader.prefetch_factor * self.num_workers
        # forkserver, not fork: forking a process whose jax/XLA runtime
        # threads are live can deadlock the child (the parent has
        # initialized the backend by training time). The forkserver is a
        # CLEAN process with paddle_tpu preloaded (imports are device-free
        # since round 2), so each worker fork is cheap and jax-free until
        # the worker itself computes — and workers pin themselves to CPU.
        ctx = mp.get_context("forkserver")
        try:
            ctx.set_forkserver_preload(["paddle_tpu.io.multiprocess"])
        except Exception:
            pass
        self._index_qs = [ctx.Queue() for _ in range(self.num_workers)]
        self._result_q = ctx.Queue()
        self._workers = []
        self._seq_sent = 0
        self._seq_next = 0
        self._cache = {}
        self._owned_shms = []
        self._batches = self._batch_source()
        self._exhausted = False
        use_shm = getattr(loader, "use_shared_memory", True)
        # honored: use_shared_memory=False pickles everything through the
        # queue (e.g. small /dev/shm containers)
        self._threshold = SHM_THRESHOLD if use_shm else float("inf")

        from . import default_collate_fn
        collate = loader.collate_fn
        if collate is default_collate_fn:
            collate = _numpy_collate  # keep workers jax-free
        for wid in range(self.num_workers):
            w = ctx.Process(
                target=_worker_loop,
                args=(loader.dataset, collate,
                      self._index_qs[wid], self._result_q, wid,
                      getattr(loader, "worker_init_fn", None),
                      loader._iterable_mode, self._threshold),
                daemon=True)
            try:
                w.start()
            except (AttributeError, TypeError, Exception) as e:
                import pickle
                if isinstance(e, (AttributeError, TypeError,
                                  pickle.PicklingError)):
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader(num_workers>0) requires a picklable "
                        f"dataset/collate_fn defined at module level "
                        f"(forkserver workers): {e}") from e
                raise
            self._workers.append(w)
        atexit.register(self._shutdown)
        self._atexit_registered = True
        for _ in range(self.prefetch):
            self._dispatch()

    def _batch_source(self):
        loader = self.loader
        if loader._iterable_mode:
            batch = []
            for item in loader.dataset:
                batch.append(item)
                if len(batch) == loader.batch_size:
                    yield list(batch)
                    batch = []
            if batch and not loader.drop_last:
                yield batch
        else:
            for idxs in loader.batch_sampler:
                yield list(idxs)

    def _dispatch(self):
        if self._exhausted:
            return
        try:
            idxs = next(self._batches)
        except StopIteration:
            self._exhausted = True
            return
        wid = self._seq_sent % self.num_workers
        self._index_qs[wid].put((self._seq_sent, idxs))
        self._seq_sent += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self._seq_next >= self._seq_sent and self._exhausted:
            self._shutdown()
            raise StopIteration
        deadline = 120.0
        while self._seq_next not in self._cache:
            try:
                seq, payload, err = self._result_q.get(timeout=2)
            except _queue.Empty:
                # watchdog (ref: dataloader_iter.py worker monitoring):
                # a dead worker means its batches will never arrive
                dead = [w for w in self._workers if not w.is_alive()]
                if dead:
                    codes = [w.exitcode for w in dead]
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader worker(s) died with exit codes "
                        f"{codes}; see worker stderr. (Note: spawn-based "
                        f"workers need picklable dataset/collate_fn "
                        f"defined at module level.)")
                deadline -= 2
                if deadline <= 0:
                    self._shutdown()
                    raise RuntimeError(
                        "DataLoader worker timed out (120s) with workers "
                        "still alive — dataset __getitem__ is stuck?")
                continue
            if err is not None:
                self._shutdown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            self._cache[seq] = payload
        payload = self._cache.pop(self._seq_next)
        self._seq_next += 1
        self._dispatch()
        owned = []
        out = _unpack(payload, owned)
        for name in owned:
            try:
                shm = shared_memory.SharedMemory(name=name)
                shm.close()
                shm.unlink()
            except FileNotFoundError:
                pass
        return _wrap_tensors(out)

    def _shutdown(self):
        if getattr(self, "_atexit_registered", False):
            atexit.unregister(self._shutdown)
            self._atexit_registered = False
        for q in self._index_qs:
            try:
                q.put(None)
            except Exception:
                pass
        for w in self._workers:
            w.join(timeout=5)
            if w.is_alive():
                w.terminate()
        self._workers = []


def _wrap_tensors(obj):
    from ..tensor.tensor import Tensor
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_wrap_tensors(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _wrap_tensors(v) for k, v in obj.items()}
    return obj
