"""paddle_tpu.io — Dataset / DataLoader
(upstream: python/paddle/io/ + the C++ blocking-queue reader ops in
paddle/fluid/operators/reader/).

TPU-native design: the loader pipelines host-side batch assembly into a
bounded blocking queue (the analog of the reference's C++
BlockingQueue), converts to device arrays, and overlaps host→HBM
transfer with compute by keeping `prefetch_factor` batches in flight.
One process owns the TPU (jax); with ``num_workers > 0`` batches are
built in true OS worker processes (spawn context — fork is unsafe after
PJRT init) exactly like the reference's multi-process workers, so
Python-heavy transforms scale past the GIL. ``num_workers=0`` keeps the
in-process threaded path.
"""
from __future__ import annotations

import itertools
import queue
import threading

import numpy as np

from ..framework.core import Tensor
from ..framework.random import default_generator


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = [
            t if isinstance(t, Tensor) else Tensor(t) for t in tensors
        ]

    def __getitem__(self, idx):
        return tuple(t.numpy()[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        di = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if di == 0 else int(self.cum[di - 1])
        return self.datasets[di][idx - prev]


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        return itertools.chain(*self.datasets)


def random_split(dataset, lengths, generator=None):
    n = len(dataset)
    idx = np.random.RandomState(
        default_generator().initial_seed()
    ).permutation(n)
    out, start = [], 0
    for l in lengths:
        out.append(Subset(dataset, idx[start:start + l]))
        start += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self.num_samples = num_samples or len(data_source)
        self._epoch = 0

    def __iter__(self):
        n = len(self.data_source)
        seed = default_generator().initial_seed() + self._epoch
        self._epoch += 1
        rng = np.random.RandomState(seed)
        if self.replacement:
            return iter(rng.randint(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(
            len(self.weights), self.num_samples, self.replacement, p
        )
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards the index space across data-parallel ranks (upstream:
    python/paddle/io/dataloader/batch_sampler.py). In one-process SPMD
    the 'rank' is a slot in the global batch: the fleet dataloader uses
    num_replicas = dp_degree and concatenates shards, so per-device
    sub-batches line up with the mesh's dp axis."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        from ..distributed import get_rank, get_world_size

        self.nranks = num_replicas if num_replicas is not None else (
            get_world_size()
        )
        self.local_rank = rank if rank is not None else get_rank()
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(
                default_generator().initial_seed() + self.epoch
            )
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - len(indices)]
        local = indices[self.local_rank::self.nranks]
        batch = []
        for idx in local:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


def _np_collate(batch):
    """Collate to host numpy (safe in worker threads — device transfer
    happens on the main thread, since PJRT client creation is not
    thread-safe to race from workers)."""
    sample = batch[0]
    if isinstance(sample, (np.ndarray, np.generic)):
        return np.stack(batch)
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._data) for s in batch])
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, float):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        return [_np_collate([b[i] for b in batch])
                for i in range(len(sample))]
    if isinstance(sample, dict):
        return {k: _np_collate([b[k] for b in batch]) for k in sample}
    return batch


def _to_device(obj):
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, list):
        return [_to_device(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _to_device(v) for k, v in obj.items()}
    return obj


def default_collate_fn(batch):
    return _to_device(_np_collate(batch))


def _make_queue(maxsize):
    """Native C++ blocking queue (csrc/runtime.cc — the analog of the
    reference's reader BlockingQueue) with queue.Queue fallback."""
    from .. import csrc

    if csrc.available():
        return csrc.BlockingQueue(maxsize)
    return queue.Queue(maxsize=maxsize)


class _LoaderIter:
    def __init__(self, loader):
        # Force PJRT backend init BEFORE spawning threads: client creation
        # is not thread/fork-safe and deadlocks if worker threads exist.
        import jax

        jax.devices()
        self.loader = loader
        self.batch_iter = iter(loader.batch_sampler)
        self.queue = _make_queue(
            max(2, loader.prefetch_factor * max(loader.num_workers, 1))
        )
        self._stop = threading.Event()
        self._threads = []
        self._seq = 0
        self._next_emit = 0
        self._lock = threading.Lock()
        self._reorder = {}
        n = max(1, loader.num_workers)
        self._sentinel_count = 0
        for wid in range(n):
            t = threading.Thread(
                target=self._worker, args=(wid,), daemon=True
            )
            t.start()
            self._threads.append(t)

    def _next_indices(self):
        with self._lock:
            try:
                idx = next(self.batch_iter)
            except StopIteration:
                return None, None
            seq = self._seq
            self._seq += 1
            return seq, idx

    def _worker(self, wid=0):
        init = getattr(self.loader, "worker_init_fn", None)
        if init is not None:
            try:
                init(wid)
            except Exception as e:
                # dedicated sentinel seq — must not collide with batch 0
                self.queue.put((-1, e))
                self.queue.put((None, None))
                return
        while not self._stop.is_set():
            seq, indices = self._next_indices()
            if seq is None:
                self.queue.put((None, None))
                return
            try:
                if self.loader.dataset_kind == "iterable":
                    raise RuntimeError
                samples = [self.loader.dataset[i] for i in indices]
                # workers collate to numpy; device upload happens on the
                # consumer (main) thread in __next__
                if self.loader.collate_fn is default_collate_fn:
                    batch = _np_collate(samples)
                else:
                    batch = self.loader.collate_fn(samples)
            except Exception as e:  # propagate errors to the consumer
                self.queue.put((seq, e))
                continue
            self.queue.put((seq, batch))

    def __next__(self):
        n_workers = max(1, self.loader.num_workers)
        while True:
            if self._next_emit in self._reorder:
                item = self._reorder.pop(self._next_emit)
                self._next_emit += 1
                if isinstance(item, Exception):
                    raise item
                if self.loader.collate_fn is default_collate_fn:
                    item = _to_device(item)
                return item
            if self._sentinel_count >= n_workers:
                if not self._reorder:
                    raise StopIteration
                # remaining items have out-of-range seq — flush in order
                k = min(self._reorder)
                self._next_emit = k
                continue
            seq, item = self.queue.get()
            if seq is None:
                self._sentinel_count += 1
                continue
            if seq == -1:  # worker_init_fn failure
                raise RuntimeError(f"worker_init_fn failed: {item!r}")
            self._reorder[seq] = item

    def __iter__(self):
        return self

    def __del__(self):
        self._stop.set()


class _WorkerInfo:
    """get_worker_info() payload inside worker processes (upstream:
    python/paddle/io/dataloader/worker.py WorkerInfo)."""

    def __init__(self, id, num_workers, seed, dataset):
        self.id = id
        self.num_workers = num_workers
        self.seed = seed
        self.dataset = dataset


_worker_info = None


class _RemoteError(Exception):
    pass


def _flatten_np(obj):
    """Split a collated batch into (ndarray leaves, structure spec).
    Non-array leaves travel inside the spec (they're tiny)."""
    if isinstance(obj, np.ndarray):
        return [obj], ("arr",)
    if isinstance(obj, (list, tuple)):
        leaves, specs = [], []
        for v in obj:
            l, s = _flatten_np(v)
            leaves.extend(l)
            specs.append(s)
        kind = "tuple" if isinstance(obj, tuple) else "list"
        return leaves, (kind, specs)
    if isinstance(obj, dict):
        leaves, items = [], []
        for k in obj:
            l, s = _flatten_np(obj[k])
            leaves.extend(l)
            items.append((k, s))
        return leaves, ("dict", items)
    return [], ("value", obj)


def _unflatten_np(spec, leaves, pos=0):
    kind = spec[0]
    if kind == "arr":
        return leaves[pos], pos + 1
    if kind in ("list", "tuple"):
        out = []
        for s in spec[1]:
            v, pos = _unflatten_np(s, leaves, pos)
            out.append(v)
        return (tuple(out) if kind == "tuple" else out), pos
    if kind == "dict":
        out = {}
        for k, s in spec[1]:
            v, pos = _unflatten_np(s, leaves, pos)
            out[k] = v
        return out, pos
    return spec[1], pos


def _mp_worker(dataset, use_default_collate, collate_fn, index_q,
               result_q, worker_init_fn, wid, num_workers, seed,
               shm_name=None):
    """Worker-process loop: pull index batches, build+collate to numpy,
    push back. Never initializes a jax backend (the parent owns the
    TPU). With ``shm_name`` the arrays go through the native
    shared-memory arena (one memcpy; the parent reads zero-copy —
    upstream analog: mmap_allocator.cc transport); batches that exceed
    a slot fall back to the pickled queue pipe."""
    import os as _os
    import traceback

    _os.environ["JAX_PLATFORMS"] = "cpu"  # belt-and-braces: no TPU grab
    global _worker_info
    _worker_info = _WorkerInfo(wid, num_workers, seed + wid, dataset)
    arena = None
    if shm_name is not None:
        try:
            from .. import csrc

            arena = csrc.ShmArena.open(shm_name)
        except Exception:
            arena = None
    if worker_init_fn is not None:
        try:
            worker_init_fn(wid)
        except Exception:
            result_q.put((-1, _RemoteError(traceback.format_exc())))
            return
    while True:
        task = index_q.get()
        if task is None:
            result_q.put((None, wid))
            return
        seq, indices = task
        try:
            samples = [dataset[i] for i in indices]
            if use_default_collate:
                batch = _np_collate(samples)
            else:
                batch = collate_fn(samples)
            sent = False
            if arena is not None:
                leaves, spec = _flatten_np(batch)
                if leaves:
                    try:
                        packed = arena.write_arrays(leaves, timeout=30.0)
                    except TimeoutError:
                        # all slots in flight (consumer lagging) — the
                        # pickled pipe still works; never fail the epoch
                        packed = None
                    if packed is not None:
                        slot, meta = packed
                        result_q.put(
                            (seq, ("__shm__", wid, slot, meta, spec))
                        )
                        sent = True
            if not sent:
                result_q.put((seq, batch))
        except Exception:
            result_q.put((seq, _RemoteError(traceback.format_exc())))


class _MPLoaderIter:
    """Multi-process iterator: an index feeder (this thread) + N worker
    processes + an in-order reorder buffer (the role the reference's
    _DataLoaderIterMultiProcess plays over its C++ blocking queue)."""

    def __init__(self, loader):
        import multiprocessing as mp

        self.loader = loader
        n = loader.num_workers
        use_default = loader.collate_fn is default_collate_fn
        ctx = mp.get_context("spawn")
        self._index_q = ctx.Queue()
        self._result_q = ctx.Queue()
        self.batch_iter = iter(loader.batch_sampler)
        self._seq = 0
        self._next_emit = 0
        self._reorder = {}
        self._sentinels = 0
        self._exhausted = False
        seed = 0
        try:
            seed = default_generator().initial_seed()
        except Exception:
            pass
        # native shared-memory arenas (one per worker, parent-owned so
        # teardown unlinks them); zero-copy batch transport with the
        # pickled pipe as automatic fallback
        self._arenas = {}
        shm_names = [None] * n
        from .. import csrc

        if csrc.available():
            import os as _os2

            depth = max(2, loader.prefetch_factor) + 2
            slot_bytes = int(
                getattr(loader, "shm_slot_bytes", 64 << 20)
            )
            for wid in range(n):
                name = f"/pt_dl_{_os2.getpid()}_{id(self) & 0xffff}_{wid}"
                try:
                    self._arenas[wid] = csrc.ShmArena.create(
                        name, depth, slot_bytes
                    )
                    shm_names[wid] = name
                except Exception:
                    self._arenas.pop(wid, None)
        self._procs = [
            ctx.Process(
                target=_mp_worker,
                args=(loader.dataset, use_default,
                      None if use_default else loader.collate_fn,
                      self._index_q, self._result_q,
                      loader.worker_init_fn, wid, n, seed,
                      shm_names[wid]),
                daemon=True,
            )
            for wid in range(n)
        ]
        # workers are host-side batch builders and must NEVER attach to
        # the accelerator (a chip belongs to one process): they boot
        # with JAX_PLATFORMS=cpu, the parent's own setting is restored
        import os as _os

        prev_plat = _os.environ.get("JAX_PLATFORMS")
        _os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for p in self._procs:
                p.start()
        finally:
            if prev_plat is None:
                _os.environ.pop("JAX_PLATFORMS", None)
            else:
                _os.environ["JAX_PLATFORMS"] = prev_plat
        # pre-dispatch the pipeline depth
        for _ in range(max(2, loader.prefetch_factor) * n):
            self._dispatch()

    def _dispatch(self):
        if self._exhausted:
            return
        try:
            indices = next(self.batch_iter)
        except StopIteration:
            self._exhausted = True
            for _ in self._procs:
                self._index_q.put(None)
            return
        self._index_q.put((self._seq, indices))
        self._seq += 1

    def _materialize(self, item):
        """Resolve a shm-transported batch: zero-copy views -> device
        upload (or host copy for custom collate), then free the slot."""
        if not (isinstance(item, tuple) and len(item) == 5
                and item[0] == "__shm__"):
            if self.loader.collate_fn is default_collate_fn:
                item = _to_device(item)
            return item
        _, wid, slot, meta, spec = item
        arena = self._arenas[wid]
        views = arena.read_arrays(slot, meta)
        try:
            # copy out of the slot BEFORE releasing: jax's CPU backend
            # may alias a numpy buffer zero-copy, so handing the raw
            # view to Tensor() would leave a live array pointing into a
            # recycled (or unmapped) slot -> use-after-free
            host = [np.array(v) for v in views]
        finally:
            arena.release(slot)
        if self.loader.collate_fn is default_collate_fn:
            host = [Tensor(v) for v in host]
        out, _ = _unflatten_np(spec, host)
        return out

    def __next__(self):
        while True:
            if self._next_emit in self._reorder:
                item = self._reorder.pop(self._next_emit)
                self._next_emit += 1
                self._dispatch()
                if isinstance(item, _RemoteError):
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader worker failed:\n{item}"
                    )
                return self._materialize(item)
            if self._sentinels >= len(self._procs) and \
                    self._seq == self._next_emit and not self._reorder:
                self._shutdown()
                raise StopIteration
            import queue as _queue

            try:
                seq, item = self._result_q.get(timeout=5.0)
            except _queue.Empty:
                # liveness check: a worker killed mid-batch (OOM,
                # segfault in native code) never sends its result or
                # sentinel — fail loudly instead of hanging forever
                dead = [
                    p.pid for p in self._procs
                    if not p.is_alive() and p.exitcode not in (0, None)
                ]
                if dead:
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader worker(s) {dead} died unexpectedly"
                    )
                continue
            if seq is None:
                self._sentinels += 1
                continue
            if seq == -1:  # worker_init_fn failure
                self._shutdown()
                raise RuntimeError(f"worker_init_fn failed:\n{item}")
            self._reorder[seq] = item

    def __iter__(self):
        return self

    def _shutdown(self):
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        for p in self._procs:
            p.join(timeout=5)
        for arena in getattr(self, "_arenas", {}).values():
            try:
                arena.close()  # parent owns: unlinks the shm segment
            except Exception:
                pass
        self._arenas = {}

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self.collate_fn = collate_fn or default_collate_fn
        self.dataset_kind = (
            "iterable" if isinstance(dataset, IterableDataset) else "map"
        )
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
        elif self.dataset_kind == "map":
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last,
            )
        else:
            self.batch_sampler = None
        self.batch_size = batch_size
        self.drop_last = drop_last
        self._mp_ok = None  # cached spawn-picklability verdict

    def __iter__(self):
        if self.dataset_kind == "iterable":
            return self._iter_iterable()
        if self.num_workers == 0:
            return self._iter_sync()
        if self.use_shared_memory:
            # reference default: true OS worker processes. Spawn needs
            # picklable dataset/collate_fn/worker_init_fn — fall back to
            # the threaded loader (with a warning) when they aren't, so
            # in-line datasets keep working. Probe once, not per epoch.
            if self._mp_ok is None:
                import pickle as _pickle

                try:
                    _pickle.dumps(self.dataset)
                    if self.collate_fn is not default_collate_fn:
                        _pickle.dumps(self.collate_fn)
                    if self.worker_init_fn is not None:
                        _pickle.dumps(self.worker_init_fn)
                    self._mp_ok = True
                except (TypeError, AttributeError, _pickle.PicklingError):
                    self._mp_ok = False
                    import warnings

                    warnings.warn(
                        "DataLoader: dataset/collate_fn/worker_init_fn "
                        "is not picklable; num_workers>0 is using "
                        "in-process threads instead of worker processes "
                        "(define them at module scope for true "
                        "multiprocess loading)"
                    )
            if self._mp_ok:
                return _MPLoaderIter(self)
        # threaded in-process path (fallback / use_shared_memory=False)
        return _LoaderIter(self)

    def _iter_sync(self):
        for indices in self.batch_sampler:
            samples = [self.dataset[i] for i in indices]
            yield self.collate_fn(samples)

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)

    def __len__(self):
        if self.batch_sampler is not None:
            return len(self.batch_sampler)
        raise TypeError("IterableDataset has no len()")


def get_worker_info():
    """Inside a worker process: (id, num_workers, seed, dataset);
    None in the main process (reference semantics)."""
    return _worker_info


class ComposeDataset(Dataset):
    """Zip-style composition: sample i concatenates the fields of every
    dataset's sample i (upstream: io/dataloader/dataset.py
    ComposeDataset)."""

    def __init__(self, datasets):
        self.datasets = list(datasets)
        if not self.datasets:
            raise ValueError("datasets must not be empty")
        n = len(self.datasets[0])
        for d in self.datasets[1:]:
            if len(d) != n:
                raise ValueError(
                    "ComposeDataset requires equal-length datasets"
                )

    def __len__(self):
        return len(self.datasets[0])

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            sample = d[idx]
            if isinstance(sample, (list, tuple)):
                out.extend(sample)
            else:
                out.append(sample)
        return tuple(out)


class SubsetRandomSampler(Sampler):
    """Random permutation over a fixed index subset (upstream
    SubsetRandomSampler)."""

    def __init__(self, indices):
        self.indices = list(indices)
        if not self.indices:
            raise ValueError("indices must not be empty")

    def __iter__(self):
        # seeded like RandomSampler: reproducible under paddle.seed and
        # consistent across data-parallel ranks
        seed = default_generator().initial_seed() + getattr(
            self, "_epoch", 0
        )
        self._epoch = getattr(self, "_epoch", 0) + 1
        order = np.random.RandomState(seed).permutation(
            len(self.indices)
        )
        return iter([self.indices[i] for i in order])

    def __len__(self):
        return len(self.indices)
