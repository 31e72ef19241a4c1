"""``paddle.io`` — datasets, samplers, DataLoader.

Reference: ``python/paddle/io/`` (SURVEY.md §2.1 "Data pipeline"): the
reference uses multiprocess workers + pinned-memory transfer; the TPU-native
pipeline keeps workers as threads (numpy preprocessing releases the GIL, and
PJRT owns the host→HBM DMA) with a bounded prefetch queue — the
``BufferedReader`` analog.
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
from typing import Any, Callable, Iterable, List, Optional, Sequence

import numpy as np

from ..core.tensor import Tensor, to_tensor
from ..enforce import InvalidArgumentError
from ..observability import metrics as _obs_metrics

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
    "ChainDataset", "Subset", "random_split", "Sampler", "SequenceSampler",
    "RandomSampler", "WeightedRandomSampler", "SubsetRandomSampler", "BatchSampler",
    "DistributedBatchSampler", "DataLoader", "get_worker_info",
    "default_collate_fn",
]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence[Tensor]):
        lens = {len(t) for t in tensors}
        if len(lens) != 1:
            raise InvalidArgumentError("TensorDataset tensors must share dim 0")
        self.tensors = list(tensors)

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)

    def __len__(self):
        return min(len(d) for d in self.datasets)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        return itertools.chain(*self.datasets)


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    import builtins

    if builtins.sum(lengths) != len(dataset):
        raise InvalidArgumentError("random_split lengths must sum to dataset size")
    perm = np.random.permutation(len(dataset))
    out, offset = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[offset : offset + n].tolist()))
        offset += n
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
    def __init__(self, data_source, replacement=False, num_samples=None, generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class SubsetRandomSampler(Sampler):
    """Random permutation over a fixed index subset (reference
    ``paddle.io.SubsetRandomSampler``)."""

    def __init__(self, indices):
        self.indices = list(indices)

    def __iter__(self):
        return iter(self.indices[i]
                    for i in np.random.permutation(len(self.indices)))

    def __len__(self):
        return len(self.indices)


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
        if sampler is None:
            sampler = RandomSampler(dataset) if shuffle else SequenceSampler(dataset)
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

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
    """Rank-sharded batch sampler (reference:
    ``python/paddle/io/dataloader/batch_sampler.py``): pads the index list to
    a multiple of world size so every rank sees the same number of batches."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        if num_replicas is None or rank is None:
            from ..distributed import get_rank, get_world_size

        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        while len(indices) < self.total_size:  # cycle for tiny datasets
            indices += indices[: self.total_size - len(indices)]
        assert len(indices) == self.total_size
        local = indices[self.local_rank : self.total_size : self.nranks]
        batch = []
        for idx in local:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


class _WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


def default_collate_fn(batch: List[Any]):
    """Stack a list of samples into batched Tensors."""
    sample = batch[0]
    if isinstance(sample, (Tensor,)):
        return to_tensor(np.stack([np.asarray(s._value) for s in batch]))
    if isinstance(sample, np.ndarray):
        return to_tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return to_tensor(np.asarray(batch, dtype="int64"))
    if isinstance(sample, (float, np.floating)):
        return to_tensor(np.asarray(batch, dtype="float32"))
    if isinstance(sample, (tuple, list)):
        return tuple(default_collate_fn(list(items)) for items in zip(*batch))
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    return to_tensor(np.asarray(batch))


def _mp_worker_loop(dataset, index_q, result_q, worker_id, num_workers,
                    worker_init_fn):
    """Subprocess worker body (module-level for spawn picklability):
    pull index batches, build samples, ship raw python/numpy batches back —
    collation into Tensors happens in the parent (jax must not be touched
    in workers)."""
    _worker_info.info = _WorkerInfo(worker_id, num_workers, dataset)
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        job = index_q.get()
        if job is None:
            return
        seq, indices = job
        try:
            samples = [dataset[i] for i in indices]
            result_q.put((seq, samples, None))
        except Exception as e:  # surface dataset errors in the parent;
            # KeyboardInterrupt/SystemExit must still kill the worker
            result_q.put((seq, None, repr(e)))


class DataLoader:
    """Batched, optionally prefetching loader.

    ``num_workers>0`` uses a thread pool + bounded queue by default (numpy
    preprocessing releases the GIL and feeds the native blob queue);
    ``use_multiprocess=True`` switches to REAL subprocess workers (spawn
    context, reference semantics) for GIL-bound python ``__getitem__``.
    ``prefetch_factor`` bounds in-flight batches either way.
    """

    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False,
                 drop_last=False, collate_fn=None, num_workers=0,
                 use_buffer_reader=True, prefetch_factor=2, use_shared_memory=False,
                 timeout=0, worker_init_fn=None, persistent_workers=False,
                 use_multiprocess=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_multiprocess = use_multiprocess
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.prefetch_factor = max(2, prefetch_factor)
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset=dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _iter_iterable(self):
        batch = []
        for item in self.dataset:
            batch.append(item)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)

    def _fetch(self, indices):
        return self.collate_fn([self.dataset[i] for i in indices])

    def _iter_multiprocess(self):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        index_q = ctx.Queue()
        result_q = ctx.Queue()
        workers = [ctx.Process(target=_mp_worker_loop,
                               args=(self.dataset, index_q, result_q,
                                     wid, self.num_workers,
                                     self.worker_init_fn),
                               daemon=True)
                   for wid in range(self.num_workers)]
        # data workers must NEVER claim the accelerator (a chip belongs
        # to one process, and the parent holds it) — force any jax the
        # child's imports may pull in onto CPU for the duration of the
        # spawns
        saved = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for w in workers:
                w.start()
        finally:
            if saved is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = saved
        try:
            pending = {}
            next_out = 0
            submitted = 0
            batches = iter(self.batch_sampler)
            exhausted = False
            max_inflight = self.num_workers * self.prefetch_factor

            def submit():
                nonlocal submitted, exhausted
                if exhausted:
                    return
                try:
                    idx = next(batches)
                except StopIteration:
                    exhausted = True
                    return
                index_q.put((submitted, list(idx)))
                submitted += 1

            for _ in range(max_inflight):
                submit()
            while next_out < submitted:
                # poll with a short tick so a silently-dead worker (OOM
                # kill, segfault, unpicklable dataset state) raises instead
                # of hanging the training loop forever
                import time as _time

                deadline = (_time.monotonic() + self.timeout
                            if self.timeout else None)
                while True:
                    try:
                        seq, samples, err = result_q.get(timeout=1.0)
                        break
                    except queue.Empty:
                        dead = [w for w in workers if not w.is_alive()]
                        if dead:
                            raise RuntimeError(
                                f"DataLoader worker(s) died unexpectedly "
                                f"(exitcodes "
                                f"{[w.exitcode for w in dead]})") from None
                        if deadline and _time.monotonic() > deadline:
                            raise RuntimeError(
                                f"DataLoader timed out after "
                                f"{self.timeout}s waiting for a worker "
                                f"batch") from None
                if err is not None:
                    raise RuntimeError(f"DataLoader worker failed: {err}")
                pending[seq] = samples
                while next_out in pending:  # preserve sampler order
                    _obs_metrics.gauge("io.prefetch_queue_depth").set(
                        submitted - next_out - 1)  # in-flight after this
                    _obs_metrics.counter("io.batches").inc()
                    yield self.collate_fn(pending.pop(next_out))
                    next_out += 1
                    submit()
        finally:
            # drain unserved jobs so workers see their sentinel promptly
            try:
                while True:
                    index_q.get_nowait()
            except queue.Empty:
                pass
            for _ in workers:
                index_q.put(None)
            # drain pending results too: a worker blocked flushing a large
            # result into an unread pipe cannot exit
            try:
                while True:
                    result_q.get_nowait()
            except queue.Empty:
                pass
            for w in workers:
                w.join(timeout=5)
                if w.is_alive():
                    w.terminate()

    def __iter__(self):
        if self._iterable:
            if self.use_multiprocess:
                raise InvalidArgumentError(
                    "use_multiprocess=True is not supported with "
                    "IterableDataset (no index-based sharding); use the "
                    "threaded workers or a map-style Dataset")
            yield from self._iter_iterable()
            return
        if self.num_workers == 0:
            for indices in self.batch_sampler:
                yield self._fetch(indices)
            return
        if self.use_multiprocess:
            yield from self._iter_multiprocess()
            return
        # threaded prefetch: workers pull index-batches, push collated batches
        from concurrent.futures import ThreadPoolExecutor

        max_inflight = self.num_workers * self.prefetch_factor
        with ThreadPoolExecutor(self.num_workers) as pool:
            futures = queue.Queue()
            batches = iter(self.batch_sampler)

            def submit_next():
                try:
                    idx = next(batches)
                except StopIteration:
                    return False
                futures.put(pool.submit(self._fetch, idx))
                return True

            alive = True
            for _ in range(max_inflight):
                alive = submit_next()
                if not alive:
                    break
            g_depth = _obs_metrics.gauge("io.prefetch_queue_depth")
            c_batches = _obs_metrics.counter("io.batches")
            while not futures.empty():
                fut = futures.get()
                submit_next()
                # depth AFTER this batch is consumed = batches still
                # prefetched ahead of the training loop (a persistently
                # empty queue means the input pipeline is the bottleneck)
                g_depth.set(futures.qsize())
                c_batches.inc()
                yield fut.result()
