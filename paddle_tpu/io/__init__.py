"""``paddle.io``: datasets, samplers, DataLoader.

Parity surface: python/paddle/io/ (Dataset, IterableDataset, DataLoader with
worker processes, BatchSampler, DistributedBatchSampler). TPU-native notes:
host->device transfer happens once per batch via ``to_tensor`` (device_put);
a background thread prefetches batches (the analogue of the reference's C++
BlockingQueue double-buffering); multiprocess workers use the standard
``multiprocessing`` pool since jax arrays are produced only at collate time.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from typing import Any, Callable, Iterable, List, Optional, Sequence

import numpy as np

from .. import observability as _obs
from ..core.random import default_generator
from ..core.tensor import Tensor, to_tensor

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
    "ChainDataset", "Subset", "random_split", "Sampler", "SequenceSampler",
    "RandomSampler", "WeightedRandomSampler", "BatchSampler",
    "DistributedBatchSampler", "DataLoader", "get_worker_info",
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
        self.tensors = [t if isinstance(t, Tensor) else to_tensor(t) for t in tensors]
        n = len(self.tensors[0])
        assert all(len(t) == n for t in self.tensors)

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
        for ds in self.datasets:
            yield from ds


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def _host_rng(generator=None):
    """Host-side numpy RNG seeded from the framework generator, so that
    ``paddle.seed`` makes shuffle order reproducible (upstream: samplers draw
    from the global phi Generator)."""
    gen = generator if generator is not None else default_generator
    key = np.asarray(gen.split_key(), dtype=np.uint64)
    return np.random.default_rng(key)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths):
        total = len(dataset)
        lengths = [int(math.floor(total * l)) for l in lengths]
        lengths[-1] = total - sum(lengths[:-1])
    if sum(lengths) != len(dataset):
        raise ValueError("sum of lengths must equal dataset length")
    perm = _host_rng(generator).permutation(len(dataset)).tolist()
    out, off = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[off:off + l]))
        off += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples
        self.generator = generator

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        rng = _host_rng(self.generator)
        if self.replacement:
            return iter(rng.integers(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        rng = _host_rng()
        idx = rng.choice(len(self.weights), self.num_samples,
                         replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
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
    """Rank-sharded batch sampler (upstream:
    python/paddle/io/dataloader/batch_sampler.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            from ..distributed import get_world_size, get_rank
            num_replicas = num_replicas if num_replicas is not None else get_world_size()
            rank = rank if rank is not None else get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = list(range(n))
        if self.shuffle:
            rng = np.random.default_rng(self.epoch)
            indices = rng.permutation(n).tolist()
        indices += indices[: self.total_size - n]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
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

    def set_epoch(self, epoch):
        self.epoch = epoch


class _WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        return to_tensor(np.stack([np.asarray(b._data) for b in batch]))
    if isinstance(sample, np.ndarray):
        return to_tensor(np.stack(batch))
    if isinstance(sample, (int, float, np.floating, np.integer)):
        return to_tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = zip(*batch)
        return type(sample)(default_collate_fn(list(s)) for s in transposed)
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


_TENSOR_TAG = "__pdtpu_tensor__"


def _encode_for_ipc(obj):
    """Tensors can't cross process boundaries as PJRT buffers; ship numpy."""
    if isinstance(obj, Tensor):
        return (_TENSOR_TAG, np.asarray(obj._data))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_encode_for_ipc(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _encode_for_ipc(v) for k, v in obj.items()}
    return obj


def _decode_from_ipc(obj):
    if isinstance(obj, tuple) and len(obj) == 2 and obj[0] == _TENSOR_TAG:
        return to_tensor(obj[1])
    if isinstance(obj, (list, tuple)):
        return type(obj)(_decode_from_ipc(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _decode_from_ipc(v) for k, v in obj.items()}
    return obj


def _np_collate(batch):
    """Worker-side default collate: stacks to numpy so the worker process
    never touches a jax backend (the parent does the single device_put)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return (_TENSOR_TAG, np.stack([np.asarray(b._data) for b in batch]))
    if isinstance(sample, np.ndarray):
        return (_TENSOR_TAG, np.stack(batch))
    if isinstance(sample, (int, float, np.floating, np.integer)):
        return (_TENSOR_TAG, np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = zip(*batch)
        return type(sample)(_np_collate(list(s)) for s in transposed)
    if isinstance(sample, dict):
        return {k: _np_collate([b[k] for b in batch]) for k in sample}
    return batch


def _worker_loop(dataset, index_queue, result_queue, collate_fn, init_fn,
                 worker_id, num_workers, iterable_mode, batch_size,
                 drop_last):
    """Body of one spawned worker process (upstream parity:
    python/paddle/io/dataloader/worker.py _worker_loop)."""
    global _worker_info
    try:
        # the parent spawned us with JAX_PLATFORMS=cpu in the environment
        # (_MultiProcessIter.__init__): a worker never touches the chip
        _worker_info = _WorkerInfo(worker_id, num_workers, dataset)
        if init_fn is not None:
            init_fn(worker_id)
        if iterable_mode:
            try:
                it = iter(dataset)
                seq = worker_id
                while True:
                    batch = list(itertools.islice(it, batch_size))
                    if not batch or (len(batch) < batch_size and drop_last):
                        break
                    result_queue.put(
                        (seq, _encode_for_ipc(collate_fn(batch))))
                    seq += num_workers
            except Exception as e:
                result_queue.put(("error", (worker_id, repr(e))))
            result_queue.put(("done", worker_id))
            # wait for the shutdown token so the queue is drained cleanly
            while True:
                cmd = index_queue.get()
                if cmd is None:
                    break
        else:
            while True:
                cmd = index_queue.get()
                if cmd is None:
                    break
                epoch, seq, idx_batch = cmd
                try:
                    out = _encode_for_ipc(
                        collate_fn([dataset[i] for i in idx_batch]))
                    result_queue.put((epoch, seq, out))
                except Exception as e:  # ship the error, keep serving
                    result_queue.put((epoch, "error", (seq, repr(e))))
    except KeyboardInterrupt:
        pass  # parent is shutting down (Ctrl-C fans out to the process
        #       group): exit the worker loop without a traceback


class _WorkerPool:
    """N spawned workers fed by an index queue, drained in submit order."""

    def __init__(self, loader):
        import multiprocessing as mp

        self._loader = loader
        ctx = mp.get_context("spawn")
        self._index_queues = []
        n = loader.num_workers
        # bounded: gives iterable-mode workers backpressure (map mode is
        # already throttled by the in-flight window) + room for control
        # tokens
        self._result_queue = ctx.Queue(
            maxsize=max(2, loader.prefetch_factor) * n + n)
        user_collate = loader.collate_fn is not default_collate_fn
        collate = loader.collate_fn if user_collate else _np_collate
        self._procs = []
        self._epoch = 0  # stale-epoch filter: an early-broken epoch leaves
        #                  in-flight results that must not leak into the next
        # children must pin to cpu BEFORE they unpickle the dataset (a
        # dataset holding Tensors would otherwise initialize the parent's
        # real backend while deserializing Process args)
        import os
        prev_plat = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for w in range(n):
                iq = ctx.Queue()
                self._index_queues.append(iq)
                p = ctx.Process(
                    target=_worker_loop,
                    args=(loader.dataset, iq, self._result_queue, collate,
                          loader.worker_init_fn, w, n, loader._iterable_mode,
                          loader.batch_size, loader.drop_last),
                    daemon=True)
                p.start()
                self._procs.append(p)
        finally:
            if prev_plat is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = prev_plat

    def _get_result(self, timeout):
        """Blocking get with worker-liveness polling: a hard worker death
        (segfault/OOM-kill) must raise, not hang the trainer forever."""
        if _obs.enabled():
            try:  # queue depth BEFORE the take: how far ahead workers are
                _obs.set_gauge("dataloader.queue_depth",
                               self._result_queue.qsize())
            except NotImplementedError:
                pass  # macOS: mp.Queue.qsize is unimplemented
            with _obs.scoped_timer("dataloader.wait_seconds"):
                return self._get_result_impl(timeout)
        return self._get_result_impl(timeout)

    def _get_result_impl(self, timeout):
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            poll = 5.0 if deadline is None else max(
                0.01, min(5.0, deadline - time.monotonic()))
            try:
                return self._result_queue.get(timeout=poll)
            except queue.Empty:
                if deadline is not None and time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"DataLoader timed out after {timeout}s waiting for "
                        "a worker batch") from None
                dead = [w for w, p in enumerate(self._procs)
                        if not p.is_alive()]
                if dead:
                    raise RuntimeError(
                        f"DataLoader worker(s) {dead} died unexpectedly "
                        "(killed or crashed outside Python)")

    def run_epoch(self):
        loader = self._loader
        timeout = (loader.timeout
                   if loader.timeout and loader.timeout > 0 else None)
        if loader._iterable_mode:
            yield from self._run_iterable(timeout)
            return
        self._epoch += 1
        epoch = self._epoch
        indices = list(loader.batch_sampler)
        n_batches = len(indices)
        inflight_target = max(2, loader.prefetch_factor) * len(self._procs)
        next_submit = 0
        received = {}
        next_yield = 0
        while next_yield < n_batches:
            while (next_submit < n_batches
                   and next_submit - next_yield < inflight_target):
                self._index_queues[next_submit % len(self._procs)].put(
                    (epoch, next_submit, indices[next_submit]))
                next_submit += 1
            while next_yield in received:
                yield _decode_from_ipc(received.pop(next_yield))
                next_yield += 1
            if next_yield >= n_batches:
                break
            ep, tag, payload = self._get_result(timeout)
            if ep != epoch:
                continue  # stale result from an early-broken prior epoch
            if tag == "error":
                seq, msg = payload
                raise RuntimeError(
                    f"DataLoader worker failed on batch {seq}: {msg}")
            received[tag] = payload

    def _run_iterable(self, timeout):
        done = 0
        received = {}
        # workers stream (seq = worker_id + k*num_workers); yield in global
        # seq order so two epochs of the same dataset agree
        next_seq = 0
        while done < len(self._procs):
            if next_seq in received:
                yield _decode_from_ipc(received.pop(next_seq))
                next_seq += 1
                continue
            tag, payload = self._get_result(timeout)
            if tag == "done":
                done += 1
                continue
            if tag == "error":
                seq, msg = payload
                raise RuntimeError(f"DataLoader worker failed: {msg}")
            received[tag] = payload
        # stragglers: some seq numbers never arrive (a worker exhausted
        # early); yield the rest in ascending order
        for seq in sorted(received):
            yield _decode_from_ipc(received.pop(seq))

    def shutdown(self):
        for iq in self._index_queues:
            try:
                iq.put(None)
            except Exception:
                pass  # queue torn down by a dead worker: join/terminate
                #       below still reaps the process
        for p in self._procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        self._procs = []

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass  # interpreter teardown: queues/processes may be half-dead
            #       and shutdown is best-effort by contract


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_buffer_reader = use_buffer_reader
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
        elif not self._iterable_mode and batch_size is not None:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
        else:
            self.batch_sampler = None
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        self.timeout = timeout
        self._pool = None
        # resumable-iteration cursor (state_dict/load_state_dict): the
        # LAST-started iteration owns these — concurrent iterators over
        # one DataLoader are outside the resume contract
        self._sd_epochs = 0        # completed full iterations
        self._sd_batch = 0         # batches handed out in the live iteration
        self._sd_in_epoch = False
        self._sd_epoch_rng = None  # generator key at iteration start
        self._sd_token = None      # cursor owner (the live iteration)
        self._resume = None        # pending load_state_dict payload

    def __del__(self):
        if self._pool is not None:
            self._pool.shutdown()

    def __len__(self):
        if self.batch_sampler is not None:
            return len(self.batch_sampler)
        raise TypeError("DataLoader over an IterableDataset has no length")

    def _iter_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for idx_batch in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idx_batch])

    def __iter__(self):
        resume, self._resume = self._resume, None
        resuming = (resume is not None and resume.get("in_epoch")
                    and int(resume.get("batch", 0)) > 0)
        mode = ("resume" if resuming
                else "workers" if self.num_workers and self.num_workers > 0
                else "buffered" if self.use_buffer_reader else "sync")
        # cursor bookkeeping: the key snapshot is taken BEFORE the sampler
        # can split it, so a resume can replay this epoch's shuffle draw
        self._sd_epoch_rng = self._rng_snapshot()
        if resuming:
            self._sd_epochs = int(resume.get("epochs_completed", 0))
            self._sd_batch = int(resume.get("batch", 0))
            if resume.get("epoch_rng") is not None:
                self._sd_epoch_rng = list(resume["epoch_rng"])
            inner = self._resume_iter(resume)
        else:
            if resume is not None:
                self._sd_epochs = int(resume.get("epochs_completed", 0))
            self._sd_batch = 0
            inner = self._iter_impl()
        self._sd_in_epoch = True
        # ownership token: an ABANDONED iterator's deferred finally (it
        # runs at GC time) must not clobber the cursor of a newer live
        # iteration — the restart path abandons the faulted epoch's
        # iterator and immediately starts the resumed one
        token = object()
        self._sd_token = token
        finished = False
        try:
            for batch in inner:
                if self._sd_token is token:
                    self._sd_batch += 1
                _obs.inc("dataloader.batches_total", mode=mode)
                yield batch
            finished = True
        finally:
            if self._sd_token is token:
                self._sd_in_epoch = False
                if finished:
                    self._sd_epochs += 1
                    self._sd_batch = 0

    # -- resumable iteration state (PR 10) ----------------------------------
    @staticmethod
    def _rng_snapshot():
        """Flat uint32 view of the framework generator key (None when the
        key is not host-readable, e.g. inside a trace)."""
        try:
            arr = np.asarray(default_generator.state._data)
        except Exception:
            return None
        return [int(x) for x in arr.ravel().tolist()]

    def state_dict(self):
        """Resumable iteration position: completed epochs, the batch cursor
        of the live iteration, and the shuffle-generator key at its start.
        JSON-serializable; pair with :meth:`load_state_dict` to resume
        mid-epoch with the exact remaining batches (same shuffle order)."""
        return {
            "version": 1,
            "epochs_completed": int(self._sd_epochs),
            "batch": int(self._sd_batch) if self._sd_in_epoch else 0,
            "in_epoch": bool(self._sd_in_epoch),
            "epoch_rng": (None if self._sd_epoch_rng is None
                          else list(self._sd_epoch_rng)),
        }

    def load_state_dict(self, state) -> None:
        """Schedule a resume: the NEXT ``iter(loader)`` replays the
        interrupted epoch's shuffle draw from the recorded generator state,
        skips the already-consumed batches, and yields the remainder —
        leaving the global generator exactly as it was (rng-neutral, so a
        caller restoring its own RNG snapshot afterwards stays bitwise
        reproducible). Map-style datasets skip on indices (no sample is
        loaded or collated twice); iterable datasets re-consume the skipped
        prefix (no random access). The resumed epoch runs on the in-process
        path even when ``num_workers > 0``; worker pools re-engage on the
        following epoch."""
        if not isinstance(state, dict) or "version" not in state:
            raise ValueError("not a DataLoader state_dict")
        if int(state["version"]) != 1:
            raise ValueError(
                f"unsupported DataLoader state_dict version "
                f"{state['version']!r}")
        self._resume = dict(state)
        self._sd_epochs = int(state.get("epochs_completed", 0))
        self._sd_batch = 0
        self._sd_in_epoch = False

    def _resume_iter(self, resume):
        """Rebuild the interrupted iteration (see :meth:`load_state_dict`)."""
        import jax.numpy as jnp

        skip = int(resume.get("batch", 0))
        saved = resume.get("epoch_rng")
        if not self._iterable_mode and self.batch_sampler is not None:
            if saved is not None:
                prev = self._rng_snapshot()
                default_generator.set_state(
                    jnp.asarray(np.asarray(saved, dtype=np.uint32)))
                try:
                    # the epoch's sampler split is replayed eagerly HERE so
                    # the generator can be restored before anything else
                    # (prefetch threads included) touches it
                    batches = list(self.batch_sampler)
                finally:
                    if prev is not None:
                        default_generator.set_state(
                            jnp.asarray(np.asarray(prev, dtype=np.uint32)))
            else:
                batches = list(self.batch_sampler)

            def _gen():
                for idx_batch in batches[skip:]:
                    yield self.collate_fn(
                        [self.dataset[i] for i in idx_batch])

            src = _gen()
        else:
            src = itertools.islice(self._iter_batches(), skip, None)
        if self.use_buffer_reader:
            return self._thread_prefetch(src)
        return src

    def _iter_impl(self):
        if self.num_workers and self.num_workers > 0:
            pool = self._pool
            if pool is None:
                pool = _WorkerPool(self)
                # iterable workers exhaust their stream once; a persistent
                # pool would hang the next epoch — always rebuild for them
                if self.persistent_workers and not self._iterable_mode:
                    self._pool = pool
            try:
                yield from pool.run_epoch()
            finally:
                if pool is not self._pool:
                    pool.shutdown()
            return
        if self.use_buffer_reader:
            yield from self._thread_prefetch(self._iter_batches())
        else:
            yield from self._iter_batches()

    def _thread_prefetch(self, gen):
        """Background-thread double buffering: the native C++ BlockingQueue
        (paddle_tpu/_native) when available — the analogue of the reference's
        C++ BlockingQueue DataLoader feed — else a Python queue."""
        from .. import _native

        if _native.available():
            yield from self._native_prefetch(gen)
            return
        q: "queue.Queue" = queue.Queue(maxsize=max(2, self.prefetch_factor))
        sentinel = object()
        err: List[BaseException] = []

        def worker():
            try:
                for item in gen:
                    q.put(item)
            except BaseException as e:  # noqa: BLE001
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            if _obs.enabled():
                # depth before the take = how far ahead the prefetcher is;
                # wait time = how long the trainer starved
                _obs.set_gauge("dataloader.queue_depth", q.qsize())
                with _obs.scoped_timer("dataloader.wait_seconds"):
                    item = q.get()
            else:
                item = q.get()
            if item is sentinel:
                break
            yield item
        if err:
            raise err[0]

    def _native_prefetch(self, gen):
        from .. import _native

        q = _native.BlockingQueue(max(2, self.prefetch_factor))
        err: List[BaseException] = []

        def worker():
            try:
                for item in gen:
                    if not q.push(item):  # queue closed by consumer
                        return
            except BaseException as e:  # noqa: BLE001
                err.append(e)
            finally:
                q.close()

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                if _obs.enabled():
                    with _obs.scoped_timer("dataloader.wait_seconds"):
                        item = q.pop()
                else:
                    item = q.pop()
                if item is _native.BlockingQueue.CLOSED:
                    break
                yield item
        finally:
            q.close()
        if err:
            raise err[0]


class SubsetRandomSampler(Sampler):
    """Sample the given indices in random order (reference:
    paddle.io.SubsetRandomSampler)."""

    def __init__(self, indices):
        self.indices = list(indices)

    def __iter__(self):
        import numpy as np

        from ..core.random import default_generator
        import jax

        key = default_generator.split_key()
        perm = np.asarray(jax.random.permutation(key, len(self.indices)))
        return iter([self.indices[int(i)] for i in perm])

    def __len__(self):
        return len(self.indices)


class ConcatDataset(Dataset):
    """Concatenation of map-style datasets (reference: io.ConcatDataset)."""

    def __init__(self, datasets):
        self.datasets = list(datasets)
        if not self.datasets:
            raise ValueError("ConcatDataset needs at least one dataset")
        self.cumulative_sizes = []
        total = 0
        for d in self.datasets:
            total += len(d)
            self.cumulative_sizes.append(total)

    def __len__(self):
        return self.cumulative_sizes[-1]

    def __getitem__(self, idx):
        n = len(self)
        if idx < 0:
            if idx < -n:
                raise IndexError(
                    f"index {idx} out of range for ConcatDataset of "
                    f"length {n}")
            idx += n
        elif idx >= n:
            raise IndexError(
                f"index {idx} out of range for ConcatDataset of length {n}")
        import bisect
        ds_idx = bisect.bisect_right(self.cumulative_sizes, idx)
        prev = 0 if ds_idx == 0 else self.cumulative_sizes[ds_idx - 1]
        return self.datasets[ds_idx][idx - prev]
