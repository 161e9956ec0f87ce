"""DataLoader with multiprocess workers.

TPU-native rebuild of ``mxnet.gluon.data.dataloader`` (reference:
python/mxnet/gluon/data/dataloader.py:35-200).

The reference rebuilds NDArrays over POSIX shared memory between workers
(cpu_shared_storage_manager.h); here workers return numpy arrays over
multiprocessing pipes and the main process device_puts the assembled batch —
host→TPU transfer is the same single DMA either way, and JAX's async
dispatch overlaps it with compute.
"""
from __future__ import annotations

import multiprocessing
import pickle

import numpy as np

from ... import ndarray as nd
from ...ndarray.ndarray import NDArray
from . import sampler as _sampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference: dataloader.py:82)."""
    if isinstance(data[0], NDArray):
        return nd.stack(*data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    out = np.asarray(data)
    return nd.array(out)


def _np_batchify(data):
    """numpy-only batchify for workers: no JAX device ops in the forked
    child (the parent's JAX runtime is multi-threaded; device work in a
    forked child can deadlock)."""
    first = data[0]
    if isinstance(first, NDArray):
        return np.stack([np.asarray(d.asnumpy()) for d in data])
    if isinstance(first, tuple):
        return [_np_batchify(list(col)) for col in zip(*data)]
    return np.asarray(data)


def _reopen_record_files(obj, _depth=0):
    """Reopen RecordIO handles after fork: dup'd fds share one file offset
    across processes, so concurrent seek/read would race (the reference
    avoids this with per-worker handles via pickling, recordio.py:87)."""
    from ... import recordio as _recordio
    if _depth > 4:
        return
    if isinstance(obj, _recordio.MXRecordIO):
        if obj.is_open:
            obj.close()
            obj.open()
        return
    for attr in ("_record", "_data", "_dataset"):
        child = getattr(obj, attr, None)
        if child is not None:
            _reopen_record_files(child, _depth + 1)


def _worker_loop(dataset, key_queue, data_queue, batchify_fn):
    """(reference: dataloader.py:104)"""
    _reopen_record_files(dataset)
    while True:
        idx, samples = key_queue.get()
        if idx is None:
            break
        try:
            if batchify_fn is default_batchify_fn:
                batch = _np_batchify([dataset[i] for i in samples])
            else:
                batch = batchify_fn([dataset[i] for i in samples])
                if isinstance(batch, NDArray):
                    batch = batch.asnumpy()
                elif isinstance(batch, (list, tuple)):
                    batch = [b.asnumpy() if isinstance(b, NDArray) else b
                             for b in batch]
            data_queue.put((idx, batch, None))
        except Exception as e:  # surface worker errors to the main process
            data_queue.put((idx, None, str(e)))


class DataLoader:
    """Loads data from a Dataset in mini-batches (reference:
    dataloader.py:35)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                if shuffle:
                    sampler = _sampler.RandomSampler(len(dataset))
                else:
                    sampler = _sampler.SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler "
                                 "is specified")
            batch_sampler = _sampler.BatchSampler(
                sampler, batch_size, last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError("batch_size, shuffle, sampler and last_batch "
                             "must not be specified if batch_sampler is "
                             "specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._batchify_fn = batchify_fn if batchify_fn is not None \
            else default_batchify_fn

    def __iter__(self):
        if self._num_workers == 0:
            for batch in self._batch_sampler:
                yield self._batchify_fn([self._dataset[i] for i in batch])
            return
        yield from self._multi_worker_iter()

    def _refuse_device_samples(self, batches):
        """One process per chip: a forked worker cannot read an array
        that lives on the accelerator its parent holds — on a TPU it
        hangs in the first index (observed on a v5e; numpy-backed
        datasets fork fine there). Probe one sample here, in the
        parent, and turn the hang into an error. On a CPU backend the
        fork can read the parent's arrays and nothing is checked."""
        import jax
        if jax.default_backend() == "cpu" or not batches:
            return
        sample = self._dataset[batches[0][0]]
        leaves = sample if isinstance(sample, (tuple, list)) else (sample,)
        if any(isinstance(x, NDArray) for x in leaves):
            from ...base import MXNetError
            raise MXNetError(
                "DataLoader(num_workers>0): the dataset yields device "
                "arrays, and a forked worker cannot touch the "
                f"{jax.default_backend()} device this process holds. "
                "Build the dataset from numpy arrays (workers return "
                "numpy batches anyway), or use num_workers=0")

    def _multi_worker_iter(self):
        """Pipelined workers: keep 2x workers batches in flight, yield in
        order (reference: dataloader.py:143 _MultiWorkerIter)."""
        batches = list(self._batch_sampler)
        self._refuse_device_samples(batches)
        ctx = multiprocessing.get_context("fork")
        key_queue = ctx.Queue()
        data_queue = ctx.Queue(2 * self._num_workers)
        workers = []
        for _ in range(self._num_workers):
            w = ctx.Process(target=_worker_loop,
                            args=(self._dataset, key_queue, data_queue,
                                  self._batchify_fn), daemon=True)
            w.start()
            workers.append(w)
        try:
            sent = 0
            rcvd = 0
            buf = {}
            for i in range(min(2 * self._num_workers, len(batches))):
                key_queue.put((i, batches[i]))
                sent += 1
            while rcvd < len(batches):
                while rcvd not in buf:
                    idx, batch, err = data_queue.get()
                    if err is not None:
                        raise RuntimeError(f"DataLoader worker error: {err}")
                    buf[idx] = batch
                batch = buf.pop(rcvd)
                rcvd += 1
                if sent < len(batches):
                    key_queue.put((sent, batches[sent]))
                    sent += 1
                if isinstance(batch, np.ndarray):
                    yield nd.array(batch)
                elif isinstance(batch, (list, tuple)):
                    yield [nd.array(b) if isinstance(b, np.ndarray) else b
                           for b in batch]
                else:
                    yield batch
        finally:
            for _ in workers:
                key_queue.put((None, None))
            for w in workers:
                w.join(timeout=5)
                if w.is_alive():
                    w.terminate()

    def __len__(self):
        return len(self._batch_sampler)
