"""Async host data pipeline: multi-worker decode, double-buffered staging.

The reference hides input latency behind a 6k-LoC C++ ``src/io/`` layer —
``dmlc::ThreadedIter`` prefetch threads feeding a multithreaded RecordIO
decode pool (iter_image_recordio_2.cc). This module is that layer's
TPU-native replacement, built over any Python :class:`~mxnet_tpu.io.
DataIter` (and over RecordIO shards directly):

    source thread ──(ordinal, batch)──► bounded work queue
        │ one thread drives the base iterator: ORDER IS ASSIGNED HERE
    worker threads (N) ── transform/decode ──► done queue (unordered)
    stager thread ── reorder by ordinal, jax.device_put ──► staged queue
        │ ``stage_ahead`` slots: the NEXT batch is on device before the
        │ current step retires (double buffering)
    consumer ``next()`` ── pops a staged, already-on-device DataBatch

Determinism is structural, not best-effort: ordinals are assigned by the
single source thread and the stager re-emits strictly in ordinal order,
so the batch stream is **byte-identical** to the unpipelined iterator for
any worker count (pinned in tests/test_data_pipeline.py). The transform
must be pure (no ambient RNG) — per-epoch shuffling belongs to the
source (``RecordIOSource`` seeds ``seed + epoch``).

The whole pipeline exposes the checkpointable-cursor protocol
(``get_state()``/``set_state()``: epoch, consumed-batch ordinal, the
base iterator's epoch-start state) that ``CheckpointManager`` persists,
so ``fit(auto_resume=True)`` restores the *data* position bit-for-bit —
a mid-epoch kill resumes at the exact next batch, never skipping or
replaying one. Worker failures (including the ``data_worker`` fault
site) surface at ``next()``; shutdown joins every thread and can never
hang on a full queue (``data/workers.py``, also registered atexit).
"""
from __future__ import annotations

import copy
import queue
import threading

import numpy as np

from ..io import DataBatch, DataDesc, DataIter
from ..telemetry import registry as _treg
from ..telemetry import trace as _trace
from . import workers as wk
from .report import register_pipeline

__all__ = ["DataPipeline", "RecordIOSource", "from_recordio",
           "maybe_wrap_for_fit"]

_EOE = object()          # end-of-epoch token


def _cfg(name, override):
    from .. import config
    return int(config.get(name)) if override is None else int(override)


class RecordIOSource(DataIter):
    """Shard-aware RecordIO batch source: yields DataBatches of RAW record
    bytes; decoding happens in the pipeline's worker threads (the split
    the reference's C++ iterators use — one reader, N decoders).

    Per-host sharding rides the ``parallel/dist`` rank: by default this
    process reads ``keys[rank::world_size]``, so a multi-host
    data-parallel job feeds each host a disjoint shard (reference:
    ``num_parts``/``part_index`` on every C++ iterator). Epoch shuffling
    is seeded ``seed + epoch`` — deterministic for checkpoint resume,
    different every epoch. ``reset()`` ADVANCES to the next epoch
    (fit-loop semantics), unlike plain iterators that rewind.
    """

    def __init__(self, path_imgrec, path_imgidx=None, batch_size=32,
                 shuffle=False, seed=0, num_parts=None, part_index=None):
        super().__init__(batch_size)
        import os
        from .. import recordio
        from ..parallel import dist
        self._path = path_imgrec
        idx_path = path_imgidx or os.path.splitext(path_imgrec)[0] + ".idx"
        self._rec = recordio.MXIndexedRecordIO(idx_path, path_imgrec, "r")
        if num_parts is None:
            num_parts = dist.world_size()
        if part_index is None:
            part_index = dist.rank()
        if not 0 <= part_index < num_parts:
            raise ValueError(f"part_index {part_index} outside "
                             f"[0, {num_parts})")
        self.num_parts = int(num_parts)
        self.part_index = int(part_index)
        self._keys = list(self._rec.keys)[part_index::num_parts]
        self.shuffle = bool(shuffle)
        self.seed = int(seed)
        self.epoch = 0
        self._pos = 0                       # next batch ordinal this epoch
        self.num_batches = len(self._keys) // batch_size   # tail discarded
        if self.num_batches == 0:
            raise ValueError(
                f"shard {part_index}/{num_parts} of {path_imgrec} holds "
                f"{len(self._keys)} records < batch_size {batch_size}")
        self._order = self._epoch_order()
        self.provide_data = None            # raw bytes: decoder knows
        self.provide_label = None

    def _epoch_order(self):
        order = np.arange(len(self._keys))
        if self.shuffle:
            np.random.RandomState(self.seed + self.epoch).shuffle(order)
        return order

    def reset(self):
        self.epoch += 1
        self._pos = 0
        self._order = self._epoch_order()

    def skip_batches(self, n):
        """Random-access fast-forward (no record reads) — the pipeline's
        checkpoint resume seeks instead of replay-and-discard."""
        self._pos = min(self._pos + int(n), self.num_batches)

    def next(self):
        if self._pos >= self.num_batches:
            raise StopIteration
        lo = self._pos * self.batch_size
        recs = [self._rec.read_idx(self._keys[int(i)])
                for i in self._order[lo:lo + self.batch_size]]
        self._pos += 1
        return DataBatch(data=[recs], label=None, pad=0)

    # -- checkpointable cursor -------------------------------------------------
    def get_state(self):
        return {"epoch": int(self.epoch), "pos": int(self._pos),
                "seed": self.seed, "shuffle": self.shuffle,
                "num_parts": self.num_parts,
                "part_index": self.part_index}

    def set_state(self, state):
        if not isinstance(state, dict) or "pos" not in state:
            raise ValueError(
                "not a RecordIOSource cursor (missing 'pos'; got keys "
                f"{sorted(state) if isinstance(state, dict) else state})")
        if state.get("num_parts", self.num_parts) != self.num_parts or \
                state.get("part_index", self.part_index) != self.part_index:
            raise ValueError(
                "RecordIOSource cursor was saved for shard "
                f"{state.get('part_index')}/{state.get('num_parts')} but "
                f"this source reads {self.part_index}/{self.num_parts}")
        # seed/shuffle DEFINE the saved stream: restore them from the
        # cursor (like NDArrayIter restores its permutation) so a
        # restart script constructed with different values still replays
        # the exact saved order instead of silently diverging
        self.seed = int(state.get("seed", self.seed))
        self.shuffle = bool(state.get("shuffle", self.shuffle))
        self.epoch = int(state.get("epoch", 0))
        self._order = self._epoch_order()
        self._pos = int(state.get("pos", 0))

    def close(self):
        self._rec.close()


def _default_record_decoder(data_shape, dtype, data_name, label_name):
    """records(bytes) -> DataBatch of arrays: ``recordio.unpack`` each
    record, ``np.frombuffer`` the payload into ``data_shape``. Pure —
    safe for any worker count."""
    from .. import ndarray as nd
    from .. import recordio

    def _decode(batch):
        datas, labels = [], []
        for rec in batch.data[0]:
            header, payload = recordio.unpack(rec)
            arr = np.frombuffer(payload, dtype=dtype)
            datas.append(arr.reshape(data_shape))
            lab = header.label
            labels.append(np.asarray(lab, np.float32).reshape(-1)[0]
                          if not np.isscalar(lab) else np.float32(lab))
        return DataBatch(
            data=[nd.array(np.stack(datas))],
            label=[nd.array(np.asarray(labels, np.float32))],
            pad=batch.pad, index=batch.index)

    return _decode


class DataPipeline(DataIter):
    """See module docstring. Wraps ``base_iter`` (any DataIter); with
    ``transform`` the decode/augment work runs on ``num_workers`` threads;
    staged batches are placed on device (``jax.device_put``, optionally
    pre-sharded via ``sharding``) ``stage_ahead`` batches ahead of the
    consumer. ``own_base=True`` closes the base with the pipeline."""

    def __init__(self, base_iter, transform=None, num_workers=None,
                 queue_depth=None, stage_ahead=None, stage_device=True,
                 sharding=None, provide_data=None, provide_label=None,
                 own_base=False, name="pipeline"):
        super().__init__(getattr(base_iter, "batch_size", 0))
        self._base = base_iter
        self._transform = transform
        self._num_workers = max(1, _cfg("MXTPU_DATA_WORKERS", num_workers))
        self._queue_depth = max(1, _cfg("MXTPU_DATA_QUEUE_DEPTH",
                                        queue_depth))
        self._stage_ahead = max(1, _cfg("MXTPU_DATA_STAGE_AHEAD",
                                        stage_ahead))
        self._stage_device = bool(stage_device)
        self._sharding = sharding
        self._provide_data = provide_data
        self._provide_label = provide_label
        self._own_base = own_base
        self.name = name
        self._group = None
        self._q_work = self._q_done = self._q_out = None
        self._epoch = 0
        self._consumed = 0          # batches handed to the consumer
        self._skip = 0              # batches to discard on next start
        self._base_epoch_state = self._snap_base_state()
        self._closed = False
        self._current = None
        self._slock = threading.Lock()
        self._zero_stats()
        self._trace_id = None       # fit's trace (set_trace): stage
        self._trace_parent = None   # spans link to the run-root span
        register_pipeline(self)
        wk.register_closeable(self)

    # -- DataIter surface ------------------------------------------------------
    @property
    def provide_data(self):
        return self._provide_data if self._provide_data is not None \
            else self._base.provide_data

    @property
    def provide_label(self):
        return self._provide_label if self._provide_label is not None \
            else self._base.provide_label

    def __getattr__(self, nm):
        # transparent passthrough (default_bucket_key and friends) so the
        # pipeline drops into any fit loop the base iterator served
        if nm.startswith("_"):
            raise AttributeError(nm)
        base = self.__dict__.get("_base")
        if base is None:
            raise AttributeError(nm)
        return getattr(base, nm)

    # -- stats -----------------------------------------------------------------
    def _zero_stats(self):
        self._wait_s = 0.0
        self._waits = 0
        self._next_calls = 0
        self._source_busy_s = 0.0
        self._decode_busy_s = 0.0
        self._stage_busy_s = 0.0
        self._batches_decoded = 0
        self._items_decoded = 0
        self._batches_staged = 0

    def stats(self, reset=False):
        """Counter snapshot for ``mx.data_report()`` (no device sync)."""
        with self._slock:
            out = {
                "name": self.name,
                "epoch": self._epoch,
                "consumed": self._consumed,
                "workers": self._num_workers,
                "queue_depth": self._queue_depth,
                "stage_ahead": self._stage_ahead,
                "queues": {
                    "work": self._q_work.qsize() if self._q_work else 0,
                    "done": self._q_done.qsize() if self._q_done else 0,
                    "staged": self._q_out.qsize() if self._q_out else 0,
                },
                "wait_s": round(self._wait_s, 6),
                "waits": self._waits,
                "next_calls": self._next_calls,
                "starvation_fraction": round(
                    self._waits / self._next_calls, 6)
                if self._next_calls else 0.0,
                "source_busy_s": round(self._source_busy_s, 6),
                "decode_busy_s": round(self._decode_busy_s, 6),
                "stage_busy_s": round(self._stage_busy_s, 6),
                "batches_decoded": self._batches_decoded,
                "items_decoded": self._items_decoded,
                "batches_staged": self._batches_staged,
                "decode_items_s": round(
                    self._items_decoded / self._decode_busy_s, 2)
                if self._decode_busy_s > 0 else None,
            }
            if reset:
                self._zero_stats()
        return out

    def _acc(self, field, dt):
        with self._slock:
            setattr(self, field, getattr(self, field) + dt)

    # -- structured tracing ----------------------------------------------------
    def set_trace(self, trace_id, parent_id=None):
        """Adopt the caller's trace (fit() hands its StepTimeline trace
        id here): stage spans recorded on the pipeline's own threads
        carry it, so Chrome-trace viewers show source/decode/stage work
        in the same trace tree as the training steps it fed."""
        self._trace_id = trace_id
        self._trace_parent = parent_id

    def _stage_span(self, name, kind="work", **args):
        """One interval of a stage: the profiler table's
        ``data::<name>`` row always (not for the consumer's wait, whose
        aggregate is ``data::wait_s``), and while tracing is on the
        ``data:<name>`` span on the trace ``fit()`` handed over."""
        return _trace.span(
            f"data:{name}", "data", kind=kind, trace=self._trace_id,
            parent=self._trace_parent, args=args or None,
            agg=False if kind == "wait"
            else _treg.timer(f"prof::data::{name}").record)

    # -- stage threads ---------------------------------------------------------
    def _start_stream(self):
        if self._closed:
            raise RuntimeError(f"DataPipeline '{self.name}' is closed")
        self._q_work = queue.Queue(maxsize=self._queue_depth)
        self._q_done = queue.Queue(
            maxsize=self._queue_depth + self._num_workers)
        self._q_out = queue.Queue(maxsize=self._stage_ahead)
        g = self._group = wk.WorkerGroup(f"data-{self.name}")
        skip, self._skip = self._skip, 0
        g.spawn(self._source_loop, g, skip, name=f"data-{self.name}-source")
        for i in range(self._num_workers):
            g.spawn(self._worker_loop, g, i,
                    name=f"data-{self.name}-worker{i}")
        g.spawn(self._stager_loop, g, name=f"data-{self.name}-stager")

    def _source_loop(self, group, skip):
        ordinal = 0
        while not group.stopped:
            with self._stage_span("source", ordinal=ordinal) as sp:
                try:
                    batch = self._base.next()
                except StopIteration:
                    break
            self._acc("_source_busy_s", sp.dur)
            if skip > 0:       # checkpoint resume: replay to the cursor
                skip -= 1
                continue
            if not wk.q_put(self._q_work, (ordinal, batch), group):
                return
            ordinal += 1
        for _ in range(self._num_workers):
            wk.q_put(self._q_work, _EOE, group)

    def _worker_loop(self, group, widx):
        from .. import faultinject
        while not group.stopped:
            ok, item = wk.q_get(self._q_work, group)
            if not ok:
                return
            if item is _EOE:
                wk.q_put(self._q_done, _EOE, group)
                return
            ordinal, batch = item
            # deterministic fault site: 'data_worker:batch=B' kills (or
            # raises in) the worker decoding the B-th batch (1-based) —
            # the chaos suites' dying-input-worker drill
            if faultinject.active("data_worker") is not None and \
                    faultinject.fire("data_worker", batch=ordinal + 1,
                                     worker=widx):
                raise faultinject.FaultInjected(
                    "data_worker", batch=ordinal + 1, worker=widx)
            with self._stage_span("decode", ordinal=ordinal,
                                  worker=widx) as sp:
                if self._transform is not None:
                    batch = self._transform(batch)
            n_items = self.batch_size or (
                len(batch.data[0]) if batch.data else 0)
            with self._slock:
                self._decode_busy_s += sp.dur
                self._batches_decoded += 1
                self._items_decoded += n_items
            wk.q_put(self._q_done, (ordinal, batch), group)

    def _stager_loop(self, group):
        pending = {}
        next_ord = 0
        eoes = 0
        while not group.stopped:
            if next_ord in pending:
                batch = self._stage(pending.pop(next_ord))
                if not wk.q_put(self._q_out, batch, group):
                    return
                next_ord += 1
                continue
            if eoes >= self._num_workers:
                if pending:
                    group.fail(RuntimeError(
                        f"data pipeline '{self.name}' lost batch "
                        f"{next_ord} (have {sorted(pending)})"))
                    return
                wk.q_put(self._q_out, _EOE, group)
                return
            ok, item = wk.q_get(self._q_done, group)
            if not ok:
                return
            if item is _EOE:
                eoes += 1
                continue
            pending[item[0]] = item[1]

    def _stage(self, batch):
        """device_put the batch arrays (async dispatch — the transfer
        overlaps the consumer's current step); the original batch object
        is never mutated."""
        if not self._stage_device:
            return batch
        with self._stage_span("stage") as sp:
            staged = copy.copy(batch)
            if batch.data is not None:
                staged.data = [self._put(a) for a in batch.data]
            if batch.label:
                staged.label = [self._put(a) for a in batch.label]
        with self._slock:
            self._stage_busy_s += sp.dur
            self._batches_staged += 1
        return staged

    def _put(self, arr):
        from ..ndarray.ndarray import NDArray, _wrap
        if not isinstance(arr, NDArray):
            return arr          # raw payloads (bytes/numpy) pass through
        try:
            import jax
            data, target = arr._data, self._sharding
            if target is not None and len(target.device_set) == 1 and \
                    data.devices() == target.device_set:
                # already on the bind's one device: handed on as it is
                # (a put would commit it, and every eager program that
                # takes it afterwards would be built again)
                return arr
            dev = jax.device_put(data, target) \
                if target is not None else jax.device_put(data)
            return _wrap(dev, arr._ctx)
        except Exception:
            return arr

    # -- consumer --------------------------------------------------------------
    def next(self):
        if self._group is None:
            self._start_stream()
        starved = None
        try:
            item = self._q_out.get_nowait()
        except queue.Empty:
            # consumer arrived before the pipeline: blocked from here
            item = None
            with self._stage_span("wait", kind="wait") as starved:
                while item is None:
                    err = self._group.error()
                    if err is not None:
                        self._stop_stream()
                        raise err
                    try:
                        item = self._q_out.get(timeout=0.05)
                    except queue.Empty:
                        continue
        with self._slock:
            self._next_calls += 1
            if starved is not None:
                self._waits += 1
                self._wait_s += starved.dur
        if item is _EOE:
            self._end_of_epoch()
            raise StopIteration
        self._consumed += 1
        self._current = item
        return item

    def _end_of_epoch(self):
        g, self._group = self._group, None
        if g is not None:
            g.stop()
            g.join()
            err = g.error()
            if err is not None:
                raise err

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._current.data

    def getlabel(self):
        return self._current.label

    def getindex(self):
        return self._current.index

    def getpad(self):
        return self._current.pad

    # -- lifecycle -------------------------------------------------------------
    def _stop_stream(self):
        g, self._group = self._group, None
        if g is None:
            return
        g.stop()
        for q in (self._q_work, self._q_done, self._q_out):
            if q is not None:
                wk.q_drain(q)     # unblock producers stuck on full queues
        g.join()
        for q in (self._q_work, self._q_done, self._q_out):
            if q is not None:
                wk.q_drain(q)

    def reset(self):
        """Advance to the next epoch (fit-loop semantics): stop the
        stream, reset the base iterator, re-snapshot its epoch-start
        state for the cursor protocol."""
        self._stop_stream()
        self._base.reset()
        self._epoch += 1
        self._consumed = 0
        self._skip = 0
        self._base_epoch_state = self._snap_base_state()

    def close(self):
        """Join every pipeline thread; idempotent, also run atexit —
        interrupted runs never hang on a full queue."""
        self._closed = True
        self._stop_stream()
        if self._own_base:
            try:
                self._base.close()
            except Exception:
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- checkpointable cursor -------------------------------------------------
    def _snap_base_state(self):
        fn = getattr(self._base, "get_state", None)
        if callable(fn):
            try:
                return fn()
            except Exception:
                return None
        return None

    def get_state(self):
        """Deterministic resume cursor: epoch ordinal, CONSUMED batch
        count (not the read-ahead position — the source thread runs
        ahead of the consumer), and the base iterator's epoch-START
        state. ``set_state`` replays the base to the cursor, so resume
        hands out exactly the batches an uninterrupted run would."""
        return {"epoch": int(self._epoch),
                "batch": int(self._consumed),
                "base": self._base_epoch_state}

    def set_state(self, state):
        if not isinstance(state, dict) or "batch" not in state:
            raise ValueError(
                "not a DataPipeline cursor (missing 'batch'; got keys "
                f"{sorted(state) if isinstance(state, dict) else state}) "
                "— was this checkpoint saved under a different "
                "MXTPU_DATA_PIPELINE setting?")
        self._stop_stream()
        # restore the BASE first: if its cursor is refused (the loud
        # ValueError path fit's auto-resume survives), the pipeline's
        # own counters stay untouched — a half-applied cursor here would
        # poison every subsequent epoch-end checkpoint
        base_state = state.get("base")
        setter = getattr(self._base, "set_state", None)
        if base_state is not None and callable(setter):
            setter(base_state)
            new_epoch_state = base_state
        else:
            self._base.reset()
            new_epoch_state = self._snap_base_state()
        self._base_epoch_state = new_epoch_state
        self._epoch = int(state.get("epoch", 0))
        self._consumed = int(state.get("batch", 0))
        self._skip = self._consumed
        # seekable sources (RecordIOSource, NDArrayIter) jump straight
        # to the cursor; the read-and-discard replay in _source_loop is
        # only for iterators that can't seek
        skipper = getattr(self._base, "skip_batches", None)
        if self._skip and callable(skipper):
            skipper(self._skip)
            self._skip = 0


def from_recordio(path_imgrec, data_shape, batch_size, path_imgidx=None,
                  shuffle=False, seed=0, dtype="float32", num_parts=None,
                  part_index=None, decode_fn=None, data_name="data",
                  label_name="softmax_label", num_workers=None,
                  queue_depth=None, stage_ahead=None, sharding=None,
                  name="recordio"):
    """RecordIO shards straight into the pipeline: a shard-aware
    :class:`RecordIOSource` (per-host shard picked from the dist rank)
    feeding ``num_workers`` decode threads. ``decode_fn`` maps a raw
    record batch to an array DataBatch; the default unpacks
    ``recordio.pack`` payloads of ``data_shape``/``dtype``."""
    src = RecordIOSource(path_imgrec, path_imgidx=path_imgidx,
                         batch_size=batch_size, shuffle=shuffle, seed=seed,
                         num_parts=num_parts, part_index=part_index)
    decode = decode_fn or _default_record_decoder(
        tuple(data_shape), np.dtype(dtype), data_name, label_name)
    provide_data = [DataDesc(data_name, (batch_size,) + tuple(data_shape),
                             np.dtype(dtype))]
    provide_label = [DataDesc(label_name, (batch_size,), np.float32)]
    return DataPipeline(src, transform=decode, num_workers=num_workers,
                        queue_depth=queue_depth, stage_ahead=stage_ahead,
                        sharding=sharding, provide_data=provide_data,
                        provide_label=provide_label, own_base=True,
                        name=name)


def maybe_wrap_for_fit(train_data, module=None):
    """``fit``'s auto-on hook (``MXTPU_DATA_PIPELINE``: 1/auto = wrap,
    0 = off). Returns ``(iter, owned_pipeline_or_None)`` — the caller
    closes an owned pipeline when training ends. Wrapping preserves the
    batch stream byte-for-byte (identity transform, ordinal reordering),
    adds read-ahead + device staging, and makes any iterator's cursor
    checkpointable at the pipeline level."""
    from .. import config
    flag = str(config.get("MXTPU_DATA_PIPELINE")).lower()
    if flag in ("0", "false", "off"):
        return train_data, None
    if isinstance(train_data, DataPipeline) or \
            not isinstance(train_data, DataIter):
        return train_data, None
    sharding = None
    fused = getattr(module, "_fused", None)
    if fused is not None:
        try:
            sharding = fused.staging_sharding()
        except Exception:
            sharding = None
    pipe = DataPipeline(train_data, sharding=sharding, name="fit")
    return pipe, pipe
