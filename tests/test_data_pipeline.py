"""Async host data pipeline (mxnet_tpu/data/): determinism, overlap,
cursors, and the chaos drills.

The contract under test (ISSUE 4 acceptance):
- pipeline-on vs pipeline-off batch streams are BYTE-identical for the
  same seed, for any worker count (ordinal reordering, not luck);
- the consumer's step wait-time, measured by the pipeline's own
  counters (not wall-clock), sits strictly below the unpipelined
  baseline (= the source/decode busy time a synchronous loop eats);
- ``get_state``/``set_state`` resume the stream bit-for-bit, including
  through ``CheckpointManager`` after a mid-epoch SIGKILL (chaos);
- worker failures surface at ``next()`` and shutdown always joins the
  pipeline threads (no leaked daemons, no hang on a full queue).
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject
from mxnet_tpu.data import DataPipeline, from_recordio, data_report

WORKER = os.path.join(os.path.dirname(__file__), "data_pipeline_worker.py")
DATA_SHAPE = (2, 4, 4)


def _pipeline_threads():
    return [t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(("data-", "prefetch"))]


def _stream(it):
    out = []
    for b in it:
        lab = b.label[0].asnumpy().tobytes() if b.label else b""
        out.append((b.data[0].asnumpy().tobytes(), lab, b.pad))
    return out


def _make_rec(tmp_path, n=48):
    from mxnet_tpu import recordio
    rec = str(tmp_path / "t.rec")
    w = recordio.MXIndexedRecordIO(str(tmp_path / "t.idx"), rec, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        arr = rng.rand(*DATA_SHAPE).astype(np.float32)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 7), i, 0), arr.tobytes()))
    w.close()
    return rec


# -- determinism --------------------------------------------------------------
def test_byte_identical_stream_pipeline_on_vs_off():
    d = np.arange(200.0).reshape(50, 4).astype(np.float32)
    l = np.arange(50).astype(np.float32)
    ref = _stream(mx.io.NDArrayIter(d, l, 8, last_batch_handle="pad"))
    pipe = DataPipeline(mx.io.NDArrayIter(d, l, 8, last_batch_handle="pad"),
                        num_workers=3, name="ab")
    got = _stream(pipe)
    assert got == ref                      # bytes, pads, count — identical
    pipe.reset()                           # epoch 2 replays the same data
    assert _stream(pipe) == ref
    pipe.close()
    assert not _pipeline_threads()


def test_determinism_across_worker_counts(tmp_path):
    rec = _make_rec(tmp_path)
    streams = []
    for workers in (1, 2, 4):
        p = from_recordio(rec, DATA_SHAPE, 4, shuffle=True, seed=9,
                          num_workers=workers, name=f"w{workers}")
        streams.append(_stream(p))
        p.close()
    assert streams[0] == streams[1] == streams[2]
    assert len(streams[0]) == 12


def test_epochs_reshuffle_deterministically(tmp_path):
    rec = _make_rec(tmp_path)
    p = from_recordio(rec, DATA_SHAPE, 4, shuffle=True, seed=9,
                      num_workers=2)
    e0 = _stream(p)
    p.reset()
    e1 = _stream(p)
    p.close()
    assert e0 != e1, "per-epoch reshuffle missing"

    def _records(stream):          # batch bytes -> sorted record chunks
        rec_bytes = int(np.prod(DATA_SHAPE)) * 4
        out = []
        for data, _, _ in stream:
            out.extend(data[i:i + rec_bytes]
                       for i in range(0, len(data), rec_bytes))
        return sorted(out)

    assert _records(e0) == _records(e1), \
        "epochs must cover the same records"
    p2 = from_recordio(rec, DATA_SHAPE, 4, shuffle=True, seed=9,
                       num_workers=3)
    assert _stream(p2) == e0, "seed+epoch shuffle must be reproducible"
    p2.close()


def test_fit_params_bit_identical_pipeline_on_vs_off():
    def train(flag):
        with mx.config.override("MXTPU_DATA_PIPELINE", flag):
            mx.random.seed(3)
            np.random.seed(3)
            d = np.random.RandomState(7).rand(64, 10).astype(np.float32)
            l = (d.sum(axis=1) > 5).astype(np.float32)
            it = mx.io.NDArrayIter(d, l, 8, shuffle=True)
            net = mx.sym.SoftmaxOutput(
                mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                      name="fc"), name="softmax")
            mod = mx.mod.Module(net, context=mx.cpu())
            mod.fit(it, num_epoch=2, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1},
                    initializer=mx.init.Xavier())
            arg, _ = mod.get_params()
            return {k: v.asnumpy().tobytes() for k, v in arg.items()}

    assert train("1") == train("0")
    assert not _pipeline_threads(), "fit must close the pipeline it made"


# -- staging on a single-device bind ------------------------------------------
def test_single_device_bind_names_its_device_for_the_stager():
    """fit()'s stager learns where the state lives: a bare device_put
    would leave a batch committed to another backend where it is, and
    the step would copy it inside every dispatch."""
    import jax
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(), fused=True)
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd")
    assert mod._fused is not None and mod._fused.mesh is None
    home = mod._fused._pvals[0].devices()
    assert len(home) == 1
    sharding = mod._fused.staging_sharding()
    assert sharding.device_set == home
    it = mx.io.NDArrayIter(np.zeros((16, 10), np.float32),
                           np.zeros((16,), np.float32), 8)
    from mxnet_tpu.data import maybe_wrap_for_fit
    pipe, owned = maybe_wrap_for_fit(it, mod)
    try:
        assert pipe is owned and pipe._sharding.device_set == home
        # a batch that lives elsewhere is staged to the bind's device
        (away,) = [d for d in jax.devices() if d not in home][:1]
        off = mx.nd.NDArray(jax.device_put(np.ones((8, 10), np.float32),
                                           away))
        assert pipe._put(off)._data.devices() == home
    finally:
        pipe.close()


def test_stager_hands_on_a_batch_already_on_the_device():
    """A batch that is where the bind is goes through untouched: a put
    would commit it, and the eager programs that take it afterwards
    (the benchmark's device-resident ring) would be built again."""
    import jax
    from jax.sharding import SingleDeviceSharding
    here, there = jax.devices()[0], jax.devices()[1]
    pipe = DataPipeline(mx.io.NDArrayIter(np.zeros((8, 4), np.float32),
                                          None, 8),
                        sharding=SingleDeviceSharding(here), name="asis")
    try:
        on = mx.nd.NDArray(jax.device_put(np.ones((8, 4), np.float32),
                                          here))
        off = mx.nd.NDArray(jax.device_put(np.ones((8, 4), np.float32),
                                           there))
        assert pipe._put(on) is on
        moved = pipe._put(off)
        assert moved is not off and moved._data.devices() == {here}
        np.testing.assert_array_equal(moved.asnumpy(), off.asnumpy())
    finally:
        pipe.close()


# -- overlap / observability --------------------------------------------------
class _SlowSource(mx.io.DataIter):
    """Deterministic iterator with a real per-batch production cost."""

    def __init__(self, nbatch=12, cost_s=0.008, batch=4):
        super().__init__(batch)
        self.provide_data = [mx.io.DataDesc("data", (batch, 3))]
        self.provide_label = [mx.io.DataDesc("softmax_label", (batch,))]
        self._n, self._cost, self._i = nbatch, cost_s, 0

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= self._n:
            raise StopIteration
        time.sleep(self._cost)
        i = self._i
        self._i += 1
        return mx.io.DataBatch(
            [mx.nd.array(np.full((self.batch_size, 3), i, np.float32))],
            [mx.nd.array(np.full((self.batch_size,), i, np.float32))],
            pad=0)


def test_step_wait_strictly_below_unpipelined_baseline():
    """The acceptance pin, by the pipeline's OWN counters: with the
    consumer doing step work between ``next()`` calls, its measured
    blocked time must fall strictly (here: 2x) below the unpipelined
    baseline — the source busy time a synchronous loop would eat on
    every batch."""
    pipe = DataPipeline(_SlowSource(nbatch=12, cost_s=0.008),
                        num_workers=2, name="overlap")
    for _ in pipe:
        time.sleep(0.008)          # the consumer's "train step"
    s = pipe.stats()
    pipe.close()
    assert s["next_calls"] == 13   # 12 batches + the end-of-epoch call
    assert s["source_busy_s"] > 0.05
    # unpipelined, the consumer waits the full production cost of every
    # batch; overlapped, it should wait for little beyond batch 0
    assert s["wait_s"] < 0.5 * s["source_busy_s"], s


def test_starvation_counter_pinned_under_slow_producer():
    pipe = DataPipeline(_SlowSource(nbatch=10, cost_s=0.01),
                        num_workers=1, name="starved")
    for _ in pipe:
        pass                       # consumer faster than the source
    s = pipe.stats()
    pipe.close()
    assert s["waits"] > 0
    assert s["starvation_fraction"] > 0.5, s   # input-bound, and it shows


def test_pipeline_runs_ahead_of_slow_consumer():
    """Artificially slow consumer: the stage queue fills ahead of it
    (double buffering visible), the wait counter stays >0 only for the
    spin-up batch, and staged batches are already device arrays."""
    import jax
    pipe = DataPipeline(_SlowSource(nbatch=8, cost_s=0.0), num_workers=2,
                        stage_ahead=2, name="ahead")
    depths = []
    first = next(pipe)
    assert isinstance(first.data[0]._data, jax.Array)   # staged on device
    for _ in range(4):
        time.sleep(0.03)           # slow step: pipeline gets ahead
        depths.append(pipe.stats()["queues"]["staged"])
        next(pipe)
    s = pipe.stats()
    pipe.close()
    assert max(depths) >= 1, depths    # next batch staged before needed
    # a pipeline that keeps ahead of a slow consumer is NOT input-bound,
    # and the starvation gauge must say so (at most the spin-up batch)
    assert s["starvation_fraction"] <= 0.5, s


def test_data_report_aggregates_live_pipelines():
    pipe = DataPipeline(_SlowSource(nbatch=4, cost_s=0.0), name="report-me")
    _stream(pipe)
    rep = data_report()
    assert "report-me" in rep["pipelines"]
    me = rep["pipelines"]["report-me"]
    assert me["batches_decoded"] == 4 and me["batches_staged"] == 4
    assert set(me["queues"]) == {"work", "done", "staged"}
    assert rep["next_calls"] >= 5
    rep2 = data_report(reset=True)
    assert data_report()["pipelines"]["report-me"]["next_calls"] == 0
    assert rep2["starvation_fraction"] >= 0.0
    # headline gauges mirror into profiler counters
    from mxnet_tpu import profiler
    assert "data::wait_s" in profiler.counters()
    pipe.close()


# -- cursor protocol ----------------------------------------------------------
def test_ndarrayiter_state_restores_shuffle_order():
    d = np.arange(120.0).reshape(30, 4).astype(np.float32)
    l = np.arange(30).astype(np.float32)
    np.random.seed(11)
    it = mx.io.NDArrayIter(d, l, 5, shuffle=True)
    ref = _stream(it)
    state = it.get_state()
    np.random.seed(99)             # a fresh process draws another shuffle
    it2 = mx.io.NDArrayIter(d, l, 5, shuffle=True)
    assert _stream(it2) != ref
    it2.set_state(state)
    it2.reset()
    assert _stream(it2) == ref     # permutation + cursor restored


def test_ndarrayiter_state_mid_epoch_cursor():
    d = np.arange(80.0).reshape(20, 4).astype(np.float32)
    it = mx.io.NDArrayIter(d, np.arange(20.0), 4)
    for _ in range(2):
        next(it)
    st = it.get_state()
    rest = _stream(it)
    it2 = mx.io.NDArrayIter(d, np.arange(20.0), 4)
    it2.set_state(st)
    assert _stream(it2) == rest


def test_ndarrayiter_state_shuffle_discard():
    """Regression: 'discard' truncates ``idx`` below the full row count,
    so the cursor must capture the FULL physical permutation — resume of
    a shuffle+discard iterator used to raise (and the remap math read a
    partially-initialized inverse)."""
    d = np.arange(40.0).reshape(10, 4).astype(np.float32)
    l = np.arange(10.0)
    np.random.seed(11)
    it = mx.io.NDArrayIter(d, l, 3, shuffle=True,
                           last_batch_handle="discard")
    ref = _stream(it)
    assert len(ref) == 3               # tail discarded
    st = it.get_state()
    np.random.seed(99)
    it2 = mx.io.NDArrayIter(d, l, 3, shuffle=True,
                            last_batch_handle="discard")
    it2.set_state(st)
    it2.reset()
    assert _stream(it2) == ref
    with pytest.raises(ValueError, match="different dataset"):
        mx.io.NDArrayIter(np.zeros((8, 4), np.float32),
                          np.zeros(8), 3).set_state(st)


def test_ndarrayiter_unshuffled_state_is_compact():
    it = mx.io.NDArrayIter(np.zeros((500, 2), np.float32),
                           np.zeros(500), 10)
    st = it.get_state()
    assert st["order"] is None         # identity order: bytes, not a
    assert st["rows"] == 500           # per-row list in every checkpoint


def test_recordio_cursor_restores_seed_and_shuffle(tmp_path):
    """Regression: the cursor's seed/shuffle must be applied on restore
    — a restart script constructed with a different seed used to replay
    a silently different permutation."""
    rec = _make_rec(tmp_path)
    p = from_recordio(rec, DATA_SHAPE, 4, shuffle=True, seed=7,
                      num_workers=2)
    for _ in range(2):
        next(p)
    st = p.get_state()
    rest_ref = _stream(p)
    p.close()
    p2 = from_recordio(rec, DATA_SHAPE, 4, shuffle=False, seed=0,
                       num_workers=2)
    p2.set_state(st)
    assert _stream(p2) == rest_ref
    p2.close()


def test_resizeiter_refuses_unplaceable_cursor():
    class Stateless(mx.io.DataIter):
        def __init__(self):
            super().__init__(2)
            self.provide_data = [mx.io.DataDesc("data", (2, 2))]
            self.provide_label = []

        def next(self):
            return mx.io.DataBatch([mx.nd.zeros((2, 2))], [], pad=0)

    rit = mx.io.ResizeIter(Stateless(), 5)
    with pytest.raises(NotImplementedError, match="get_state"):
        rit.get_state()
    with pytest.raises(ValueError, match="set_state"):
        rit.set_state({"cur": 2, "inner": {"anything": 1}})


def test_resizeiter_state_roundtrip():
    it = mx.io.NDArrayIter(np.zeros((20, 2)), np.arange(20.0), 5)
    rit = mx.io.ResizeIter(it, 3)
    next(rit)
    st = rit.get_state()
    assert st["cur"] == 1 and st["inner"]["cursor"] == 0
    it2 = mx.io.NDArrayIter(np.zeros((20, 2)), np.arange(20.0), 5)
    rit2 = mx.io.ResizeIter(it2, 3)
    rit2.set_state(st)
    assert _stream(rit2) == _stream(rit)


def test_pipeline_cursor_resumes_mid_epoch(tmp_path):
    rec = _make_rec(tmp_path)
    p = from_recordio(rec, DATA_SHAPE, 4, shuffle=True, seed=5,
                      num_workers=2)
    p.reset()                      # epoch 1: prove the epoch rides along
    for _ in range(3):
        next(p)
    st = p.get_state()
    assert st["epoch"] == 1 and st["batch"] == 3
    rest_ref = _stream(p)
    p.close()
    p2 = from_recordio(rec, DATA_SHAPE, 4, shuffle=True, seed=5,
                       num_workers=4)
    p2.set_state(st)
    assert _stream(p2) == rest_ref     # no skipped, no duplicated batch
    p2.close()


def test_cursor_formats_refuse_cross_application(tmp_path):
    """Regression: a pipeline-shaped cursor applied to a raw NDArrayIter
    (or vice versa — MXTPU_DATA_PIPELINE toggled between save and
    resume) must REFUSE, not silently un-shuffle the dataset by reading
    every missing key's default."""
    d = np.arange(80.0).reshape(20, 4).astype(np.float32)
    np.random.seed(11)
    it = mx.io.NDArrayIter(d, np.arange(20.0), 4, shuffle=True)
    pipe = DataPipeline(mx.io.NDArrayIter(d, np.arange(20.0), 4),
                        name="fmt")
    pipe_state = pipe.get_state()
    it_state = it.get_state()
    before = _stream(it)
    it.reset()
    with pytest.raises(ValueError, match="NDArrayIter cursor"):
        it.set_state(pipe_state)
    it.reset()
    assert _stream(it) == before, "a refused cursor must not mutate rows"
    with pytest.raises(ValueError, match="DataPipeline cursor"):
        pipe.set_state(it_state)
    rec = _make_rec(tmp_path)
    p = from_recordio(rec, DATA_SHAPE, 4)
    with pytest.raises(ValueError, match="RecordIOSource cursor"):
        p._base.set_state(it_state)
    pipe.close()
    p.close()


def test_refused_cursor_leaves_pipeline_state_clean():
    """Regression: a cursor whose INNER restore is refused must not
    half-apply — the pipeline's epoch/consumed counters stay untouched,
    so later epoch-end checkpoints aren't poisoned with a consumed
    count from the dead cursor."""
    d = np.arange(360.0).reshape(90, 4).astype(np.float32)
    pipe = DataPipeline(mx.io.NDArrayIter(d, np.arange(90.0), 10),
                        name="clean")
    before = pipe.get_state()
    bad = {"epoch": 3, "batch": 10,
           "base": {"cursor": 0, "order": None, "rows": 100}}  # 100 != 90
    with pytest.raises(ValueError, match="different dataset"):
        pipe.set_state(bad)
    assert pipe.get_state() == before
    assert len(_stream(pipe)) == 9     # full epoch, nothing skipped
    pipe.close()


def test_seekable_sources_skip_without_replay(tmp_path):
    """skip_batches (the pipeline resume fast path) must land on the
    same position as consuming the batches."""
    it = mx.io.NDArrayIter(np.arange(80.0).reshape(20, 4),
                           np.arange(20.0), 4)
    for _ in range(2):
        next(it)
    ref = _stream(it)
    it2 = mx.io.NDArrayIter(np.arange(80.0).reshape(20, 4),
                            np.arange(20.0), 4)
    it2.skip_batches(2)
    assert _stream(it2) == ref

    from mxnet_tpu.data import RecordIOSource
    rec = _make_rec(tmp_path)
    s1 = RecordIOSource(rec, batch_size=4, shuffle=True, seed=3,
                        num_parts=1, part_index=0)
    for _ in range(3):
        s1.next()
    ref_keys = [s1.next().data[0] for _ in range(2)]
    s2 = RecordIOSource(rec, batch_size=4, shuffle=True, seed=3,
                        num_parts=1, part_index=0)
    s2.skip_batches(3)
    got = [s2.next().data[0] for _ in range(2)]
    assert got == ref_keys
    s1.close()
    s2.close()


def test_fit_auto_resume_survives_pipeline_flag_toggle(tmp_path):
    """A checkpoint saved with the pipeline ON must still auto-resume
    with it OFF: params restore, the un-appliable data cursor is skipped
    with a warning instead of crashing (or corrupting) the job."""
    d = np.random.RandomState(7).rand(48, 6).astype(np.float32)
    l = (d.sum(axis=1) > 3).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    ckdir = str(tmp_path / "ck")
    with mx.config.override("MXTPU_DATA_PIPELINE", "1"):
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(mx.io.NDArrayIter(d, l, 8, shuffle=True),
                num_epoch=1, optimizer="sgd", initializer=mx.init.Xavier(),
                checkpoint_manager=mx.CheckpointManager(ckdir))
    with mx.config.override("MXTPU_DATA_PIPELINE", "0"):
        mod2 = mx.mod.Module(net, context=mx.cpu())
        mod2.fit(mx.io.NDArrayIter(d, l, 8, shuffle=True),
                 num_epoch=2, optimizer="sgd",
                 initializer=mx.init.Xavier(),
                 checkpoint_manager=mx.CheckpointManager(ckdir),
                 auto_resume=True)   # completes; cursor skipped loudly


def test_fit_auto_resume_restores_data_cursor(tmp_path):
    """fit(auto_resume=True) restores the DATA position: the resumed
    job's epoch-1 batch stream equals the uninterrupted run's, even
    though the fresh iterator was shuffled differently."""
    d = np.random.RandomState(7).rand(48, 6).astype(np.float32)
    l = (d.sum(axis=1) > 3).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")

    def run(it, manager, num_epoch, auto_resume=False, begin=0):
        seen = []

        def _cb(param):
            batch = param.locals["data_batch"]
            seen.append(batch.label[0].asnumpy().tobytes())

        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(it, num_epoch=num_epoch, begin_epoch=begin,
                optimizer="sgd", optimizer_params={"learning_rate": 0.1},
                initializer=mx.init.Xavier(), batch_end_callback=_cb,
                checkpoint_manager=manager, auto_resume=auto_resume)
        return seen

    np.random.seed(11)
    ref = run(mx.io.NDArrayIter(d, l, 8, shuffle=True), None, num_epoch=2)

    ckdir = str(tmp_path / "ck")
    np.random.seed(11)
    first = run(mx.io.NDArrayIter(d, l, 8, shuffle=True),
                mx.CheckpointManager(ckdir), num_epoch=1)
    assert first == ref[:len(first)]

    np.random.seed(99)             # "new process": different shuffle
    resumed = run(mx.io.NDArrayIter(d, l, 8, shuffle=True),
                  mx.CheckpointManager(ckdir), num_epoch=2,
                  auto_resume=True)
    assert resumed == ref[len(first):]


# -- chaos --------------------------------------------------------------------
@pytest.mark.chaos
def test_worker_death_surfaces_at_next_and_drains():
    """A decode worker dying mid-epoch must (a) surface its exception at
    the consumer's ``next()`` — never a silent end-of-epoch — and (b)
    leave zero live pipeline threads after close()."""
    d = np.arange(200.0).reshape(50, 4).astype(np.float32)
    pipe = DataPipeline(mx.io.NDArrayIter(d, np.arange(50.0), 5),
                        num_workers=2, name="dying")
    with faultinject.inject("data_worker:batch=4"):
        consumed = 0
        with pytest.raises(faultinject.FaultInjected):
            for _ in pipe:
                consumed += 1
    assert consumed < 10, "the error must cut the epoch short"
    assert faultinject.fired("data_worker") == 1
    pipe.close()
    assert not _pipeline_threads()


@pytest.mark.chaos
def test_prefetching_iter_reraises_worker_error_and_joins():
    class Bad(mx.io.DataIter):
        def __init__(self):
            super().__init__(4)
            self.provide_data = [mx.io.DataDesc("data", (4, 2))]
            self.provide_label = [mx.io.DataDesc("softmax_label", (4,))]
            self.n = 0

        def next(self):
            self.n += 1
            if self.n == 3:
                raise RuntimeError("decoder exploded")
            return mx.io.DataBatch([mx.nd.zeros((4, 2))],
                                   [mx.nd.zeros((4,))], pad=0)

    pit = mx.io.PrefetchingIter(Bad())
    with pytest.raises(RuntimeError, match="decoder exploded"):
        for _ in pit:
            pass
    pit.close()
    pit.close()                    # idempotent
    assert not _pipeline_threads(), "prefetch threads must join on close"


@pytest.mark.chaos
def test_mid_epoch_sigkill_and_resume(tmp_path):
    """The acceptance drill: MXTPU_FAULT_INJECT kills a decode WORKER
    THREAD (whole process, SIGKILL) mid-epoch; resume loads the newest
    valid checkpoint's data cursor and replays the remaining batches
    EXACTLY — the combined stream relative to the checkpoint equals the
    uninterrupted run's, no batch skipped or duplicated."""
    import json

    def _run(args, fault=None):
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                            "MXTPU_FAULT_INJECT")}
        env["JAX_PLATFORMS"] = "cpu"
        if fault is not None:
            env["MXTPU_FAULT_INJECT"] = fault
        return subprocess.run(
            [sys.executable, WORKER, str(tmp_path)] + args,
            capture_output=True, text=True, env=env, timeout=600)

    r0 = _run(["ref.log", "--ref"])
    assert r0.returncode == 0, r0.stdout + r0.stderr
    ref = open(tmp_path / "ref.log").read().splitlines()
    assert len(ref) == 20

    # batch=16 is beyond the pipeline's max read-ahead (~9), so several
    # checkpoints are durably committed before any worker CAN reach the
    # armed ordinal — deterministic, not a race on the first save
    r1 = _run(["crash.log"], fault="data_worker:batch=16:action=kill")
    assert r1.returncode != 0, "killed run must not exit cleanly"
    assert "faultinject: SIGKILL at site 'data_worker'" in r1.stdout
    crash = open(tmp_path / "crash.log").read().splitlines()
    assert 5 < len(crash) < 20, "the kill must land mid-epoch"
    assert crash == ref[:len(crash)]

    r2 = _run(["resume.log", "--resume"])   # fault disarmed
    assert r2.returncode == 0, r2.stdout + r2.stderr
    m = [ln for ln in r2.stdout.splitlines() if ln.startswith("resumed")]
    assert m, r2.stdout
    cursor = int(m[0].split()[-1])
    assert 0 < cursor <= len(crash)
    resumed = open(tmp_path / "resume.log").read().splitlines()
    # checkpoint-relative exactness: the resumed stream IS the reference
    # tail from the cursor — nothing skipped, nothing replayed twice
    assert resumed == ref[cursor:]
    json.dumps({"cursor": cursor})  # sanity: state is plain-JSON-able


# -- lifecycle ----------------------------------------------------------------
def test_pipeline_registered_for_atexit_shutdown():
    from mxnet_tpu.data import workers as wk
    d = np.zeros((12, 2), np.float32)
    pipe = DataPipeline(mx.io.NDArrayIter(d, np.zeros(12), 4), name="atexit")
    pit = mx.io.PrefetchingIter(mx.io.NDArrayIter(d, np.zeros(12), 4))
    assert pipe in wk._closeables and pit in wk._closeables
    next(pipe)                     # threads live, queues in play
    wk._close_all()                # what the interpreter runs at exit
    assert not _pipeline_threads()
    with pytest.raises(RuntimeError):
        pipe._start_stream()       # closed is closed


def test_close_never_hangs_on_full_queues():
    pipe = DataPipeline(_SlowSource(nbatch=50, cost_s=0.0), num_workers=2,
                        queue_depth=1, stage_ahead=1, name="full")
    next(pipe)                     # stream running, every queue jammed
    time.sleep(0.1)
    t0 = time.monotonic()
    pipe.close()
    assert time.monotonic() - t0 < 5.0
    assert not _pipeline_threads()
