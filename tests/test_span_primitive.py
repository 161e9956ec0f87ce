"""The span primitive (telemetry/trace.py) and its call sites.

One clock read per interval feeds three sinks: the registry aggregate
always, the ring and a ``mx:<cat>/<name>`` TraceAnnotation while tracing
is on. Tracing is on under ``MXTPU_TRACE_DIR`` or a running
``jax.profiler`` trace. ``fit()``, ``TrainStep``, bind / init_optimizer,
the pass gate and the compile registry are traced through it, and the
three step programs carry stable names on the device.
"""
import glob
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.telemetry import registry, trace


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXTPU_TRACE_DIR", raising=False)
    trace.reset()
    yield
    trace.reset()


class _FakeAnnotation:
    seen = []

    def __init__(self, label):
        self.label = label

    def __enter__(self):
        _FakeAnnotation.seen.append(("enter", self.label))

    def __exit__(self, *exc):
        _FakeAnnotation.seen.append(("exit", self.label))


def _timer(name):
    return registry.snapshot(prefix=name, kinds=("timer",))[name]


# -- the primitive -----------------------------------------------------------
def test_one_clock_read_reaches_all_three_sinks(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(trace, "_TRACE_ANN", _FakeAnnotation)
    _FakeAnnotation.seen = []
    registry.remove("prof::sink::")
    with trace.span("one", "sink", args={"k": 1}) as sp:
        pass
    (rec,) = trace.spans()
    agg = _timer("prof::sink::one")
    # the same duration everywhere: measured once
    assert agg["count"] == 1 and agg["total"] == sp.dur
    assert rec["dur"] == pytest.approx(sp.dur * 1e6, rel=1e-9)
    assert (rec["name"], rec["cat"], rec["kind"]) == ("one", "sink", "work")
    assert rec["span_id"] == sp.span_id and rec["args"] == {"k": 1}
    assert _FakeAnnotation.seen == [("enter", "mx:sink/one"),
                                    ("exit", "mx:sink/one")]


def test_the_callers_own_aggregate_takes_the_place_of_the_timer():
    got = []
    registry.remove("prof::own::")
    with trace.span("a", "own", agg=got.append) as sp:
        pass
    with trace.span("b", "own", agg=False):
        pass
    assert got == [sp.dur]
    assert registry.snapshot(prefix="prof::own::") == {}


def test_off_with_neither_a_directory_nor_a_profiler():
    registry.remove("prof::off::")
    assert not trace.enabled()
    with trace.span("quiet", "off") as sp:
        assert trace.current() is None
    assert trace.spans() == [] and sp.span_id is None
    # the aggregate is fed all the same
    assert _timer("prof::off::quiet")["count"] == 1


def test_on_under_a_running_profiler_trace_alone(tmp_path):
    assert not trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.enabled()
        with trace.span("seen", "prof"):
            jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert not trace.enabled()
    # nothing exports or empties the ring without MXTPU_TRACE_DIR
    assert trace.export_trace() is None
    assert [s["name"] for s in trace.spans()] == ["seen"]
    assert trace.trace_files(str(tmp_path)) == []
    # and the span lies on the profiler's clock under the one prefix
    (xplane,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(xplane)
    names = {e.name for p in data.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events}
    assert "mx:prof/seen" in names


def test_a_jax_without_the_private_attribute_turns_tracing_off_only(
        monkeypatch):
    from jax._src import profiler as jax_profiler
    monkeypatch.delattr(jax_profiler, "_profile_state")
    monkeypatch.setattr(trace, "_PROFILE_STATE", None)
    assert trace.enabled() is False
    with trace.span("still", "works") as sp:
        pass
    assert sp.dur >= 0 and trace.spans() == []
    monkeypatch.setenv("MXTPU_TRACE_DIR", "/nonexistent-but-set")
    assert trace.enabled() is True


def test_kind_and_parent_by_the_threads_open_span_stack(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    other = {}

    def elsewhere():
        with trace.span("alone", "t") as sp:
            other["span"] = sp

    with trace.span("outer", "t") as outer:
        with trace.span("blocked", "t", kind="wait") as inner:
            assert trace.current() is inner
            t = threading.Thread(target=elsewhere)
            t.start()
            t.join(timeout=30)
        # a start()/stop() object of the profiler facade records its
        # parent but is nobody's
        task = mx.profiler.Domain("t").new_task("facade").start()
        assert trace.current() is outer
        with trace.span("after", "t") as after:
            pass
        task.stop()
    by = {s["name"]: s for s in trace.spans()}
    assert by["blocked"]["kind"] == "wait" and by["outer"]["kind"] == "work"
    assert by["blocked"]["parent_id"] == outer.span_id
    assert by["blocked"]["trace_id"] == outer.trace_id
    assert by["facade"]["parent_id"] == outer.span_id
    assert by["after"]["parent_id"] == outer.span_id == after.parent_id
    # another thread's stack is its own: a new trace, no parent
    assert by["alone"]["parent_id"] is None
    assert by["alone"]["trace_id"] != outer.trace_id
    # the wait shows as such in the exported file
    events = trace.read_trace(trace.export_trace())
    (blocked,) = [e for e in events if e.get("name") == "blocked"]
    assert blocked["args"]["kind"] == "wait"


def test_removed_options_and_gauges_are_gone():
    from mxnet_tpu import config
    assert "MXTPU_TRACE_ANNOTATE" not in config._REGISTRY
    with pytest.raises(TypeError):
        mx.telemetry.StepTimeline(hbm_peak_bytes_s=1e9)
    tl = mx.telemetry.StepTimeline("gone").activate()
    tl.note_cost(flops=2e9, bytes_accessed=1e9)
    tl.step_start()
    tl.step_end()
    tl.close()
    flat = registry.snapshot()
    assert "step::roofline_fraction" not in flat
    assert flat["step::bytes_accessed"]["value"] == 1e9
    assert not [k for k in flat if k.startswith("trace::exports")
                or k.startswith("trace::spans_exported")]


# -- fit() -------------------------------------------------------------------
def _net():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=32,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_fit_spans_nest_as_the_phases_do(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    x = rng.rand(96, 16).astype(np.float32)
    y = (rng.rand(96) * 10).astype(np.int32).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=32)
    metric = mx.metric.Accuracy()
    reads = []

    def on_batch(param):
        reads.append(param.eval_metric.get())   # waits for the device

    # this process's earlier fits have fed the same aggregates
    for prefix in ("prof::setup::", "prof::compile::", "step::phase::"):
        registry.remove(prefix)
    mod = mx.mod.Module(context=mx.cpu(), symbol=_net(), fused=True)
    mod.fit(it, num_epoch=2, eval_metric=metric, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(), batch_end_callback=on_batch)
    assert len(reads) == 6

    (path,) = trace.trace_files(str(tmp_path))
    spans = [e for e in trace.read_trace(path) if e["ph"] == "X"]
    by_id = {e["args"]["span_id"]: e for e in spans}

    def parent(e):
        return by_id.get(e["args"].get("parent_id"))

    def named(cat, name):
        return [e for e in spans if e["cat"] == cat and e["name"] == name]

    (root,) = [e for e in spans if e["cat"] == "train"]
    assert root["name"] == "fit:softmax" and root["args"]["steps"] == 6
    steps = named("step", "step")
    assert len(steps) == 6 and all(parent(e) is root for e in steps)
    for name in ("data_wait", "device_step", "callbacks"):
        found = named("step", name)
        assert len(found) >= 6, name
        assert all(parent(e) in steps for e in found), name
    # fit's metric update under the step, the fused step's own
    # bookkeeping under fit's device_step
    sync = [parent(e)["name"] for e in named("step", "metric_ft_sync")]
    assert sorted(sync) == ["device_step"] * 6 + ["step"] * 6
    assert all(e["args"]["kind"] == "wait"
               for e in named("step", "data_wait"))
    # the compiled call is an enqueue inside fit's device_step
    dispatch = named("step", "dispatch")
    assert len(dispatch) == 6
    assert all(parent(e)["name"] == "device_step" for e in dispatch)
    # the metric read in the callback is the loop's wait for the device
    reads_ = named("step", "device_read")
    assert reads_ and all(parent(e)["name"] == "callbacks" and
                          e["args"]["kind"] == "wait" for e in reads_)
    # set-up: bind and init_optimizer once, the step program acquired
    # through the registry under its own name
    assert len(named("setup", "bind")) == 1
    assert len(named("setup", "init_optimizer")) == 1
    # (twice: once more when the metric's counter joins the program)
    acquires = named("compile", "acquire:fused_step:softmax")
    assert len(acquires) == 2
    for acquire in acquires:
        assert parent(acquire)["name"] == "compile"       # the fit phase
        inner = {e["name"] for e in spans if parent(e) is acquire}
        assert "compile" in inner
        assert inner <= {"load", "compile", "serialize"}
    # the phases' aggregates are where they were; the new spans' are the
    # profiler table's rows
    flat = registry.snapshot()
    assert flat["step::phase::dispatch_s"]["count"] == 6
    assert flat["step::phase::callbacks_s"]["count"] == 6
    assert flat["prof::setup::bind"]["count"] == 1
    assert flat["prof::compile::acquire:fused_step:softmax"]["count"] == 2


def test_a_fit_that_raises_leaves_no_span_open(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    x = np.zeros((64, 16), np.float32)
    y = np.zeros((64,), np.float32)

    def boom(param):
        raise RuntimeError("callback failed")

    mod = mx.mod.Module(context=mx.cpu(), symbol=_net(), fused=True)
    with pytest.raises(RuntimeError, match="callback failed"):
        mod.fit(mx.io.NDArrayIter(x, y, batch_size=32), num_epoch=1,
                batch_end_callback=boom)
    assert trace.current() is None


def test_pass_gate_spans_hold_the_proxys_lower_and_compile(tmp_path,
                                                           monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    from mxnet_tpu.symbol.passes import manager
    from mxnet_tpu.symbol.passes.base import GraphPass

    class Renamed(GraphPass):
        """Rewrites nothing but claims a site, so the gate measures."""
        name = "renamed"
        modes = ("train",)

        def apply(self, sym, shapes, ctx):
            return sym, {"sites": ["fc1"], "bailouts": []}

    sym = _net()
    arg_shapes, _, aux_shapes = sym.infer_shape(data=(8, 16),
                                                softmax_label=(8,))
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    shapes.update(zip(sym.list_auxiliary_states(), aux_shapes))
    manager.reset_measure_memo()
    with mx.config.override("MXTPU_PASS_GATE_BYTES", "1"):
        manager.PassManager([Renamed()]).run(sym, shapes, tag="t")
    spans = trace.spans()
    (apply_,) = [s for s in spans if s["name"] == "apply:renamed"]
    (gate,) = [s for s in spans if s["name"] == "gate:renamed"]
    assert apply_["cat"] == gate["cat"] == "pass"
    inside = [s for s in spans if s["parent_id"] == gate["span_id"]]
    # one program measured (before and after are the same graph: the
    # memo answers the second), lowered and compiled under the gate
    assert [(s["cat"], s["name"]) for s in inside] == [
        ("compile", "lower"), ("compile", "compile")]


# -- TrainStep ---------------------------------------------------------------
def test_trainstep_spans_and_its_row_in_compile_report(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    from mxnet_tpu.parallel.step import TrainStep
    import mxnet_tpu.gluon.nn as nn
    mx.compile.registry.reset()
    net = nn.Dense(4, prefix="spanstep_")
    net.initialize()
    step = TrainStep(net, loss="l2")
    x = mx.nd.array(np.ones((8, 3), np.float32))
    y = mx.nd.array(np.ones((8, 4), np.float32))
    for _ in range(3):
        loss = step(x, y)
    assert np.isfinite(float(loss.asnumpy()))

    spans = trace.spans()
    steps = [s for s in spans if (s["cat"], s["name"]) == ("step", "step")]
    assert [s["args"]["step"] for s in steps] == [1, 2, 3]
    assert len({s["trace_id"] for s in steps}) == 1

    def inside(step_span):
        return [s["name"] for s in spans
                if s["parent_id"] == step_span["span_id"]]

    # the first call builds and compiles; a steady call is two spans
    assert inside(steps[0]) == ["compile", "compile"]
    assert inside(steps[1]) == inside(steps[2]) == ["dispatch"]
    (acquire,) = [s for s in spans if s["name"] == "acquire:mx_train_step"]
    assert acquire["cat"] == "compile"

    report = mx.compile_report()
    (row,) = [p for p in report["programs"] if p["name"] == "mx_train_step"]
    assert row["kind"] == "train_step"
    assert row["compiles"] + row["cache_hits"] == 1
    assert report["totals"]["fresh_compiles"] + \
        report["totals"]["cache_hits"] >= 1
    # with tracing off a call still counts, and records nothing
    monkeypatch.delenv("MXTPU_TRACE_DIR")
    n = len(trace.spans())
    step(x, y)
    assert len(trace.spans()) == n
    assert _timer("prof::step::dispatch")["count"] >= 3


# -- names on the device -----------------------------------------------------
def test_the_three_step_programs_are_named():
    """``XLA Modules`` reads the lowered module's name."""
    from mxnet_tpu.parallel.step import TrainStep
    import mxnet_tpu.gluon.nn as nn

    def module_name(lowered):
        return lowered.compiler_ir().operation.attributes[
            "sym_name"].value

    mod = mx.mod.Module(context=mx.cpu(), symbol=_net(), fused=True)
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd")
    assert mod._fused is not None
    feed = {"data": jnp.zeros((8, 16)), "softmax_label": jnp.zeros((8,))}
    assert module_name(mod._fused.lowered(feed)) == "jit_mx_fused_step"

    net = nn.Dense(4, prefix="named_")
    net.initialize()
    step = TrainStep(net, loss="l2")
    x, y = np.ones((8, 3), np.float32), np.ones((8, 4), np.float32)
    step(mx.nd.array(x), mx.nd.array(y))
    lowered = step._step_jit.lower(step._pvals, step._opt_state,
                                   jnp.asarray(x), jnp.asarray(y),
                                   step._t_dev, step._lr_cache[1])
    assert module_name(lowered) == "jit_mx_train_step"

    pred = mod.as_predictor(buckets=(2,))
    lowered = pred._infer_jit.lower(pred._pvals_t, (jnp.zeros((2, 16)),),
                                    pred._avals, pred._hvals)
    assert module_name(lowered) == "jit_mx_predict"
