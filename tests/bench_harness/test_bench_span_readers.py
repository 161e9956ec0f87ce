"""The readers of the program's own spans and names (PR 26): each on a
hand-made ring, aggregate table or ``Trace``; nothing to read gives
nothing and never an error; and one traced rehearsal of each cell whose
line carries the new metrics or leaves them out."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import trace_reduce  # noqa: E402

READERS = os.path.join(BENCH, "readers")
window_spans = harness.load_module(os.path.join(READERS, "window_spans.py"))
span_aggregate = harness.load_module(
    os.path.join(READERS, "span_aggregate.py"))
trace_module_ms = harness.load_module(
    os.path.join(READERS, "trace_module_ms.py"))


def _span(span_id, parent_id, cat, name, ts, dur, kind="work"):
    """One record of the ring as ``mx.telemetry.trace.spans()`` gives
    it (microseconds)."""
    return {"span_id": span_id, "parent_id": parent_id, "cat": cat,
            "name": name, "ts": float(ts), "dur": float(dur), "kind": kind,
            "trace_id": "t1", "tid": 1, "args": None}


def _fit_ring():
    """Two fits; the second (the window) has two steps.

    step A, 0..1000: data_wait 100..300 (wait) with the pipeline's own
    wait 120..280 lying inside it under the root, device_step 300..600
    holding dispatch 320..580, callbacks 700..1000 holding a device_read
    750..950 (wait) that itself holds a nested wait 800..900.
    step B, 1000..1400: data_wait 1010..1030 (wait), no other wait."""
    return [
        _span("old", None, "train", "fit:net", -5000, 2000),
        _span("old-step", "old", "step", "step", -4000, 900),
        _span("old-wait", "old-step", "step", "data_wait", -3900, 800,
              "wait"),
        _span("root", None, "train", "fit:net", -10, 1500),
        _span("A", "root", "step", "step", 0, 1000),
        _span("A1", "A", "step", "data_wait", 100, 200, "wait"),
        _span(None, "root", "data", "data:wait", 120, 160, "wait"),
        _span("A2", "A", "step", "device_step", 300, 300),
        _span("A3", "A2", "step", "dispatch", 320, 260),
        _span("A4", "A", "step", "callbacks", 700, 300),
        _span("A5", "A4", "step", "device_read", 750, 200, "wait"),
        _span("A6", "A5", "step", "inner_wait", 800, 100, "wait"),
        _span("B", "root", "step", "step", 1000, 400),
        _span("B1", "B", "step", "data_wait", 1010, 20, "wait"),
    ]


def test_self_time_takes_each_waited_microsecond_off_once():
    ring = _fit_ring()
    # A: 1000 less data_wait 200 less device_read 200 (the wait nested
    # in it counts once); B: 400 less 20. The first fit is not the window
    assert window_spans.per_step_us(ring, 2, "self_less_wait") == \
        [600.0, 380.0]
    assert window_spans.per_step_us(ring, 2, "child", "data_wait") == \
        [200.0, 20.0]
    assert window_spans.per_step_us(ring, 2, "child", "dispatch") == \
        [260.0, 0.0]


def test_a_wait_that_overhangs_its_step_is_cut_to_it():
    ring = [_span("S", None, "step", "step", 0, 100),
            _span("W", "S", "step", "device_read", 50, 500, "wait")]
    assert window_spans.per_step_us(ring, 1, "self_less_wait") == [50.0]


def test_a_step_object_s_window_is_its_last_calls():
    ring = [_span(f"s{i}", None, "step", "step", 100 * i, 10 + i)
            for i in range(5)]
    ring.append(_span("d4", "s4", "step", "dispatch", 401, 3))
    assert window_spans.per_step_us(ring, 2, "self_less_wait") == \
        [13.0, 14.0]
    # fewer steps in the ring than the driver counted: it has wrapped
    assert window_spans.per_step_us(ring, 9, "self_less_wait") == []


def _facts(trace=None, steps=3):
    return {"window": {"steps": steps},
            "trace": trace or trace_reduce.Trace([], [])}


def test_readers_read_the_programs_ring_and_table(monkeypatch):
    import mxnet_tpu as mx
    monkeypatch.setattr(mx.telemetry.trace, "spans", _fit_ring)
    assert window_spans.read({"what": "self_less_wait"}, _facts()) == \
        pytest.approx(0.49)                       # (600 + 380) / 2 us
    assert window_spans.read({"what": "child", "name": "data_wait"},
                             _facts()) == pytest.approx(0.11)
    table = {"pass::gate:pallas_fusion": (2, 12.5, 4.0, 8.5),
             "pass::gate:residual_fusion": (1, 4.25, 4.25, 4.25),
             "pass::apply:pallas_fusion": (1, 0.2, 0.2, 0.2),
             "compile::acquire:fused_step:softmax": (2, 14.0, 6.0, 8.0),
             "compile::acquire:predict:softmax:b8": (1, 3.0, 3.0, 3.0),
             "compile::acquire:mx_train_step": (1, 1.3, 1.3, 1.3),
             "compile::compile": (5, 30.0, 1.0, 9.0)}
    monkeypatch.setattr(mx.profiler, "aggregate", lambda: table)
    gate = harness.load_json(os.path.join(
        BENCH, "layer_metrics", "gate_s.setup.json"))
    acquire = harness.load_json(os.path.join(
        BENCH, "layer_metrics", "step_acquire_s.setup.json"))
    assert span_aggregate.read(gate["params"], _facts()) == 16.75
    # the two step programs, not the predictor's nor the bare compiles
    assert span_aggregate.read(acquire["params"], _facts()) == 15.3


def test_nothing_to_read_gives_nothing(monkeypatch):
    """The parent commit: an empty ring under the profiler, no span
    aggregates, step programs both called ``jit_step_fn``."""
    import mxnet_tpu as mx
    monkeypatch.setattr(mx.telemetry.trace, "spans", lambda: [])
    monkeypatch.setattr(
        mx.profiler, "aggregate",
        lambda: {"compile::compile": (2, 9.0, 4.0, 5.0),
                 "compile::load": (1, 0.5, 0.5, 0.5)})
    for metric in ("host_step_ms.train", "data_wait_ms.train",
                   "gate_s.setup", "step_acquire_s.setup",
                   "step_program_ms.train"):
        spec = harness.load_json(os.path.join(
            BENCH, "layer_metrics", metric + ".json"))
        reader = harness.load_module(os.path.join(
            READERS, spec["reader"] + ".py"))
        old = trace_reduce.DeviceTrace(
            "/device:TPU:0", ops=[(0.0, 0.1, "%fusion = ...")],
            modules=[(0.0, 0.1, "jit_step_fn(123)")])
        for trace in (trace_reduce.Trace([], []),
                      trace_reduce.Trace([old], [])):
            assert reader.read(spec.get("params", {}),
                               _facts(trace)) is None, metric


def test_step_program_is_found_by_the_name_the_program_gives_it():
    spec = harness.load_json(os.path.join(
        BENCH, "layer_metrics", "step_program_ms.train.json"))
    dev = trace_reduce.DeviceTrace("/device:TPU:0", modules=[
        (0.00, 0.100, "jit_mx_fused_step(8417368182)"),
        (0.10, 0.104, "jit_mx_fused_step(8417368182)"),
        (0.21, 0.002, "jit_mx_predict(77)"),
        (0.22, 0.050, "jit_step_fn(5)"),
        (0.30, 0.001, "jit__threefry_split(9)")])
    assert trace_module_ms.read(
        spec["params"], _facts(trace_reduce.Trace([dev], []))) == \
        pytest.approx(102.0)
    lm = trace_reduce.DeviceTrace("/device:TPU:0", modules=[
        (0.0, 0.0595, "jit_mx_train_step(1)")])
    assert trace_module_ms.read(
        spec["params"], _facts(trace_reduce.Trace([lm], []))) == \
        pytest.approx(59.5)


def test_the_five_metrics_are_additions_that_name_their_files():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    new = {m["name"]: m for m in bench["per_layer"][-5:]}
    assert set(new) == {"step_program_ms.train", "host_step_ms.train",
                        "data_wait_ms.train", "gate_s.setup",
                        "step_acquire_s.setup"}
    both = ["resnet50-train", "lstm-lm-train"]
    assert new["step_program_ms.train"]["workloads"] == both
    assert new["step_acquire_s.setup"]["workloads"] == both
    # no sync in TrainStep's loop: past the runtime's executions in
    # flight its enqueue blocks, and the program cannot tell that wait
    # from work, so the host's step time lists the fit() cell alone
    for name in ("host_step_ms.train", "data_wait_ms.train", "gate_s.setup"):
        assert new[name]["workloads"] == ["resnet50-train"], name
    for name, m in new.items():
        assert m["source"] == ("device_trace" if name.startswith("step_p")
                               else "program_span")
        spec = harness.load_json(os.path.join(
            BENCH, "layer_metrics", name + ".json"))
        assert os.path.exists(os.path.join(READERS,
                                           spec["reader"] + ".py"))


# -- one traced rehearsal of each cell ---------------------------------------
@pytest.mark.parametrize("workload,carries,leaves_out", [
    ("resnet50-train",
     {"host_step_ms.train", "data_wait_ms.train", "step_acquire_s.setup"},
     # no XLA Modules line in a CPU trace; the pass gate is off the TPU
     {"step_program_ms.train", "gate_s.setup"}),
    ("lstm-lm-train",
     {"step_acquire_s.setup"},
     {"step_program_ms.train", "host_step_ms.train", "data_wait_ms.train",
      "gate_s.setup"}),
])
def test_traced_rehearsal_carries_the_new_metrics_or_leaves_them_out(
        workload, carries, leaves_out, capsys):
    import mxnet_tpu as mx
    import run
    mx.telemetry.trace.reset()
    # a run is a process of its own; here earlier tests have left theirs
    for prefix in ("prof::pass::", "prof::compile::"):
        mx.telemetry.remove(prefix)
    run.main(["--workload", workload, "--seed", "3000000029", "--seconds",
              "1", "--trace", "1", "--rehearsal", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert carries <= set(metrics) and not leaves_out & set(metrics)
    assert all(metrics[m]["value"] > 0 for m in carries)
    if "host_step_ms.train" in carries:
        # a step's own time holds its data wait apart
        assert metrics["data_wait_ms.train"]["value"] >= 0
        assert metrics["host_step_ms.train"]["unit"] == "ms"
    # the window's spans were there for the readers, and stay
    steps = [s for s in mx.telemetry.trace.spans()
             if (s["cat"], s["name"]) == ("step", "step")]
    assert len(steps) >= line["attempted"]
