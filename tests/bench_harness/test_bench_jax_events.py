"""Set-up's programs by phase and name (PR 52): ``readers/jax_events.py``
on a hand-made log and ring, the six ``*.setup`` entries it and
``import_s.setup`` add, and one traced rehearsal that reports all six."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402

READERS = os.path.join(BENCH, "readers")
jax_events = harness.load_module(os.path.join(READERS, "jax_events.py"))

NEW = {"jax_trace_s.setup": "program_span",
       "jax_lower_s.setup": "program_span",
       "jax_backend_s.setup": "program_span",
       "step_trace_lower_s.setup": "program_span",
       "cache_miss_programs.setup": "program_counter",
       "import_s.setup": "program_span"}


def _event(phase, name, ts, dur, tid=1, cache=None):
    out = {"ts": float(ts), "dur": float(dur), "phase": phase,
           "name": name, "tid": tid}
    return dict(out, cache=cache) if cache else out


def _log():
    """Set-up: a helper's three phases; the step's trace 2000..5000 with
    a trace of another thread 4000..6000 beside it and one this thread
    kept inside it (3000..3500), its lowering and its load. Then the
    window, from 20000, in which a program was built, and the reference
    after it."""
    return [
        _event("trace", "_where", 100, 50),
        _event("lower", "_where", 160, 40),
        _event("backend", "_where", 210, 300, cache="none"),
        _event("trace", "unit", 3000, 500),
        _event("trace", "mx_train_step", 2000, 3000),
        _event("trace", "prefetch", 4000, 2000, tid=2),
        _event("lower", "mx_train_step", 6000, 1000),
        _event("backend", "mx_train_step", 7000, 9000, cache="hit"),
        _event("backend", "ends_in_the_window", 19000, 1500, cache="miss"),
        _event("trace", "late", 21000, 100),
        _event("backend", "late", 21200, 100, cache="miss"),
        _event("trace", "step", 90000, 5000),
        _event("lower", "step", 95000, 1000),
        _event("backend", "step", 96000, 7000, cache="miss"),
    ]


def _span(cat, name, ts, dur, span_id=None, parent_id=None):
    return {"ts": float(ts), "dur": float(dur), "cat": cat, "name": name,
            "span_id": span_id, "parent_id": parent_id}


# what set-up itself recorded into the ring starts no window
RING = [_span("setup", "router_bias", 500, 1500),
        _span("step", "dispatch", 20010, 900, parent_id="s1"),
        _span("step", "step", 20000, 1000, "s1"),
        _span("step", "step", 30000, 1000, "s2")]
FACTS = {"window": {"steps": 2}}


def _spec(metric):
    return harness.load_json(os.path.join(
        BENCH, "layer_metrics", metric + ".json"))


def test_the_events_of_set_up_are_those_that_ended_before_the_ring_began():
    steps = jax_events.window_spans.window_steps(RING, 2)
    assert [s["ts"] for s in steps] == [20000.0, 30000.0]
    kept = jax_events.before(_log(), steps)
    assert [e["name"] for e in kept] == [
        "_where", "_where", "_where", "unit", "mx_train_step", "prefetch",
        "mx_train_step", "mx_train_step"]
    assert jax_events.before(_log(), []) is None
    # a window that began before everything: nothing was set-up
    assert jax_events.before(_log(), [dict(steps[0], ts=0.0)]) == []


@pytest.mark.parametrize("metric,value", [
    # this thread 100..150 and 2000..5000 (the trace inside counts once),
    # the other thread's 2000 beside them
    ("jax_trace_s.setup", 5050e-6),
    ("jax_lower_s.setup", 1040e-6),
    ("jax_backend_s.setup", 9300e-6),
    ("step_trace_lower_s.setup", 4000e-6),
    ("cache_miss_programs.setup", 1),
])
def test_each_metric_reads_its_phases_of_set_up(metric, value, monkeypatch):
    import mxnet_tpu as mx
    monkeypatch.setattr(mx, "compile_report",
                        lambda: {"jax": {"events": _log()}})
    monkeypatch.setattr(mx.telemetry.trace, "spans", lambda: RING)
    spec = _spec(metric)
    assert spec["reader"] == "jax_events"
    assert jax_events.read(spec["params"], FACTS) == pytest.approx(value)


def test_nothing_to_read_gives_nothing_and_nothing_built_gives_zero(
        monkeypatch):
    import mxnet_tpu as mx
    params = _spec("step_trace_lower_s.setup")["params"]
    monkeypatch.setattr(mx.telemetry.trace, "spans", lambda: RING)
    # the parent: a report from before the log
    monkeypatch.setattr(mx, "compile_report", lambda: {"programs": []})
    assert jax_events.read(params, FACTS) is None
    # a step that came whole out of the AOT cache was never traced
    monkeypatch.setattr(mx, "compile_report",
                        lambda: {"jax": {"events": _log()[:3]}})
    assert jax_events.read(params, FACTS) == 0.0
    # no step in the ring: no window to part set-up from the reference
    monkeypatch.setattr(mx.telemetry.trace, "spans", lambda: RING[:1])
    assert jax_events.read(params, FACTS) is None
    with pytest.raises(ValueError):
        jax_events.reduce_events(_log(), "trace", "mean_s")


def test_the_six_metrics_are_additions_that_name_files_that_exist():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, source in NEW.items():
        # every cell that reports setup_s reports it: no list of cells
        assert entries[name] == {
            "name": name, "unit": "programs" if "programs" in name else "s",
            "better": "lower", "source": source, "layer": "Compile",
            "moves": "setup_s"}
        assert os.path.isfile(os.path.join(
            READERS, _spec(name)["reader"] + ".py"))
    assert _spec("import_s.setup") == {
        "reader": "span_aggregate", "params": {"pattern": "^setup::import$"}}


def test_a_traced_rehearsal_reports_all_six():
    """A run is a process of its own: its import, its programs, its ring."""
    import subprocess
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "lstm-lm-train", "--seed", "3000000031", "--seconds", "1",
         "--trace", "1", "--rehearsal", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0
    got = {m: line["metrics"][m]["value"] for m in NEW}
    assert got["jax_trace_s.setup"] > 0 and got["import_s.setup"] > 0
    assert got["jax_lower_s.setup"] > 0 and got["jax_backend_s.setup"] > 0
    assert 0 < got["step_trace_lower_s.setup"] <= \
        got["jax_trace_s.setup"] + got["jax_lower_s.setup"]
    # tests leave JAX's cache unused: every program of set-up compiled,
    # and the harness's count of them is the program's
    assert got["cache_miss_programs.setup"] == \
        line["metrics"]["xla_programs.setup"]["value"]
    # the step's trace and lowering are a part of what its acquisition held
    assert line["metrics"]["step_acquire_s.setup"]["value"] > \
        got["step_trace_lower_s.setup"]
