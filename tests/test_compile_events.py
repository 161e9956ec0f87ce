"""Every XLA program the process builds, filed by phase and name (PR 52):
``compile/registry.py``'s one ``jax.monitoring`` listener pair behind
``mx.compile_report()["jax"]``, on the CPU with small programs."""
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

import mxnet_tpu as mx
from mxnet_tpu.compile import registry
from mxnet_tpu.telemetry import trace

TRACE, LOWER, BACKEND = registry._JAX_PHASES     # in this order


@pytest.fixture(autouse=True)
def _clean():
    """The table, the log, the ring and the aggregates as a new process
    has them; earlier tests of this worker have left theirs."""
    registry.reset()
    trace.reset()
    mx.telemetry.remove("prof::jax::")
    yield
    registry.reset()
    trace.reset()
    mx.telemetry.remove("prof::jax::")


@pytest.fixture
def jax_cache(tmp_path):
    """JAX's persistent cache at an empty directory, keeping whatever
    compiles; the programs in memory forgotten, before and after."""
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path / "cache"), 0, -1)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    jax.clear_caches()


def _jax():
    return mx.compile_report()["jax"]


def _events(name):
    return [e for e in _jax()["events"] if e["name"] == name]


def _step():
    inner = jax.jit(lambda x: jnp.tanh(x) * 2)

    def mx_train_step(x):
        return inner(x).sum()

    return jax.jit(mx_train_step)


def test_one_row_a_name_with_its_three_phases_and_the_union_of_traces():
    step = _step()
    step(jnp.ones((4, 4)))
    (row,) = [p for p in _jax()["programs"] if p["name"] == "mx_train_step"]
    # jit( ) is taken off: the three phases of one program share a name
    assert row["traces"] == row["programs"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert [e["phase"] for e in _events("mx_train_step")] == \
        ["trace", "lower", "backend"]
    # the jit inside was traced inside: the table has its row, the log
    # keeps the outermost trace, and the union counts a second once
    assert any(p["name"] == "<lambda>" and p["traces"] == 1
               for p in _jax()["programs"])
    assert not _events("<lambda>")
    totals = _jax()["totals"]
    traced = [e for e in _jax()["events"] if e["phase"] == "trace"]
    assert 0 < totals["trace_s"] <= 1e-6 * (
        sum(e["dur"] for e in traced) + 1)      # rounded to the microsecond
    assert totals["trace_s"] < sum(
        p["trace_s"] for p in _jax()["programs"])
    assert totals["programs"] == sum(
        e["phase"] == "backend" for e in _jax()["events"])
    # on the ring's clock, and the aggregates beside the other spans'
    (outer,) = [e for e in traced if e["name"] == "mx_train_step"]
    now_us = (trace._now() - trace._EPOCH) * 1e6
    assert 0 < outer["ts"] < outer["ts"] + outer["dur"] < now_us
    assert outer["tid"] == threading.get_ident()
    table = mx.profiler.aggregate()
    for phase in ("trace", "lower", "backend"):
        assert table[f"jax::{phase}:mx_train_step"][0] == 1
    # a second call builds nothing
    n = len(_jax()["events"])
    step(jnp.ones((4, 4)))
    assert len(_jax()["events"]) == n
    assert mx.telemetry.report()["subsystems"]["compile"]["jax"][
        "totals"]["programs"] == totals["programs"]


def test_a_program_reads_miss_then_hit_and_none_without_the_cache(jax_cache):
    step = _step()
    step(jnp.ones((4, 4)))
    (first,) = [e for e in _events("mx_train_step")
                if e["phase"] == "backend"]
    assert first["cache"] == "miss"
    jax.clear_caches()
    step(jnp.ones((4, 4)))
    assert [e["cache"] for e in _events("mx_train_step")
            if e["phase"] == "backend"] == ["miss", "hit"]
    (row,) = [p for p in _jax()["programs"] if p["name"] == "mx_train_step"]
    assert (row["cache_hits"], row["cache_misses"], row["programs"]) == \
        (1, 1, 2)
    totals = _jax()["totals"]
    assert totals["cache_hits"] >= 1 and totals["cache_misses"] >= 1
    # JAX does not ask the cache: neither hit nor miss
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    step(jnp.ones((4, 4)))
    assert _events("mx_train_step")[-1]["cache"] == "none"


def test_jit_acquire_reads_its_own_programs_verdict(jax_cache):
    """A helper compiled inside the acquisition (a miss) does not turn a
    loaded step into a fresh compile."""
    x = jnp.ones((4, 4))
    step = _step()
    with registry.jit_acquire("mx_train_step", "train_step", (x,)):
        step(x)
    (row,) = [p for p in mx.compile_report()["programs"]
              if p["name"] == "mx_train_step"]
    assert (row["source"], row["compiles"], row["cache_hits"]) == \
        ("compile", 1, 0)
    jax.clear_caches()
    helper = jax.jit(lambda a: jnp.cos(a) - 3)
    with registry.jit_acquire("mx_train_step", "train_step", (x,)):
        helper(x)
        step(x)
    assert [e["cache"] for e in _jax()["events"]
            if e["phase"] == "backend"][-2:] == ["miss", "hit"]
    (row,) = [p for p in mx.compile_report()["programs"]
              if p["name"] == "mx_train_step"]
    assert (row["source"], row["compiles"], row["cache_hits"]) == \
        ("cache", 1, 1)
    assert row["load_s"] > 0


def _folded():
    return mx.fault.counters().get("compile.jax_names_folded", 0)


def _dropped():
    return mx.fault.counters().get("compile.jax_events_dropped", 0)


def test_the_257th_name_folds_into_other():
    before = _folded()
    for i in range(registry._MAX_JAX_NAMES):
        registry._on_jax_duration(BACKEND, 1e-3, fun_name=f"jit(f{i})")
    assert len(_jax()["programs"]) == registry._MAX_JAX_NAMES
    assert _folded() == before
    registry._on_jax_duration(BACKEND, 2e-3, fun_name="jit(one_more)")
    registry._on_jax_duration(LOWER, 1e-3, fun_name="jit(and_another)")
    registry._on_jax_duration(BACKEND, 1e-3, fun_name="jit(f0)")
    rows = {p["name"]: p for p in _jax()["programs"]}
    assert len(rows) == registry._MAX_JAX_NAMES + 1
    assert "one_more" not in rows and rows["f0"]["programs"] == 2
    assert rows["other"]["programs"] == 1
    assert rows["other"]["backend_s"] == pytest.approx(2e-3)
    assert rows["other"]["lower_s"] == pytest.approx(1e-3)
    assert _folded() == before + 2
    assert _jax()["events"][-3]["name"] == "other"
    assert mx.profiler.aggregate()["jax::backend:other"][0] == 1


def test_the_log_wraps_at_its_bound_and_counts_the_drop():
    before = _dropped()
    for i in range(registry._MAX_JAX_EVENTS):
        registry._on_jax_duration(BACKEND, 1e-6 * (i + 1), fun_name="g")
    assert len(_jax()["events"]) == registry._MAX_JAX_EVENTS
    assert _dropped() == before
    registry._on_jax_duration(BACKEND, 7.0, fun_name="g")
    events = _jax()["events"]
    assert len(events) == registry._MAX_JAX_EVENTS
    assert events[0]["dur"] == pytest.approx(2.0) and events[-1]["dur"] == 7e6
    assert _dropped() == before + 1
    # the table and the totals go on counting
    assert _jax()["totals"]["programs"] == registry._MAX_JAX_EVENTS + 1
    # one clearing for the records, the table and the log
    assert mx.compile_report(reset=True)["jax"]["totals"]["programs"] > 0
    assert _jax() == {"programs": [], "events": [], "totals": {
        "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "programs": 0,
        "cache_hits": 0, "cache_misses": 0}}


def test_under_a_trace_directory_the_events_are_spans_of_the_open_span(
        tmp_path, monkeypatch):
    step = _step()
    with trace.span("quiet", "own"):
        _step()(jnp.ones((2, 2)))
    assert trace.spans() == []              # tracing off: the log alone
    assert _events("mx_train_step")
    monkeypatch.setenv("MXTPU_TRACE_DIR", str(tmp_path))
    with trace.span("restart", "own") as outer:
        step(jnp.ones((4, 4)))
    mine = [s for s in trace.spans() if s["name"].endswith(":mx_train_step")]
    assert [s["name"] for s in sorted(mine, key=lambda s: s["ts"])] == [
        "jax:trace:mx_train_step", "jax:lower:mx_train_step",
        "jax:backend:mx_train_step"]
    for s in mine:
        assert s["cat"] == "compile" and s["kind"] == "work"
        assert s["parent_id"] == outer.span_id
        assert s["trace_id"] == outer.trace_id
    assert mine[-1]["args"] == {"cache": "none"}
    # every trace is there, the nested ones too, for the Chrome export
    assert any(s["name"] == "jax:trace:<lambda>" for s in trace.spans())
    # outside any span an event is a trace of its own
    jax.jit(lambda x: x - 5)(jnp.ones(3))
    alone = [s for s in trace.spans() if s["name"] == "jax:backend:<lambda>"]
    assert alone[-1]["parent_id"] is None and alone[-1]["trace_id"]
