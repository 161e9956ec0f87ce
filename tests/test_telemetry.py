"""Unified telemetry subsystem (mxnet_tpu/telemetry/):

- the unified report is a SUPERSET of all six legacy report surfaces
  (fusion/serving/data/fault/compile reports + profiler counters) —
  each legacy ``*_report()`` is a filtered view of it;
- registry thread-safety: concurrent serving-style + data-pipeline-style
  writers against snapshot-and-clear readers conserve every increment
  exactly (no torn or double-counted window), for raw registry counters
  AND for the legacy ``fault_report(reset=True)`` path routed through
  the registry;
- profiler hardening: no ``inf`` min for zero-count rows, stable
  total-time sort, and profiler counters / subsystem gauge mirrors are
  ONE registry store (no drift between mirrors);
- StepTimeline: a real ``fit()`` run on the CPU proxy attributes >= 90%
  of measured step wall time to named phases, records XLA
  cost-analysis bytes-accessed from the already-compiled step program,
  and (with MXTPU_TELEMETRY_DIR) produces a parseable JSONL event log
  that round-trips through ``tools/telemetry.py summary``;
- durable export chaos (faultinject site ``telemetry_write``): a
  SIGKILL mid-rotation loses no committed event and the next run tails
  the log cleanly; a torn final line is skipped, never fatal;
- ``tools/telemetry.py diff --gate-bytes``: the bytes-accessed
  regression gate fails loudly when bytes-per-step grew, passes on
  shrink/equal/tolerated growth;
- serving fleet-readiness: every Predictor/DynamicBatcher report entry
  carries a stable process-unique id and per-bucket latency histograms
  key by predictor id (two replicas never merge into one pool).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import export as texp
from mxnet_tpu.telemetry import registry as treg

_TESTS = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_TESTS)
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import telemetry as telemetry_cli  # noqa: E402  (tools/telemetry.py)


@pytest.fixture
def tdir(tmp_path):
    """Point MXTPU_TELEMETRY_DIR at a fresh directory for the test and
    drop the exporter singleton on both sides."""
    d = str(tmp_path / "telem")
    texp.reset_exporter()
    with mx.config.override("MXTPU_TELEMETRY_DIR", d):
        yield d
    texp.reset_exporter()


# ---------------------------------------------------------------------------
# unified report = superset of the six legacy surfaces
# ---------------------------------------------------------------------------
def test_report_is_superset_of_all_legacy_reports():
    # touch every subsystem so the trees are non-trivial
    mx.fault.count("ckpt.saves")
    mx.profiler.Counter(mx.profiler.Domain("ft"), "skipped_steps", 3)
    tree = telemetry.report()
    legacy = {
        "fusion": mx.fusion_report(),
        "serving": mx.serving_report(),
        "data": mx.data_report(),
        "fault": mx.fault_report(),
        "compile": mx.compile_report(),
        "profiler": {"counters": mx.profiler.counters()},
    }
    for name, rep in legacy.items():
        assert name in tree["subsystems"], \
            f"telemetry.report() missing subsystem '{name}'"
        missing = set(rep) - set(tree["subsystems"][name])
        assert not missing, \
            f"telemetry.report()['subsystems'][{name!r}] lacks {missing}"
    # the flat metric layer exists and carries the fault counter
    assert tree["metrics"]["fault::ckpt.saves"]["value"] >= 1
    # and each legacy surface IS the filtered view (same collector)
    assert mx.fault_report() == telemetry.collect("fault")
    assert mx.compile_report()["cache"] == \
        telemetry.collect("compile")["cache"]


def test_report_reset_clears_counters_keeps_gauges():
    telemetry.counter("tw::resets").inc(7)
    telemetry.gauge("tw::level").set(4.5)
    first = telemetry.report(reset=True)
    assert first["metrics"]["tw::resets"]["value"] == 7
    second = telemetry.report()
    assert second["metrics"]["tw::resets"]["value"] == 0
    assert second["metrics"]["tw::level"]["value"] == 4.5


def test_report_reset_metrics_layer_carries_collector_series():
    """A reset read must carry collector-owned registry series (fault::,
    prof::…) in the flat ``metrics`` layer — the layer the diff gate
    consumes — not zeros: the flat snapshot is taken before collectors
    clear their prefixes."""
    from mxnet_tpu import fault
    fault.count("twr.window_probe")
    tree = telemetry.report(reset=True)
    assert tree["metrics"]["fault::twr.window_probe"]["value"] == 1
    after = telemetry.report()
    assert after["metrics"].get("fault::twr.window_probe",
                                {"value": 0})["value"] == 0


# ---------------------------------------------------------------------------
# registry thread-safety: snapshot-and-clear conserves every write
# ---------------------------------------------------------------------------
def test_concurrent_writers_vs_snapshot_and_clear_conserve_counts():
    """Serving-style and data-pipeline-style writers hammer counters and
    histograms while a reader snapshot-and-clears: every increment must
    land in EXACTLY one window (sum over windows + final == written)."""
    n_writers, per_writer = 4, 3000
    c_name, h_name = "tw::conserve", "tw::lat_ms"
    treg.snapshot(reset=True, prefix="tw::")
    stop = threading.Event()
    seen = {"count": 0, "hist": 0}

    def writer():
        c = telemetry.counter(c_name)
        h = telemetry.histogram(h_name)
        for i in range(per_writer):
            c.inc()
            h.observe(float(i % 17))

    def reader():
        while not stop.is_set():
            snap = treg.snapshot(reset=True, prefix="tw::")
            if c_name in snap:
                assert snap[c_name]["value"] >= 0
                seen["count"] += snap[c_name]["value"]
            if h_name in snap:
                seen["hist"] += snap[h_name]["count"]

    threads = [threading.Thread(target=writer) for _ in range(n_writers)]
    rt = threading.Thread(target=reader)
    rt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rt.join()
    final = treg.snapshot(reset=True, prefix="tw::")
    seen["count"] += final.get(c_name, {}).get("value", 0)
    seen["hist"] += final.get(h_name, {}).get("count", 0)
    assert seen["count"] == n_writers * per_writer
    assert seen["hist"] == n_writers * per_writer


def test_legacy_fault_report_reset_is_atomic():
    """The standardized reset semantics, through a legacy surface: a
    concurrent ``fault.count`` writer against ``fault_report(reset=
    True)`` readers never loses or double-counts an increment (the old
    per-subsystem read-then-clear could drop writes that landed between
    the read and the clear)."""
    total = 5000
    key = "injected.telemetry_test"     # rides fault_report()['injected']
    mx.fault_report(reset=True)          # clean window

    def writer():
        for _ in range(total):
            mx.fault.count(key)

    taken = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            rep = mx.fault_report(reset=True)
            taken.append(rep["injected"].get("telemetry_test", 0))

    wt = threading.Thread(target=writer)
    rt = threading.Thread(target=reader)
    rt.start()
    wt.start()
    wt.join()
    stop.set()
    rt.join()
    final = mx.fault_report(reset=True)
    leftover = final["injected"].get("telemetry_test", 0)
    assert sum(taken) + leftover == total


# ---------------------------------------------------------------------------
# profiler hardening / single source of truth
# ---------------------------------------------------------------------------
def test_profiler_dumps_no_inf_and_stable_sort():
    mx.profiler.dumps(reset=True)
    # a zero-count row (created, never recorded) must never render an
    # inf min — it is omitted outright (no data this window)
    treg.timer("prof::zz_empty_row")
    for name in ("bb_op", "aa_op", "cc_op"):   # identical totals
        treg.timer("prof::" + name).record(0.001)
    stats = json.loads(mx.profiler.dumps(format="json"))
    assert "zz_empty_row" not in stats
    assert "inf" not in mx.profiler.dumps().lower()
    # the registry snapshot of the same row guards min -> 0.0, not inf
    snap = treg.snapshot(prefix="prof::zz_empty_row")
    assert snap["prof::zz_empty_row"]["min"] == 0.0
    rows = [n for n in stats if n.endswith("_op")]
    assert rows == sorted(rows), \
        "equal-total rows must sort stably by name"
    # reset=True is atomic snapshot-and-clear
    mx.profiler.dumps(reset=True)
    assert json.loads(mx.profiler.dumps(format="json")) == {}


def test_profiler_counters_are_registry_gauges():
    """profiler.Counter, telemetry.gauge, and the subsystem mirrors are
    ONE store — no drift between mirrors possible."""
    c = mx.profiler.Counter(mx.profiler.Domain("twx"), "depth", 2)
    assert telemetry.gauge("twx::depth").get() == 2
    telemetry.gauge("twx::depth").set(9)
    assert c.value == 9
    assert mx.profiler.counters()["twx::depth"] == 9


def test_data_report_counter_mirror_deduplicated():
    mx.data_report()
    cs = mx.profiler.counters()
    assert "data::wait_s" in cs
    # the mirror IS the registry gauge
    assert cs["data::wait_s"] == telemetry.gauge("data::wait_s").get()


# ---------------------------------------------------------------------------
# StepTimeline
# ---------------------------------------------------------------------------
def test_timeline_nested_phases_subtract():
    import time as _time
    tl = telemetry.StepTimeline(name="unit")
    tl.step_start()
    with tl.phase("device_step"):
        _time.sleep(0.02)
        with tl.phase("compile"):
            _time.sleep(0.03)
    wall = tl.step_end()
    acc = tl._acc
    assert acc["compile"] >= 0.025
    # the outer phase's self-time excludes the nested compile span
    assert acc["device_step"] < 0.03
    assert sum(acc.values()) <= wall + 1e-6


def test_timeline_current_is_thread_pinned():
    """Only the activating thread attributes into the timeline: its
    span stack is lock-free, so another thread (a second fit, a serving
    loop) must see None — never a shared mutable stack it could
    corrupt or crash on."""
    tl = telemetry.StepTimeline(name="twt").activate()
    try:
        assert telemetry.current() is tl
        seen = []
        t = threading.Thread(target=lambda: seen.append(
            telemetry.current()))
        t.start()
        t.join()
        assert seen == [None]
    finally:
        tl.close()
    assert telemetry.current() is None


def test_step_start_noop_while_open_keeps_prestep_wait():
    """``fit()`` opens the epoch's first step before the epoch-start
    batch fetch; the loop-top ``step_start`` must not reset it — the
    initial data wait lands in the first step's attribution."""
    treg.snapshot(reset=True, prefix="step::")
    tl = telemetry.StepTimeline(name="tws")
    tl.step_start()
    with tl.phase("data_wait"):
        time.sleep(0.01)
    tl.step_start()                   # no-op: a step is already open
    wall = tl.step_end()
    assert wall >= 0.009
    snap = treg.snapshot(prefix="step::")
    assert snap["step::phase::data_wait_s"]["total"] >= 0.009


def _fit_mlp(num_epoch=2, batch=16, n=64):
    mx.random.seed(0)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    sym = mx.sym.SoftmaxOutput(fc, name="softmax")
    X = np.random.RandomState(0).rand(n, 10).astype(np.float32)
    Y = np.random.RandomState(1).randint(0, 8, (n,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch, label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.current_context())
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    return mod


def test_fit_step_timeline_phase_sums_within_10pct(tdir):
    """Acceptance pin: a fit() run on the CPU proxy produces a
    StepTimeline whose NAMED phase attribution sums to within 10% of
    the measured step wall time, records cost-analysis bytes, and
    writes a parseable JSONL event log."""
    telemetry.reset(prefix="step::")
    _fit_mlp()
    snap = treg.snapshot(prefix="step::")
    steps = snap["step::steps"]["value"]
    assert steps == 2 * 4          # 2 epochs x 64/16 batches
    wall = snap["step::wall_s"]["total"]
    named = sum(m["total"] for k, m in snap.items()
                if k.startswith("step::phase::")
                and k != "step::phase::unattributed_s")
    assert wall > 0
    assert named >= 0.9 * wall, \
        f"phases attribute only {named / wall:.1%} of step wall time"
    assert named <= wall * 1.001 + 1e-6
    # bytes-accessed recorded from the already-compiled step program
    assert snap["step::bytes_accessed"]["value"] > 0
    assert snap["step::arithmetic_intensity_flop_b"]["value"] > 0
    # durable event log: parseable, with milestone + epoch events
    events, torn = texp.read_events(tdir)
    assert torn == 0
    kinds = {e["kind"] for e in events}
    assert {"train_step", "epoch", "timeline_close"} <= kinds
    ts = [e for e in events if e["kind"] == "train_step"]
    assert ts and "phases" in ts[0] and "wall_s" in ts[0]
    # and a final snapshot landed
    assert texp.snapshot_files(tdir)


def test_event_log_roundtrips_through_cli_summary(tdir, capsys):
    telemetry.reset(prefix="step::")
    _fit_mlp(num_epoch=1)
    rc = telemetry_cli.main(["summary", "--dir", tdir, "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["events"] >= 2
    assert out["torn_lines"] == 0
    assert out["by_kind"]["train_step"] >= 1
    assert out["train"]["mean_wall_s"] > 0
    assert out["snapshot"]["headline"]["step::bytes_accessed"] > 0
    # tail also parses and filters
    rc = telemetry_cli.main(["tail", "--dir", tdir, "-n", "5",
                             "--kind", "train_step", "--json"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert lines and all(
        json.loads(ln)["kind"] == "train_step" for ln in lines)


def test_exporter_follows_dir_repoint(tmp_path):
    """Repointing MXTPU_TELEMETRY_DIR mid-process moves the event log
    with the snapshots — the export is never silently split across the
    old and new directories."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    texp.reset_exporter()
    with mx.config.override("MXTPU_TELEMETRY_DIR", a):
        assert texp.emit_event("unit", n=1)
    with mx.config.override("MXTPU_TELEMETRY_DIR", b):
        assert texp.emit_event("unit", n=2)
    assert [e["n"] for e in texp.read_events(a)[0]] == [1]
    assert [e["n"] for e in texp.read_events(b)[0]] == [2]
    texp.reset_exporter()


def test_exporter_recovers_after_failed_rotation(tdir):
    """A transient failure during log rotation (injected raise — the
    ENOSPC shape) must not end durable export for the process: the next
    emit reopens the already-advanced segment index and the stream
    stays contiguous."""
    from mxnet_tpu import faultinject
    with mx.config.override("MXTPU_TELEMETRY_ROTATE_BYTES", 80):
        texp.reset_exporter()
        pad = "x" * 60
        with faultinject.inject("telemetry_write:rotation=2"):
            assert texp.emit_event("unit", n=0, pad=pad)
            # this write triggers rotation to segment 2, which raises;
            # the event is dropped and counted, never propagated
            assert not texp.emit_event("unit", n=1, pad=pad)
        from mxnet_tpu import fault
        assert fault.counters().get("telemetry.write_errors", 0) >= 1
        # recovery: the next emits land, in the new segment
        assert texp.emit_event("unit", n=2, pad=pad)
        assert texp.emit_event("unit", n=3)
    events, torn = texp.read_events(tdir)
    assert torn == 0
    assert [e["n"] for e in events if e["kind"] == "unit"] == [0, 2, 3]
    assert len(texp.event_files(tdir)) >= 2
    texp.reset_exporter()


def test_predictor_churn_does_not_leak_registry_series():
    """Per-predictor ``serving::<id>::…`` series are removed when the
    replica is garbage-collected: a model-reload loop must not grow the
    registry (and every report/scrape) without bound."""
    import gc
    p = _small_predictor()
    pid = p.telemetry_id
    b = serving_batcher(p)
    x = np.random.RandomState(0).rand(2, 8, 4, 4).astype(np.float32)
    with b:
        b.predict(x)
    assert treg.snapshot(prefix=f"serving::{pid}::"), \
        "live replica must have registry series"
    del b, p
    gc.collect()
    assert not treg.snapshot(prefix=f"serving::{pid}::"), \
        "dead replica's series must be dropped from the registry"


def test_serving_report_reset_clears_registry_histograms():
    """One reset, every serving surface: ``serving_report(reset=True)``
    clears the per-predictor registry histograms along with the
    instance-local latency windows — the next telemetry window never
    mixes samples from before the reset."""
    p = _small_predictor()
    x = np.random.RandomState(0).rand(2, 8, 4, 4).astype(np.float32)
    with serving_batcher(p) as b:
        b.predict(x)
        prefix = f"serving::{p.telemetry_id}::"
        assert any(m["count"] > 0
                   for m in treg.snapshot(prefix=prefix).values()
                   if m["kind"] == "histogram")
        mx.serving_report(reset=True)
        assert all(m["count"] == 0
                   for m in treg.snapshot(prefix=prefix).values()
                   if m["kind"] == "histogram")


def test_profiler_counter_facade_never_clobbers_shared_gauge():
    """The reference Counter API is a facade over the shared registry
    gauge: constructing a SECOND facade for an existing domain::name
    must not zero another producer's live value."""
    from mxnet_tpu import profiler
    telemetry.gauge("twc::shared").set(7)
    c = profiler.Counter("twc", "shared")
    assert c.value == 7
    assert telemetry.gauge("twc::shared").get() == 7


def test_torn_final_line_is_skipped_and_repaired(tdir):
    texp.emit_event("unit", n=1)
    texp.emit_event("unit", n=2)
    seg = texp.event_files(tdir)[-1]
    with open(seg, "a") as f:
        f.write('{"ts": 1.0, "kind": "torn", "pa')   # no newline: torn
    events, torn = texp.read_events(tdir)
    assert torn == 1
    assert [e["n"] for e in events] == [1, 2]
    # a restarted writer repairs the tear before appending
    texp.reset_exporter()
    texp.emit_event("unit", n=3)
    events, torn = texp.read_events(tdir)
    assert torn == 1
    assert [e.get("n") for e in events] == [1, 2, 3]


@pytest.mark.chaos
def test_chaos_sigkill_mid_rotation_log_stays_tailable(tmp_path):
    """faultinject site ``telemetry_write``: a writer SIGKILLed mid-
    rotation (between closing segment K and opening K+1) loses nothing
    committed, and the next run tails the log cleanly — no torn JSONL
    line surfaces as an error."""
    d = str(tmp_path / "telem")
    child = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.telemetry import export as texp\n"
        "for i in range(1000):\n"
        "    assert texp.emit_event('ping', n=i)\n"
        "print('UNREACHED')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_TELEMETRY_DIR=d,
               MXTPU_TELEMETRY_ROTATE_BYTES="600",
               MXTPU_FAULT_INJECT="telemetry_write:rotation=3:action=kill")
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=_ROOT)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)
    assert "UNREACHED" not in r.stdout
    # the survivor log parses cleanly: every committed event intact,
    # contiguous from 0, across the rotated segments
    events, torn = texp.read_events(d)
    assert torn == 0
    ns = [e["n"] for e in events if e["kind"] == "ping"]
    assert ns == list(range(len(ns))) and len(ns) >= 2
    assert len(texp.event_files(d)) >= 2    # it actually rotated
    # a restarted writer appends seamlessly and the CLI summarizes
    env.pop("MXTPU_FAULT_INJECT")
    child2 = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.telemetry import export as texp\n"
        "assert texp.emit_event('ping', n=-1)\n"
    )
    r2 = subprocess.run([sys.executable, "-c", child2], env=env,
                        capture_output=True, text=True, timeout=300,
                        cwd=_ROOT)
    assert r2.returncode == 0, r2.stderr
    events2, torn2 = texp.read_events(d)
    assert torn2 == 0
    assert len(events2) == len(events) + 1


# ---------------------------------------------------------------------------
# diff / bytes-accessed regression gate
# ---------------------------------------------------------------------------
def _snapshot_file(tmp_path, name, bytes_accessed):
    tree = {"schema": 1, "subsystems": {},
            "metrics": {"step::bytes_accessed":
                        {"kind": "gauge", "value": bytes_accessed},
                        "step::steps":
                        {"kind": "counter", "value": 10}}}
    p = tmp_path / name
    p.write_text(json.dumps(tree))
    return str(p)


def test_diff_gate_bytes_fails_on_regression(tmp_path, capsys):
    old = _snapshot_file(tmp_path, "old.json", 1000.0)
    worse = _snapshot_file(tmp_path, "worse.json", 1100.0)
    better = _snapshot_file(tmp_path, "better.json", 900.0)
    assert telemetry_cli.main(["diff", old, worse, "--gate-bytes"]) == 2
    assert "BYTES REGRESSION" in capsys.readouterr().err
    assert telemetry_cli.main(["diff", old, better, "--gate-bytes"]) == 0
    assert telemetry_cli.main(["diff", old, old, "--gate-bytes"]) == 0
    # tolerated growth passes; beyond tolerance fails
    assert telemetry_cli.main(["diff", old, worse, "--gate-bytes",
                               "--tolerance", "15"]) == 0
    assert telemetry_cli.main(["diff", old, worse, "--gate-bytes",
                               "--tolerance", "5"]) == 2
    # metric-by-metric diff output
    capsys.readouterr()                      # flush prior table output
    assert telemetry_cli.main(["diff", old, worse, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["changed"]["step::bytes_accessed"] == \
        {"old": 1000.0, "new": 1100.0}


# ---------------------------------------------------------------------------
# serving fleet-readiness: per-predictor identity
# ---------------------------------------------------------------------------
def _small_predictor(buckets=(2, 4)):
    mx.random.seed(0)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=6,
                               name="fc")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    mod = mx.mod.Module(context=mx.cpu(), symbol=net)
    mod.bind(data_shapes=[("data", (4, 8, 4, 4))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    with mx.config.override("MXTPU_PALLAS_FUSION", "0"):
        return mod.as_predictor(buckets=buckets)


@pytest.mark.serving
def test_serving_report_tags_by_predictor_id():
    p1 = _small_predictor()
    p2 = _small_predictor()
    assert p1.telemetry_id != p2.telemetry_id
    x = np.random.RandomState(0).rand(2, 8, 4, 4).astype(np.float32)
    p1.predict(x)
    p2.predict(x)
    rep = mx.serving_report()
    ids = [r["id"] for r in rep["predictors"]]
    assert p1.telemetry_id in ids and p2.telemetry_id in ids
    assert ids == sorted(ids), "report order must be stable (by id)"
    with serving_batcher(p1) as bat:
        bat.predict(x)
        rep = mx.serving_report()
        mine = [b for b in rep["batchers"]
                if b["id"] == bat.telemetry_id]
        assert mine and mine[0]["predictor_id"] == p1.telemetry_id
    # per-bucket latency histograms key by PREDICTOR id — p2's series
    # stays empty while p1's batcher served traffic
    snap = treg.snapshot(prefix=f"serving::{p1.telemetry_id}::")
    assert any(k.endswith("latency_ms") and m["count"] > 0
               for k, m in snap.items())
    snap2 = treg.snapshot(prefix=f"serving::{p2.telemetry_id}::")
    assert all(m["count"] == 0 for k, m in snap2.items()
               if k.endswith("latency_ms"))


def serving_batcher(pred):
    from mxnet_tpu import serving
    return serving.DynamicBatcher(pred, max_wait_us=100, name="tw")


# ---------------------------------------------------------------------------
# prometheus rendering
# ---------------------------------------------------------------------------
def test_prometheus_rendering():
    telemetry.counter("twp::hits").inc(3)
    telemetry.histogram("twp::lat").observe(1.5)
    text = telemetry.render_prometheus()
    assert "# TYPE mxtpu_twp__hits counter" in text
    assert "mxtpu_twp__hits 3" in text
    assert 'mxtpu_twp__lat{quantile="0.5"} 1.5' in text
    assert "mxtpu_twp__lat_count 1" in text
