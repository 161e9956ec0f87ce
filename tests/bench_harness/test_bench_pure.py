"""The parts of the benchmark that need no model: the trace reduction
against a trace recorded on the chip, the operation counts against hand
counts, the traffic generator, and BENCHMARK.json against the files it
points to."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import flops  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


# -- trace reduction ---------------------------------------------------------
@pytest.fixture(scope="module")
def small_trace():
    """Recorded on a v5e by testdata/record_small_trace.py: three rounds
    of a 5 ms host span, one `small_step` (three matmul fusions), a 10 ms
    host span and one `other_step` (one elementwise fusion)."""
    return trace_reduce.load(os.path.join(BENCH, "testdata",
                                          "small_tpu.xplane.pb"))


def test_trace_has_one_device_with_ops_modules_and_host_spans(small_trace):
    (dev,) = small_trace.devices
    assert dev.name == "/device:TPU:0"
    assert len(dev.ops) == 18 and len(dev.modules) == 6
    names = [n for _, _, n in small_trace.host_spans]
    assert names.count("bench:host_prepare") == 3
    assert names.count("bench:host_wait") == 3


def test_idle_share_of_the_small_trace(small_trace):
    (dev,) = small_trace.devices
    busy = trace_reduce.busy_seconds(dev.ops)
    first = min(s for s, _, _ in dev.ops)
    last = max(s + d for s, d, _ in dev.ops)
    # 0.925 ms of operations in a 48.9 ms span: the host slept 45 ms of it
    assert busy == pytest.approx(925.235e-6, rel=1e-4)
    assert last - first == pytest.approx(48.8986e-3, rel=1e-4)
    assert 1 - busy / (last - first) == pytest.approx(0.98108, abs=1e-4)
    assert trace_reduce.mean_busy_seconds(small_trace) == busy


def test_time_by_name_and_executions(small_trace):
    (dev,) = small_trace.devices
    assert trace_reduce.module_executions(dev, "small_step") == \
        (3, pytest.approx(849.961e-6, rel=1e-4))
    assert trace_reduce.module_executions(dev, "other_step")[0] == 3
    # the nine matmul fusions are output fusions; the elementwise is not
    mxu = trace_reduce.time_matching(dev.ops, r"kind=kOutput")
    assert mxu == pytest.approx(813.62e-6, rel=1e-4)
    assert mxu / trace_reduce.total_op_seconds(dev.ops) > 0.85
    top = trace_reduce.top_ops(dev.ops, 3)
    assert [n for n, _ in top] == ["fusion kOutput", "fusion.1 kOutput",
                                   "fusion.2 kOutput"]


def test_gaps_go_to_the_host_span_that_covers_them(small_trace):
    gaps = dict(trace_reduce.breakdown(small_trace)["idle_gaps"])
    # three 10 ms sleeps and, between rounds, two 5 ms sleeps (the first
    # comes before the first operation); the launches fill the rest
    assert gaps["bench:host_wait"] == pytest.approx(35.0e-3, rel=0.02)
    assert gaps["bench:host_prepare"] == pytest.approx(13.0e-3, rel=0.02)
    assert sum(gaps.values()) < 48.9e-3


def test_union_of_overlapping_intervals():
    ops = [(0.0, 2.0, "a"), (1.0, 2.0, "b"), (5.0, 1.0, "c"),
           (5.5, 0.1, "d")]
    assert trace_reduce.merge(ops) == [(0.0, 3.0), (5.0, 6.0)]
    assert trace_reduce.busy_seconds(ops) == 4.0
    assert trace_reduce.total_op_seconds(ops) == pytest.approx(5.1)
    gaps = trace_reduce.idle_gaps(ops, [(2.5, 3.0, "bench:x")], min_gap_s=0)
    assert gaps == [["bench:x", 2.0]]


# -- operation counts --------------------------------------------------------
def test_resnet50_forward_is_4_089e9_macs():
    total, layers = flops.resnet_v2_forward_macs(
        [3, 4, 6, 3], [64, 256, 512, 1024, 2048], 1000, 224)
    by = dict(layers)
    assert by["conv0"] == 64 * 3 * 49 * 112 * 112 == 118013952
    assert by["stage1_unit1_conv2"] == 64 * 64 * 9 * 56 * 56
    assert by["stage4_unit1_sc"] == 2048 * 1024 * 7 * 7
    assert by["fc1"] == 2048 * 1000
    assert len(layers) == 1 + 16 * 3 + 4 + 1
    assert total == 4089184256
    assert flops.forward_flops(total) == pytest.approx(8.18e9, rel=1e-3)
    assert flops.train_flops(total) == pytest.approx(24.5e9, rel=2e-3)


def test_lstm_lm_counts():
    total, layers = flops.lstm_lm_forward_macs(33278, 650, 650, 2)
    by = dict(layers)
    assert by["decoder"] == 650 * 33278
    assert by["lstm0"] == by["lstm1"] == 4 * 650 * (650 + 650)
    # the projection of one step of 512 x 35 tokens
    assert 2 * 17920 * by["decoder"] == 2 * 17920 * 650 * 33278
    assert flops.train_flops(total) * 17920 == pytest.approx(3.05e12,
                                                             rel=2e-3)


# -- traffic -----------------------------------------------------------------
MIX = {"1": 0.70, "2": 0.15, "4": 0.10, "8": 0.05}


def test_schedule_is_a_function_of_the_seed():
    a = traffic.arrival_times(500, 4, 7, seed=3000000019)
    b = traffic.arrival_times(500, 4, 7, seed=3000000019)
    c = traffic.arrival_times(500, 4, 7, seed=5)
    assert len(a) == 2000 and (a == b).all() and not (a == c).all()
    assert (np.diff(a) >= 0).all() and 0 < a[0] and a[-1] < 4


def test_seeds_offer_the_same_work_in_another_order():
    a = traffic.arrival_times(500, 4, 7, seed=1)
    c = traffic.arrival_times(500, 4, 7, seed=2)
    gaps = lambda t: np.sort(np.diff(np.concatenate([[0.0], t])))  # noqa
    # the last gap of n + 1 is the one not sent, so compare all but it
    assert np.allclose(np.sort(np.concatenate([gaps(a), [4 - a[-1]]])),
                       np.sort(np.concatenate([gaps(c), [4 - c[-1]]])))
    ra, rc = traffic.request_rows(MIX, 2000, 1), \
        traffic.request_rows(MIX, 2000, 2)
    assert not (ra == rc).all()
    assert sorted(ra) == sorted(rc)
    assert [int((ra == k).sum()) for k in (1, 2, 4, 8)] == \
        [1400, 300, 200, 100]


def test_exponential_gaps_and_bursts_keep_the_mean_rate():
    t = traffic.arrival_times(1000, 10, 7, seed=1)
    gaps = np.diff(t)
    assert gaps.mean() == pytest.approx(1e-3, rel=0.01)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.1)  # Poisson
    bursty = traffic.arrival_times(1000, 10, 7, seed=1,
                                   profile=[[0.5, 4.0], [1.5, 0.0]])
    assert len(bursty) == len(t) and bursty[-1] <= 10
    # every arrival lands in the on-phase (first 0.5 s of every 2 s)
    assert ((bursty % 2.0) <= 0.5 + 1e-9).all()


def test_lateness_is_taken_from_the_due_time():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    sent = due + np.array([0.0, 0.001, 0.004, 0.002])
    assert traffic.percentile((sent - due) * 1e3, 95) == pytest.approx(4.0)
    assert traffic.percentile(range(1, 101), 95) == 95
    assert traffic.percentile([3.0], 50) == 3.0


def test_sample_holds_a_largest_request():
    rows = traffic.request_rows(MIX, 400, 9)
    idx = traffic.sample_indices(rows, 16, 9)
    assert len(idx) == 16 == len(set(idx))
    assert max(rows[i] for i in idx) == 8
    assert idx == traffic.sample_indices(rows, 16, 9)


# -- BENCHMARK.json ----------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_points_at_files_that_exist():
    bench = harness.benchmark_json(proposed=False)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in bench["paths"])
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert os.path.isfile(os.path.join(BENCH, "configs", cfg["module"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        t = harness.load_json(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           t["driver"] + ".py"))
    for m in list(e2e.values()) + list(layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in layer.values():
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        reports = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reports)) <= reports
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              m["name"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    for cell in cells:
        mine = [m for m in e2e.values()
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in layer.values()), cell


def test_peak_table_refuses_a_device_it_does_not_know():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9")


def test_the_run_fails_without_a_chip():
    import subprocess
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "lstm-lm-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not out.stdout.strip().endswith("}")
