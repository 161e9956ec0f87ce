"""The search-driven autotuning subsystem (mxnet_tpu/tune/):

- declarative search spaces: deterministic enumeration, seeded trial
  ordering (default config always first), canonical config ids, loud
  knob validation;
- the trial runner: exhaustive + successive-halving search, env knobs
  applied per trial via config.override with the pass manager's
  measurement memo scoped per trial, static pruning, a failing config
  failing the TRIAL never the process;
- the trial journal: CRC-guarded append-only crash log, torn lines
  skipped, resumed searches replaying completed trials instead of
  re-measuring;
- tuning records: CRC-guarded atomic persistence keyed like the
  compile registry — corrupt/stale records rejected loudly and never
  applied, fault-injected mid-write death tearing nothing;
- the acceptance pins: autotune finds a strictly-better-than-default
  config on the conv proxy, a warm process boots tuned with ZERO
  search trials and ZERO fresh XLA compiles (subprocess-pinned), and
  the SIGKILL-mid-search chaos drill resumes from the journal;
- MXTPU_PALLAS_TILES: loud validation, per-dimension override of the
  Pallas tile selection;
- tools/tune.py verify: exit 2 on objective regression, exit 1 on a
  corrupt store.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject
from mxnet_tpu import tune
from mxnet_tpu.base import MXNetError
from mxnet_tpu.tune import (Knob, SearchSpace, Trial, TrialJournal,
                            TrialRunner, TuneRecordError, TuneStore,
                            TuningRecord)

_TESTS = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_TESTS)


# ---------------------------------------------------------------------------
# search spaces
# ---------------------------------------------------------------------------
def _space(**domains):
    return SearchSpace([Knob(n, v, kind="param")
                        for n, v in domains.items()], name="t")


def test_space_enumeration_deterministic():
    sp = _space(a=(1, 2), b=("x", "y", "z"))
    assert sp.size == 6
    cfgs = sp.enumerate()
    assert len(cfgs) == 6
    assert cfgs[0] == {"a": 1, "b": "x"}          # declared order
    assert cfgs == sp.enumerate()                 # stable
    assert sp.default_config() == {"a": 1, "b": "x"}


def test_configs_seeded_and_default_first():
    sp = _space(a=tuple(range(6)), b=tuple(range(6)))
    one = sp.configs(seed=7)
    two = sp.configs(seed=7)
    assert one == two                             # deterministic
    assert one[0] == sp.default_config()          # baseline always runs
    assert sorted(map(sp.config_id, one)) == \
        sorted(map(sp.config_id, sp.enumerate()))
    other = sp.configs(seed=8)
    assert other[0] == sp.default_config()
    assert one != other                           # seed actually shuffles
    # bounded sampling still includes the default
    small = sp.configs(seed=7, max_trials=5)
    assert len(small) <= 6 and small[0] == sp.default_config()
    assert small == sp.configs(seed=7, max_trials=5)


def test_config_id_canonical_across_orderings():
    sp = _space(a=(1, 2), b=(3, 4))
    assert sp.config_id({"a": 1, "b": 3}) == \
        sp.config_id({"b": 3, "a": 1})
    assert sp.config_id({"a": 1, "b": 3}) != \
        sp.config_id({"a": 1, "b": 4})


def test_knob_validation_is_loud():
    with pytest.raises(ValueError):
        Knob("k", ())                             # empty domain
    with pytest.raises(ValueError):
        Knob("k", (1, 2), kind="magic")           # unknown kind
    with pytest.raises(ValueError):
        Knob("k", (1, 2), default=3)              # default outside domain
    with pytest.raises(ValueError):
        SearchSpace([Knob("k", (1,)), Knob("k", (2,))])   # duplicate


# ---------------------------------------------------------------------------
# the trial runner (pure measure functions — no compiles)
# ---------------------------------------------------------------------------
def test_runner_exhaustive_finds_best():
    sp = _space(x=(3, 1, 2))
    runner = TrialRunner(sp, lambda cfg, budget: float(cfg["x"]),
                         name="t")
    best, trials = runner.search()
    assert best.objective == 1.0
    assert sorted(t.config["x"] for t in trials) == [1, 2, 3]
    assert all(t.status == "measured" for t in trials)


def test_static_pruning_skips_measurement():
    sp = _space(x=(1, 2, 3))
    measured = []

    def measure(cfg, budget):
        measured.append(cfg["x"])
        return float(cfg["x"])

    runner = TrialRunner(sp, measure, name="t",
                         static=lambda cfg:
                         "too big" if cfg["x"] == 3 else None)
    best, trials = runner.search()
    assert 3 not in measured
    pruned = [t for t in trials if t.status == "pruned"]
    assert len(pruned) == 1 and pruned[0].reason == "too big"
    assert best.objective == 1.0


def test_failing_config_fails_trial_not_process():
    sp = _space(x=(1, 2, 3))

    def measure(cfg, budget):
        if cfg["x"] == 1:                 # the DEFAULT config fails
            raise RuntimeError("boom")
        return float(cfg["x"])

    best, trials = TrialRunner(sp, measure, name="t").search()
    failed = [t for t in trials if t.status == "failed"]
    assert len(failed) == 1 and "boom" in failed[0].reason
    assert failed[0].objective is None
    assert best.objective == 2.0          # the search survived


def test_successive_halving_converges_on_minimum():
    sp = _space(x=tuple(range(16)))
    calls = []

    def measure(cfg, budget):
        calls.append((cfg["x"], budget))
        return float(cfg["x"])

    runner = TrialRunner(sp, measure, name="t", halving_threshold=4,
                         base_budget=1, full_budget=4, eta=2)
    best, trials = runner.search()
    assert best.objective == 0.0
    assert best.budget == runner.full_budget      # winner fully measured
    # rungs shrink: everyone measured cheap, only survivors at full
    assert sum(1 for _, b in calls if b == 1) == 16
    assert sum(1 for _, b in calls if b == 4) <= 8


def test_env_knobs_applied_per_trial_and_restored():
    sp = SearchSpace([Knob("MXTPU_DATA_WORKERS", ("3", "5"),
                           kind="env")], name="t")
    seen = []

    def measure(cfg, budget):
        seen.append(int(mx.config.get("MXTPU_DATA_WORKERS")))
        return float(seen[-1])

    outside = os.environ.get("MXTPU_DATA_WORKERS")
    best, _ = TrialRunner(sp, measure, name="t").search()
    assert sorted(seen) == [3, 5]
    assert best.objective == 3.0
    assert os.environ.get("MXTPU_DATA_WORKERS") == outside  # restored


def test_measure_memo_scope_isolates_and_restores():
    from mxnet_tpu.symbol.passes import manager as pm
    with pm._LOCK:
        saved = dict(pm._MEASURE_MEMO)
    try:
        pm._MEASURE_MEMO.clear()
        pm._MEASURE_MEMO["sentinel"] = 1.0
        with pm.measure_memo_scope():
            assert not pm._MEASURE_MEMO        # trial sees a clean memo
            pm._MEASURE_MEMO["trial-junk"] = 2.0
        assert pm._MEASURE_MEMO == {"sentinel": 1.0}   # junk gone
    finally:
        with pm._LOCK:
            pm._MEASURE_MEMO.clear()
            pm._MEASURE_MEMO.update(saved)


# ---------------------------------------------------------------------------
# the trial journal: crash log + resume
# ---------------------------------------------------------------------------
def test_journal_roundtrip_skips_torn_lines(tmp_path):
    j = TrialJournal(str(tmp_path / "t.trials.jsonl"))
    entries = [Trial({"x": i}, f"id{i}", status="measured",
                     objective=float(i)).to_entry() for i in range(3)]
    for e in entries:
        j.append(e)
    with open(j.path, "a") as f:
        f.write('{"crc": 1, "e": {"config_id": "forged"}}\n')
        f.write('{"crc": 99, "e": {"conf')          # torn tail line
    got = j.load()
    assert [e["config_id"] for e in got] == ["id0", "id1", "id2"]


def test_resumed_search_reuses_journal(tmp_path):
    sp = _space(x=(1, 2, 3))
    j = TrialJournal(str(tmp_path / "t.trials.jsonl"))
    first = TrialRunner(sp, lambda c, b: float(c["x"]), journal=j,
                        name="t")
    first.search()
    calls = []
    second = TrialRunner(sp, lambda c, b: calls.append(c) or
                         float(c["x"]), journal=j, name="t")
    best, trials = second.search()
    assert calls == []                      # nothing re-measured
    assert all(t.status == "reused" for t in trials)
    assert best.objective == 1.0


# ---------------------------------------------------------------------------
# tuning records: round-trip, staleness, corruption, torn writes
# ---------------------------------------------------------------------------
def _record(digest="d" * 64, best=10.0):
    sp = SearchSpace([Knob("MXTPU_PALLAS_FUSION", ("auto", "1"),
                           kind="env"),
                      Knob("batch", (8, 16), kind="param")], name="t")
    return TuningRecord({
        "digest": digest, "name": "t", "workload": None,
        "objective": "step_bytes_per_row", "space": sp.describe(),
        "default_config": {"MXTPU_PALLAS_FUSION": "auto", "batch": 8},
        "default_value": 20.0,
        "best_config": {"MXTPU_PALLAS_FUSION": "1", "batch": 16},
        "best_value": best,
        "trials": {"run": 4, "pruned": 0, "reused": 0, "failed": 0},
        "search_wall_s": 1.0, "created": 1.0, "seed": 0})


def test_record_roundtrip_and_apply(tmp_path):
    store = TuneStore(str(tmp_path))
    rec = _record()
    path = store.put(rec)
    assert os.path.exists(path)
    back = store.get(rec.digest)
    assert back.data == rec.data
    assert back.improvement() == pytest.approx(0.5)
    assert back.env_items() == [("MXTPU_PALLAS_FUSION", "1")]
    env = {}
    params = back.apply(environ=env)
    assert env == {"MXTPU_PALLAS_FUSION": "1"}
    assert params == {"batch": 16}
    assert store.get("0" * 64) is None      # absent != corrupt


def test_stale_record_rejected_never_applied(tmp_path):
    store = TuneStore(str(tmp_path))
    rec = _record()
    store.put(rec, fingerprint="jax=0.0.0;mxtpu=0.0.0;fmt=0")
    with pytest.raises(TuneRecordError) as ei:
        store.get(rec.digest)
    assert ei.value.reason == "stale"
    before = mx.tune_report()["records_rejected"]
    assert store.load(rec.digest) is None   # fallback contract
    assert mx.tune_report()["records_rejected"] == before + 1


def test_corrupt_record_rejected_never_applied(tmp_path):
    store = TuneStore(str(tmp_path))
    rec = _record()
    path = store.put(rec)
    with open(path, "rb+") as f:
        f.truncate(os.path.getsize(path) - 7)
    with pytest.raises(TuneRecordError) as ei:
        store.get(rec.digest)
    assert ei.value.reason == "corrupt"
    assert store.load(rec.digest) is None
    ok, bad = store.verify()
    assert ok == 0 and bad and bad[0][1] == "corrupt"


@pytest.mark.chaos
def test_record_write_fault_never_tears_an_entry(tmp_path):
    """A crash at any byte of the record write (tune_trial byte-budget
    site) aborts the atomic_write temp file: the store simply has no
    entry — never a torn one."""
    store = TuneStore(str(tmp_path))
    faultinject.reset()
    with faultinject.inject("tune_trial:byte=40"):
        with pytest.raises(faultinject.FaultInjected):
            store.put(_record())
    assert faultinject.fired("tune_trial") == 1
    assert [n for n in os.listdir(str(tmp_path))
            if n.endswith(".mxtune")] == []
    store.put(_record())                    # store stays usable
    assert store.get("d" * 64) is not None


@pytest.mark.chaos
def test_record_truncated_below_rename_caught_by_crc(tmp_path):
    """Post-commit tearing (tune_trial bytes=N: storage lying below the
    rename) must be caught by the header CRC on load and rejected."""
    store = TuneStore(str(tmp_path))
    faultinject.reset()
    with faultinject.inject("tune_trial:bytes=64"):
        path = store.put(_record())
    assert os.path.getsize(path) == 64
    assert store.load("d" * 64) is None
    store.put(_record())                    # a re-search overwrites
    assert store.get("d" * 64) is not None


def test_default_store_configuration(tmp_path):
    with mx.config.override("MXTPU_TUNE_DIR", str(tmp_path / "t")):
        assert tune.default_store().directory == str(tmp_path / "t")
        with mx.config.override("MXTPU_TUNE_CACHE", "0"):
            assert tune.default_store() is None
    with mx.config.override("MXTPU_TUNE_DIR", None), \
            mx.config.override("MXTPU_COMPILE_CACHE_DIR",
                               str(tmp_path / "c")):
        assert tune.default_store().directory == \
            os.path.join(str(tmp_path / "c"), "tune")
    with mx.config.override("MXTPU_TUNE_DIR", None), \
            mx.config.override("MXTPU_COMPILE_CACHE_DIR", None):
        assert tune.default_store() is None


# ---------------------------------------------------------------------------
# MXTPU_PALLAS_TILES: loud validation, per-dimension override
# ---------------------------------------------------------------------------
def test_pallas_tiles_override_changes_selection():
    from mxnet_tpu.ops import pallas_fused as pf
    base = pf.select_tiles(512, 256, 64)
    with mx.config.override("MXTPU_PALLAS_TILES", "128,128"):
        assert pf.select_tiles(512, 256, 64) == (128, 128)
        # non-dividing override falls back per dimension
        assert pf.select_tiles(8, 256, 64) == (8, 128)
        assert pf.select_conv_tiles(64, 128, 32) == (64, 128)
    with mx.config.override("MXTPU_PALLAS_TILES", "128,64"):
        # an override Mosaic would refuse (64 lanes of 256) steers
        # nothing: the built-in scan answers for that dimension
        assert pf.select_tiles(512, 256, 64) == (128, 256)
    assert pf.select_tiles(512, 256, 64) == base


@pytest.mark.parametrize("bad", [
    "100,100",        # not multiples of 8
    "256",            # one value
    "256,128,64",     # three values
    "0,128",          # non-positive
    "-8,128",
    "2048,128",       # bm above the built-in maximum
    "256,1024",       # bn above the built-in maximum
    "a,b",            # not integers
])
def test_pallas_tiles_invalid_is_loud(bad):
    from mxnet_tpu.ops import pallas_fused as pf
    with mx.config.override("MXTPU_PALLAS_TILES", bad):
        with pytest.raises(MXNetError, match="MXTPU_PALLAS_TILES"):
            pf.select_tiles(512, 256, 64)


def test_invalid_tile_fails_trial_not_search():
    """A bad tile in the search space fails its TRIAL loudly; the
    search continues and the winner comes from the valid configs."""
    from mxnet_tpu.ops import pallas_fused as pf
    sp = SearchSpace([Knob("MXTPU_PALLAS_TILES",
                           ("", "256,128", "100,100"), kind="env")],
                     name="t")

    def measure(cfg, budget):
        tiles = pf.select_tiles(512, 256, 64)  # raises on the bad knob
        return float(tiles[0])

    best, trials = TrialRunner(sp, measure, name="t").search()
    failed = [t for t in trials if t.status == "failed"]
    assert len(failed) == 1
    assert failed[0].config["MXTPU_PALLAS_TILES"] == "100,100"
    assert "MXTPU_PALLAS_TILES" in failed[0].reason
    assert best is not None and best.objective in (256.0, 512.0)


# ---------------------------------------------------------------------------
# autotune end-to-end on the conv proxy (measured, CPU cost analysis)
# ---------------------------------------------------------------------------
def test_autotune_beats_default_and_warm_hits(tmp_path):
    """The round-15 core pin, in-process: the search measures the
    default, finds a strictly better config on the bytes-per-row
    objective, persists the record — and the second autotune of the
    same workload is a warm hit: zero trials, same answer."""
    store = TuneStore(str(tmp_path / "tune"))
    wl = mx.tune.workloads.conv_proxy(batch=4, batches=(4, 8))
    rec = tune.autotune(wl, store=store, seed=0, max_trials=6)
    assert rec.default_value is not None
    assert rec.best_value < rec.default_value          # strictly better
    assert rec.improvement() > 0
    assert os.path.exists(store.path_for(rec.digest))
    assert not os.path.exists(store.journal_path(rec.digest))

    before = mx.tune_report()
    seen = []
    warm = tune.autotune(wl, store=store, seed=0, max_trials=6,
                         on_trial=seen.append)
    after = mx.tune_report()
    assert seen == []                                  # zero trials
    assert warm.data == rec.data
    assert after["warm_hits"] == before["warm_hits"] + 1
    assert after["trials_run"] == before["trials_run"]
    assert after["searches"] == before["searches"]


def test_autotune_never_regresses_below_default(tmp_path):
    """When nothing beats the measured default, the record stores the
    default as best — tuning can't make a workload worse."""
    sp = _space(x=(1, 2, 3))

    class WL(tune.workloads.Workload):
        name = "mono"
        objective = "x"

        def measure(self, cfg, budget):
            return float(cfg["x"])        # default (x=1) is the optimum

    rec = tune.autotune(WL(sp), store=TuneStore(str(tmp_path)))
    assert rec.best_config == sp.default_config()
    assert rec.best_value == rec.default_value == 1.0
    assert rec.improvement() == 0.0


def test_static_hbm_pruning_bounds_batch(tmp_path):
    """The batch knob is bounded by measured peak-HBM headroom: a
    candidate whose compiled step peak exceeds the budget is pruned
    before measurement; the default batch is never pruned away."""
    probe = mx.tune.workloads.conv_proxy(batch=4, batches=(4, 64))
    big = dict(probe.space.default_config(), batch=64)
    peak = probe.static_peak_bytes(big)
    assert peak and peak > 0
    wl = mx.tune.workloads.conv_proxy(batch=4, batches=(4, 64),
                                      hbm_budget=peak - 1)
    assert wl.static(big) is not None                  # over budget
    assert wl.static(wl.space.default_config()) is None


# ---------------------------------------------------------------------------
# acceptance: a tuned process boots tuned (subprocess pins)
# ---------------------------------------------------------------------------
def _run_worker(tmp_path, tag, fault=None, timeout=600):
    out = str(tmp_path / f"{tag}.json")
    env = dict(os.environ,
               MXTPU_TUNE_DIR=str(tmp_path / "tune"),
               MXTPU_COMPILE_CACHE_DIR=str(tmp_path / "compile"),
               TUNE_WORKER_MAX_TRIALS="5")
    env.pop("MXTPU_FAULT_INJECT", None)
    if fault:
        env["MXTPU_FAULT_INJECT"] = fault
    r = subprocess.run(
        [sys.executable, os.path.join(_TESTS, "tune_worker.py"), out],
        cwd=_ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)
    if r.returncode == 0:
        with open(out) as f:
            return r, json.load(f)
    return r, None


def test_tuned_process_boots_tuned_zero_research(tmp_path):
    """THE acceptance pin: run 1 searches (trials measured, record +
    compile-cache entries written); run 2 — same stores — must perform
    ZERO search trials (warm record hit) and ZERO fresh XLA compiles
    (the tuned-batch step AOT-loads), and reach the same winner."""
    r, cold = _run_worker(tmp_path, "cold")
    assert cold is not None, r.stdout + r.stderr
    assert cold["searches"] == 1 and cold["trials_run"] >= 2
    assert cold["records_written"] == 1 and cold["warm_hits"] == 0
    assert cold["fresh_compiles"] >= 1

    r, warm = _run_worker(tmp_path, "warm")
    assert warm is not None, r.stdout + r.stderr
    assert warm["trials_run"] == 0, warm       # zero re-search
    assert warm["searches"] == 0, warm
    assert warm["warm_hits"] == 1, warm
    assert warm["fresh_compiles"] == 0, warm   # zero fresh compiles
    assert warm["cache_hits"] == cold["fresh_compiles"], (cold, warm)
    assert warm["cache_errors"] == 0, warm
    assert warm["digest"] == cold["digest"]
    assert warm["best_config"] == cold["best_config"]
    assert warm["best_value"] == cold["best_value"]


@pytest.mark.chaos
def test_sigkill_mid_search_resumes_from_journal(tmp_path):
    """The kill-mid-search chaos drill: SIGKILL at the 3rd trial-commit
    boundary. No record may exist after the kill (a torn search is
    never applied), the trial journal holds only complete CRC-valid
    lines, and the clean re-run REUSES them instead of re-measuring."""
    r, _ = _run_worker(tmp_path, "killed",
                       fault="tune_trial:trial=3:action=kill")
    assert r.returncode == -9, (r.returncode, r.stdout, r.stderr)
    assert "faultinject: SIGKILL at site 'tune_trial'" in r.stdout
    store_dir = str(tmp_path / "tune")
    assert [n for n in os.listdir(store_dir)
            if n.endswith(".mxtune")] == []        # no torn record
    journals = [n for n in os.listdir(store_dir)
                if n.endswith(".trials.jsonl")]
    assert len(journals) == 1
    lines = TrialJournal(os.path.join(store_dir, journals[0])).load()
    # the fault fires BEFORE trial 3's journal append: exactly the two
    # completed commits survive, each a valid line
    assert len(lines) == 2

    r, resumed = _run_worker(tmp_path, "resumed")
    assert resumed is not None, r.stdout + r.stderr
    assert resumed["trials_reused"] == 2, resumed  # journal replayed
    assert resumed["trials_run"] >= 1              # only the rest ran
    assert resumed["records_written"] == 1
    assert [n for n in os.listdir(store_dir)
            if n.endswith(".trials.jsonl")] == []  # record supersedes


# ---------------------------------------------------------------------------
# tools/tune.py verify: the regression gate
# ---------------------------------------------------------------------------
def _cli(tmp_path, *args):
    env = dict(os.environ)
    env.pop("MXTPU_FAULT_INJECT", None)
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "tune.py"),
         "--dir", str(tmp_path / "tune"), *args],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_cli_verify_exit_codes(tmp_path):
    """search → verify passes (0); a record whose stored best_value is
    doctored impossibly low re-measures as a regression (exit 2); a
    truncated record file fails integrity (exit 1)."""
    r = _cli(tmp_path, "search", "--workload", "conv", "--max-trials",
             "3", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    digest = json.loads(r.stdout.strip().splitlines()[-1])["digest"]

    r = _cli(tmp_path, "verify", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] == 1 and len(out["remeasured"]) == 1

    # doctor the stored claim: half the recorded best — the honest
    # re-measurement now exceeds it by far more than the tolerance
    store = TuneStore(str(tmp_path / "tune"))
    rec = store.get(digest)
    rec.data["best_value"] = rec.data["best_value"] * 0.5
    store.put(rec)
    r = _cli(tmp_path, "verify", "--json")
    assert r.returncode == 2, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["regressions"]

    # integrity failure dominates: a truncated entry is exit 1
    path = store.path_for(digest)
    with open(path, "rb+") as f:
        f.truncate(32)
    r = _cli(tmp_path, "verify", "--json")
    assert r.returncode == 1, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# data-pipeline workload: env knobs reach the pipeline
# ---------------------------------------------------------------------------
def test_data_pipeline_workload_measures_under_knobs():
    sp = SearchSpace([Knob("MXTPU_DATA_WORKERS", ("1", "2"),
                           kind="env")], name="dp")

    def make_iter():
        x = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        return mx.io.NDArrayIter(x, None, batch_size=8)

    wl = mx.tune.workloads.DataPipelineWorkload(
        "dp", make_iter, batches=4, space=sp)
    best, trials = TrialRunner(sp, wl.measure, name="dp").search()
    assert best is not None and best.objective > 0
    assert all(t.status == "measured" for t in trials)
    assert all(t.metrics["batches"] >= 4 for t in trials)


# ---------------------------------------------------------------------------
# observability: the tune collector in the unified report
# ---------------------------------------------------------------------------
def test_tune_report_rides_unified_telemetry(tmp_path):
    store = TuneStore(str(tmp_path))
    sp = _space(x=(1, 2))

    class WL(tune.workloads.Workload):
        name = "obs"
        objective = "x"

        def measure(self, cfg, budget):
            return float(cfg["x"])

    before = mx.tune_report()
    tune.autotune(WL(sp), store=store)
    rep = mx.tune_report()
    assert rep["searches"] == before["searches"] + 1
    assert rep["trials_run"] == before["trials_run"] + 2
    assert rep["records_written"] == before["records_written"] + 1
    assert any(s["name"] == "obs" for s in rep["recent_searches"])
    # the collector rides the unified report under its registered name
    full = mx.telemetry.report()
    assert "tune" in full["subsystems"]
    assert full["subsystems"]["tune"]["searches"] == rep["searches"]
