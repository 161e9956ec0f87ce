"""The cells at a tiny size on the CPU: each plain reference against the
system, the control that has to come out as not correct, a run with the
timed path broken underneath, and a cell, a configuration, a traffic
mix, a driver, a reader and a metric added as new files only."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import training  # noqa: E402


def _float32(cell):
    """The same cell computing in float32: what separates the program
    from its reference is then rounding order alone."""
    cell.config = dict(cell.config, compute_dtype=None)
    return cell


# -- each reference against the system --------------------------------------
@pytest.mark.parametrize("workload", ["resnet50-train", "lstm-lm-train"])
def test_training_reference_agrees_with_the_system(workload):
    cell = _float32(harness.load_cell(workload, rehearsal=True))
    session = cell.driver.setup(cell, 7)
    got, want = session["first"], training.reference(cell, 7)
    cell.driver.close(session)
    assert len(got["losses"]) == cell.traffic["first_steps"] == 3
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    for k, ref in want["first_grad_norms"].items():
        assert got["first_grad_norms"][k] == pytest.approx(
            ref, rel=2e-3, abs=1e-6), k
    assert harness.update_difference(got["first_update"],
                                     want["first_update"]) < 1e-3
    assert harness.worst_leaf_gap(got["change_norms"],
                                  want["change_norms"])[0] < 1e-3


def test_serving_reference_agrees_with_the_system():
    cell = _float32(harness.load_cell("resnet50-serve-steady",
                                      rehearsal=True))
    weights = cell.model.make_weights(cell.sizes, 3)
    predictor = cell.model.build(cell.config, cell.sizes, "serve", weights)
    rows, _ = cell.model.make_rows(cell.sizes, 3, 6)
    want = cell.model.reference_forward(cell.sizes, weights, rows)
    got = np.concatenate([np.asarray(predictor.predict(rows[:2])),
                          np.asarray(predictor.predict(rows[2:]))])
    assert got.shape == want.shape == (6, cell.sizes["classes"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    # rows differ, so their answers do
    assert np.abs(want[0] - want[1]).max() > 1e-4


# -- the control -------------------------------------------------------------
@pytest.mark.parametrize("workload,number", [
    ("resnet50-train", "first_step_diff"),
    ("lstm-lm-train", "first_step_diff"),
    ("resnet50-serve-steady", "output_rel_l2"),
])
def test_lower_precision_control_stands_apart(workload, number):
    """The reference computed in 8-bit floats, in the program's place,
    against the reference: far from it where the float32 program is
    close. (The limits themselves were set on the chip at the cells' own
    sizes, PERF.md section 2; at this size the bfloat16 program's own
    distance is another.)"""
    cell = harness.load_cell(workload, rehearsal=True)
    rows = {name: value for name, value, _, _ in
            cell.driver.control(cell, 5)}
    assert rows[number] > 2e-3
    fine = _float32(harness.load_cell(workload, rehearsal=True))
    if number == "output_rel_l2":
        weights = fine.model.make_weights(fine.sizes, 5)
        predictor = fine.model.build(fine.config, fine.sizes, "serve",
                                     weights)
        x, _ = fine.model.make_rows(fine.sizes, 5, 4)
        want = fine.model.reference_forward(fine.sizes, weights, x)
        sound = harness.compare_outputs(
            [np.asarray(predictor.predict(x))], [want],
            fine.limits)[0][1]
    else:
        session = fine.driver.setup(fine, 5)
        sound = harness.update_difference(
            session["first"]["first_update"],
            training.reference(fine, 5)["first_update"])
        fine.driver.close(session)
    assert rows[number] > 3 * sound


# -- a run with the timed path broken underneath -----------------------------
def _run(argv, capsys):
    import run
    run.main(argv)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    from mxnet_tpu.parallel import TrainStep
    real = TrainStep.__init__

    def frozen(self, *a, **kw):
        real(self, *a, **kw)
        self.lr = 0.0          # the step runs and moves nothing

    monkeypatch.setattr(TrainStep, "__init__", frozen)
    line = _run(["--workload", "lstm-lm-train", "--seed", "11", "--seconds",
                 "0.5", "--trace", "0", "--rehearsal", "1"], capsys)
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 0


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from mxnet_tpu.serving import Predictor
    real = Predictor._run_bucket

    def shifted(self, arrays, rows, bucket):
        return [np.roll(o, 1, axis=-1) for o in real(self, arrays, rows,
                                                     bucket)]

    monkeypatch.setattr(Predictor, "_run_bucket", shifted)
    line = _run(["--workload", "resnet50-serve-saturated", "--seed", "12",
                 "--seconds", "1", "--trace", "0", "--rehearsal", "1"],
                capsys)
    assert line["correct"] is False
    assert set(line["metrics"]) == {"serve_throughput", "setup_s"}


# -- new files only ----------------------------------------------------------
DUMMY_CONFIG_PY = '''
import numpy as np


def scale(sizes, seed):
    return float(seed % 7 + 1) * sizes["width"]
'''

DUMMY_DRIVER_PY = '''
import time
import jax
import jax.numpy as jnp


def setup(cell, seed):
    x = jnp.full((cell.sizes["width"],), cell.model.scale(cell.sizes, seed))
    double = jax.jit(lambda v: v * 2.0)
    double(x).block_until_ready()
    return {"x": x, "double": double, "seed": seed}


def window(cell, session, seconds):
    n, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        y = session["double"](session["x"])
        n += 1
    y.block_until_ready()
    return {"metrics": {"dummy_rate": n / (time.perf_counter() - t0)},
            "attempted": n, "failed": 0, "y": y,
            "facts": {"steps": n, "answer": 42.0}}


def check(cell, session, result):
    want = 2.0 * cell.model.scale(cell.sizes, session["seed"])
    import numpy as np
    return [("doubling", abs(float(np.asarray(result["y"])[0]) - want), 0.0,
             "exact")]


def close(session):
    session.clear()
'''

DUMMY_READER_PY = '''
def read(params, facts):
    return facts["window"]["answer"] * params["times"]
'''


def test_a_cell_is_added_with_new_files_and_entries_alone(tmp_path):
    tree = tmp_path / "tree"
    shutil.copytree(BENCH, tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tree / "benchmark").rglob("*")
              if p.is_file()}
    b = tree / "benchmark"
    (b / "configs" / "dummy.json").write_text(json.dumps({
        "name": "dummy", "source": "https://example.org/dummy",
        "module": "dummy.py", "sizes": {"width": 64},
        "rehearsal_sizes": {"width": 8}, "limits": {"loop": {}}}))
    (b / "configs" / "dummy.py").write_text(DUMMY_CONFIG_PY)
    (b / "traffic" / "dummy-traffic.json").write_text(json.dumps({
        "driver": "dummy_loop", "role": "loop", "trace_seconds": 1}))
    (b / "drivers" / "dummy_loop.py").write_text(DUMMY_DRIVER_PY)
    (b / "readers" / "dummy_reader.py").write_text(DUMMY_READER_PY)
    (b / "layer_metrics" / "dummy_answer.json").write_text(json.dumps({
        "reader": "dummy_reader", "params": {"times": 2}}))
    bench = harness.benchmark_json(proposed=False)
    bench["configs"].append({
        "name": "dummy", "source": "https://example.org/dummy",
        "file": "benchmark/configs/dummy.json", "reduced": [], "why": "x"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy", "traffic": "dummy-traffic",
        "chips": 1, "why": "x"})
    bench["end_to_end"].append({
        "name": "dummy_rate", "unit": "calls/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["dummy-cell"]})
    bench["per_layer"].append({
        "name": "dummy_answer", "unit": "x", "better": "higher",
        "source": "program_counter", "layer": "Dummy",
        "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}

    def run(*argv):
        out = subprocess.run(
            [sys.executable, str(b / "run.py"), *argv], env=env, cwd=tree,
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()

    assert any(line.startswith("dummy-cell: dummy under dummy-traffic")
               for line in run("--list"))
    common = ["--workload", "dummy-cell", "--seed", "3000000001",
              "--seconds", "0.3", "--rehearsal", "1"]
    line = json.loads(run(*common, "--trace", "0")[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"dummy_rate", "setup_s"}
    traced = json.loads(run(*common, "--trace", "1")[-1])
    # the new metric, and the one that lists no cells and moves setup_s
    assert traced["metrics"]["dummy_answer"]["value"] == 84.0
    assert "xla_programs.setup" in traced["metrics"]
    assert traced["device"]["window_s"] > 0
    # nothing that was there was edited
    for p, content in before.items():
        assert p.read_bytes() == content, p
