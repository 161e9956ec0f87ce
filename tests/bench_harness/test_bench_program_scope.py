"""The reader of the program's own scope table (``readers/
program_scope.py``) and the six metrics of ISSUE 39 that read it: a loop
and its trips counted once, nothing where there is no table, and metric
files whose reader and patterns exist. Compared by name, never by a
metric's place in ``BENCHMARK.json``."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import trace_reduce  # noqa: E402

CELLS = ("resnet50-train", "lstm-lm-train", "nemotron3-super-train-8k",
         "ouro-2.6b-train-4k", "moonlight-16b-a3b-train-8k")
#: metric -> (layer, better, cells, a scope path its pattern has to match,
#: one it must not)
METRICS = {
    "scoped_time_share.train": (
        "Step program", "higher", CELLS, "mx_loop_body/mx_norm", None),
    # only where the update stands as fusions of its own: elsewhere XLA
    # fuses it into the weight gradient's product, which keeps its name
    "opt_update_time_share.train": (
        "Step program", "lower", ("ouro-2.6b-train-4k",
                                  "moonlight-16b-a3b-train-8k"),
        "mx_opt_update", "mx_loss"),
    "head_time_share.train": (
        "Kernels", "lower", ("lstm-lm-train", "nemotron3-super-train-8k",
                             "moonlight-16b-a3b-train-8k"),
        "mx_head/mx_dense", "mx_exit_head"),
    "rnn_scan_time_share.train": (
        "Kernels", "lower", ("lstm-lm-train",), "mx_rnn_scan",
        "mx_rnn_input"),
    "rnn_input_time_share.train": (
        "Kernels", "lower", ("lstm-lm-train",), "mx_rnn_input",
        "mx_rnn_scan"),
    "bn_act_time_share.train": (
        "Kernels", "lower", ("resnet50-train",), "mx_op_BatchNorm",
        "mx_op_Convolution"),
}


def _reader():
    return harness.load_module(os.path.join(BENCH, "readers",
                                            "program_scope.py"))


def _facts(ops, modules=()):
    dev = trace_reduce.DeviceTrace("/device:TPU:0", ops=list(ops),
                                   modules=list(modules))
    return {"trace": trace_reduce.Trace([dev], []), "window": {"steps": 2}}


OPS = [(0.0, 1.0, "%before = f32[] add()"),
       (1.0, 4.0, "%while.1 = () while(%t), condition=%c, body=%b"),
       (1.0, 1.5, "%fusion.1 = f32[] fusion()"),
       (2.5, 0.5, "%fusion.2 = f32[] fusion()"),
       (3.0, 1.5, "%fusion.1 = f32[] fusion()"),
       (4.5, 0.5, "%fusion.2 = f32[] fusion()"),
       (5.0, 3.0, "%after = f32[] add()")]
TABLE = {"fusion.1": "mx_loop_body/mx_attn_fwd", "fusion.2": "mx_loop_body",
         "after": "mx_opt_update"}


def test_the_reader_counts_a_loop_once_and_asks_the_program(monkeypatch,
                                                           capsys):
    """The ``while`` event spans its trips' events: shares are of unions.
    The table is the program's, for the module the trace shows running."""
    import mxnet_tpu as mx
    asked = []

    def table_of(name):
        asked.append(name)
        return TABLE if name == "jit_mx_train_step" else None

    monkeypatch.setattr(mx.telemetry.trace, "scope_table", table_of)
    reader = _reader()
    del reader._PRINTED[:]
    facts = _facts(OPS, [(0.0, 0.5, "jit_make(12)"),
                         (0.0, 8.0, "jit_mx_train_step(345)")])
    share = lambda rx: reader.read({"scopes": rx}, facts)  # noqa: E731
    assert share("^mx_") == pytest.approx(87.5)         # all but `before`
    assert asked[0] == "jit_mx_train_step"              # the longest first
    assert share("^mx_loop_body") == pytest.approx(50.0)
    assert share("(^|/)mx_attn_fwd$") == pytest.approx(37.5)
    assert share("(^|/)mx_opt_update$") == pytest.approx(37.5)
    assert share("^mx_nothing") is None
    out = capsys.readouterr().out
    printed = [line for line in out.splitlines()
               if line.startswith("program_scope: ms a step by scope ")]
    assert len(printed) == 1                            # once a process
    per = json.loads(printed[0].split("scope ", 1)[1])
    assert per == {"mx_loop_body": 500.0, "mx_loop_body/mx_attn_fwd": 1500.0,
                   "mx_opt_update": 1500.0}
    bare = [line for line in out.splitlines()
            if line.startswith("program_scope: ms a step under no scope")]
    assert "500.0" in bare[0] and "before" in bare[0] \
        and "while.1" not in bare[0]


def test_no_table_no_number(monkeypatch):
    """A program from before it owned its table (the parent, run under
    this PR's benchmark files), a program without a step acquired, a
    trace without a device: nothing, and nothing raised."""
    import mxnet_tpu as mx
    reader = _reader()
    facts = _facts(OPS, [(0.0, 8.0, "jit_mx_train_step(1)")])
    monkeypatch.setattr(mx.telemetry.trace, "scope_table", lambda name: None)
    assert reader.read({"scopes": "^mx_"}, facts) is None
    monkeypatch.setattr(mx.telemetry.trace, "scope_table", lambda name: {})
    assert reader.read({"scopes": "^mx_"}, facts) is None
    monkeypatch.delattr(mx.telemetry.trace, "scope_table")
    assert reader.read({"scopes": "^mx_"}, facts) is None
    assert reader.read({"scopes": "^mx_"},
                       {"trace": trace_reduce.Trace([], []),
                        "window": {}}) is None


@pytest.mark.parametrize("name", list(METRICS))
def test_a_metric_names_a_reader_and_a_pattern_that_exist(name):
    layer, better, cells, hit, miss = METRICS[name]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "train_throughput", "workloads": list(cells)}
    known = {w["name"] for w in bench["workloads"]}
    assert set(cells) <= known
    spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                          name + ".json"))
    assert spec["reader"] == "program_scope"
    assert os.path.isfile(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    rx = re.compile(spec["params"]["scopes"])
    assert rx.search(hit) and (miss is None or not rx.search(miss))
    # the scopes a pattern names are opened somewhere in the program
    opened = set()
    for top, _, files in os.walk(os.path.join(ROOT, "mxnet_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(top, f)) as src:
                    opened |= set(re.findall(r'"(mx_\w+)"', src.read()))
    assert hit.split("/")[-1] in opened | {"mx_op_BatchNorm"}
    assert '"mx_op_" + node.op' in open(os.path.join(
        ROOT, "mxnet_tpu", "symbol", "symbol.py")).read()


def test_the_metrics_come_out_of_a_traced_rehearsal_run(capsys):
    """``lstm-lm-train`` end to end on the CPU: the four metrics the cell
    lists are in the result line (a rehearsal is no measurement)."""
    import run
    run.main(["--workload", "lstm-lm-train", "--seed", "11", "--seconds",
              "1", "--trace", "1", "--rehearsal", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["metrics"]
    for name, (_, _, cells, _, _) in METRICS.items():
        assert (name in got) == ("lstm-lm-train" in cells), name
    assert 0 < got["rnn_scan_time_share.train"]["value"] < 100
    assert got["scoped_time_share.train"]["value"] \
        >= got["rnn_scan_time_share.train"]["value"]
