"""The names the program gives the parts of its step programs
(``jax.named_scope("mx_...")``), and the table that puts them on a
compiled program's instructions (``telemetry.trace.scope_table``).

Small forms of the steps the benchmark times (each cell at its
rehearsal sizes, on the CPU): the scopes that existed before ISSUE 39
keep their paths, the new ones name instructions, and the lowered text of
every step that existed then, debug information stripped, is the text it
had: scopes are metadata, and an option a later cell's layers brought
(ISSUE 41's) changes nothing where it is not taken. Nothing here is a
time."""
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.telemetry import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

#: what the parent of ISSUE 39 (commit 60a7c02) gave, computed there with
#: ``_lowered`` below: sha256 of the step's lowered text without debug
#: information, and the scope paths of its compiled text on the CPU
PARENT = {
    "lstm-lm-train": (
        "fadd698662e942bb9bdb0a691e911ddb16f877c123d04d53178197b31832c5ec",
        {"mx_rnn_input", "mx_rnn_scan"}),
    "nemotron3-super-train-8k": (
        "f4169444813cd31c901bcbe93c46a76dd09336db3455a863829bd0b001d24d92",
        {"mx_attn_fwd", "mx_moe_combine", "mx_moe_dispatch",
         "mx_moe_gmm_down", "mx_moe_gmm_up", "mx_moe_latent",
         "mx_moe_route", "mx_moe_score", "mx_moe_shared", "mx_ssd_conv",
         "mx_ssd_fwd", "mx_ssd_fwd/mx_ssd_fwd", "mx_ssd_gate"}),
    # since PR 49 a unit holds a gated MLP's first product (``ops.seq.
    # gated_mlp``): its backward multiplies once less, here and in the
    # Moonlight step (67 products in this text for 68, like units being one
    # function of it; 88 for 90 there, the dense layer's and the shared
    # experts'), and the scan stacks the product with what else it holds
    "ouro-2.6b-train-4k": (
        "291d29eed6397feba7cb17b3832ba94ef5ecbecfaf1fd60e3cfa24b3256aeb37",
        {"mx_attn_fwd", "mx_exit_gate", "mx_exit_gate/mx_exit_gate",
         "mx_exit_gate/mx_exit_gate/mx_exit_gate", "mx_exit_head",
         "mx_loop_body", "mx_loop_body/mx_attn_fwd",
         "mx_loop_body/mx_gated_mlp", "mx_loop_body/mx_rope", "mx_rope"}),
    # since PR 48 ``_combine`` rounds the routed sum where it forms it, so
    # that conversion stands before the shared experts' lines and not after
    # them, and ``_dispatch_pooled`` takes the experts' row counts before
    # the gather: the same operations in another order (the pool of 352
    # rows at the rehearsal sizes is no whole tile: the plain moves). The
    # Nemotron step rounded there already and is the parent's to the letter
    "moonlight-16b-a3b-train-8k": (
        "b0a57397910961b5bef1de494e9f41d029e1ed5ce341769dcd969c7dbc3e6c96",
        {"mx_attn_fwd", "mx_gated_mlp", "mx_mla_kv_down", "mx_mla_kv_up",
         "mx_mla_out", "mx_mla_q", "mx_mla_rope", "mx_mla_rope/mx_rope",
         "mx_moe_combine", "mx_moe_dispatch", "mx_moe_gmm_down",
         "mx_moe_gmm_up", "mx_moe_route", "mx_moe_score",
         "mx_moe_shared/mx_gated_mlp"}),
    "resnet50-train": (
        "a9850af6e761c9c7012329457e57efd95a4ed85646b029b8d7975e8a2377be90",
        set()),
}

#: the scopes ISSUE 39 added; each opens where its work is emitted
NEW = {"mx_opt_update", "mx_loss", "mx_metric", "mx_cast", "mx_head",
       "mx_dense", "mx_embed", "mx_norm", "mx_attn_proj", "mx_mamba_proj"}
#: a scope that holds whole layers: the only old scope a new one may be
#: opened inside (its readers match ``^mx_loop_body``)
CONTAINERS = {"mx_loop_body"}


def _is_new(scope):
    return scope in NEW or scope.startswith("mx_op_")


def _lowered(name):
    """The cell's step at its rehearsal sizes, lowered as the tests that
    pinned three of these texts before lower it."""
    cell = harness.load_cell(name, rehearsal=True)
    sizes = cell.sizes
    if name == "resnet50-train":
        session = cell.driver.setup(cell, 0)
        fused = session["module"]._fused
        batch = session["ring"][0]
        return fused.lowered({fused.data_names[0]: batch.data[0].data,
                              fused.label_names[0]: batch.label[0].data})
    mx.random.seed(0)       # the step's base key is a constant of its text
    step = cell.model.build(cell.config, sizes, "step",
                            cell.model.make_weights(sizes, 0)).step
    step._init_state()
    step._build_step()
    length = sizes.get("seq_len", sizes.get("bptt"))
    return step._step_jit.lower(
        step._pvals, step._opt_state,
        jnp.zeros((sizes["batch"], length), jnp.int32),
        jnp.zeros((sizes["batch"] * length,), jnp.int32), step._t_dev,
        jnp.asarray(0.1, jnp.float32))


_STEPS = {}


def _step_of(name):
    """``(lowered, {instruction: scope path})``, built once a cell."""
    if name not in _STEPS:
        lowered = _lowered(name)
        _STEPS[name] = (lowered, trace.hlo_scopes(
            lowered.compile().as_text(), path=True))
    return _STEPS[name]


@pytest.fixture(params=list(PARENT))
def step(request):
    return (request.param,) + _step_of(request.param)


def test_a_step_s_lowered_text_is_the_parent_s(step):
    """Scopes are metadata: with debug information stripped, which is how
    JAX hashes a program for its cache, each step is the program it was.
    (This is what makes a scopes-only change safe to measure, and what
    hands it the parent's executable where a cache holds one.)"""
    name, lowered, _ = step
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() \
        == PARENT[name][0]


def test_the_old_scopes_keep_their_paths(step):
    """The accepted readers anchor their patterns at the start of a path
    (``^mx_moe_``, ``^mx_loop_body``) or at both ends
    (``^mx_mla_(q|kv_down|kv_up|out)$``, ``(^|/)mx_attn_fwd$``): the paths
    made of the parent's scopes alone are the parent's, no new scope
    stands before an old one, and none stands inside an old one but a
    container of layers."""
    name, _, table = step
    paths = set(table.values())
    assert {p for p in paths
            if not any(_is_new(s) for s in p.split("/"))} == PARENT[name][1]
    for path in paths:
        parts = path.split("/")
        for i, scope in enumerate(parts):
            if _is_new(scope):
                assert all(s in CONTAINERS or _is_new(s)
                           for s in parts[:i]), path
                assert all(_is_new(s) for s in parts[i:]), path


@pytest.mark.parametrize("cell,scopes", [
    ("lstm-lm-train", ["mx_opt_update", "mx_loss", "mx_dense", "mx_embed"]),
    ("nemotron3-super-train-8k",
     ["mx_opt_update", "mx_loss", "mx_head/mx_dense", "mx_embed",
      "mx_norm", "mx_attn_proj", "mx_mamba_proj"]),
    ("ouro-2.6b-train-4k",
     ["mx_opt_update", "mx_embed", "mx_loop_body/mx_norm",
      "mx_loop_body/mx_attn_proj"]),
    ("moonlight-16b-a3b-train-8k",
     ["mx_opt_update", "mx_loss", "mx_head/mx_dense", "mx_embed",
      "mx_norm"]),
    ("qwen3-next-80b-a3b-train-8k",
     ["mx_opt_update", "mx_loss", "mx_head/mx_dense", "mx_embed", "mx_norm",
      "mx_attn_proj", "mx_gdn_proj", "mx_gdn_conv", "mx_gdn_rule",
      "mx_gdn_gate", "mx_attn_qk_norm", "mx_attn_gate"]),
    ("lfm2-24b-a2b-train-8k",
     ["mx_opt_update", "mx_loss", "mx_head/mx_dense", "mx_embed", "mx_norm",
      "mx_attn_proj", "mx_attn_qk_norm", "mx_sconv_proj", "mx_sconv_gate",
      "mx_sconv_conv"]),
    ("resnet50-train",
     ["mx_opt_update", "mx_loss", "mx_metric", "mx_op_Convolution",
      "mx_op_BatchNorm", "mx_op_Activation", "mx_op_Pooling",
      "mx_op_FullyConnected", "mx_op_SoftmaxOutput"]),
])
def test_the_new_scopes_name_instructions(cell, scopes):
    paths = set(_step_of(cell)[1].values())
    assert not [s for s in scopes if s not in paths]


#: the scopes ISSUE 41 added (a delta-rule layer's four parts, the gated
#: attention's head norms and gate), and the scopes its cell shares with
#: the older cells, whose paths are theirs
ADDED_41 = {"mx_gdn_proj", "mx_gdn_conv", "mx_gdn_rule", "mx_gdn_gate",
            "mx_attn_qk_norm", "mx_attn_gate"}
SHARED_41 = {"mx_attn_fwd", "mx_rope", "mx_moe_combine", "mx_moe_dispatch",
             "mx_moe_gmm_down", "mx_moe_gmm_up", "mx_moe_route",
             "mx_moe_score", "mx_moe_shared", "mx_moe_shared/mx_gated_mlp"}


def test_the_newest_cell_s_scopes_stand_beside_the_shared_ones():
    """``qwen3-next-80b-a3b-train-8k`` has no parent to be compared with:
    the scopes it shares with the older cells have the paths the accepted
    readers match there, its own scopes enclose none of them and stand
    inside none, and no older cell's step holds one of its scopes."""
    paths = set(_step_of("qwen3-next-80b-a3b-train-8k")[1].values())
    old = {p for p in paths if not any(
        _is_new(s) or s in ADDED_41 for s in p.split("/"))}
    assert old == SHARED_41
    for path in paths:
        parts = path.split("/")
        if ADDED_41 & set(parts):
            assert set(parts) <= ADDED_41, path
    assert ADDED_41 <= paths
    for name in PARENT:
        assert not [p for p in _step_of(name)[1].values()
                    if ADDED_41 & set(p.split("/"))], name


#: the scopes ISSUE 43 added (several residual streams: a layer's maps, the
#: mix it reads, the mix it writes, the copy into streams and their sum),
#: and the scopes its cell shares with Moonlight's, whose paths are theirs
ADDED_43 = {"mx_mhc_maps", "mx_mhc_pre", "mx_mhc_post", "mx_mhc_in",
            "mx_mhc_out"}


def test_the_streams_scopes_stand_beside_moonlight_s():
    """``xing4.0-29b-a4b-train-4k`` has no parent to be compared with: the
    paths made of older scopes alone are exactly Moonlight's (a query
    latent's two products and norm stay under ``mx_mla_q``; the YaRN
    frequencies open nothing), its own scopes enclose none of them and
    stand inside none, and no older cell's step holds one of its
    scopes."""
    paths = set(_step_of("xing4.0-29b-a4b-train-4k")[1].values())
    old = {p for p in paths if not any(
        _is_new(s) or s in ADDED_43 for s in p.split("/"))}
    assert old == PARENT["moonlight-16b-a3b-train-8k"][1]
    for path in paths:
        parts = path.split("/")
        if ADDED_43 & set(parts):
            assert len(parts) == 1, path
    assert ADDED_43 <= paths
    assert {"mx_opt_update", "mx_loss", "mx_head/mx_dense", "mx_embed",
            "mx_norm", "mx_cast"} <= paths
    for name in list(PARENT) + ["qwen3-next-80b-a3b-train-8k"]:
        assert not [p for p in _step_of(name)[1].values()
                    if ADDED_43 & set(p.split("/"))], name


#: the scopes ISSUE 47 added (a gated short convolution's products, its
#: two gates and its taps); its cell has no shared expert, so no
#: ``mx_moe_shared``
ADDED_47 = {"mx_sconv_proj", "mx_sconv_gate", "mx_sconv_conv"}


def test_the_short_convolution_s_scopes_stand_beside_the_shared_ones():
    """``lfm2-24b-a2b-train-8k`` has no parent to be compared with: the
    paths made of older scopes alone are the ones the accepted readers
    match in the sibling cells, its own scopes stand alone, and no older
    cell's step holds one of them."""
    paths = set(_step_of("lfm2-24b-a2b-train-8k")[1].values())
    old = {p for p in paths if not any(
        _is_new(s) or s in ADDED_47 | ADDED_41 for s in p.split("/"))}
    assert old == {"mx_attn_fwd", "mx_rope", "mx_gated_mlp",
                   "mx_moe_combine", "mx_moe_dispatch", "mx_moe_gmm_down",
                   "mx_moe_gmm_up", "mx_moe_route", "mx_moe_score"}
    for path in paths:
        if ADDED_47 & set(path.split("/")):
            assert "/" not in path, path
    assert ADDED_47 | {"mx_attn_qk_norm"} <= paths
    for name in list(PARENT) + ["qwen3-next-80b-a3b-train-8k",
                                "xing4.0-29b-a4b-train-4k"]:
        assert not [p for p in _step_of(name)[1].values()
                    if ADDED_47 & set(p.split("/"))], name


def _rnn_then_fc():
    seq = mx.sym.RNN(mx.sym.Variable("data"), mx.sym.Variable("p"),
                     mx.sym.Variable("s"), mx.sym.Variable("c"),
                     state_size=4, num_layers=1, mode="lstm", name="rnn")
    net = mx.sym.FullyConnected(seq, num_hidden=3, name="fc")
    arrays = {"data": jnp.zeros((5, 2, 3)),
              "p": jnp.zeros((4 * 4 * (3 + 4 + 2),)),
              "s": jnp.zeros((1, 2, 4)), "c": jnp.zeros((1, 2, 4)),
              "fc_weight": jnp.zeros((3, 8)), "fc_bias": jnp.zeros((3,))}
    return net, arrays


def test_an_operator_that_names_its_parts_gets_no_operator_scope():
    """``Symbol._apply_node_op`` opens ``mx_op_<op>`` unless the
    operator's registration says that it names its own parts:
    ``mx_op_RNN`` would enclose ``mx_rnn_scan``."""
    from mxnet_tpu.ops import registry
    for op in ("RNN", "RMSNorm", "Mamba2Mixer", "GatedDeltaNet", "LatentMoE",
               "GatedMoE", "CausalGQAttention", "LatentAttention",
               "GatedMLP", "RoPE", "ExitGate", "HyperConnectionMaps",
               "HyperConnectionPre", "HyperConnectionPost",
               "HyperConnectionSpread", "HyperConnectionMerge"):
        assert registry.get_op(op).names_its_parts, op
    for op in ("Convolution", "BatchNorm", "FullyConnected", "Embedding"):
        assert not registry.get_op(op).names_its_parts, op
    net, arrays = _rnn_then_fc()
    text = jax.jit(lambda a: net.eval_arrays(a)[0]).lower(
        arrays).compile().as_text()
    paths = set(trace.hlo_scopes(text, path=True).values())
    assert "mx_op_FullyConnected" in paths
    assert {p for p in paths if "mx_rnn_" in p} and \
        not [p for p in paths if "mx_op_RNN" in p]


def test_the_eager_walk_of_a_placed_graph_opens_no_operator_scope(
        monkeypatch):
    """A group2ctx Executor walks its graph unjitted every step
    (``eval_arrays_ex`` with a ``device_map``): no ``mx_op_*`` scope is
    opened there, where nothing would read it. The same graph walked
    for a program opens one an operator."""
    net, arrays = _rnn_then_fc()
    opened = []
    scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: opened.append(name) or scope(name))
    net.eval_arrays(arrays, device_map={})
    assert not [n for n in opened if n.startswith("mx_op_")]
    net.eval_arrays(arrays)
    assert [n for n in opened if n.startswith("mx_op_")] \
        == ["mx_op_FullyConnected"]


# -- the table knows whose names it carries ----------------------------------
_COMPILES = []
_LISTENING = []


def _count_compiles():
    import jax.monitoring
    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: _COMPILES.append(event)
            if event == harness.COMPILE_EVENT else None)
    del _COMPILES[:]
    return _COMPILES


@pytest.fixture()
def own_jax_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's, holding
    every program however fast it compiled; the process's own afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    before = [getattr(jax.config, n) for n in names]
    for n, value in zip(names, (str(tmp_path), True, 0)):
        jax.config.update(n, value)     # tests run with the cache off
    compilation_cache.reset_cache()
    yield str(tmp_path)
    for n, value in zip(names, before):
        jax.config.update(n, value)
    compilation_cache.reset_cache()


def _old_tree():
    def step(x):
        return jnp.sum(jnp.tanh(x @ x.T))
    return jax.jit(step)


def _this_tree():
    def step(x):
        with jax.named_scope("mx_probe"):
            y = jnp.tanh(x @ x.T)
        return jnp.sum(y)
    return jax.jit(step)


def test_a_table_from_a_cached_executable_is_rebuilt_once(own_jax_cache):
    """A cache holds the step as a tree without the scope compiled it.
    The same program from this tree hashes to the same key (names are
    stripped first) and is handed that executable: its text has none of
    our names. ``scope_table`` sees that the frames are not its own,
    compiles once beside the cache, counts it, and names the cached
    executable's instructions."""
    x = np.ones((8, 8), np.float32)     # no program of its own
    _old_tree()(x).block_until_ready()
    entries = sorted(os.listdir(own_jax_cache))
    assert len(entries) == 1 and entries[0].startswith("jit_step-")
    fn = _this_tree()
    fn(x).block_until_ready()
    assert sorted(os.listdir(own_jax_cache)) == entries     # found, not built
    shapes = trace.shapes_of((x,))
    cached = fn.lower(*shapes).compile().as_text()
    assert "mx_probe" not in cached
    gauge = mx.telemetry.gauge("trace::scope_table_recompiles")
    before = gauge.get()
    prog = trace.note_program("jit_step", fn.trace(*shapes))
    compiles = _count_compiles()
    table = prog.table()
    assert prog.stale is True and len(compiles) == 1
    assert gauge.get() == before + 1
    assert "mx_probe" in set(table.values())
    assert sorted(os.listdir(own_jax_cache)) == entries     # nothing written
    # same optimised program: the names are the cached executable's
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", cached, re.M))
    assert set(table) <= names
    assert trace.scope_table("jit_step") is table and len(compiles) == 1


def test_a_table_from_this_tree_s_executable_compiles_nothing(own_jax_cache):
    fn = _this_tree()
    x = np.ones((8, 8), np.float32)
    fn(x).block_until_ready()
    gauge = mx.telemetry.gauge("trace::scope_table_recompiles")
    before = gauge.get()
    prog = trace.note_program("jit_step", fn.trace(*trace.shapes_of((x,))))
    compiles = _count_compiles()
    table = prog.table()
    assert prog.stale is False and not compiles
    assert gauge.get() == before
    assert "mx_probe" in set(table.values())


def test_a_text_without_a_frame_table_is_taken_for_stale(own_jax_cache,
                                                         monkeypatch):
    """Whose names an executable carries is read off the frame tables at
    the head of its text. Where a text shows none (another XLA's
    format), nothing is known, and the table is read from a compile of
    its own rather than from names that may be another tree's."""
    fn = _this_tree()
    x = np.ones((8, 8), np.float32)
    fn(x).block_until_ready()
    monkeypatch.setattr(trace, "_FRAMES", re.compile(r"no such table\Z"))
    gauge = mx.telemetry.gauge("trace::scope_table_recompiles")
    before = gauge.get()
    prog = trace.note_program("jit_step", fn.trace(*trace.shapes_of((x,))))
    compiles = _count_compiles()
    table = prog.table()
    assert prog.stale is True and len(compiles) == 1
    assert gauge.get() == before + 1
    assert "mx_probe" in set(table.values())


def test_a_record_holds_its_executable_weakly_and_lets_go_when_built():
    """The record of the program acquired last under a name outlives
    its step (a trace is read after the step was closed): it holds the
    executable weakly, reads another where that one is gone, and keeps
    nothing but the table once that is built."""
    import gc
    fn = _this_tree()
    shapes = trace.shapes_of((np.ones((8, 8), np.float32),))
    exe = fn.lower(*shapes).compile()
    prog = trace.note_program("jit_step", fn.trace(*shapes), exe)
    assert prog._executable() is exe
    del exe
    gc.collect()
    assert prog._executable() is None
    assert "mx_probe" in set(prog.table().values())
    assert prog._traced is None and prog._executable is None
    assert trace.scope_table("jit_step") is prog.table()


def test_a_step_hands_out_its_table_and_a_call_costs_nothing_for_it():
    """``TrainStep.scope_table()``: None before the first call, then the
    table of ``telemetry.trace.scope_table("jit_mx_train_step")``, built
    when asked for; the record is made once, at the acquisition, whose
    trace it reuses."""
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Embedding(11, 8), gluon.nn.Dense(11, flatten=False))
    net.initialize()
    step = TrainStep(net, loss=lambda out, y: jnp.mean(
        jnp.square(out.reshape((-1, 11))[:, 0] - y)))
    assert step.scope_table() is None
    x = mx.nd.array(jnp.zeros((2, 5), jnp.int32))
    y = mx.nd.array(jnp.zeros((10,), jnp.float32))
    step(x, y)
    record = step._program
    assert record is not None and record._table is None
    step(x, y)
    assert step._program is record and record._table is None
    table = step.scope_table()
    assert {"mx_embed", "mx_dense", "mx_opt_update"} <= set(table.values())
    assert trace.scope_table("jit_mx_train_step") is table
    assert not [k for k in table if k.startswith("while")]


def test_hlo_scopes_leaves_loops_out_and_files_what_xla_names():
    text = "\n".join([
        '  %while.3 = (s32[], f32[4]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(f)/mx_loop_body/while"}',
        '  %fusion.7 = f32[4] fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(f)/transpose(jvp(mx_loop_body))/mx_norm/mul"}',
        '  %ragged-dot-none.2 = bf16[8,4] custom-call(%a, %b), '
        'metadata={op_name="ragged-dot-none"}',
        '  ROOT %copy.1 = f32[4] copy(%fusion.7), metadata={op_name='
        '"jit(mx_train_step)/convert_element_type"}'])
    assert trace.hlo_scopes(text, path=True) == {
        "while.3": "mx_loop_body", "fusion.7": "mx_loop_body/mx_norm"}
    assert trace.hlo_scopes(text, path=True, loops=False,
                            xla_named=trace.XLA_NAMED) == {
        "fusion.7": "mx_loop_body/mx_norm",
        "ragged-dot-none.2": "mx_moe_gmm_ragged"}
    assert trace.hlo_scopes(text)["fusion.7"] == "mx_norm"
