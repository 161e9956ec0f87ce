"""AOT compile & persistent program-cache subsystem
(``mxnet_tpu/compile/``).

Tier-1 pins for the round-10 acceptance criteria:

- **Warm start across processes**: a second process re-running the same
  fused train step and Predictor bucket set out of a populated
  ``MXTPU_COMPILE_CACHE_DIR`` performs ZERO fresh XLA compiles
  (``compile_report()`` totals, subprocess-pinned) and produces
  bit-identical params/predictions — a cache hit may never change the
  math.
- **Key discipline**: the canonical key misses (never wrongly hits) on
  a changed optimizer config, fusion flag, mesh, shapes, or metric
  slots.
- **Failure honesty**: corrupt entries (CRC) and version-stale entries
  (fingerprint) are rejected loudly — warning + counters + fresh
  compile that overwrites — never a wrong or crashing program. Armed
  via the ``compile_cache`` faultinject site like the other chaos
  drills.
- **Observability**: ``mx.compile_report()`` counts compiles / hits /
  retraces with the diverging signature, and the CLI
  (tools/compile_cache.py) lists, verifies, and prunes entries.
"""
import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.compile as compile_mod
from mxnet_tpu import faultinject
from mxnet_tpu.compile.cache import CacheEntryError, PersistentCache

pytestmark = pytest.mark.chaos

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.abspath(os.path.join(_HERE, os.pardir))


def _mlp(hidden=16, classes=8, name="softmax"):
    # every node explicitly named: auto-naming counts up per process
    # (flatten0, flatten1, ...) which would make two in-process builds
    # of the "same" graph serialize differently — the key is honest
    # about that (different JSON IS a different program identity)
    data = mx.sym.Variable("data")
    h = mx.sym.Flatten(data, name="flat")
    h = mx.sym.FullyConnected(h, num_hidden=hidden, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(h, name=name)


def _module(sym=None, batch=8, feat=4, optimizer="sgd", opt_params=None):
    mod = mx.mod.Module(sym or _mlp(), context=mx.cpu())
    mod.bind([("data", (batch, feat))], [("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(
        optimizer=optimizer,
        optimizer_params=opt_params or {"learning_rate": 0.1})
    return mod


def _step(mod, batch=8, feat=4, classes=8, seed=0):
    rng = np.random.RandomState(seed)
    b = mx.io.DataBatch(
        [mx.nd.array(rng.rand(batch, feat).astype(np.float32))],
        [mx.nd.array(rng.randint(0, classes, (batch,))
                     .astype(np.float32))])
    mod.forward(b, is_train=True)
    mod.backward()
    mod.update()


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------
def test_program_key_canonical_and_selective():
    """Same materials -> same digest; each ISSUE-named key ingredient
    (optimizer config, fusion flag, mesh, shapes) -> a different digest
    (cache MISS, never a wrong hit)."""
    sym = _mlp()
    sgd = mx.optimizer.create("sgd", learning_rate=0.1)
    base = dict(symbol=sym, input_sigs=(((8, 4), "float32"),),
                optimizer=sgd, fusion={"flag": "auto", "sites": 0})
    k1 = compile_mod.program_key("fused_step", "t", **base)
    k2 = compile_mod.program_key("fused_step", "t", **base)
    assert k1.digest == k2.digest

    # optimizer type AND hyperparameters are material
    adam = mx.optimizer.create("adam", learning_rate=0.1)
    k_adam = compile_mod.program_key(
        "fused_step", "t", **dict(base, optimizer=adam))
    sgd_mom = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    k_mom = compile_mod.program_key(
        "fused_step", "t", **dict(base, optimizer=sgd_mom))
    # ...but the mutable step counter and the base learning rate are
    # NOT: both ride as runtime arguments of the fused program, and a
    # process resuming mid lr-schedule must still hit the warm entries
    sgd2 = mx.optimizer.create("sgd", learning_rate=0.007)
    sgd2.num_update = 1000
    k_stepped = compile_mod.program_key(
        "fused_step", "t", **dict(base, optimizer=sgd2))

    k_fusion = compile_mod.program_key(
        "fused_step", "t", **dict(base, fusion={"flag": "1", "sites": 3}))
    k_shape = compile_mod.program_key(
        "fused_step", "t", **dict(base, input_sigs=(((16, 4), "float32"),)))

    class _FakeMesh:
        axis_names = ("data",)
        devices = np.array([type("D", (), {"id": 0})(),
                            type("D", (), {"id": 1})()])

    k_mesh = compile_mod.program_key(
        "fused_step", "t", **base, mesh=_FakeMesh())

    digests = [k1.digest, k_adam.digest, k_mom.digest, k_fusion.digest,
               k_shape.digest, k_mesh.digest]
    assert len(set(digests)) == len(digests), digests
    assert k_stepped.digest == k1.digest
    assert "optimizer" in k_adam.diff(k1)
    assert "fusion" in k_fusion.diff(k1)


def test_program_key_stable_across_processes(tmp_path):
    """The digest is a pure function of the materials — a fresh
    interpreter computes the same one (what makes cross-process cache
    hits possible at all)."""
    prog = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "import mxnet_tpu.compile as C\n"
        "d = mx.sym.Variable('data')\n"
        "s = mx.sym.SoftmaxOutput(mx.sym.FullyConnected("
        "mx.sym.Flatten(d), num_hidden=16, name='fc1'), name='softmax')\n"
        "o = mx.optimizer.create('sgd', learning_rate=0.1)\n"
        "k = C.program_key('fused_step', 't', symbol=s,"
        " input_sigs=(((8, 4), 'float32'),), optimizer=o)\n"
        "print(k.digest)\n")
    outs = set()
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", prog], cwd=_ROOT,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        outs.add(r.stdout.strip().splitlines()[-1])
    assert len(outs) == 1, outs


# ---------------------------------------------------------------------------
# the acceptance pin: warm start across processes
# ---------------------------------------------------------------------------
def test_second_process_performs_zero_fresh_compiles(tmp_path):
    """Cold run populates MXTPU_COMPILE_CACHE_DIR; the restart AOT-loads
    every program (fused train step + both Predictor buckets): fresh
    compiles == 0, and params/predictions are bit-identical — the
    round-10 acceptance criterion."""
    cache_dir = str(tmp_path / "cache")
    worker = os.path.join(_HERE, "compile_cache_worker.py")

    def run(tag):
        out = str(tmp_path / f"{tag}.json")
        env = dict(os.environ, MXTPU_COMPILE_CACHE_DIR=cache_dir)
        env.pop("MXTPU_FAULT_INJECT", None)
        r = subprocess.run([sys.executable, worker, out], cwd=_ROOT,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        with open(out) as f:
            return json.load(f)

    cold = run("cold")
    assert cold["fresh_compiles"] >= 3, cold   # step + 2 buckets
    assert cold["cache_hits"] == 0, cold
    assert cold["cache_errors"] == 0, cold

    warm = run("warm")
    assert warm["fresh_compiles"] == 0, warm
    assert warm["cache_hits"] == cold["fresh_compiles"], (cold, warm)
    assert warm["cache_errors"] == 0, warm
    assert warm["predictor_retraces"] == 0, warm
    # identical key set across processes, identical MATH out of the
    # loaded executables
    assert warm["digests"] == cold["digests"]
    assert warm["params_sha"] == cold["params_sha"]
    assert warm["pred_sha"] == cold["pred_sha"]


# ---------------------------------------------------------------------------
# failure honesty: corrupt + stale entries
# ---------------------------------------------------------------------------
def _entry_paths(cache_dir):
    return [os.path.join(cache_dir, n) for n in os.listdir(cache_dir)
            if n.endswith(".mxprog")]


def test_corrupt_entry_falls_back_to_fresh_compile(tmp_path, caplog):
    """A cache entry torn below the rename (compile_cache faultinject
    site, bytes=N truncation) is detected by CRC on the next load:
    warning + cache_errors counter + fresh compile that overwrites —
    training proceeds, never a wrong program."""
    import logging
    cache_dir = str(tmp_path / "cache")
    faultinject.reset()
    with mx.config.override("MXTPU_COMPILE_CACHE_DIR", cache_dir):
        # write the entry, then the armed site truncates it post-commit
        with faultinject.inject("compile_cache:bytes=64"):
            mod = _module()
            _step(mod)
        assert faultinject.fired("compile_cache") >= 1
        paths = _entry_paths(cache_dir)
        assert paths and os.path.getsize(paths[0]) == 64

        compile_mod.reset()
        with caplog.at_level(logging.WARNING, "mxnet_tpu.compile"):
            mod2 = _module()
            _step(mod2)
        assert any("corrupt" in r.message for r in caplog.records)
        rep = mx.compile_report()
        assert rep["totals"]["cache_errors"] == 1, rep
        assert rep["totals"]["fresh_compiles"] == 1, rep
        assert rep["totals"]["cache_hits"] == 0, rep
        # the fresh compile overwrote the torn entry: next consumer hits
        assert os.path.getsize(paths[0]) > 64
        compile_mod.reset()
        mod3 = _module()
        _step(mod3)
        rep = mx.compile_report()
        assert rep["totals"]["cache_hits"] == 1, rep
        assert rep["totals"]["fresh_compiles"] == 0, rep


def test_byte_budget_write_fault_never_tears_an_entry(tmp_path):
    """A crash AT ANY BYTE of the entry write must not leave a torn
    file: atomic_write means the armed compile_cache byte-budget fault
    aborts the temp file and the cache simply has no entry — the next
    process recompiles, it never loads garbage."""
    cache_dir = str(tmp_path / "cache")
    faultinject.reset()
    with mx.config.override("MXTPU_COMPILE_CACHE_DIR", cache_dir):
        with faultinject.inject("compile_cache:byte=100"):
            mod = _module()
            _step(mod)       # serialize fails mid-write; step still runs
        assert faultinject.fired("compile_cache") >= 1
        assert _entry_paths(cache_dir) == []
        # cache stays usable: a clean run writes the entry after all
        compile_mod.reset()
        mod2 = _module()
        _step(mod2)
        assert len(_entry_paths(cache_dir)) == 1
        ok, bad = PersistentCache(cache_dir).verify()
        assert (ok, bad) == (1, [])


def test_stale_fingerprint_falls_back_loudly(tmp_path, caplog):
    """An entry written by a different jax/jaxlib/mxnet_tpu stack (the
    version fingerprint rides in the header) is rejected as stale and
    recompiled fresh — an upgrade can slow the first restart down, it
    can never feed an old executable to a new runtime."""
    import logging
    cache_dir = str(tmp_path / "cache")
    with mx.config.override("MXTPU_COMPILE_CACHE_DIR", cache_dir):
        mod = _module()
        _step(mod)
        (path,) = _entry_paths(cache_dir)
        # rewrite the header in place with a doctored fingerprint
        with open(path, "rb") as f:
            magic = f.read(8)
            (hlen,) = struct.unpack(">I", f.read(4))
            header = json.loads(f.read(hlen).decode())
            payload = f.read()
        header["fingerprint"] = "jax=0.0.1;jaxlib=0.0.1;mxtpu=0;fmt=0"
        hdr = json.dumps(header, sort_keys=True).encode()
        with open(path, "wb") as f:
            f.write(magic + struct.pack(">I", len(hdr)) + hdr + payload)

        cache = PersistentCache(cache_dir)
        with pytest.raises(CacheEntryError) as ei:
            cache.get(header["digest"])
        assert ei.value.reason == "stale"

        compile_mod.reset()
        with caplog.at_level(logging.WARNING, "mxnet_tpu.compile"):
            mod2 = _module()
            _step(mod2)
        assert any("stale" in r.message for r in caplog.records)
        rep = mx.compile_report()
        assert rep["totals"]["cache_errors"] == 1, rep
        assert rep["totals"]["fresh_compiles"] == 1, rep
        # overwritten with the current fingerprint: valid again
        ok, bad = cache.verify()
        assert (ok, bad) == (1, [])


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------
def test_compile_report_counters_and_retrace_guard():
    """compile_report(): fused-step programs appear with compile wall
    time; attaching a device metric retraces the step ONCE and the
    retrace guard records what diverged (the absorbed serving-local
    counter's semantics, now framework-wide)."""
    compile_mod.reset()
    mod = _module()
    _step(mod)
    rep = mx.compile_report()
    fused = [p for p in rep["programs"] if p["kind"] == "fused_step"]
    assert len(fused) == 1 and fused[0]["compiles"] == 1
    assert fused[0]["compile_s"] > 0
    assert rep["totals"]["retraces"] == 0

    # device-metric attach: new metric slot -> one retrace, key diff
    # names the metric material
    metric = mx.metric.Accuracy()
    rng = np.random.RandomState(1)
    b = mx.io.DataBatch(
        [mx.nd.array(rng.rand(8, 4).astype(np.float32))],
        [mx.nd.array(rng.randint(0, 8, (8,)).astype(np.float32))])
    mod.forward(b, is_train=True)
    mod.backward()
    mod.update()
    mod.update_metric(metric, b.label)
    _step(mod, seed=2)
    rep = mx.compile_report()
    name = [n for n in rep["retraces"]][0]
    assert name.startswith("fused_step:")
    assert rep["retraces"][name]["count"] == 1
    assert rep["retraces"][name]["events"][0]["changed"] == ["extra"]
    assert rep["totals"]["fresh_compiles"] == 2

    # profiler mirror: live counters without pulling a report
    counters = mx.profiler.counters()
    assert counters.get("compile::fresh_compiles", 0) >= 2


def test_compile_spans_reach_profiler_aggregates(tmp_path):
    """Predictor.warmup() / the fused step's first compile run inside
    compile:: profiler spans — cold-start cost is visible in
    mx.profiler dumps instead of invisible (round-10 small fix)."""
    mx.profiler.set_config(aggregate_stats=True,
                           filename=str(tmp_path / "profile.json"))
    mx.profiler.set_state("run")
    try:
        mod = _module()
        _step(mod)
        pred = mod.as_predictor(buckets=(1, 2))
        pred.warmup()
    finally:
        mx.profiler.set_state("stop")
    table = mx.profiler.dumps(reset=True)
    assert "compile::compile" in table


def test_report_reset_and_cache_section(tmp_path):
    cache_dir = str(tmp_path / "cache")
    with mx.config.override("MXTPU_COMPILE_CACHE_DIR", cache_dir):
        rep = mx.compile_report(reset=True)
        assert rep["cache"]["enabled"] is True
        assert rep["cache"]["dir"] == cache_dir
    with mx.config.override("MXTPU_COMPILE_CACHE", "0"):
        with mx.config.override("MXTPU_COMPILE_CACHE_DIR", cache_dir):
            assert mx.compile_report()["cache"]["enabled"] is False
    rep = mx.compile_report()
    assert rep["totals"]["programs"] == 0   # reset above took


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_ls_verify_prune(tmp_path):
    cache_dir = str(tmp_path / "cache")
    with mx.config.override("MXTPU_COMPILE_CACHE_DIR", cache_dir):
        mod = _module()
        _step(mod)
    (path,) = _entry_paths(cache_dir)
    cli = os.path.join(_ROOT, "tools", "compile_cache.py")

    def run(*args):
        return subprocess.run([sys.executable, cli, "--dir", cache_dir,
                               *args], capture_output=True, text=True,
                              cwd=_ROOT, timeout=120)

    r = run("ls", "--json")
    assert r.returncode == 0, r.stderr
    listing = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(listing["entries"]) == 1
    assert listing["entries"][0]["kind"] == "fused_step"
    assert listing["entries"][0]["status"] == "ok"

    assert run("verify").returncode == 0

    # corrupt it -> verify fails, prune removes invalid entries
    with open(path, "r+b") as f:
        f.truncate(64)
    r = run("verify", "--json")
    assert r.returncode == 1
    assert json.loads(r.stdout.strip().splitlines()[-1])["bad"]
    r = run("prune", "--json")
    assert r.returncode == 0
    assert json.loads(r.stdout.strip().splitlines()[-1])["removed"]
    assert _entry_paths(cache_dir) == []


def test_jax_cache_is_placed_from_outside_or_at_a_fixed_path(monkeypatch):
    """JAX's own persistent cache: with ``JAX_COMPILATION_CACHE_DIR``
    set the program sets no directory in code — not even when the
    ``.mxprog`` layer has a directory of its own — and with it unset
    the directory is the fixed ``<repo>/.jax_cache``."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        # what importing jax with the variable set leaves behind
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        jax.config.update("jax_compilation_cache_dir", "/x")
        with mx.config.override("MXTPU_COMPILE_CACHE_DIR", "/y"):
            assert compile_mod.wire_jax_cache() == "/x"
            assert compile_mod.default_cache().directory == "/y"
            assert jax.config.jax_compilation_cache_dir == "/x"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_mod.wire_jax_cache() == \
            os.path.join(_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(_ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
