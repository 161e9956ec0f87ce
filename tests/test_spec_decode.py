"""Speculative + disaggregated decode (round 21, serving/decode/spec.py
+ batcher roles).

The acceptance pins:

- speculative continuous-batched streams are BIT-IDENTICAL to solo
  greedy decode under a mixed join/leave drill — including lanes
  pinned to plain semantics (``submit(speculative=False)``) riding the
  same verify launches;
- a degenerate (random-init) draft can only cost efficiency, never
  correctness: acceptance stays inside [0, 1], every verify round
  still commits at least one token per lane, and the stream equals the
  reference bit for bit;
- the compile surface is exactly per-bucket prefill + ONE decode + ONE
  verify program on the target (the draft adds its own per-bucket
  prefill + decode) — warmup materializes all of it and live serving
  performs ZERO fresh traces;
- the ``spec_verify`` faultinject site (divergence storm) drives the
  windowed degrade to plain decode and back without corrupting a
  single token;
- the ``kv_handoff`` faultinject site (lost lane transfer) forces the
  decode-role adopter down the re-prefill path with zero dropped
  streams and bit-identical output;
- under slow decode steps (sleep-armed ``decode_step``), the
  disaggregated prefill->decode formation's TTFT p99 on a mixed
  prompt-length workload beats the unified batcher's — prefill lanes
  free at handoff instead of waiting behind held decode lanes.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject
from mxnet_tpu.serving import loadgen
from mxnet_tpu.serving.decode import (
    DecodeBatcher, DecodePredictor, SpecDecodePredictor,
    TransformerLMSpec, init_params, make_draft_spec)

pytestmark = pytest.mark.serving


def small_spec(name, max_seq=64, vocab=64, dim=32, heads=2, layers=2):
    return TransformerLMSpec(vocab_size=vocab, num_embed=dim,
                             num_heads=heads, num_layers=layers,
                             max_seq=max_seq, name=name)


def make_plain(name, slots=4, seq_buckets=(8, 16, 32)):
    spec = small_spec(name)
    return DecodePredictor(spec, init_params(spec, seed=0), slots=slots,
                           seq_buckets=seq_buckets)


def make_spec_engine(name, slots=4, seq_buckets=(8, 16, 32), k=4, **kw):
    """Target (seed 0, matching :func:`make_plain`) + a random-init
    shrink-2 draft (seed 1) — draft quality is deliberately terrible;
    these tests pin correctness and bookkeeping, not amortization."""
    spec = small_spec(name)
    dspec = make_draft_spec(spec, num_layers=1, shrink=2)
    return SpecDecodePredictor(spec, init_params(spec, seed=0), dspec,
                               init_params(dspec, seed=1), k=k,
                               slots=slots, seq_buckets=seq_buckets,
                               **kw)


def make_prompts(n, vocab=64, seed=7, lens=(5, 12, 3, 20, 7, 9, 15, 4)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=lens[i % len(lens)]
                        ).astype(np.int32) for i in range(n)]


def solo_streams(prompts, budgets, name="specref"):
    eng = make_plain(name)
    return [list(eng.generate(p, max_new_tokens=m))
            for p, m in zip(prompts, budgets)]


def engine_rows(report, name):
    pre = f"decode:{name}:"
    return [p for p in report["programs"]
            if p["kind"] == "decode" and p["name"].startswith(pre)]


# ---------------------------------------------------------------------------
# bit-identity: speculation must not change a single token
# ---------------------------------------------------------------------------
def test_spec_batched_bit_identical_mixed_join_leave():
    """THE round-21 pin: 8 staggered requests through 3 speculative
    lanes — joins mid-flight, freed lanes backfilled, every third
    request pinned to plain semantics — and every stream must equal
    solo greedy decode bit for bit."""
    prompts = make_prompts(8)
    budgets = [6, 9, 4, 12, 7, 5, 10, 8]
    solo = solo_streams(prompts, budgets, name="specbitref")

    eng = make_spec_engine("specbit", slots=3)
    with DecodeBatcher(eng, max_wait_us=500, name="specbit") as bat:
        futs = []
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            futs.append(bat.submit(p, max_new_tokens=m,
                                   speculative=(i % 3 != 2)))
            time.sleep(0.003 * (i % 3))     # force mid-flight joins
        streams = [f.result(timeout=120) for f in futs]
    assert streams == solo
    rep = bat.report()
    assert rep["served_generations"] == 8
    assert rep["streamed_tokens"] == sum(budgets)
    assert rep["speculative"] is True
    assert eng.report()["spec"]["rounds"] > 0


def test_degenerate_draft_costs_efficiency_never_correctness():
    """A random-init draft proposes junk: acceptance may hit the
    windowed degrade, but the accept-prefix contract guarantees every
    verify round commits >= 1 token per lane and the stream is exact."""
    prompts = make_prompts(6)
    budgets = [8, 5, 10, 7, 6, 9]
    solo = solo_streams(prompts, budgets, name="specdegref")

    eng = make_spec_engine("specdegen", slots=4, window=8,
                           probe_steps=4)
    with DecodeBatcher(eng, max_wait_us=0, name="degen") as bat:
        futs = [bat.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, budgets)]
        streams = [f.result(timeout=120) for f in futs]
    assert streams == solo
    s = eng.report()["spec"]
    assert s["rounds"] >= 1
    assert s["accepted_per_step"] is not None \
        and 1.0 <= s["accepted_per_step"] <= eng.spec_k + 1
    assert s["acceptance_rate"] is not None \
        and 0.0 <= s["acceptance_rate"] <= 1.0
    assert s["degrade_events"] >= 0    # policy may or may not trip...
    assert eng.spec_bytes_per_accepted_token() is not None, \
        "verify rounds ran — the measured-bytes surface must report"


# ---------------------------------------------------------------------------
# compile surface: prefills + decode + verify at warmup, then silence
# ---------------------------------------------------------------------------
def test_verify_program_in_warmup_and_zero_serving_retraces():
    # UNIQUE dims (vocab 66 / width 40): registry rows are keyed by
    # program key and named by the FIRST engine to compile them, so
    # sharing dims with any earlier test would hide this engine's rows
    # behind cache hits on foreign names
    spec = small_spec("specpin", max_seq=48, vocab=66, dim=40)
    dspec = make_draft_spec(spec, num_layers=1, shrink=2)
    eng = SpecDecodePredictor(spec, init_params(spec, seed=0), dspec,
                              init_params(dspec, seed=1), slots=2,
                              seq_buckets=(8, 16))
    eng.warmup()
    rows = engine_rows(mx.compile_report(), eng.name)
    # per-bucket prefill + 1 decode + 1 verify (width k+1)
    assert len(rows) == len(eng.buckets) + 2
    assert any(f":verify:k{eng.spec_k + 1}" in p["name"]
               for p in rows), "the batched verify program must be a "\
        "first-class registry row materialized at warmup"
    drows = engine_rows(mx.compile_report(), eng.draft.name)
    assert len(drows) == len(eng.buckets) + 1, \
        "the draft is a plain per-bucket-prefill + decode engine"

    t_before, d_before = eng.retraces, eng.draft.retraces
    prompts = make_prompts(6, lens=(5, 12, 3, 9, 7, 15))
    with DecodeBatcher(eng, max_wait_us=200, name="specpin") as bat:
        futs = [bat.submit(p, max_new_tokens=6) for p in prompts]
        for f in futs:
            f.result(timeout=120)
    assert eng.retraces == t_before and eng.draft.retraces == d_before, \
        "live speculative serving must never trace"
    assert len(engine_rows(mx.compile_report(), eng.name)) \
        == len(eng.buckets) + 2


# ---------------------------------------------------------------------------
# chaos: divergence storm + lost handoff
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_spec_verify_storm_degrades_and_stays_exact():
    """``spec_verify`` fires every speculative round: proposals are
    replaced with guaranteed-wrong tokens, acceptance collapses to 0,
    the windowed policy degrades to plain decode — and the streams
    never move a bit."""
    prompts = make_prompts(6)
    budgets = [8, 6, 10, 7, 9, 5]
    solo = solo_streams(prompts, budgets, name="specstormref")

    eng = make_spec_engine("specstorm", slots=3, window=8,
                           probe_steps=1000)
    with DecodeBatcher(eng, max_wait_us=0, name="storm") as bat:
        with faultinject.inject(spec_verify={}):
            futs = [bat.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            streams = [f.result(timeout=120) for f in futs]
            assert faultinject.fired("spec_verify") >= 1
    assert streams == solo
    s = eng.report()["spec"]
    assert s["degrade_events"] >= 1, \
        "a full storm must trip the windowed degrade"
    # storm tokens are (last+1+j) % vocab — wrong unless the target's
    # greedy argmax happens to collide, so the rate is ~0, not exactly 0
    assert s["acceptance_rate"] is not None \
        and s["acceptance_rate"] < eng.disable_below


@pytest.mark.chaos
def test_kv_handoff_fault_reprefills_zero_dropped():
    """Every lane transfer is lost mid-handoff (``kv_handoff`` fires),
    the sink still places the request, and the decode-role adopter
    re-prefills from the prompt: zero dropped streams, bit-identical
    tokens, the adoption ledger full."""
    prompts = make_prompts(6)
    budgets = [7, 5, 9, 6, 8, 4]
    solo = solo_streams(prompts, budgets, name="spechandref")

    pre_eng = make_plain("spechandpre", slots=3)
    dec_eng = make_plain("spechanddec", slots=4)
    dec = DecodeBatcher(dec_eng, max_wait_us=0, name="hand-dec",
                        role="decode")
    pre = DecodeBatcher(pre_eng, max_wait_us=0, name="hand-pre",
                        role="prefill")
    dec.start()

    def _sink(req, last, produced, lane, t0):
        assert lane is None, "the fault loses every export"
        dec.adopt(req, last, produced, lane, t0)
        return True

    pre.set_handoff(_sink)
    pre.start()
    try:
        with faultinject.inject(kv_handoff={}):
            futs = [pre.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            streams = [f.result(timeout=120) for f in futs]
            assert faultinject.fired("kv_handoff") >= len(prompts)
    finally:
        pre.stop()
        dec.stop()
    assert streams == solo
    assert pre.report()["handoffs"] == len(prompts)
    assert dec.report()["adopted"] == len(prompts)
    assert pre.report()["shed_requests"] == 0
    assert dec.report()["cancelled"] == 0


# ---------------------------------------------------------------------------
# disaggregation: a prompt's first token waits for decode launches in the
# unified batcher and for none in the prefill role
# ---------------------------------------------------------------------------
class _CountedWaits:
    """Stands before a batcher: for every stream, how many ``decode_step``
    launches its engine made between the prompt's arrival and its first
    token. A count, which the machine's load cannot move as it moves a
    percentile of 16 streams on a wall clock."""

    def __init__(self, batcher, engine):
        self.batcher, self.engine, self.waited = batcher, engine, []

    def submit(self, prompt, **kw):
        before = self.engine._decode_steps
        return self._counted(self.batcher.submit(prompt, **kw), before)

    def _counted(self, stream, before):
        for i, token in enumerate(stream):
            if i == 0:
                self.waited.append(self.engine._decode_steps - before)
            yield token


def test_disagg_ttft_p99_beats_unified_under_slow_decode():
    """Sleep-armed ``decode_step`` (the straggler stand-in, ~12 ms per
    launch) makes decode the bottleneck. In the unified batcher a new
    prompt waits for a decode lane to free before its prefill runs: with
    8 clients on 3 lanes at least 5 prompts' first tokens come after
    sleep-armed launches of the engine that serves them. The prefill-role
    batcher releases lanes at handoff: its engine launches no decode step
    at all, so no first token waits for one, on the same mixed-length
    workload, while the decode role's engine sleeps as often."""
    mixed = loadgen.mixed_prompts({4: 3, 8: 2, 16: 1}, vocab_size=64,
                                  n=8, seed=3)

    uni_eng = make_plain("specuni", slots=3, seq_buckets=(8, 16))
    with faultinject.inject(decode_step={"action": "sleep", "ms": 12}):
        with DecodeBatcher(uni_eng, max_wait_us=0,
                           name="specuni") as bat:
            uni_waits = _CountedWaits(bat, uni_eng)
            uni = loadgen.token_closed_loop(uni_waits, mixed, 8, 2,
                                            max_new_tokens=6)
    assert faultinject.fired("decode_step") == uni_eng._decode_steps > 0

    pre_eng = make_plain("specdispre", slots=3, seq_buckets=(8, 16))
    dec_eng = make_plain("specdisdec", slots=3, seq_buckets=(8, 16))
    dec = DecodeBatcher(dec_eng, max_wait_us=0, name="dis-dec",
                        role="decode")
    pre = DecodeBatcher(pre_eng, max_wait_us=0, name="dis-pre",
                        role="prefill")
    dec.start()
    pre.set_handoff(
        lambda req, last, produced, lane, t0:
        bool(dec.adopt(req, last, produced, lane, t0)) or True)
    pre.start()
    dis_waits = _CountedWaits(pre, pre_eng)
    try:
        with faultinject.inject(decode_step={"action": "sleep",
                                             "ms": 12}):
            dis = loadgen.token_closed_loop(dis_waits, mixed, 8, 2,
                                            max_new_tokens=6)
    finally:
        pre.stop()
        dec.stop()
    assert faultinject.fired("decode_step") == dec_eng._decode_steps > 0

    assert uni["gave_up"] == dis["gave_up"] == 0
    assert sum(b["streams"] for b in uni["by_length"].values()) == 16
    assert sum(b["streams"] for b in dis["by_length"].values()) == 16
    assert len(uni_waits.waited) == len(dis_waits.waited) == 16
    # three lanes for eight clients: the other five wait for a stream to
    # end, five launches away
    assert sum(w >= 1 for w in uni_waits.waited) >= 8 - 3, uni_waits.waited
    assert pre_eng._decode_steps == 0 and not any(dis_waits.waited)
    # per-length-bucket percentile families ride both runs
    for run in (uni, dis):
        assert set(run["by_length"]) == {4, 8, 16}
        for b in run["by_length"].values():
            assert b["streams"] >= 1
