"""chip_smoke.py on the CPU: its phase functions at a toy size (ResNet-20
at 32x32, a 2-layer LM, the rewrite passes forced on, kernels
interpreted), and its refusal to say anything without a chip. The
run that counts is ``python chip_smoke.py`` on the TPU; this keeps the
script importable and its assertions exercised between chip runs.
"""
import os
import sys

import pytest

import mxnet_tpu as mx

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir))
import chip_smoke  # noqa: E402

TINY = dict(
    chip_smoke.FULL, layers=20, image=32, classes=10, batch=8,
    stem="std", manual_steps=3, fit_batches=2, buckets=(1, 4), clients=2,
    requests_per_client=3,
    lm={"vocab_size": 64, "num_embed": 32, "num_heads": 2,
        "num_layers": 2, "max_seq": 32},
    slots=4, seq_buckets=(8, 16), streams=3, new_tokens=6,
    # ResNet-20 has basic blocks, no 1x1 bottleneck conv: the Pallas
    # pass matches nothing there, the residual pass does the rewriting
    min_pallas_sites=0)


def test_train_and_serve_phases_at_toy_size():
    # forced: on the CPU the passes' ``auto`` is off and nothing would
    # be rewritten at all
    mod, train = chip_smoke.train_phase(mx.cpu(0), TINY, forced=True)
    serve = chip_smoke.serve_phase(mod, TINY)
    assert train["ok"] and train["losses"][-1] < train["losses"][0]
    assert train["passes"]["residual_fusion"]["applied"] > 0
    assert train["mosaic_calls"] == 0          # interpreted off the chip
    assert train["setup_s"] > 0
    assert serve["ok"] and serve["requests"] == 6


def test_decode_phase_at_toy_size():
    assert chip_smoke.decode_phase(TINY)["streams"] == 3


def test_main_refuses_before_any_phase_without_a_chip(monkeypatch):
    """Under ``JAX_PLATFORMS=cpu`` the script exits non-zero in the
    preamble: no phase starts, no result line is printed."""
    started = []
    for name in ("train_phase", "serve_phase", "decode_phase"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, _n=name, **k: started.append(_n))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "not 'tpu'" in str(exc.value.code)
    assert started == []


def test_last_line_is_the_result_object_and_nothing_else(monkeypatch,
                                                         capsys):
    """The driver parses the last line of standard output and accepts
    exactly ``{"ok", "device": {"platform", "kind", "count"}}``; the
    per-phase summary is the line before, not part of it."""
    import json
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "preamble", lambda: dict(dev))
    monkeypatch.setattr(mx, "tpu", lambda i=0: mx.cpu(0))
    monkeypatch.setattr(
        chip_smoke, "train_phase",
        lambda *a, **k: (None, {"ok": True, "losses": [2.0, 1.0]}))
    monkeypatch.setattr(chip_smoke, "serve_phase",
                        lambda *a, **k: {"ok": True})
    monkeypatch.setattr(chip_smoke, "decode_phase",
                        lambda *a, **k: {"ok": True})
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": dev}
    assert type(last["device"]["count"]) is int
    summary = json.loads(lines[-2])
    assert set(summary["phases"]) == {"train", "kernels", "serve", "decode"}
    assert summary["mesh"] == "not run: 1 device"


def test_accelerator_context_raises_without_an_accelerator():
    """``mx.tpu(0)`` in a CPU-only process is an error, not the CPU; an
    honest default context is still the CPU."""
    with pytest.raises(mx.MXNetError, match="names an accelerator"):
        mx.Context("tpu", 0).jax_device
    with pytest.raises(mx.MXNetError, match="names an accelerator"):
        mx.gpu(0).jax_device
    assert mx.current_context().device_type == "cpu"
