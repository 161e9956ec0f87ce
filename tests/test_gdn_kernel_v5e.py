"""The step of ``qwen3-next-80b-a3b-train-8k`` compiled for a v5e that is
described and not attached, at the sizes the cell times: Mosaic takes the
gated delta rule's kernels (``ops/gdn_kernel.py``) at the cell's shapes
(16 key heads, 32 value heads, 128 x 128, chunks of 64, bfloat16), forward
and backward, three calls a linear-attention layer, each under the scope
path ``mx_gdn_rule`` and no longer one; the gated norm is inside them (no
instruction under ``mx_gdn_gate``, their output and its cotangent in
bfloat16, ``z`` read from the packed projection where it is); no
``triangular-solve`` expansion
and no ``while`` is left for the rule; the kernels of the rule's operands
(``ops/gdn_conv_kernel.py``: convolution, SiLU and the heads' norm from
the packed projection) are there too, three calls a linear layer under
exactly ``mx_gdn_conv``, with no pad and no slice of the projection left
beside them; the step fits what one chip gives a
program, and a delta-rule unit keeps what it kept before the kernels. The
attention and grouped-product kernels are there by name and count as
``tests/bench_harness/test_bench_qwen3_next_compile.py`` found them
(that file compares the whole set of custom calls for equality and counts
three ``while`` a linear layer, so it is red since the rule's kernels;
ROADMAP D0 c). Nothing runs here, so nothing here is a time or a result.
The chip is described and the step compiled, once a process, by
``tests/described_v5e.py``."""
import collections
import re

import jax.numpy as jnp

from described_v5e import (CHIP_BYTES, ROW_KERNELS, compiled_step, harness,
                           peak_bytes, row_kernels_stand)

CELL = "qwen3-next-80b-a3b-train-8k"


def _linear_layers(sizes):
    layers = sizes["num_hidden_layers"]
    return layers - layers // sizes["full_attention_interval"]


def test_mosaic_takes_the_rule_s_kernels_three_a_linear_layer():
    """Forward, the unit's recomputation (whose states and inverses the
    backward reads) and backward: six ``gdn_fwd_kernel`` and three
    ``gdn_bwd_kernel`` for three layers of one shape, which share one
    lowered program (the gauge reads 1); before each of them the kernel
    of its operands, six ``gdn_conv_fwd_kernel`` and three
    ``gdn_conv_bwd_kernel`` under a gauge of their own."""
    from mxnet_tpu.ops import (attn_kernel, gdn_conv_kernel, gdn_kernel,
                               gmm_kernel, mhc_kernel, moe_rows_kernel, seq)
    step = compiled_step(CELL)
    sizes = step.sizes
    assert step.gauges == {attn_kernel.GAUGE: 1, attn_kernel.FUSED_BWD_GAUGE: 0,
                           attn_kernel.WINDOW_GAUGE: 0,
                           gmm_kernel.GAUGE: 1, gdn_kernel.GAUGE: 1,
                           gdn_conv_kernel.GAUGE: 1, mhc_kernel.GAUGE: 0,
                           moe_rows_kernel.GAUGE: 1, seq.MHC_GAUGE: 0}
    calls = collections.Counter(step.calls.values())
    linear, layers = _linear_layers(sizes), sizes["num_hidden_layers"]
    assert calls == {
        "gdn_fwd_kernel": 2 * linear, "gdn_bwd_kernel": linear,
        "gdn_conv_fwd_kernel": 2 * linear, "gdn_conv_bwd_kernel": linear,
        "attn_fwd_kernel": 1, "attn_bwd_dq_kernel": 1,
        "attn_bwd_dkv_kernel": 1,
        **{f"moe_gmm_{side}{part}_kernel": layers
           for side in ("up", "down") for part in ("", "_rows", "_weights")},
        ROW_KERNELS[0]: layers, ROW_KERNELS[1]: 2 * layers}


def test_no_solve_and_no_loop_is_left_for_the_rule():
    """The plain form's ``triangular_solve`` expansion and its three
    ``while`` a linear layer (forward, recomputation, backward) are gone,
    and nothing else in this step loops; no grouped product fell back to
    XLA's either."""
    hlo = compiled_step(CELL).text
    assert not re.findall(r" while\(", hlo)
    assert "triangular-solve" not in hlo and "triangular_solve" not in hlo
    assert "ragged-dot" not in hlo


def test_every_kernel_of_the_rule_is_under_mx_gdn_rule_and_no_longer_path():
    """``gdn_roofline.train`` reads ``^mx_gdn_rule$``: a call under
    ``mx_gdn_rule/mx_gdn_rule`` (a scope opened twice) or under another
    part's scope would stand outside it and flatter the rule."""
    step = compiled_step(CELL)
    paths = step.paths
    mine = {name: paths.get(name) for name, kernel
            in step.calls.items()
            if kernel in ("gdn_fwd_kernel", "gdn_bwd_kernel")}
    assert len(mine) == 9
    assert set(mine.values()) == {"mx_gdn_rule"}, mine
    # and no instruction of the step has the scope twice in its path
    assert not [p for p in set(paths.values())
                if p.split("/").count("mx_gdn_rule") > 1]


def _instructions(hlo):
    """``{name: (shape, operation, operands' names, line)}`` of the entry
    computation (a tuple's shape whole)."""
    found = re.findall(
        r"\n\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)"
        r"([^\n]*)", hlo[hlo.index("\nENTRY"):])
    return {name: (shape, op, re.findall(r"%([\w.\-]+)", args), rest)
            for name, shape, op, args, rest in found}


def test_the_gated_norm_is_inside_the_rule_s_kernels():
    """Read off the same compiled text: nothing of the step stands under
    ``mx_gdn_gate``; a forward kernel's first result and a backward
    kernel's last operand, the output's cotangent, are the tokens' rows of
    value heads in bfloat16, no float32 array that XLA turns for the gate's
    fusions; the output product (and, backward, its weight's gradient)
    reads the kernel's result as it is, no ``copy`` or ``convert`` between;
    and ``z`` is the packed projection itself, the product's result handed
    to the kernel whole: no slice, pad or copy of the 12288-wide rows gives
    a 4096-wide array anywhere in the step."""
    step = compiled_step(CELL)
    sizes, paths = step.sizes, step.paths
    assert not [p for p in set(paths.values()) if "mx_gdn_gate" in p]
    tokens = sizes["batch"] * sizes["seq_len"]
    heads = f"bf16[1,{tokens},4096]"
    packed = f"bf16[1,{tokens},12288]"
    ins = _instructions(step.text)
    users = collections.defaultdict(list)
    for name, (_, _, operands, _) in ins.items():
        for operand in operands:
            users[operand].append(name)
    rule = [n for n, k in step.calls.items()
            if k in ("gdn_fwd_kernel", "gdn_bwd_kernel")]
    assert len(rule) == 9
    for name in rule:
        shape, _, operands, rest = ins[name]
        taken = re.search(r"operand_layout_constraints=\{(.*?)\}\}, ", rest
                          ).group(1).split("}, ")
        assert taken[5].startswith(packed), taken         # z, in place
        assert ins[operands[5]][0].startswith(packed)
        assert ins[operands[5]][1] in ("fusion", "get-tuple-element"), \
            ins[operands[5]]
        if step.calls[name] == "gdn_bwd_kernel":
            assert taken[-1].startswith(heads), taken     # the cotangent
            assert shape.count(heads) == 2                # dv and dz
            continue
        assert shape.startswith("(" + heads), shape
        (y,) = [u for u in users[name] if "index=0" in ins[u][3]]
        assert users[y]
        for reader in users[y]:
            assert ins[reader][1] == "fusion" and re.search(
                r"mx_gdn_proj/(dot_general|transpose)", ins[reader][3]), \
                ins[reader]
    # nothing 4096 wide is cut out of rows 12288 wide
    narrow = re.compile(rf"\w+\[(?:1,)?{tokens},(?:4096|32,128)\]")
    for name, (shape, op, operands, _) in ins.items():
        if op in ("slice", "pad", "copy", "dynamic-slice") and narrow.match(
                shape):
            assert not [o for o in operands
                        if f"{tokens},12288]" in ins.get(o, ("",))[0]], name
    # in a fusion either: no slice anywhere takes the gate's columns
    wide = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
    conv = wide + 2 * sizes["linear_num_key_heads"] \
        * sizes["linear_key_head_dim"]
    assert (conv, wide) == (8192, 4096)
    assert f"[{conv}:{conv + wide}]" not in step.text


def test_every_kernel_of_the_operands_is_under_exactly_mx_gdn_conv():
    """The backward rule opens no scope: it carries ``mx_gdn_conv`` from
    its forward's call site, so all nine calls stand under that path and
    none under ``mx_gdn_rule``, whose nine ``gdn_roofline.train``
    reads."""
    step = compiled_step(CELL)
    paths = step.paths
    mine = {name: paths.get(name) for name, kernel
            in step.calls.items() if kernel.startswith("gdn_conv_")}
    assert len(mine) == 9
    assert set(mine.values()) == {"mx_gdn_conv"}, mine


def _entry(hlo):
    """``[(name, shape, operation)]`` of the entry computation's
    instructions."""
    return re.findall(r"\n\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(",
                      hlo[hlo.index("\nENTRY"):])


def test_no_pad_and_no_slice_of_the_projection_is_left_for_the_convolution():
    """The plain form padded the 8192-wide slice of the kept projection
    for its taps and sliced ``v`` out again for the rule's kernels: 134 MB
    copies under ``mx_gdn_conv``. The kernels window the packed rows, so
    under that scope nothing is left but their calls (and what unpacks a
    call's results)."""
    step = compiled_step(CELL)
    sizes, hlo, paths = step.sizes, step.text, step.paths
    under = [(name, shape, op) for name, shape, op in _entry(hlo)
             if "mx_gdn_conv" in (paths.get(name) or "")]
    assert under
    tokens = sizes["batch"] * sizes["seq_len"]
    # of the tokens' rows (a handful of numbers a channel, the taps'
    # gradient on its way to the weight's layout, is no pass over them)
    assert not [u for u in under if u[2] in ("pad", "slice", "copy")
                and f"{tokens}," in u[1]], under
    rows = re.compile(rf"bf16\[(?:1,)?{tokens},8192\]")
    assert not [u for u in under if rows.match(u[1])], under
    assert not re.findall(r"pad[\w.\-]*fusion[^\n]*mx_gdn_conv", hlo)


def test_step_fits_one_v5e_and_a_unit_keeps_nothing_of_the_rule():
    """625.7 M parameters with Adam's moments, 8192 tokens, recomputation
    by layer: arguments, outputs and temporaries on one described v5e,
    under the 13.5 GB the chip's run is held to; a delta-rule unit
    keeps both input products, not the convolution, not the rule's
    output, states or inverses. (The count walks every branch of the
    mixer's program, so it still names the plain form's norm statistics, a
    float a token and value head, 1 MB a layer, which a program that took
    the kernels never forms: ``remat_saved_gb.train`` reads what it
    read.)"""
    step = compiled_step(CELL)
    sizes, compiled, kept = step.sizes, step.compiled, step.kept
    m = compiled.memory_analysis()
    peak = peak_bytes(compiled)
    print(f"qwen3-next-80b-a3b step: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units")
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < peak < 13.5e9 < CHIP_BYTES, peak
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    tokens = sizes["batch"] * sizes["seq_len"]
    (first,) = [v for k, v in kept.items() if k.endswith("_l0_")]
    assert first == tokens * 2 * (12288 + 64) + tokens * 32 * 4 + tokens * 4


def test_what_the_kernels_hold_in_vmem_is_under_their_budget():
    """At the cell's shapes, by the modules' own statements: the blocks
    twice (the gate's ``z``, the gated output and its cotangent in
    bfloat16, ``dz``, the weight and its row sums among them), the
    states' scratch and a block's values before the chain, ``z`` 32 whole
    blocks of a key head's 256 value columns into the packed rows; the
    operands' kernels' blocks of half the convolved columns."""
    from mxnet_tpu.ops import gdn_conv_kernel, gdn_kernel
    cell = harness.load_cell(CELL)
    sz = cell.sizes
    n, p = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    group = sz["linear_num_value_heads"] // sz["linear_num_key_heads"]
    assert (n, p, group) == (128, 128, 2)
    conv = 2 * sz["linear_num_key_heads"] * n \
        + sz["linear_num_value_heads"] * p
    assert gdn_kernel.takes(n, p, 64, jnp.bfloat16, group, gate_offset=conv)
    for held in (gdn_kernel.forward_bytes(n, p, 64, group, 2),
                 gdn_kernel.backward_bytes(n, p, 64, group, 2)):
        assert 2e6 < held < gdn_kernel._BUDGET_BYTES
    heads = gdn_conv_kernel.Heads(sz["linear_num_key_heads"], n,
                                  sz["linear_num_value_heads"], p)
    taps = sz["linear_conv_kernel_dim"]
    assert gdn_conv_kernel.takes(heads, taps, jnp.bfloat16, jnp.bfloat16)
    assert 8e6 < gdn_conv_kernel.held_bytes(heads, 2) \
        < gdn_conv_kernel._BUDGET_BYTES


def test_rows_travel_by_the_row_kernels():
    """32 held experts: a tile of tokens walks up to 32 stretches of the
    pool."""
    step = compiled_step(CELL)
    row_kernels_stand(step, step.sizes["num_hidden_layers"],
                      step.sizes["hidden_size"])
