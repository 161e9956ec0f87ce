"""Two training steps compiled for a v5e that is described and not
attached, at the sizes their cells time. ``nemotron3-super-train-8k``
with what its units keep (``ops.remat``): it fits the chip, it multiplies
once, it chooses and sorts once. ``lstm-lm-train``: each of the ``RNN``
operator's four loops holds one matrix product. Nothing runs here: counts
by XLA, not times. (On the pattern of
``tests/bench_harness/test_bench_nemotron_compile.py``; the topology is
described inside a fixture only.)"""
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402

CELL = "nemotron3-super-train-8k"

#: XLA's count for the same step with no recomputation at all
#: (``remat=None``), and with units that keep their input alone (the
#: parent of the PR that brought ``ops.remat``): compiled as below
FLOPS_NO_RECOMPUTATION = 11.93e12
FLOPS_INPUT_ALONE = 13.85e12


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_jax_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _peak_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _sorts(text, scope):
    """The sort instructions of a compiled program under ``scope``."""
    return [line for line in text.splitlines()
            if re.search(r"\bsort\(", line) and scope in line]


def _compiled_step(name, one_chip):
    """``(cell, net, compiled)``: the training step of a ``PatternLM`` cell
    at its timed sizes, as its configuration module builds it, compiled
    for the described chip from shapes alone."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import TrainStep, exit_weighted_loss
    cell = harness.load_cell(name)
    sizes = cell.sizes
    net = cell.model._net(sizes)
    net.initialize(mx.init.Zero())
    opt = dict(cell.config["optimizer"])
    loss = exit_weighted_loss(sizes["exit_entropy_beta"]) \
        if "exit_entropy_beta" in sizes else "softmax_ce"
    step = TrainStep(net, loss=loss, optimizer=opt.pop("name"),
                     optimizer_params=opt,
                     compute_dtype=cell.config["compute_dtype"],
                     remat="layer")

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pvals = tuple(spec(p.shape) for p in step.param_list)
    state = tuple((spec(p.shape),) * 2 if t else ()
                  for p, t in zip(step.param_list, step._trainable))
    tokens = sizes["batch"] * sizes["seq_len"]
    step._build_step()
    return cell, net, step._step_jit.lower(
        pvals, state, spec((sizes["batch"], sizes["seq_len"]), jnp.int32),
        spec((tokens,), jnp.int32), spec((), jnp.uint32),
        spec(())).compile()


def test_units_keep_what_is_dear_and_the_step_fits(one_chip, no_jax_cache):
    import mxnet_tpu as mx
    cell, _, compiled = _compiled_step(CELL, one_chip)
    sizes = cell.sizes
    pattern = sizes["hybrid_override_pattern"]
    tokens = sizes["batch"] * sizes["seq_len"]
    m = compiled.memory_analysis()
    peak = _peak_bytes(compiled)
    snap = mx.telemetry.snapshot(prefix="remat::")
    kept = {k.rsplit("::", 1)[1]: v["value"] for k, v in snap.items()
            if k.startswith("remat::saved_bytes::")}
    cost = compiled.cost_analysis()
    print(f"{CELL} step, {tokens} tokens: {peak / 1e9:.2f} GB "
          f"({m.argument_size_in_bytes / 1e9:.2f} of state, "
          f"{m.temp_size_in_bytes / 1e9:.2f} of temporaries), "
          f"{sum(kept.values()) / 1e9:.3f} GB kept by {len(kept)} units "
          f"({ {k: round(v / 1e6, 1) for k, v in sorted(kept.items())} } "
          f"MB), {cost['flops'] / 1e12:.2f} TFLOP and "
          f"{cost['bytes accessed'] / 1e9:.1f} GB accessed by XLA's count")
    # it fits one chip, with room for what the runtime reserves beside
    # XLA's count (2.3 GB on the chip: PERF.md, section 4)
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    assert peak < hbm - 3e9, peak
    # every unit keeps something, a Mamba-2 layer the most (its scan),
    # and together no more than the chip has room for
    assert snap["remat::units"]["value"] == len(pattern) == len(kept)
    by_kind = {kind: kept[next(k for k in kept if k.endswith(f"_l{i}_"))]
               for i, kind in enumerate(pattern)}
    assert 0 < by_kind["*"] < by_kind["E"] < by_kind["M"] < 200e6
    assert 1.2e9 < sum(kept.values()) < 1.6e9
    # the matrix products run once forward: XLA's count is the count of
    # the step that recomputes nothing (what is left over it: the
    # attention's scores and weighted sums, 0.1 TFLOP)
    assert cost["flops"] < 1.02 * FLOPS_NO_RECOMPUTATION < FLOPS_INPUT_ALONE
    # one choice and one sort an expert layer, not two
    text = compiled.as_text()
    experts = pattern.count("E")
    assert len(_sorts(text, "mx_moe_dispatch/jit(argsort)")) == experts
    assert len(_sorts(text, "mx_moe_route/top_k")) == experts


#: XLA's memory for the ``lstm-lm-train`` step while the ``RNN`` operator's
#: scans still held the input's product and both weight gradients
LSTM_STEP_BYTES_BEFORE = 5.01e9


def _computations(text):
    """``{name: [instruction lines]}`` of a compiled program's text."""
    out, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if head and not line.startswith(" "):
            lines = out.setdefault(head.group(1), [])
        elif lines is not None:
            lines.append(line)
    return out


def _products_under(computations, name):
    """How many ``convolution`` instructions (a matrix product on the TPU)
    a computation and the fusions it calls hold."""
    return sum(
        bool(re.search(r"=\s*\S+\s+convolution\(", line))
        + sum(_products_under(computations, called)
              for called in re.findall(r"calls=%?([\w.\-]+)", line))
        for line in computations[name])


def test_lstm_lm_loops_hold_one_product_each(one_chip, no_jax_cache):
    cell = harness.load_cell("lstm-lm-train")
    sizes = cell.sizes
    step = cell.model.build(cell.config, sizes, "step",
                            cell.model.make_weights(sizes, 0)).step
    step._init_state()
    step._build_step()
    args = (step._pvals, step._opt_state,
            jnp.zeros((sizes["batch"], sizes["bptt"]), jnp.int32),
            jnp.zeros((sizes["batch"] * sizes["bptt"],), jnp.int32),
            step._t_dev, jnp.asarray(0.1, jnp.float32))
    compiled = step._step_jit.lower(*jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)).compile()
    peak = _peak_bytes(compiled)
    computations = _computations(compiled.as_text())
    bodies = [body for lines in computations.values() for line in lines
              for body in re.findall(r"\bwhile\(.*body=%?([\w.\-]+)", line)]
    per_body = [_products_under(computations, b) for b in bodies]
    print(f"lstm-lm-train step: {peak / 1e9:.2f} GB, {len(bodies)} loops "
          f"with {per_body} products")
    # two layers, forward and backward; the backward bodies held four
    # products and the forward ones two before the scan was cut down
    assert len(bodies) == sizes["layers"] * 2 == 4
    assert per_body == [1, 1, 1, 1]
    assert peak <= LSTM_STEP_BYTES_BEFORE, peak


# -- the attention of both ``PatternLM`` cells is the kernel --------------------
def _kernel_calls(text, kernel):
    """The Mosaic custom calls of ``kernel`` in a compiled program's text,
    as ``[(instruction name, op_name)]``."""
    return [(m.group(1), m.group(2)) for m in re.finditer(
        r"^\s*%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*op_name=\"([^\"]*)\"", text, re.M)
        if f"/{kernel}/" in m.group(2)]


@pytest.mark.parametrize("name,heads", [("nemotron3-super-train-8k", 4),
                                        ("ouro-2.6b-train-4k", 16)])
def test_attention_is_the_kernel_forward_and_backward(one_chip, no_jax_cache,
                                                      name, heads):
    """Compiled for the described v5e from this CPU host, the step holds
    the attention as Mosaic calls under ``mx_attn_fwd``: one forward
    kernel a layer (the unit keeps its output and log-sum-exp, so the
    backward loop holds none), one fused backward kernel a layer, which
    the gauge ``attn::fused_bwd_sites`` counts, and no float32 (heads,
    block, block) score value anywhere."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel
    from mxnet_tpu.telemetry.trace import hlo_scopes
    text = _compiled_step(name, one_chip)[2].as_text()
    calls = {k: _kernel_calls(text, k) for k in
             ("attn_fwd_kernel", "attn_bwd_kernel")}
    counts = {k: len(v) for k, v in calls.items()}
    print(f"{name}: {counts}, attn::kernel_sites "
          f"{mx.telemetry.gauge(attn_kernel.GAUGE).get()}")
    # like layers share one lowered program: the gauge counts programs
    assert mx.telemetry.gauge(attn_kernel.GAUGE).get() == 1
    assert mx.telemetry.gauge(attn_kernel.FUSED_BWD_GAUGE).get() == 1
    assert counts["attn_fwd_kernel"] >= 1
    assert len(set(counts.values())) == 1, counts   # no second forward
    scopes = hlo_scopes(text, path=True)
    for kernel, found in calls.items():
        for instruction, op_name in found:
            assert re.search(r"(^|/)mx_attn_fwd$", scopes[instruction]), \
                (kernel, op_name)
    assert f"f32[{heads},1024,1024]" not in text
    assert not re.search(rf"f32\[1,{heads},1024,1024\]", text)


@pytest.mark.parametrize("length,hq,hk,dim,dtype,theta", [
    (200, 4, 1, 128, "bfloat16", None),      # padded to one block of 256
    (1100, 2, 1, 128, "bfloat16", 1e4),      # padded to nine blocks of 128
    (1536, 4, 2, 256, "bfloat16", 1e4),      # heads of two lane tiles
    (2048, 8, 8, 128, "float32", None)])     # blocks of 1024 in float32
def test_mosaic_takes_the_kernels_at_other_shapes(one_chip, no_jax_cache,
                                                  length, hq, hk, dim, dtype,
                                                  theta):
    """Any ``head_dim`` that is a multiple of 128 takes the kernels on a
    TPU, whatever the length, the grouping or the dtype: Mosaic has to
    compile them all (tiling, VMEM), forward and backward."""
    from mxnet_tpu.ops import seq

    def loss(data):
        out = seq.causal_gq_attention(data, num_heads=hq, num_kv_heads=hk,
                                      head_dim=dim, rope_theta=theta)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    data = jax.ShapeDtypeStruct((2, length, (hq + 2 * hk) * dim), dtype,
                                sharding=one_chip)
    text = jax.jit(jax.grad(loss)).lower(data).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("length,hq,hk,d2,fused", [
    (8192, 16, 16, 64, True),    # Moonlight's: a head's dQ and dq2, and dk2
    (8192, 4, 1, 0, True),       # Nemotron's: a group of four heads' dQ
    (8192, 8, 1, 0, True),       # 64 MiB for all the rows: the rule's edge
    (16384, 8, 1, 0, False),     # twice that: a kernel for each side
    (16384, 2, 2, 64, True),     # a second part on the fused side ...
    (65536, 2, 2, 64, False)])   # ... and past the rule
def test_the_backward_fits_vmem_on_both_sides_of_the_rule(
        one_chip, no_jax_cache, length, hq, hk, d2, fused):
    """The fused backward holds a group's float32 ``dQ`` over all the rows
    in VMEM: at the cells' shapes and up to the rule's edge Mosaic takes
    it under the kernels' VMEM limit; past the edge the backward is the
    two kernels that hold one block's sums, and the gauge says which."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import attn_kernel
    bf16 = jnp.bfloat16

    def rows(width, dtype=bf16):
        return jax.ShapeDtypeStruct((1, length, width), dtype,
                                    sharding=one_chip)

    args = [rows(hq * 128), rows(hk * 128), rows(hk * 128), rows(hq * 128),
            jax.ShapeDtypeStruct((1, hq, length), jnp.float32,
                                 sharding=one_chip), rows(hq * 128)]
    extra = [rows(hq * d2), rows(d2)] if d2 else []
    assert (attn_kernel.resident_bytes(length, hq // hk, 128, d2, 2)
            <= attn_kernel._RESIDENT_LIMIT_BYTES) == fused

    def backward(q, k, v, out, lse, dout, *extra):
        return attn_kernel.backward(q, k, v, out, lse, dout, hq, hk, 0.08,
                                    extra=extra or None)

    mx.telemetry.gauge(attn_kernel.FUSED_BWD_GAUGE).set(0)
    text = jax.jit(backward).lower(*args, *extra).compile().as_text()
    assert mx.telemetry.gauge(attn_kernel.FUSED_BWD_GAUGE).get() == fused
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [k for k in ("attn_bwd_kernel", "attn_bwd_dq_kernel",
                        "attn_bwd_dkv_kernel")
            for line in calls if f"/{k}/" in line] \
        == (["attn_bwd_kernel"] if fused
            else ["attn_bwd_dq_kernel", "attn_bwd_dkv_kernel"])


# -- the routed experts' pooled products are this repo's kernels ----------------
MOE_KERNELS = {"moe_gmm_up_kernel": "mx_moe_gmm_up",
               "moe_gmm_down_kernel": "mx_moe_gmm_down",
               "moe_gmm_down_rows_kernel": "mx_moe_gmm_down",
               "moe_gmm_down_weights_kernel": "mx_moe_gmm_down",
               "moe_gmm_up_rows_kernel": "mx_moe_gmm_up",
               "moe_gmm_up_weights_kernel": "mx_moe_gmm_up"}


def test_pooled_experts_are_the_kernels_forward_and_backward(one_chip,
                                                             no_jax_cache):
    """The step of ``moonlight-16b-a3b-train-8k`` compiled for the
    described v5e: no ``ragged-dot`` is left and no expert weight is
    copied to another layout for a backward product; an expert layer holds
    each of the six grouped-product kernels once (its unit keeps ``gate``,
    ``up`` and the result, so the backward pass runs no forward kernel
    again), every one under ``mx_moe_gmm_up`` or ``mx_moe_gmm_down`` by the
    table the roofline's reader uses; the gauge reads what the metric
    ``moe_gmm_kernel_sites.train`` will; the walk the units keep is bytes
    beside the 115 MB of an expert layer; the step fits the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import gmm_kernel
    from mxnet_tpu.telemetry.trace import hlo_scopes
    cell, _, compiled = _compiled_step("moonlight-16b-a3b-train-8k", one_chip)
    sizes = cell.sizes
    text = compiled.as_text()
    layers = sizes["num_hidden_layers"] - 1         # the expert layers
    experts = len(cell.model.held_experts(sizes))
    hidden, ff = sizes["hidden_size"], sizes["moe_intermediate_size"]
    calls = {k: _kernel_calls(text, k) for k in MOE_KERNELS}
    kept = sum(v["value"] for v in mx.telemetry.snapshot(
        prefix="remat::saved_bytes::").values())
    peak = _peak_bytes(compiled)
    print(f"moonlight step: {peak / 1e9:.2f} GB, {kept / 1e9:.6f} GB kept, "
          f"{ {k: len(v) for k, v in calls.items()} }, "
          f"{gmm_kernel.GAUGE} {mx.telemetry.gauge(gmm_kernel.GAUGE).get()}")
    assert "ragged-dot" not in text
    weight = rf"bf16\[{experts},({hidden},{ff}|{ff},{hidden})\]"
    assert not [line for line in text.splitlines()
                if re.search(rf"= {weight}\S* copy\(", line)]
    scopes = hlo_scopes(text, path=True)
    for kernel, found in calls.items():
        # once a layer: a forward kernel is not run again for the backward
        assert len(found) == layers, (kernel, len(found))
        for instruction, op_name in found:
            assert re.search(rf"(^|/){MOE_KERNELS[kernel]}$",
                             scopes[instruction]), (kernel, op_name)
    # like layers share one lowered program: the gauge counts programs
    assert mx.telemetry.gauge(gmm_kernel.GAUGE).get() == 1
    # ``sizes`` and the walk: a few hundred bytes a layer beside 1.1912 GB
    assert 1.19120e9 < kept < 1.19125e9, kept
    assert peak < 15.0e9, peak
