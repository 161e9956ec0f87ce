"""The BN(+ReLU)→1×1-conv graph-rewrite fusion pass (symbol/fusion.py)
and its Pallas-backed op (ops/pallas_fused.py), in interpret mode:

- fused-vs-unfused numerical equivalence, forward AND gradients,
  through the jitted Executor path;
- the bare BN→conv (no relu) variant;
- bail-out on non-divisible output channels (with results unchanged);
- BatchNorm aux running-mean/var updates unchanged by the rewrite;
- a ResNet-style block training bit-close through the fused Module
  step;
- ≥ 1 rewritten site on the bench (ResNet-50) symbol;
- the fused train step's XLA-cost "bytes accessed" strictly below the
  unfused step's (the HBM-traffic claim, measured on the whole step).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def _flag(value):
    """Temporarily force MXTPU_PALLAS_FUSION."""
    return mx.config.override("MXTPU_PALLAS_FUSION", value)


def _block_sym(num_filter=16, relu=True, name="f"):
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name=f"{name}_bn", fix_gamma=False,
                          eps=1e-3, momentum=0.9)
    x = mx.sym.Activation(bn, act_type="relu", name=f"{name}_relu") \
        if relu else bn
    return mx.sym.Convolution(x, kernel=(1, 1), stride=(1, 1),
                              pad=(0, 0), num_filter=num_filter,
                              no_bias=True, name=f"{name}_conv")


def _run_executor(sym, flag, shape=(2, 8, 4, 4), num_filter=16,
                  name="f"):
    with _flag(flag):
        ex = sym.simple_bind(ctx=mx.current_context(), grad_req="write", data=shape)
        rng = np.random.RandomState(0)
        B, C, H, W = shape
        ex.arg_dict["data"][:] = rng.randn(*shape).astype(np.float32)
        ex.arg_dict[f"{name}_bn_gamma"][:] = \
            rng.rand(C).astype(np.float32) + 0.5
        ex.arg_dict[f"{name}_bn_beta"][:] = \
            rng.randn(C).astype(np.float32) * 0.1
        ex.arg_dict[f"{name}_conv_weight"][:] = \
            rng.randn(num_filter, C, 1, 1).astype(np.float32) * 0.1
        ex.aux_dict[f"{name}_bn_moving_mean"][:] = 0
        ex.aux_dict[f"{name}_bn_moving_var"][:] = 1
        ex.forward(is_train=True)
        out = ex.outputs[0].asnumpy().copy()
        ex.backward(out_grads=[mx.nd.ones((B, num_filter, H, W))])
        grads = {k: v.asnumpy().copy() for k, v in ex.grad_dict.items()}
        aux = {k: v.asnumpy().copy() for k, v in ex.aux_dict.items()}
        return out, grads, aux, ex._fusion_report


@pytest.mark.parametrize("relu", [True, False])
def test_rewrite_equivalence_fwd_and_grad(relu):
    """Fused and unfused executors agree on output, every gradient, and
    the BatchNorm aux running-stat updates (fwd + bwd, interpret mode);
    both the BN→ReLU→conv and the bare BN→conv patterns rewrite."""
    sym = _block_sym(relu=relu)
    o1, g1, a1, rep = _run_executor(sym, "1")
    o0, g0, a0, rep0 = _run_executor(sym, "0")
    assert rep is not None and len(rep["sites"]) == 1
    site = rep["sites"][0]
    assert site["conv"] == "f_conv" and site["bn"] == "f_bn"
    assert site["activation"] == ("f_relu" if relu else None)
    assert rep0 is None  # pass disabled entirely with the flag off
    np.testing.assert_allclose(o1, o0, rtol=2e-5, atol=2e-5)
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=2e-5, atol=2e-5,
                                   err_msg=f"grad {k}")
    for k in a0:
        # running-stat fold must be bit-identical: the fused op emits
        # the same batch statistics BatchNorm does
        np.testing.assert_allclose(a1[k], a0[k], rtol=1e-6, atol=1e-7,
                                   err_msg=f"aux {k}")


def test_bailout_non_divisible_channels():
    """num_filter=12 cannot tile (not divisible by 8): the pass must
    bail with a recorded reason and leave results identical to the
    unfused path (no partial rewrite)."""
    sym = _block_sym(num_filter=12)
    o1, g1, a1, rep = _run_executor(sym, "1", num_filter=12)
    o0, g0, a0, _ = _run_executor(sym, "0", num_filter=12)
    assert rep is not None and len(rep["sites"]) == 0
    assert len(rep["bailouts"]) == 1
    assert "num_filter=12 not divisible by 8" in \
        rep["bailouts"][0]["reason"]
    np.testing.assert_allclose(o1, o0, rtol=2e-5, atol=2e-5)
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=2e-5, atol=2e-5)


def test_shared_activation_bails_out():
    """A BN/ReLU whose output feeds two consumers (the dim-change
    shortcut pattern in ResNet) must not be rewritten — the
    intermediate is materialized for the other consumer anyway."""
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="s_bn", fix_gamma=False)
    act = mx.sym.Activation(bn, act_type="relu", name="s_relu")
    conv = mx.sym.Convolution(act, kernel=(1, 1), num_filter=16,
                              no_bias=True, name="s_conv")
    sc = mx.sym.Convolution(act, kernel=(1, 1), num_filter=16,
                            no_bias=True, name="s_sc")
    from mxnet_tpu.symbol.fusion import fuse_symbol
    _, rep = fuse_symbol(conv + sc, {"data": (2, 8, 4, 4)})
    assert len(rep["sites"]) == 0
    assert any("other consumers" in b["reason"] for b in rep["bailouts"])


def _train_block(flag, steps=3):
    with _flag(flag):
        mx.random.seed(0)
        np.random.seed(0)
        data = mx.sym.Variable("data")
        stem = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                                  num_filter=8, no_bias=True,
                                  name="conv0")
        bn = mx.sym.BatchNorm(stem, name="bn1", fix_gamma=False,
                              eps=1e-3, momentum=0.9)
        act = mx.sym.Activation(bn, act_type="relu", name="relu1")
        conv = mx.sym.Convolution(act, kernel=(1, 1), num_filter=16,
                                  no_bias=True, name="conv1")
        fc = mx.sym.FullyConnected(mx.sym.Flatten(conv), num_hidden=10,
                                   name="fc")
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        mod = mx.mod.Module(context=mx.current_context(), symbol=net, fused=True)
        mod.bind(data_shapes=[("data", (8, 3, 4, 4))],
                 label_shapes=[("softmax_label", (8,))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        rng = np.random.RandomState(0)
        for _ in range(steps):
            b = mx.io.DataBatch(
                [mx.nd.array(rng.randn(8, 3, 4, 4).astype(np.float32))],
                [mx.nd.array(rng.randint(0, 10, (8,)).astype(
                    np.float32))])
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()
        ap, au = mod.get_params()
        rep = mod._fused.fusion_report
        return ({k: v.asnumpy() for k, v in ap.items()},
                {k: v.asnumpy() for k, v in au.items()}, rep)


def test_fused_module_step_trains_bit_close():
    """A ResNet-style stem→BN→ReLU→1×1-conv block trains bit-close
    through the whole-step donated program with the rewrite on vs off
    (params AND aux running stats), and the step reports the site."""
    from mxnet_tpu.ops.pallas_fused import interpret_mode
    p1, a1, rep = _train_block("1")
    p0, a0, _ = _train_block("0")
    assert rep is not None and len(rep["sites"]) == 1
    # on the chip both programs multiply in bf16 passes, in different
    # orders: close, not bit-close (measured 1.7e-2 relative)
    tol = 2e-5 if interpret_mode() else 5e-2
    for k in p0:
        np.testing.assert_allclose(p1[k], p0[k], rtol=tol, atol=tol,
                                   err_msg=f"param {k}")
    for k in a0:
        np.testing.assert_allclose(a1[k], a0[k], rtol=tol, atol=tol,
                                   err_msg=f"aux {k}")


def test_bench_model_has_rewritten_sites():
    """The pass finds the bottleneck 1×1 convs of the flagship bench
    symbol (ResNet-50): ≥ 1 (in fact dozens of) rewritten sites."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "examples",
        "image_classification"))
    from symbols import resnet as resnet_sym
    from mxnet_tpu.symbol.fusion import fuse_symbol
    net = resnet_sym.get_symbol(1000, 50, "3,224,224")
    fused, rep = fuse_symbol(net, {"data": (8, 3, 224, 224)})
    assert len(rep["sites"]) >= 1
    # argument/aux ordering must survive the rewrite — the executors
    # feed values positionally by the original symbol's lists
    assert fused.list_arguments() == net.list_arguments()
    assert fused.list_auxiliary_states() == net.list_auxiliary_states()


def test_fusion_report_hook():
    """mxnet_tpu.fusion_report() aggregates the rewrites this process
    performed."""
    mx.fusion_report(reset=True)
    _run_executor(_block_sym(), "1")
    rep = mx.fusion_report()
    assert rep["num_rewritten_sites"] >= 1
    assert rep["rewrites"][-1]["tag"] == "executor"
    assert rep["by_tag"]["executor"] >= 1


def test_predict_program_rewrites_in_eval_mode():
    """The inference path gets the rewrite too: an inference-only bind
    (grad_req all null) routes through the pass under its own
    fusion_report tag, and the fused predict program matches the
    unfused one in EVAL mode — i.e. through the moving-stats branch of
    the fused op, which the train-step tests never touch."""
    sym = _block_sym()
    shape = (2, 8, 4, 4)
    rng = np.random.RandomState(3)
    x = rng.randn(*shape).astype(np.float32)
    mmean = rng.rand(8).astype(np.float32)
    mvar = rng.rand(8).astype(np.float32) + 0.5

    def run_predict(flag):
        with _flag(flag):
            mx.fusion_report(reset=True)
            mx.random.seed(0)
            np.random.seed(0)
            mod = mx.mod.Module(context=mx.current_context(), symbol=sym,
                                label_names=())
            mod.bind(data_shapes=[("data", shape)], for_training=False)
            mod.init_params(mx.init.Xavier())
            # distinctive moving stats so the eval path is actually
            # exercised (zeros/ones would alias the batch-stat branch)
            mod._exec.aux_dict["f_bn_moving_mean"][:] = mmean
            mod._exec.aux_dict["f_bn_moving_var"][:] = mvar
            mod.forward(mx.io.DataBatch([mx.nd.array(x)], None),
                        is_train=False)
            out = mod.get_outputs()[0].asnumpy().copy()
            return out, mx.fusion_report()

    o1, rep1 = run_predict("1")
    o0, rep0 = run_predict("0")
    assert rep1["by_tag"].get("executor_infer", 0) == 1, \
        "inference-only executor build must report under its own tag"
    assert rep0["num_rewritten_sites"] == 0
    np.testing.assert_allclose(o1, o0, rtol=2e-5, atol=2e-5)


def test_fused_step_bytes_accessed_below_unfused():
    """The HBM-traffic claim, pinned on the compiled whole train step:
    with the rewrite on, XLA cost analysis must report strictly fewer
    bytes accessed than the unfused step (same model, same shapes).
    The saving comes from the op's analytic fused backward — autodiff's
    separate BatchNorm statistics chains are collapsed into one
    full-tensor assembly pass."""
    from mxnet_tpu.ops.pallas_fused import interpret_mode
    if not interpret_mode():
        pytest.skip(
            "a count of the CPU proxy: on the chip XLA fuses the "
            "BN+ReLU prologue into the conv by itself and counts the "
            "Mosaic call's operands whole (PERF.md, PR 21 finding)")

    def lower_bytes(flag):
        with _flag(flag):
            mx.random.seed(0)
            np.random.seed(0)
            B, C, HW, NF = 16, 32, 8, 64
            data = mx.sym.Variable("data")
            stem = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                                      num_filter=C, no_bias=True,
                                      name="conv0")
            bn = mx.sym.BatchNorm(stem, name="bn1", fix_gamma=False,
                                  eps=1e-3, momentum=0.9)
            act = mx.sym.Activation(bn, act_type="relu", name="relu1")
            conv = mx.sym.Convolution(act, kernel=(1, 1), num_filter=NF,
                                      no_bias=True, name="conv1")
            pool = mx.sym.Pooling(conv, global_pool=True, kernel=(1, 1),
                                  pool_type="avg", name="pool")
            fc = mx.sym.FullyConnected(mx.sym.Flatten(pool),
                                       num_hidden=10, name="fc")
            net = mx.sym.SoftmaxOutput(fc, name="softmax")
            mod = mx.mod.Module(context=mx.current_context(), symbol=net,
                                fused=True)
            mod.bind(data_shapes=[("data", (B, 3, HW, HW))],
                     label_shapes=[("softmax_label", (B,))])
            mod.init_params(mx.init.Xavier())
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.1})
            fused = mod._fused
            rng = np.random.RandomState(0)
            feed = {
                fused.data_names[0]: mx.nd.array(
                    rng.randn(B, 3, HW, HW).astype(np.float32)).data,
                fused.label_names[0]: mx.nd.array(
                    rng.randint(0, 10, (B,)).astype(np.float32)).data,
            }
            cost = fused.step_cost(feed)
            sites = len((fused.fusion_report or {}).get("sites", []))
            return float(cost.get("bytes accessed", 0.0)), sites

    fused_bytes, sites = lower_bytes("1")
    unfused_bytes, _ = lower_bytes("0")
    assert sites == 1
    assert fused_bytes > 0 and unfused_bytes > 0
    assert fused_bytes < unfused_bytes, (
        f"fused step bytes {fused_bytes} not below unfused "
        f"{unfused_bytes}")
