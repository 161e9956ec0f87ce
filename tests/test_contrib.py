"""Contrib tests: INT8 quantization, text embeddings/vocab, tensorboard
bridge, visualization (reference: python/mxnet/contrib/,
python/mxnet/visualization.py)."""
import collections
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn


class TestQuantization:
    def _mlp(self):
        mx.random.seed(0)
        net = nn.HybridSequential(prefix="q_")
        with net.name_scope():
            net.add(nn.Dense(64, activation="relu"),
                    nn.Dense(32, activation="relu"),
                    nn.Dense(10))
        net.initialize(mx.init.Xavier())
        return net

    def test_quantize_net_close_to_fp32(self):
        from mxnet_tpu.contrib.quantization import quantize_net
        net = self._mlp()
        rng = np.random.RandomState(0)
        calib = [nd.array(rng.randn(16, 20).astype(np.float32))
                 for _ in range(4)]
        qnet = quantize_net(net, calib, calib_mode="naive")
        x = nd.array(rng.randn(8, 20).astype(np.float32))
        fp32 = net(x).asnumpy()
        int8 = qnet(x).asnumpy()
        # int8 sim must track fp32 closely relative to activation scale
        denom = np.abs(fp32).max() + 1e-6
        rel = np.abs(fp32 - int8).max() / denom
        assert rel < 0.1, f"relative int8 error {rel}"
        # argmax predictions agree on most samples
        agree = (fp32.argmax(1) == int8.argmax(1)).mean()
        assert agree >= 0.75, agree

    def test_quantize_net_entropy_mode(self):
        from mxnet_tpu.contrib.quantization import quantize_net
        net = self._mlp()
        rng = np.random.RandomState(1)
        calib = [nd.array(rng.randn(16, 20).astype(np.float32))
                 for _ in range(4)]
        qnet = quantize_net(net, calib, calib_mode="entropy")
        x = nd.array(rng.randn(4, 20).astype(np.float32))
        fp32 = net(x).asnumpy()
        int8 = qnet(x).asnumpy()
        denom = np.abs(fp32).max() + 1e-6
        assert np.abs(fp32 - int8).max() / denom < 0.25

    def test_quantize_model_symbolic_facade(self):
        from mxnet_tpu.contrib.quantization import quantize_model
        sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                    name="fc")
        rng = np.random.RandomState(0)
        args = {"fc_weight": nd.array(rng.randn(4, 6).astype(np.float32)),
                "fc_bias": nd.zeros((4,))}
        qsym, qargs, qaux, th = quantize_model(sym, args, {})
        assert "fc_weight_quantized" in qargs
        assert qargs["fc_weight_quantized"].dtype == np.int8
        # dequantized weight close to original
        np.testing.assert_allclose(qargs["fc_weight"].asnumpy(),
                                   args["fc_weight"].asnumpy(),
                                   atol=float(th["fc_weight"]) / 127 + 1e-6)

    def test_quantize_array(self):
        from mxnet_tpu.contrib.quantization import quantize_array
        a = np.array([-2.0, -1.0, 0.0, 1.0, 2.0], np.float32)
        q, scale = quantize_array(nd.array(a))
        np.testing.assert_allclose(np.asarray(q) * scale, a, atol=scale)
        assert np.asarray(q).dtype == np.int8


class TestTextContrib:
    def test_vocabulary(self):
        from mxnet_tpu.contrib.text import Vocabulary
        counter = collections.Counter(
            ["a", "a", "a", "b", "b", "c", "rare"])
        v = Vocabulary(counter, min_freq=2, reserved_tokens=["<pad>"])
        assert v.idx_to_token[0] == "<unk>"
        assert v.idx_to_token[1] == "<pad>"
        assert v.to_indices("a") == 2          # most frequent first
        assert v.to_indices(["b", "zzz"]) == [3, 0]
        assert v.to_tokens(2) == "a"
        assert len(v) == 4                     # unk, pad, a, b

    def test_count_tokens(self):
        from mxnet_tpu.contrib.text.utils import count_tokens_from_str
        c = count_tokens_from_str("a b  b\nc a", to_lower=False)
        assert c["a"] == 2 and c["b"] == 2 and c["c"] == 1

    def test_custom_embedding_from_file(self, tmp_path):
        from mxnet_tpu.contrib.text.embedding import CustomEmbedding
        p = tmp_path / "emb.txt"
        p.write_text("hello 0.1 0.2 0.3\nworld 0.4 0.5 0.6\n")
        emb = CustomEmbedding(str(p))
        assert emb.vec_len == 3
        np.testing.assert_allclose(
            emb.get_vecs_by_tokens("world").asnumpy(), [0.4, 0.5, 0.6],
            rtol=1e-6)
        # unknown -> zeros
        np.testing.assert_allclose(
            emb.get_vecs_by_tokens("nope").asnumpy(), [0, 0, 0])
        batch = emb.get_vecs_by_tokens(["hello", "world"])
        assert batch.shape == (2, 3)
        emb.update_token_vectors("hello", nd.array([1.0, 1.0, 1.0]))
        np.testing.assert_allclose(
            emb.get_vecs_by_tokens("hello").asnumpy(), [1, 1, 1])

    def test_registry_create(self):
        from mxnet_tpu.contrib.text import embedding as emb_mod
        names = emb_mod.get_pretrained_file_names()
        assert "glove" in names and "fasttext" in names


class TestTensorboardBridge:
    def test_log_metrics_callback(self, tmp_path):
        from mxnet_tpu.contrib.tensorboard import LogMetricsCallback
        cb = LogMetricsCallback(str(tmp_path), prefix="train")
        metric = mx.metric.Accuracy()
        metric.update([nd.array([0, 1])], [nd.array([0, 1])])

        class Param:
            eval_metric = metric
        cb(Param())
        files = os.listdir(tmp_path)
        assert files, "no event files written"
        jsonl = tmp_path / "metrics.jsonl"
        if jsonl.exists():
            rec = json.loads(jsonl.read_text().splitlines()[0])
            assert rec["metric"].startswith("train-")


class TestVisualization:
    def _sym(self):
        data = mx.sym.Variable("data")
        fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
        fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
        return mx.sym.SoftmaxOutput(fc2, name="softmax")

    def test_plot_network_dot(self, tmp_path):
        dot = mx.viz.plot_network(self._sym(), title="mlp")
        src = dot.source
        assert "fc1" in src and "relu1" in src and "->" in src
        # weights hidden by default
        assert "fc1_weight" not in src
        path = dot.render(str(tmp_path / "mlp"), format="dot")
        assert os.path.exists(path)

    def test_plot_network_show_weights(self):
        dot = mx.viz.plot_network(self._sym(), hide_weights=False)
        assert "fc1_weight" in dot.source

    def test_print_summary(self, capsys):
        total = mx.viz.print_summary(self._sym(), shape={"data": (1, 16)})
        out = capsys.readouterr().out
        assert "fc1" in out and "Total params" in out
        # fc1: 16*8+8, fc2: 8*3+3
        assert total == 16 * 8 + 8 + 8 * 3 + 3


class TestModelStore:
    def test_get_model_file_missing_raises(self, tmp_path):
        from mxnet_tpu.gluon.model_zoo.model_store import get_model_file
        try:
            get_model_file("resnet18_v1", root=str(tmp_path))
            assert False
        except FileNotFoundError as e:
            assert "egress" in str(e)

    def test_pretrained_loads_local_params(self, tmp_path, monkeypatch):
        # drop a params file in the zoo root -> pretrained=True finds it
        from mxnet_tpu.gluon.model_zoo.model_store import get_model_file
        from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
        net = resnet18_v1()
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 3, 32, 32)))  # materialize params
        net.save_parameters(str(tmp_path / "resnet18_v1.params"))
        path = get_model_file("resnet18_v1", root=str(tmp_path))
        net2 = resnet18_v1(pretrained=True, root=str(tmp_path))
        a = net.collect_params()
        b = net2.collect_params()
        k = sorted(a.keys())[0]
        kb = sorted(b.keys())[0]
        np.testing.assert_allclose(a[k].data().asnumpy(),
                                   b[kb].data().asnumpy())


class TestOnnxImport:
    """Converter exercised with duck-typed GraphProto objects — the op
    mapping is the capability; .onnx protobuf parsing needs the onnx pkg
    (reference: contrib/onnx/_import/import_onnx.py)."""

    @staticmethod
    def _graph():
        class Attr:
            def __init__(self, name, **kw):
                self.name = name
                for k, v in kw.items():
                    setattr(self, k, v)

        class Tensor:
            def __init__(self, name, array):
                self.name = name
                self.array = array
                self.dims = array.shape

        class Node:
            def __init__(self, op_type, inputs, outputs, name="", attrs=()):
                self.op_type = op_type
                self.input = inputs
                self.output = outputs
                self.name = name
                self.attribute = attrs

        class Graph:
            pass

        rng = np.random.RandomState(0)
        w1 = rng.randn(8, 6).astype(np.float32)     # (units, in): transB=1
        b1 = np.zeros(8, np.float32)
        w2 = rng.randn(8, 3).astype(np.float32)     # transB=0: needs .T
        b2 = np.zeros(3, np.float32)
        g = Graph()
        g.node = [
            Node("Gemm", ["x", "w1", "b1"], ["h"], "gemm1",
                 (Attr("transB", i=1),)),
            Node("Relu", ["h"], ["hr"], "relu1"),
            Node("Gemm", ["hr", "w2", "b2"], ["logits"], "gemm2",
                 (Attr("transB", i=0),)),
            Node("Softmax", ["logits"], ["prob"], "softmax",
                 (Attr("axis", i=1),)),
        ]
        g.input = ["x", "w1", "b1", "w2", "b2"]
        g.output = ["prob"]
        g.initializer = [Tensor("w1", w1), Tensor("b1", b1),
                         Tensor("w2", w2), Tensor("b2", b2)]
        return g, w1, b1, w2, b2

    def test_import_mlp_and_run(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        g, w1, b1, w2, b2 = self._graph()
        sym, arg_params, aux_params = import_onnx_graph(g)
        assert "x" in sym.list_arguments()
        exe = sym.simple_bind(mx.cpu(), x=(2, 6))
        for k, v in arg_params.items():
            if k in exe.arg_dict:
                exe.arg_dict[k][:] = v.asnumpy()
        x = np.random.RandomState(1).randn(2, 6).astype(np.float32)
        exe.arg_dict["x"][:] = x
        out = exe.forward(is_train=False)[0].asnumpy()
        # numpy reference
        h = np.maximum(x @ w1.T + b1, 0)
        logits = h @ w2 + b2
        e = np.exp(logits - logits.max(1, keepdims=True))
        expect = e / e.sum(1, keepdims=True)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)

    def test_unmapped_op_raises(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        g, *_ = self._graph()

        class Node:
            op_type = "NonexistentOp"
            input = ["x"]
            output = ["y"]
            name = "bad"
            attribute = ()
        g.node = [Node()]
        g.output = ["y"]
        try:
            import_onnx_graph(g)
            assert False
        except NotImplementedError as e:
            assert "NonexistentOp" in str(e)

    def test_import_model_requires_onnx_pkg(self, tmp_path):
        from mxnet_tpu.contrib.onnx import import_model
        # a bad path is a file error, not a masked onnx-package error
        with pytest.raises(OSError):
            import_model("/nonexistent.onnx")
        try:
            import onnx  # noqa: F401
        except ImportError:
            # real file the vendored parser can't read -> needs onnx pkg
            bad = tmp_path / "junk.onnx"
            bad.write_bytes(b"\x00\x01 not a model")
            try:
                import_model(str(bad))
                assert False
            except ImportError as e:
                assert "onnx" in str(e)


class TestConfig:
    def test_registered_defaults_and_env_override(self, monkeypatch):
        from mxnet_tpu import config
        assert config.get("MXNET_KVSTORE_BIGARRAY_BOUND") == 1000000
        monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "42")
        assert config.get("MXNET_KVSTORE_BIGARRAY_BOUND") == 42
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "true")
        assert config.get("MXNET_BACKWARD_DO_MIRROR") is True

    def test_show_table(self, capsys):
        from mxnet_tpu import config
        config.show()
        out = capsys.readouterr().out
        assert "MXNET_ENGINE_TYPE" in out

    def test_remat_step_trains(self):
        # gradient mirroring: jax.checkpoint path numerically matches
        import numpy as np
        from mxnet_tpu.parallel import TrainStep
        x = np.random.RandomState(0).randn(8, 12).astype(np.float32)
        y = np.random.RandomState(1).randint(0, 4, (8,))
        losses = {}
        for remat in (False, True):
            mx.random.seed(11)
            net = nn.HybridSequential(prefix=f"remat{remat}_")
            with net.name_scope():
                net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
            net.initialize(mx.init.Xavier())
            step = TrainStep(net, lr=0.05, remat=remat)
            losses[remat] = [float(step(x, y).asscalar()) for _ in range(3)]
        np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5)


class _OnnxAttr:
    def __init__(self, name, **kw):
        self.name = name
        for k, v in kw.items():
            setattr(self, k, v)


class _OnnxTensor:
    def __init__(self, name, array):
        self.name = name
        self.array = np.asarray(array)
        self.dims = self.array.shape


class _OnnxNode:
    def __init__(self, op_type, ins, outs, name="", attrs=()):
        self.op_type = op_type
        self.input = ins
        self.output = outs
        self.name = name
        self.attribute = attrs


class TestOnnxImportDetails:
    """Regression tests for the importer's attribute handling."""

    @staticmethod
    def _mk(nodes, inputs, outputs, initializers):
        class Graph:
            pass
        g = Graph()
        g.node = [_OnnxNode(*n[:3], **(n[3] if len(n) > 3 else {}))
                  for n in nodes]
        g.input = inputs
        g.output = outputs
        g.initializer = [_OnnxTensor(k, v) for k, v in initializers.items()]
        return g, _OnnxAttr

    def test_batchnorm_running_stats_are_aux(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        Attr = _OnnxAttr
        g, _ = self._mk(
            [("BatchNormalization", ["x", "g", "b", "m", "v"], ["y"], {
                "name": "bn",
                "attrs": (Attr("epsilon", f=1e-5),)})],
            ["x", "g", "b", "m", "v"], ["y"],
            {"g": np.ones(3, np.float32), "b": np.zeros(3, np.float32),
             "m": np.full(3, 2.0, np.float32),
             "v": np.full(3, 4.0, np.float32)})
        sym, args, aux = import_onnx_graph(g)
        assert set(aux.keys()) == {"m", "v"}
        assert set(sym.list_auxiliary_states()) == {"m", "v"}
        exe = sym.simple_bind(mx.cpu(), x=(2, 3, 4, 4))
        for k, v in args.items():
            if k in exe.arg_dict:
                exe.arg_dict[k][:] = v.asnumpy()
        for k, v in aux.items():
            exe.aux_dict[k][:] = v.asnumpy()
        x = np.random.RandomState(0).randn(2, 3, 4, 4).astype(np.float32)
        exe.arg_dict["x"][:] = x
        out = exe.forward(is_train=False)[0].asnumpy()
        expect = (x - 2.0) / np.sqrt(4.0 + 1e-5)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)

    def test_pad_interleaving(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        Attr = _OnnxAttr
        g, _ = self._mk(
            [("Pad", ["x"], ["y"], {
                "name": "pad",
                "attrs": (Attr("pads", ints=(0, 0, 1, 1, 0, 0, 1, 1)),
                          Attr("mode", s="constant"))})],
            ["x"], ["y"], {})
        sym, args, _ = import_onnx_graph(g)
        exe = sym.simple_bind(mx.cpu(), x=(1, 2, 3, 3))
        exe.arg_dict["x"][:] = np.ones((1, 2, 3, 3), np.float32)
        out = exe.forward(is_train=False)[0]
        assert out.shape == (1, 2, 5, 5)   # H and W padded, not C

    def test_clip_minmax_from_inputs(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        g, _ = self._mk(
            [("Clip", ["x", "lo", "hi"], ["y"], {"name": "clip"})],
            ["x"], ["y"],
            {"lo": np.float32(0.0), "hi": np.float32(6.0)})
        sym, args, _ = import_onnx_graph(g)
        exe = sym.simple_bind(mx.cpu(), x=(4,))
        exe.arg_dict["x"][:] = np.array([-1, 3, 7, 100], np.float32)
        out = exe.forward(is_train=False)[0].asnumpy()
        np.testing.assert_allclose(out, [0, 3, 6, 6])

    def test_gemm_alpha_beta(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        w = np.ones((2, 3), np.float32)
        b = np.ones(2, np.float32)
        Attr = _OnnxAttr
        g, _ = self._mk(
            [("Gemm", ["x", "w", "b"], ["y"], {
                "name": "gemm",
                "attrs": (Attr("transB", i=1), Attr("alpha", f=0.5),
                          Attr("beta", f=2.0))})],
            ["x", "w", "b"], ["y"], {"w": w, "b": b})
        sym, args, _ = import_onnx_graph(g)
        exe = sym.simple_bind(mx.cpu(), x=(1, 3))
        for k, v in args.items():
            if k in exe.arg_dict:
                exe.arg_dict[k][:] = v.asnumpy()
        exe.arg_dict["x"][:] = np.ones((1, 3), np.float32)
        out = exe.forward(is_train=False)[0].asnumpy()
        np.testing.assert_allclose(out, [[3.5, 3.5]])  # 0.5*3 + 2*1

    def test_asymmetric_pads_raise(self):
        from mxnet_tpu.contrib.onnx import import_onnx_graph
        w = np.ones((4, 3, 3, 3), np.float32)
        Attr = _OnnxAttr
        g, _ = self._mk(
            [("Conv", ["x", "w"], ["y"], {
                "name": "conv",
                "attrs": (Attr("kernel_shape", ints=(3, 3)),
                          Attr("pads", ints=(0, 0, 1, 1)))})],
            ["x", "w"], ["y"], {"w": w})
        try:
            import_onnx_graph(g)
            assert False
        except NotImplementedError as e:
            assert "asymmetric" in str(e)


class TestMiscParity:
    def test_count_sketch(self):
        rng = np.random.RandomState(0)
        data = rng.randn(3, 10).astype(np.float32)
        h = rng.randint(0, 6, (1, 10))
        s = rng.choice([-1, 1], (1, 10)).astype(np.float32)
        out = nd.count_sketch(nd.array(data), nd.array(h), nd.array(s),
                              out_dim=6)
        ref = np.zeros((3, 6), np.float32)
        for i in range(10):
            ref[:, h[0, i]] += s[0, i] * data[:, i]
        np.testing.assert_allclose(out.asnumpy(), ref, atol=1e-5)

    def test_count_sketch_grad(self):
        rng = np.random.RandomState(1)
        data = nd.array(rng.randn(2, 6).astype(np.float32))
        h = nd.array(rng.randint(0, 4, (1, 6)))
        s = nd.array(rng.choice([-1, 1], (1, 6)).astype(np.float32))
        data.attach_grad()
        with mx.autograd.record():
            loss = nd.count_sketch(data, h, s, out_dim=4).sum()
        loss.backward()
        np.testing.assert_allclose(data.grad.asnumpy(),
                                   np.broadcast_to(s.asnumpy(), (2, 6)),
                                   atol=1e-6)

    def test_legacy_v1_aliases(self):
        x = nd.Pooling_v1(nd.ones((1, 2, 4, 4)), kernel=(2, 2),
                          stride=(2, 2), pool_type="avg")
        assert x.shape == (1, 2, 2, 2)
        np.testing.assert_allclose(x.asnumpy(), 1.0)
        sym = mx.sym.Convolution_v1(mx.sym.Variable("data"),
                                    kernel=(3, 3), num_filter=4, pad=(1, 1),
                                    name="conv")
        exe = sym.simple_bind(mx.cpu(), data=(1, 3, 8, 8))
        assert exe.forward(is_train=False)[0].shape == (1, 4, 8, 8)

    def test_engine_bulk_scope(self):
        prev = mx.engine.set_bulk_size(0)
        with mx.engine.bulk(16):
            y = nd.ones((2, 2)) + 1
        np.testing.assert_allclose(y.asnumpy(), 2.0)
        mx.engine.set_bulk_size(prev)

    def test_launch_py_spawns_workers(self, tmp_path):
        import subprocess
        import sys
        import pathlib
        script = tmp_path / "worker.py"
        # per-rank result files: shared inherited stdout interleaves
        # nondeterministically under load (the r3 flake)
        script.write_text(
            "import os\n"
            "assert 'COORDINATOR_ADDRESS' in os.environ\n"
            f"open(os.path.join({str(tmp_path)!r}, "
            "'rank_' + os.environ['PROCESS_ID']), 'w').write('ok')\n")
        launcher = (pathlib.Path(__file__).parent.parent / "tools"
                    / "launch.py")
        cmd = [sys.executable, str(launcher), "-n", "2",
               sys.executable, str(script)]
        import os
        out = subprocess.run(cmd, capture_output=True, timeout=60,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr.decode()
        assert (tmp_path / "rank_0").read_text() == "ok"
        assert (tmp_path / "rank_1").read_text() == "ok"
        # workers that may open a chip: two per host is refused, with
        # the reason, before anything starts
        (tmp_path / "rank_0").unlink()
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_PLATFORMS"}
        out = subprocess.run(cmd, capture_output=True, timeout=60,
                             env=env)
        assert out.returncode != 0
        assert b"one process" in out.stderr
        assert not (tmp_path / "rank_0").exists()


class TestQuantizedConvNet:
    def test_quantized_resnet18_tracks_fp32(self):
        """VERDICT criterion: quantized resnet18 within tolerance of fp32."""
        from mxnet_tpu.contrib.quantization import quantize_net
        from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
        mx.random.seed(0)
        net = resnet18_v1(classes=10)
        net.initialize(mx.init.Xavier())
        rng = np.random.RandomState(0)
        calib = [nd.array(rng.rand(4, 3, 32, 32).astype(np.float32))
                 for _ in range(2)]
        net(calib[0])                    # materialize deferred params
        qnet = quantize_net(net, calib, calib_mode="naive")
        x = nd.array(rng.rand(4, 3, 32, 32).astype(np.float32))
        fp32 = net(x).asnumpy()
        int8 = qnet(x).asnumpy()
        denom = np.abs(fp32).max() + 1e-6
        rel = np.abs(fp32 - int8).max() / denom
        assert rel < 0.15, f"relative int8 error {rel}"
        agree = (fp32.argmax(1) == int8.argmax(1)).mean()
        assert agree >= 0.75, agree
