"""Fused RNN layers (reference: python/mxnet/gluon/rnn/rnn_layer.py:32-432).

Each layer keeps per-layer/direction i2h/h2h weights (reference param
naming for checkpoint parity) and concatenates them into the flat
cuDNN-layout vector consumed by the fused ``RNN`` op (the
cuDNN-fused-kernel analog, src/operator/cudnn_rnn-inl.h). There a layer
and direction is one i2h product over the whole sequence before a
``lax.scan``, one h2h product a step inside it, and in the backward pass
one whole-sequence product before the scan (the h2h pre-activations
again), one product a step inside it and three whole-sequence products
after it (``ops/nn.py`` ``_run_layer``). The op has no forward-mode
derivative (``jax.jvp`` raises).
"""
from __future__ import annotations

import numpy as np

from ... import ndarray as F
from ..block import HybridBlock

__all__ = ["RNN", "LSTM", "GRU"]


class _RNNLayer(HybridBlock):
    """(reference: rnn_layer.py:32)"""

    def __init__(self, hidden_size, num_layers, layout, dropout,
                 bidirectional, input_size, i2h_weight_initializer,
                 h2h_weight_initializer, i2h_bias_initializer,
                 h2h_bias_initializer, mode, **kwargs):
        super().__init__(**kwargs)
        assert layout in ("TNC", "NTC"), \
            f"Invalid layout {layout}; must be one of ['TNC' or 'NTC']"
        self._hidden_size = hidden_size
        self._num_layers = num_layers
        self._mode = mode
        self._layout = layout
        self._dropout = dropout
        self._dir = 2 if bidirectional else 1
        self._input_size = input_size
        self._i2h_weight_initializer = i2h_weight_initializer
        self._h2h_weight_initializer = h2h_weight_initializer
        self._i2h_bias_initializer = i2h_bias_initializer
        self._h2h_bias_initializer = h2h_bias_initializer
        self._gates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4,
                       "gru": 3}[mode]
        ng, ni, nh = self._gates, input_size, hidden_size
        for i in range(num_layers):
            for j in ["l", "r"][:self._dir]:
                self._register_param(f"{j}{i}_i2h_weight",
                                     (ng * nh, ni), i2h_weight_initializer)
                self._register_param(f"{j}{i}_h2h_weight",
                                     (ng * nh, nh), h2h_weight_initializer)
                self._register_param(f"{j}{i}_i2h_bias",
                                     (ng * nh,), i2h_bias_initializer)
                self._register_param(f"{j}{i}_h2h_bias",
                                     (ng * nh,), h2h_bias_initializer)
            ni = nh * self._dir

    def _register_param(self, name, shape, init):
        p = self.params.get(name, shape=shape, init=init,
                            allow_deferred_init=True)
        self._reg_params[name] = p
        object.__setattr__(self, name, p)

    def __repr__(self):
        s = "{name}({mapping}, {_layout}"
        if self._num_layers != 1:
            s += ", num_layers={_num_layers}"
        if self._dropout != 0:
            s += ", dropout={_dropout}"
        if self._dir == 2:
            s += ", bidirectional"
        s += ")"
        shape = self.l0_i2h_weight.shape
        mapping = f"{shape[1] if shape[1] else None} -> " \
            f"{shape[0] // self._gates}"
        return s.format(name=self.__class__.__name__, mapping=mapping,
                        **self.__dict__)

    def state_info(self, batch_size=0):
        raise NotImplementedError

    def begin_state(self, batch_size=0, func=None, **kwargs):
        """Initial recurrent states (reference: rnn_layer.py:166)."""
        from ... import ndarray as nd
        states = []
        for info in self.state_info(batch_size):
            info = {k: v for k, v in info.items() if not k.startswith("__")}
            if func is None:
                states.append(nd.zeros(**info, **kwargs))
            else:
                info.update(kwargs)
                states.append(func(**info))
        return states

    def infer_shape(self, x, *args):
        ni = x.shape[2] if self._layout == "TNC" else x.shape[-1]
        ng, nh = self._gates, self._hidden_size
        for i in range(self._num_layers):
            for j in ["l", "r"][:self._dir]:
                getattr(self, f"{j}{i}_i2h_weight")._infer_shape(
                    (ng * nh, ni))
                getattr(self, f"{j}{i}_h2h_weight")._infer_shape(
                    (ng * nh, nh))
                getattr(self, f"{j}{i}_i2h_bias")._infer_shape((ng * nh,))
                getattr(self, f"{j}{i}_h2h_bias")._infer_shape((ng * nh,))
            ni = nh * self._dir

    def forward(self, inputs, states=None):
        """(reference: rnn_layer.py:183)"""
        batch_size = inputs.shape[self._layout.find("N")]
        skip_states = states is None
        if skip_states:
            states = self.begin_state(batch_size)
        if hasattr(states, "shape"):  # single NDArray
            states = [states]
        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except Exception:
            self.infer_shape(inputs)
            for p in self._reg_params.values():
                if p._deferred_init:
                    p._finish_deferred_init()
            params = {k: p.data() for k, p in self._reg_params.items()}
        out = self._forward_kernel(inputs, states, params)
        return out[0] if skip_states else out

    def _flat_params(self, params):
        """Concatenate per-layer params into the cuDNN layout
        (weights for all layers, then all biases — rnn-inl.h)."""
        order = []
        for i in range(self._num_layers):
            for j in ["l", "r"][:self._dir]:
                order.append(params[f"{j}{i}_i2h_weight"])
                order.append(params[f"{j}{i}_h2h_weight"])
        for i in range(self._num_layers):
            for j in ["l", "r"][:self._dir]:
                order.append(params[f"{j}{i}_i2h_bias"])
                order.append(params[f"{j}{i}_h2h_bias"])
        return F.concat(*[p.reshape((-1,)) for p in order], dim=0)

    def _forward_kernel(self, inputs, states, params):
        if self._layout == "NTC":
            inputs = inputs.swapaxes(0, 1)
        flat = self._flat_params(params)
        outputs = F.RNN(inputs, flat, *states, state_size=self._hidden_size,
                        num_layers=self._num_layers,
                        bidirectional=self._dir == 2, p=self._dropout,
                        state_outputs=True, mode=self._mode)
        if self._mode == "lstm":
            outputs, states = outputs[0], [outputs[1], outputs[2]]
        else:
            outputs, states = outputs[0], [outputs[1]]
        if self._layout == "NTC":
            outputs = outputs.swapaxes(0, 1)
        return outputs, states


class RNN(_RNNLayer):
    """Vanilla Elman RNN with relu/tanh (reference: rnn_layer.py:244)."""

    def __init__(self, hidden_size, num_layers=1, activation="relu",
                 layout="TNC", dropout=0, bidirectional=False, i2h_weight_initializer=None,
                 h2h_weight_initializer=None, i2h_bias_initializer="zeros",
                 h2h_bias_initializer="zeros", input_size=0, **kwargs):
        super().__init__(hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, i2h_weight_initializer,
                         h2h_weight_initializer, i2h_bias_initializer,
                         h2h_bias_initializer, "rnn_" + activation, **kwargs)

    def state_info(self, batch_size=0):
        return [{"shape": (self._num_layers * self._dir, batch_size,
                           self._hidden_size), "__layout__": "LNC"}]


class LSTM(_RNNLayer):
    """(reference: rnn_layer.py:318)"""

    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 **kwargs):
        super().__init__(hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, i2h_weight_initializer,
                         h2h_weight_initializer, i2h_bias_initializer,
                         h2h_bias_initializer, "lstm", **kwargs)

    def state_info(self, batch_size=0):
        return [{"shape": (self._num_layers * self._dir, batch_size,
                           self._hidden_size), "__layout__": "LNC"},
                {"shape": (self._num_layers * self._dir, batch_size,
                           self._hidden_size), "__layout__": "LNC"}]


class GRU(_RNNLayer):
    """(reference: rnn_layer.py:398)"""

    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 **kwargs):
        super().__init__(hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, i2h_weight_initializer,
                         h2h_weight_initializer, i2h_bias_initializer,
                         h2h_bias_initializer, "gru", **kwargs)

    def state_info(self, batch_size=0):
        return [{"shape": (self._num_layers * self._dir, batch_size,
                           self._hidden_size), "__layout__": "LNC"}]
