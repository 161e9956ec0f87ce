"""Basic Gluon layers (reference: python/mxnet/gluon/nn/basic_layers.py).

Dense/Dropout/BatchNorm/Embedding/... — thin parameterized wrappers over the
op library; all compute lowers to XLA (matmuls hit the MXU directly).
"""
from __future__ import annotations

import numpy as np

import jax

from ... import autograd
from ..block import Block, HybridBlock, stateful_write
from .activations import Activation

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Embedding",
           "BatchNorm", "InstanceNorm", "LayerNorm", "Flatten", "Lambda",
           "HybridLambda", "HybridConcurrent", "Concurrent", "Identity"]


class Sequential(Block):
    """Stacks Blocks sequentially (reference: basic_layers.py:29)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        """Sequential (non-hybrid) only hybridizes children
        (reference: basic_layers.py:76)."""
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Stacks HybridBlocks; hybridizable as one XLA program
    (reference: basic_layers.py:92)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer: ``act(dot(x, W^T) + b)``
    (reference: basic_layers.py:128). Weight layout (units, in_units) matches
    the reference so checkpoints interchange. In a compiled program the
    layer's product, forward and backward, carries the scope ``mx_dense``
    (``telemetry.trace.scope_table``)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        self._units = units
        self._in_units = in_units
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), dtype=dtype,
                    init=bias_initializer, allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def infer_shape(self, x):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight._infer_shape((self._units, in_units))
        if self.bias is not None:
            self.bias._infer_shape((self._units,))

    def hybrid_forward(self, F, x, weight, bias=None):
        with jax.named_scope("mx_dense"):
            out = F.FullyConnected(x, weight, bias, no_bias=bias is None,
                                   num_hidden=self._units,
                                   flatten=self._flatten)
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (f"{self.__class__.__name__}"
                f"({shape[1] if len(shape) > 1 and shape[1] else None} -> "
                f"{shape[0]}, "
                f"{'linear' if self.act is None else self.act._act_type})")


class Dropout(HybridBlock):
    """(reference: basic_layers.py:219)"""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return f"{self.__class__.__name__}(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """Batch normalization with running-average stats
    (reference: basic_layers.py:262; op src/operator/nn/batch_norm.cc).

    Functional-state design: the batch mean/var computed inside the (possibly
    jitted) forward are threaded out via ``stateful_write`` and folded into
    the running stats — the XLA-native analog of the reference's in-place aux
    state mutation.
    """

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale,
                        "use_global_stats": use_global_stats}
        self._axis = axis
        self._momentum = momentum
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)
            self.running_mean = self.params.get(
                "running_mean", grad_req="null", shape=(in_channels,),
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False)
            self.running_var = self.params.get(
                "running_var", grad_req="null", shape=(in_channels,),
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False)

    def infer_shape(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p._infer_shape((c,))

    def cast(self, dtype):
        if np.dtype(dtype).name == "float16":
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        training = autograd.is_training()
        out, batch_mean, batch_var = F.BatchNorm(
            x, gamma, beta, running_mean, running_var,
            training=training, **self._kwargs)
        if training and not self._kwargs["use_global_stats"]:
            m = self._momentum
            stateful_write(self.running_mean,
                           running_mean * m + batch_mean * (1 - m))
            stateful_write(self.running_var,
                           running_var * m + batch_var * (1 - m))
        return out

    def __repr__(self):
        in_channels = self.gamma.shape[0] if self.gamma.shape else None
        return (f"{self.__class__.__name__}(axis={self._axis}, "
                f"eps={self._kwargs['eps']}, momentum={self._momentum}, "
                f"in_channels={in_channels})")


class InstanceNorm(HybridBlock):
    """(reference: basic_layers.py:415)"""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self._axis = axis
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x):
        c = x.shape[self._axis]
        self.gamma._infer_shape((c,))
        self.beta._infer_shape((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis != 1:
            x = x.swapaxes(1, self._axis)
        out = F.InstanceNorm(x, gamma, beta, eps=self._epsilon)
        return out if self._axis == 1 else out.swapaxes(1, self._axis)


class LayerNorm(HybridBlock):
    """(reference: basic_layers.py:497)"""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x):
        c = x.shape[self._axis]
        self.gamma._infer_shape((c,))
        self.beta._infer_shape((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class Embedding(HybridBlock):
    """Index → dense vector lookup (reference: basic_layers.py:565;
    op src/operator/tensor/indexing_op.cc Embedding).

    On TPU the lookup is an XLA gather; sharding the (large) table over the
    mesh is handled by the parallel layer."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": dtype, "sparse_grad": sparse_grad}
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)

    def infer_shape(self, x):
        pass

    def hybrid_forward(self, F, x, weight):
        # the lookup, and backward the scatter of its gradient
        with jax.named_scope("mx_embed"):
            return F.Embedding(x, weight, **self._kwargs)

    def __repr__(self):
        return (f"{self.__class__.__name__}({self._input_dim} -> "
                f"{self._output_dim}, {self._kwargs['dtype']})")


class Flatten(HybridBlock):
    """(reference: basic_layers.py:629)"""

    def hybrid_forward(self, F, x):
        return x.reshape((0, -1))

    def __repr__(self):
        return self.__class__.__name__


class HybridConcurrent(HybridBlock):
    """Run children on the same input and concat their outputs
    (reference: python/mxnet/gluon/contrib/nn/basic_layers.py
    HybridConcurrent; used by squeezenet/densenet/inception)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x):
        out = [block(x) for block in self._children.values()]
        return F.concat(*out, dim=self.axis)


class Concurrent(HybridConcurrent):
    """Non-hybrid alias (reference: contrib/nn Concurrent)."""


class Identity(HybridBlock):
    """(reference: contrib/nn Identity)"""

    def hybrid_forward(self, F, x):
        return x


class Lambda(Block):
    """Wraps a function as a Block (reference: basic_layers.py:647)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as F
            if not hasattr(F, function):
                raise ValueError(f"Function name {function} is not found in "
                                 "ndarray namespace")
            self._func_impl = getattr(F, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: "
                             f"{function} of type {type(function)}")

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._func_name})"


class HybridLambda(HybridBlock):
    """Wraps a function as a HybridBlock (reference: basic_layers.py:694)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as F
            if not hasattr(F, function):
                raise ValueError(f"Function name {function} is not found in "
                                 "ndarray namespace")
            fname = function
            self._func = lambda F_, *args: getattr(F_, fname)(*args)
            self._func_name = function
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: "
                             f"{function} of type {type(function)}")

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._func_name})"
