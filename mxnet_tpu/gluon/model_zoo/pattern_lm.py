"""A language model built from a layer pattern: one character a layer,
``M`` a Mamba-2 mixer, ``E`` a latent mixture of experts, ``*`` causal
grouped-query attention (the ``hybrid_override_pattern`` of the
``nemotron_h`` family's configurations), ``G`` a gated MLP, ``L``
multi-head latent attention, ``F`` a mixture of gated experts on the full
hidden vector (``deepseek_v3``'s two sublayers: ``LG`` a dense layer,
``LF`` an expert layer), ``D`` a Gated DeltaNet mixer (``qwen3_next``'s
linear-attention layers: ``DFDFDF*F`` a period), ``C`` a gated short
convolution (``lfm2_moe``'s ``conv`` layers: ``CG`` a dense layer,
``*FCFCFCF`` a period of expert layers), ``W`` a second kind of causal
grouped-query attention with keyword arguments of its own (``laguna``'s
``sliding_attention`` layers, more heads under a window beside the
``full_attention`` layers' ``*``: ``*G`` the dense layer, ``WFWFWF*F`` a
period of expert layers). Pre-norm
residual throughout, ``x <- x + Mixer_l(RMSNorm_l(x))``, or with
``post_norm`` a norm on either side of the mixer, ``x <- x +
RMSNorm'_l(Mixer_l(RMSNorm_l(x)))``; one final RMSNorm, an untied head,
no bias but the convolution's. With ``residual_streams`` the residual is
several streams a token and ``x <- H_res x + H_post^T Mixer_l(RMSNorm_l(
H_pre x))``, the three maps computed from the streams by every layer
(manifold-constrained hyper-connections).

With ``loops`` above 1 the whole stack, final norm included, runs that
many times over its own output with the same weights, as one scanned body
(``nn.HybridLoop``). With ``exit_gate`` the model returns what a loss over
every pass needs (``parallel.exit_weighted_loss``) in place of logits.

The model is told what it holds of each layer (heads, groups, experts,
columns, rows of the vocabulary): one chip's share of a deployment, whose
partial sums go on to the next layer as they are.
"""
from __future__ import annotations

import math

import jax

from ...ndarray.ndarray import _wrap
from ..block import HybridBlock, stateful_write
from .. import nn
from ..nn.seq_layers import _Start

__all__ = ["PatternLM"]


class _Layer(HybridBlock):
    """``x + mixer(norm(x))``, or ``x + post_norm(mixer(norm(x)))``: the
    unit that ``TrainStep(remat="layer")`` recomputes.

    With ``streams`` = n the input is a token's n residual streams side
    by side, (B, L, n * units), and the residual is a manifold-constrained
    hyper-connection (arXiv:2512.24880; ``ops.seq.mhc_maps``): three maps
    computed from the streams (``hc_weight`` the paper's phi, (n (n + 2),
    n * units), rows ``[pre | post | res]``; ``hc_alpha`` a scalar a map;
    ``hc_bias``), the sublayer reads ``u = sum_j H_pre[j] X_j`` and the
    streams become ``H_res X + H_post^T f(u)`` with ``H_res`` doubly
    stochastic by ``hyper_connections["iters"]`` Sinkhorn iterations.
    ``hc_dev`` (no gradient, written every forward) holds what the
    iterations leave (``nn.publish_mhc_counters``). The start values keep
    the streams apart and hand the sublayer their mean: ``H_res`` close to
    the identity, ``H_pre`` 1 / n, ``H_post`` 1; with one stream that is
    ``x + f(x)``."""
    _remat_unit = True

    def __init__(self, units, mixer, epsilon, post_norm=False,
                 unit_offset=False, streams=None, hyper_connections=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._hc = None
        with self.name_scope():
            if streams is not None:
                n = int(streams)
                self._hc = dict(hyper_connections or {}, streams=n)
                get = self.params.get
                self.hc_weight = get("hc_weight",
                                     shape=(n * (n + 2), n * units))
                self.hc_alpha = get("hc_alpha", shape=(3,),
                                    init=_Start(0.01))
                start = [-math.log(n - 1) if n > 1 else 30.0] * n \
                    + [0.0] * n + [8.0 * (i == j) for i in range(n)
                                   for j in range(n)]
                self.hc_bias = get("hc_bias", shape=(n * (n + 2),),
                                   init=_Start(start))
                self.hc_dev = get("hc_dev", shape=(1,), init="zeros",
                                  grad_req="null")
            self.norm = nn.RMSNorm(units, epsilon, unit_offset=unit_offset)
            self.mixer = mixer()
            # reads the mixer's last product, which the unit then keeps
            self.post_norm = nn.RMSNorm(units, epsilon, keep_input=True,
                                        unit_offset=unit_offset) \
                if post_norm else None

    def _sublayer(self, u):
        out = self.mixer(self.norm(u))
        return out if self.post_norm is None else self.post_norm(out)

    def hybrid_forward(self, F, x, hc_weight=None, hc_alpha=None,
                       hc_bias=None, hc_dev=None):
        if self._hc is None:
            return x + self._sublayer(x)
        x, u, post, res, dev = F.HyperConnectionRead(
            x, hc_weight, hc_alpha, hc_bias, **self._hc)
        stateful_write(self.hc_dev, dev)
        return F.HyperConnectionPost(x, self._sublayer(u), res, post)


class _Streams(HybridBlock):
    """Where the stack's hidden state becomes ``streams`` residual streams
    (``ops.seq.mhc_spread``: the vector copied into each) and where they
    become one again (``mhc_merge``: their sum)."""

    def __init__(self, op, streams, **kwargs):
        super().__init__(**kwargs)
        self._op, self._streams = op, int(streams)

    def hybrid_forward(self, F, x):
        return getattr(F, self._op)(x, streams=self._streams)


class PatternLM(HybridBlock):
    """``pattern``: the layers held, e.g. ``"MEMEMEMEM*E"`` or
    ``"*G*G"`` or ``"LGLFLF"``. ``mamba``, ``moe``, ``attention``, ``mlp``,
    ``latent_attention``, ``experts``: the keyword arguments of
    ``nn.Mamba2Mixer``, ``nn.LatentMoE``, ``nn.GQAttention``,
    ``nn.GatedMLP``, ``nn.LatentAttention`` and ``nn.GatedMoE`` after
    ``in_units`` (what each layer of that kind holds), and
    ``linear_attention`` those of ``nn.GatedDeltaNet``, ``short_conv``
    those of ``nn.GatedShortConv`` and ``window_attention`` those of the
    ``nn.GQAttention`` of the letter ``W`` (heads, window and rotation of
    its own beside ``attention``'s). ``post_norm``: a
    second norm in every layer, after its mixer. ``norm_unit_offset``:
    every layer's norm and the final norm scale by ``1 + w`` from ``w =
    0``. ``loops``: how often the stack and the final norm run, each pass
    on the one before's output. ``residual_streams``: every token's
    residual is that many streams, mixed around every layer by a
    manifold-constrained hyper-connection (``_Layer``): the embedding is
    copied into each (scope ``mx_mhc_in``), a layer reads a learned mix of
    them and writes back through a doubly stochastic matrix, and their sum
    (``mx_mhc_out``) goes to the final norm; ``hyper_connections`` holds
    ``ops.seq.mhc_maps``' ``iters``, ``eps`` and ``clamp``. Not with
    ``loops`` above 1.

    Input (B, L) token ids below ``vocab``; output (B * L, vocab) logits
    of the last pass. With ``exit_gate``, three outputs for a loss over
    all passes: the hidden states after each pass, (``loops``, B * L,
    units); the logits of the gates of all passes but the last
    (``nn.ExitGate``), (``loops`` - 1, B * L) float32; and the head's
    weight, (vocab, units), for the loss to compute each pass's logits
    where it can drop them again."""

    def __init__(self, pattern, vocab, units, mamba=None, moe=None,
                 attention=None, mlp=None, epsilon=1e-5, post_norm=False,
                 loops=1, exit_gate=False, latent_attention=None,
                 experts=None, linear_attention=None,
                 norm_unit_offset=False, residual_streams=None,
                 hyper_connections=None, short_conv=None,
                 window_attention=None, **kwargs):
        super().__init__(**kwargs)
        if residual_streams is not None and loops > 1:
            raise ValueError("residual_streams with loops > 1: a layer "
                             "writes what its Sinkhorn iterations leave, "
                             "which a looped body may not")
        make = {"M": lambda: nn.Mamba2Mixer(units, epsilon=epsilon,
                                            **mamba),
                "E": lambda: nn.LatentMoE(units, **moe),
                "*": lambda: nn.GQAttention(units, **attention),
                "G": lambda: nn.GatedMLP(units, **mlp),
                "L": lambda: nn.LatentAttention(units, epsilon=epsilon,
                                                **latent_attention),
                "F": lambda: nn.GatedMoE(units, **experts),
                "D": lambda: nn.GatedDeltaNet(units, epsilon=epsilon,
                                              **linear_attention),
                "C": lambda: nn.GatedShortConv(units, **(short_conv or {})),
                "W": lambda: nn.GQAttention(units, **window_attention)}
        self._vocab, self._units = vocab, units
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units)
            # the container takes no part in its children's names
            self.stack = nn.HybridLoop(loops, prefix="")
            if residual_streams is not None:
                self.stack.add(_Streams("HyperConnectionSpread",
                                        residual_streams))
            for i, kind in enumerate(pattern):
                if kind not in make:
                    *known, last = make
                    raise ValueError(f"layer kind {kind!r} in {pattern!r}: "
                                     f"{', '.join(known)} and {last} are "
                                     "known")
                self.stack.add(_Layer(units, make[kind], epsilon, post_norm,
                                      norm_unit_offset, residual_streams,
                                      hyper_connections, prefix=f"l{i}_"))
            if residual_streams is not None:
                self.stack.add(_Streams("HyperConnectionMerge",
                                        residual_streams))
            final = nn.RMSNorm(units, epsilon, unit_offset=norm_unit_offset)
            # a scan holds for its backward pass whatever its body computes
            # outside a unit as autodiff leaves it (of this norm, three
            # float32 copies of its rows a pass): a unit holds its input
            final._remat_unit = loops > 1
            self.stack.add(final)
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=units)
            self.gate = nn.ExitGate(units, loops) if exit_gate else None

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        if self.gate is None:
            h = self.stack.last(h)
            with jax.named_scope("mx_head"):    # forward and backward
                return self.head(h).reshape((-1, self._vocab))
        hidden = self.stack(h).reshape((0, -1, self._units))
        # the weight's array of this trace, not the Parameter's own holder
        return hidden, self.gate(hidden), _wrap(self.head.weight.data()._data)
