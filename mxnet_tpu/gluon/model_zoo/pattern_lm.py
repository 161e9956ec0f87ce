"""A language model built from a layer pattern: one character a layer,
``M`` a Mamba-2 mixer, ``E`` a latent mixture of experts, ``*`` causal
grouped-query attention (the ``hybrid_override_pattern`` of the
``nemotron_h`` family's configurations), ``G`` a gated MLP, ``L``
multi-head latent attention, ``F`` a mixture of gated experts on the full
hidden vector (``deepseek_v3``'s two sublayers: ``LG`` a dense layer,
``LF`` an expert layer), ``D`` a Gated DeltaNet mixer (``qwen3_next``'s
linear-attention layers: ``DFDFDF*F`` a period). Pre-norm
residual throughout, ``x <- x + Mixer_l(RMSNorm_l(x))``, or with
``post_norm`` a norm on either side of the mixer, ``x <- x +
RMSNorm'_l(Mixer_l(RMSNorm_l(x)))``; one final RMSNorm, an untied head,
no bias but the convolution's.

With ``loops`` above 1 the whole stack, final norm included, runs that
many times over its own output with the same weights, as one scanned body
(``nn.HybridLoop``). With ``exit_gate`` the model returns what a loss over
every pass needs (``parallel.exit_weighted_loss``) in place of logits.

The model is told what it holds of each layer (heads, groups, experts,
columns, rows of the vocabulary): one chip's share of a deployment, whose
partial sums go on to the next layer as they are.
"""
from __future__ import annotations

import jax

from ...ndarray.ndarray import _wrap
from ..block import HybridBlock
from .. import nn

__all__ = ["PatternLM"]


class _Layer(HybridBlock):
    """``x + mixer(norm(x))``, or ``x + post_norm(mixer(norm(x)))``: the
    unit that ``TrainStep(remat="layer")`` recomputes."""
    _remat_unit = True

    def __init__(self, units, mixer, epsilon, post_norm=False,
                 unit_offset=False, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = nn.RMSNorm(units, epsilon, unit_offset=unit_offset)
            self.mixer = mixer()
            # reads the mixer's last product, which the unit then keeps
            self.post_norm = nn.RMSNorm(units, epsilon, keep_input=True,
                                        unit_offset=unit_offset) \
                if post_norm else None

    def hybrid_forward(self, F, x):
        out = self.mixer(self.norm(x))
        return x + (out if self.post_norm is None else self.post_norm(out))


class PatternLM(HybridBlock):
    """``pattern``: the layers held, e.g. ``"MEMEMEMEM*E"`` or
    ``"*G*G"`` or ``"LGLFLF"``. ``mamba``, ``moe``, ``attention``, ``mlp``,
    ``latent_attention``, ``experts``: the keyword arguments of
    ``nn.Mamba2Mixer``, ``nn.LatentMoE``, ``nn.GQAttention``,
    ``nn.GatedMLP``, ``nn.LatentAttention`` and ``nn.GatedMoE`` after
    ``in_units`` (what each layer of that kind holds), and
    ``linear_attention`` those of ``nn.GatedDeltaNet``. ``post_norm``: a
    second norm in every layer, after its mixer. ``norm_unit_offset``:
    every layer's norm and the final norm scale by ``1 + w`` from ``w =
    0``. ``loops``: how often the stack and the final norm run, each pass
    on the one before's output.

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
                 norm_unit_offset=False, **kwargs):
        super().__init__(**kwargs)
        make = {"M": lambda: nn.Mamba2Mixer(units, epsilon=epsilon,
                                            **mamba),
                "E": lambda: nn.LatentMoE(units, **moe),
                "*": lambda: nn.GQAttention(units, **attention),
                "G": lambda: nn.GatedMLP(units, **mlp),
                "L": lambda: nn.LatentAttention(units, epsilon=epsilon,
                                                **latent_attention),
                "F": lambda: nn.GatedMoE(units, **experts),
                "D": lambda: nn.GatedDeltaNet(units, epsilon=epsilon,
                                              **linear_attention)}
        self._vocab, self._units = vocab, units
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units)
            # the container takes no part in its children's names
            self.stack = nn.HybridLoop(loops, prefix="")
            for i, kind in enumerate(pattern):
                if kind not in make:
                    raise ValueError(f"layer kind {kind!r} in {pattern!r}: "
                                     "M, E, *, G, L, F and D are known")
                self.stack.add(_Layer(units, make[kind], epsilon, post_norm,
                                      norm_unit_offset, prefix=f"l{i}_"))
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
