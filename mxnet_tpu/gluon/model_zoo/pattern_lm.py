"""A language model built from a layer pattern: one character a layer,
``M`` a Mamba-2 mixer, ``E`` a latent mixture of experts, ``*`` causal
grouped-query attention (the ``hybrid_override_pattern`` of the
``nemotron_h`` family's configurations). Pre-norm residual throughout:
``x <- x + Mixer_l(RMSNorm_l(x))``, one final RMSNorm, an untied head, no
bias but the convolution's.

The model is told what it holds of each layer (heads, groups, experts,
columns, rows of the vocabulary): one chip's share of a deployment, whose
partial sums go on to the next layer as they are.
"""
from __future__ import annotations

from ..block import HybridBlock
from .. import nn

__all__ = ["PatternLM"]


class _Layer(HybridBlock):
    """``x + mixer(norm(x))``: the unit that ``TrainStep(remat="layer")``
    recomputes."""
    _remat_unit = True

    def __init__(self, units, mixer, epsilon, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = nn.RMSNorm(units, epsilon)
            self.mixer = mixer()

    def hybrid_forward(self, F, x):
        return x + self.mixer(self.norm(x))


class PatternLM(HybridBlock):
    """``pattern``: the layers held, e.g. ``"MEMEMEMEM*E"``. ``mamba``,
    ``moe``, ``attention``: the keyword arguments of ``nn.Mamba2Mixer``,
    ``nn.LatentMoE`` and ``nn.GQAttention`` after ``in_units`` (what each
    layer of that kind holds). Input (B, L) token ids below ``vocab``;
    output (B * L, vocab) logits."""

    def __init__(self, pattern, vocab, units, mamba=None, moe=None,
                 attention=None, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        make = {"M": lambda: nn.Mamba2Mixer(units, epsilon=epsilon,
                                            **mamba),
                "E": lambda: nn.LatentMoE(units, **moe),
                "*": lambda: nn.GQAttention(units, **attention)}
        self._vocab = vocab
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units)
            self.layers = []
            for i, kind in enumerate(pattern):
                if kind not in make:
                    raise ValueError(f"layer kind {kind!r} in {pattern!r}: "
                                     "M, E and * are known")
                layer = _Layer(units, make[kind], epsilon,
                               prefix=f"l{i}_")
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = nn.RMSNorm(units, epsilon)
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=units)

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        for layer in self.layers:
            h = layer(h)
        return self.head(self.final_norm(h)).reshape((-1, self._vocab))
