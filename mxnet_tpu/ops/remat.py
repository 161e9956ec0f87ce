"""What a recomputation unit keeps beside its inputs.

A unit (``gluon.block.HybridBlock._call_remat``, which only
``TrainStep(remat="layer")`` enters) runs under ``jax.checkpoint`` with
``POLICY``: its backward pass holds the unit's inputs and every value an
operator handed to ``kept``, and computes everything else again. An
operator hands over what is dear to compute a second time and small to
hold: a matrix product's output, the result of a choice or a sort, a
scan's output, a reduction's one number a row. Dear decides where the
two pull apart: a gated MLP's first product is the widest value of a
layer and is held, because forming it again is 2 of the MLP's 11
products a unit on the MXU (``ops.seq.gated_mlp`` has both readings on
the chip). Activations, gates,
casts, reshapes and a norm's scaled rows are left to recomputation, and
so is a value no backward pass reads (a unit's last product): JAX holds
no kept value that nothing reads, and ``kept_bytes`` would count it all
the same.

Outside a unit ``kept`` is the identity, in the traced program too.
"""
from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

NAME = "mx_kept"
POLICY = jax.checkpoint_policies.save_only_these_names(NAME)


def kept(x):
    """``x``, which the unit around this call holds for its backward."""
    return checkpoint_name(x, NAME)


def kept_bytes(jaxpr):
    """The bytes of the values ``jaxpr`` (a unit's, traced) passes through
    ``kept``, in the programs it calls too."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name" and eqn.params["name"] == NAME:
            total += sum(v.aval.size * v.aval.dtype.itemsize
                         for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += kept_bytes(sub)
    return total
