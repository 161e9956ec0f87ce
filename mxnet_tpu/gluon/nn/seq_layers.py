"""Sequence-model blocks: ``RMSNorm``, ``Mamba2Mixer``, ``GatedDeltaNet``,
``GatedShortConv``, ``LatentMoE``, ``GatedMoE``, ``GQAttention``,
``LatentAttention``, ``GatedMLP``, the
``HybridLoop`` container that runs its children several times as one
scanned body, and ``ExitGate``, over the ops of ``ops/seq.py``.

Each block is told what it holds of the layer: how many heads and groups,
which experts, how many of the shared expert's columns. A block that holds
a share returns that share's partial sum (``ops/seq.py``).
"""
from __future__ import annotations

import jax

from ... import autograd, initializer
from ...ndarray.ndarray import _wrap
from ..block import HybridBlock, _TraceState, stateful_write

__all__ = ["RMSNorm", "Mamba2Mixer", "GatedDeltaNet", "GatedShortConv",
           "LatentMoE", "GatedMoE", "GQAttention", "LatentAttention",
           "GatedMLP", "HybridLoop", "ExitGate",
           "MOE_COUNTERS", "publish_moe_counters", "publish_loop_counters",
           "publish_mhc_counters"]

#: what an expert layer's ``counters`` hold, in order: the gauges
#: ``moe::<name>::<block>`` of ``publish_moe_counters``
MOE_COUNTERS = ("pairs_held", "overflow_pairs", "load_max_over_mean",
                "buffer_fill")


class _Start(initializer.Constant):
    """A parameter's own start value, whatever the initializers' choice by
    name would make of it (a ``gamma`` is set to 1 and a ``bias`` to 0 by
    every initializer): a norm's ``gamma`` that starts at 0 under ``1 +
    gamma``, a ``dt_bias`` that starts at 1."""
    _init_gamma = _init_bias = initializer.Constant._init_weight


def _blocks_under(net):
    """``net`` and every block below it."""
    todo = [net]
    while todo:
        block = todo.pop()
        todo.extend(block._children.values())
        yield block


def publish_moe_counters(net):
    """Read the counters of every expert layer (``LatentMoE``,
    ``GatedMoE``) under ``net`` (what the
    last forward, or ``TrainStep`` call, wrote beside its output) and set
    the gauges ``moe::<counter>::<block>``: one read a layer, no work in
    the step. Returns ``{gauge: value}``."""
    from ... import telemetry
    out = {}
    for block in _blocks_under(net):
        if isinstance(block, (LatentMoE, GatedMoE)):
            name = block.counters.name.rsplit("_", 1)[0]
            for counter, value in zip(MOE_COUNTERS,
                                      block.counters.data().asnumpy()):
                out[f"moe::{counter}::{name}"] = float(value)
                telemetry.gauge(f"moe::{counter}::{name}").set(float(value))
    return out


def publish_mhc_counters(net):
    """Read ``hc_dev`` of every hyper-connected sublayer under ``net``
    (``PatternLM(residual_streams=...)``'s layers: what the last forward,
    or ``TrainStep`` call, wrote beside its output) and set the gauges
    ``mhc::res_sum_dev::<layer>``: the largest ``|row or column sum of
    H_res - 1|`` over the call's tokens, what the Sinkhorn iterations
    leave. Returns ``{gauge: value}``."""
    from ... import telemetry
    out = {}
    for block in _blocks_under(net):
        dev = getattr(block, "hc_dev", None)
        if dev is not None:
            name = f"mhc::res_sum_dev::{dev.name.rsplit('_hc_', 1)[0]}"
            out[name] = float(dev.data().asnumpy()[0])
            telemetry.gauge(name).set(out[name])
    return out


class RMSNorm(HybridBlock):
    """``keep_input``: inside a recomputation unit, hold the norm's input
    for the backward pass (a norm after a sublayer, whose input is that
    sublayer's last product). ``unit_offset``: the scale is ``1 +
    gamma`` and ``gamma`` starts at zero."""

    def __init__(self, in_channels, epsilon=1e-5, keep_input=False,
                 unit_offset=False, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"eps": epsilon}
        if keep_input:
            self._attrs["keep_input"] = True
        if unit_offset:
            self._attrs["unit_offset"] = True
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,),
                init=_Start(0.0) if unit_offset else "ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, **self._attrs)


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer over ``num_heads`` heads of ``head_dim`` in
    ``num_groups`` groups, the ones held here (``ops.seq.mamba2_mixer``):
    ``in_units -> [z | x B C | dt] -> conv -> SSD scan -> gated group
    norm -> in_units``."""

    def __init__(self, in_units, num_heads, head_dim=64, state_size=128,
                 num_groups=1, conv_kernel=4, chunk_size=128, epsilon=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        d_in = num_heads * head_dim
        conv = d_in + 2 * num_groups * state_size
        self._attrs = {"num_heads": num_heads, "head_dim": head_dim,
                       "state_size": state_size, "num_groups": num_groups,
                       "chunk_size": chunk_size, "eps": epsilon}
        with self.name_scope():
            get = self.params.get
            self.in_proj_weight = get(
                "in_proj_weight", shape=(d_in + conv + num_heads, in_units))
            self.conv_weight = get("conv_weight", shape=(conv, conv_kernel))
            self.conv_bias = get("conv_bias", shape=(conv,), init="zeros")
            self.dt_bias = get("dt_bias", shape=(num_heads,), init="zeros")
            self.a_log = get("a_log", shape=(num_heads,), init="zeros")
            self.d = get("d", shape=(num_heads,), init="ones")
            self.gate_norm_weight = get("gate_norm_weight", shape=(d_in,),
                                        init="ones")
            self.out_proj_weight = get("out_proj_weight",
                                       shape=(in_units, d_in))

    def hybrid_forward(self, F, x, in_proj_weight, conv_weight, conv_bias,
                       dt_bias, a_log, d, gate_norm_weight, out_proj_weight):
        return F.Mamba2Mixer(x, in_proj_weight, conv_weight, conv_bias,
                             dt_bias, a_log, d, gate_norm_weight,
                             out_proj_weight, **self._attrs)


class GatedDeltaNet(HybridBlock):
    """The Gated DeltaNet mixer, linear attention by the gated delta rule
    (``ops.seq.gated_delta_net``), over the ``num_k_heads`` key heads,
    ``key_dim`` wide, and the ``num_v_heads`` value heads, ``value_dim``
    wide, held here: ``in_units -> [q k v z], [b a] -> conv over q k v ->
    the rule in chunks -> gated head norm -> in_units``. The state a
    value head carries is ``key_dim x value_dim``, whatever the
    length."""

    def __init__(self, in_units, num_k_heads, num_v_heads, key_dim=128,
                 value_dim=128, conv_kernel=4, chunk_size=64, epsilon=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        conv = 2 * num_k_heads * key_dim + num_v_heads * value_dim
        self._attrs = {"num_k_heads": num_k_heads,
                       "num_v_heads": num_v_heads, "key_dim": key_dim,
                       "value_dim": value_dim, "chunk_size": chunk_size,
                       "eps": epsilon}
        with self.name_scope():
            get = self.params.get
            self.qkvz_weight = get(
                "qkvz_weight", shape=(conv + num_v_heads * value_dim,
                                      in_units))
            self.ba_weight = get("ba_weight",
                                 shape=(2 * num_v_heads, in_units))
            self.conv_weight = get("conv_weight", shape=(conv, conv_kernel))
            self.dt_bias = get("dt_bias", shape=(num_v_heads,),
                               init=_Start(1.0))
            self.a_log = get("a_log", shape=(num_v_heads,), init="zeros")
            self.gate_norm_weight = get("gate_norm_weight",
                                        shape=(value_dim,), init="ones")
            self.out_weight = get("out_weight",
                                  shape=(in_units, num_v_heads * value_dim))

    def hybrid_forward(self, F, x, qkvz_weight, ba_weight, conv_weight,
                       dt_bias, a_log, gate_norm_weight, out_weight):
        return F.GatedDeltaNet(x, qkvz_weight, ba_weight, conv_weight,
                               dt_bias, a_log, gate_norm_weight, out_weight,
                               **self._attrs)


class GatedShortConv(HybridBlock):
    """The gated short convolution (``ops.seq.gated_short_conv``), the
    mixer of the ``lfm2`` family's ``conv`` layers: ``in_units -> [B | C |
    z] -> conv over B * z, ``kernel`` causal taps a channel, no bias -> C
    * conv -> in_units``, every gate linear. Held whole: its channels are
    the hidden size, which no chip's share cuts."""

    def __init__(self, in_units, kernel=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            get = self.params.get
            self.in_weight = get("in_weight", shape=(3 * in_units, in_units))
            self.conv_weight = get("conv_weight", shape=(in_units, kernel))
            self.out_weight = get("out_weight", shape=(in_units, in_units))

    def hybrid_forward(self, F, x, in_weight, conv_weight, out_weight):
        return F.GatedShortConv(x, in_weight, conv_weight, out_weight)


class LatentMoE(HybridBlock):
    """A mixture of experts in a latent (``ops.seq.latent_moe``): a router
    ``num_experts`` wide with a correction bias that no gradient reaches,
    the experts ``expert_ids`` held here, a static receive buffer of
    ``buffer_rows`` rows, the shared expert's ``shared_units`` columns.

    ``counters`` (no gradient, written every forward) holds
    ``MOE_COUNTERS``; the pairs beyond the buffer add up from call to
    call. With ``bias_update_rate`` above 0 every training forward also
    moves ``router_bias`` one step of the auxiliary-loss-free balancing
    rule on its own loads (``ops.seq.balanced_bias``), as BatchNorm moves
    its statistics: the next call routes by the new bias. (Gated experts
    on the full hidden vector, on the same routing path: ``GatedMoE``.)"""

    def __init__(self, in_units, num_experts, expert_ids, top_k, latent_units,
                 expert_units, shared_units, buffer_rows, scaling=1.0,
                 norm_topk=True, bias_update_rate=0.0, **kwargs):
        super().__init__(**kwargs)
        held = len(expert_ids)
        self._attrs = {"expert_ids": tuple(int(e) for e in expert_ids),
                       "top_k": top_k, "buffer_rows": buffer_rows,
                       "scaling": float(scaling),
                       "norm_topk": bool(norm_topk),
                       "bias_rate": float(bias_update_rate)}
        with self.name_scope():
            get = self.params.get
            self.router_weight = get("router_weight",
                                     shape=(num_experts, in_units))
            self.router_bias = get("router_bias", shape=(num_experts,),
                                   init="zeros", grad_req="null")
            self.down_weight = get("down_weight",
                                   shape=(latent_units, in_units))
            self.up_weight = get("up_weight", shape=(in_units, latent_units))
            self.w1 = get("w1", shape=(held, latent_units, expert_units))
            self.w2 = get("w2", shape=(held, expert_units, latent_units))
            self.shared_w1 = get("shared_w1", shape=(shared_units, in_units))
            self.shared_w2 = get("shared_w2", shape=(in_units, shared_units))
            self.counters = get("counters", shape=(len(MOE_COUNTERS),),
                                init="zeros", grad_req="null")

    def hybrid_forward(self, F, x, router_weight, router_bias, down_weight,
                       up_weight, w1, w2, shared_w1, shared_w2, counters):
        out, new, bias = F.LatentMoE(
            x, router_weight, router_bias, down_weight, up_weight, w1, w2,
            shared_w1, shared_w2, counters, **self._attrs)
        stateful_write(self.counters, new)
        if self._attrs["bias_rate"] and autograd.is_training():
            stateful_write(self.router_bias, bias)
        return out


class GatedMoE(HybridBlock):
    """A mixture of gated experts on the full hidden vector
    (``ops.seq.gated_moe``): ``LatentMoE``'s router, correction bias,
    counters and balancing step around experts that are gated MLPs
    ``expert_units`` wide with no latent projection, the ones
    ``expert_ids`` held here, which share ONE pool of ``buffer_rows``
    rows (a pair is beyond the buffer only when the held experts' pairs
    together outnumber its rows; ``buffer_fill`` is the pool's filled
    share); the shared experts are one gated MLP ``shared_units`` wide,
    whole, and with ``shared_units`` 0 the layer has none: no shared
    parameter, the routed sum alone (``ops.seq.routed_moe``).
    ``scoring``: the router's scores, each expert's ``sigmoid`` or a
    ``softmax`` over all experts; ``norm_topk_eps``: added to the chosen
    scores' sum before it divides them. ``shared_gate``: the shared
    experts' output goes through ``sigmoid(u . shared_gate_weight)``, a
    gate of its own a token."""

    def __init__(self, in_units, num_experts, expert_ids, top_k,
                 expert_units, shared_units, buffer_rows, scaling=1.0,
                 norm_topk=True, bias_update_rate=0.0, scoring="sigmoid",
                 shared_gate=False, norm_topk_eps=0.0, **kwargs):
        super().__init__(**kwargs)
        held = len(expert_ids)
        self._attrs = {"expert_ids": tuple(int(e) for e in expert_ids),
                       "top_k": top_k, "buffer_rows": buffer_rows,
                       "scaling": float(scaling),
                       "norm_topk": bool(norm_topk),
                       "bias_rate": float(bias_update_rate)}
        if scoring != "sigmoid":
            self._attrs["scoring"] = scoring
        if norm_topk_eps:
            self._attrs["norm_topk_eps"] = float(norm_topk_eps)
        with self.name_scope():
            get = self.params.get
            self.router_weight = get("router_weight",
                                     shape=(num_experts, in_units))
            self.router_bias = get("router_bias", shape=(num_experts,),
                                   init="zeros", grad_req="null")
            self.w1 = get("w1", shape=(held, in_units, expert_units))
            self.w3 = get("w3", shape=(held, in_units, expert_units))
            self.w2 = get("w2", shape=(held, expert_units, in_units))
            if shared_units:
                self.shared_gate_up_weight = get(
                    "shared_gate_up_weight",
                    shape=(2 * shared_units, in_units))
                self.shared_down_weight = get(
                    "shared_down_weight", shape=(in_units, shared_units))
            elif shared_gate:
                raise ValueError("shared_gate without shared experts")
            self.counters = get("counters", shape=(len(MOE_COUNTERS),),
                                init="zeros", grad_req="null")
            if shared_gate:
                self.shared_gate_weight = get("shared_gate_weight",
                                              shape=(1, in_units))

    def hybrid_forward(self, F, x, router_weight, router_bias, w1, w3, w2,
                       counters, shared_gate_up_weight=None,
                       shared_down_weight=None, shared_gate_weight=None):
        if shared_gate_up_weight is None:
            out, new, bias = F.RoutedMoE(x, router_weight, router_bias, w1,
                                         w3, w2, counters, **self._attrs)
        else:
            more = () if shared_gate_weight is None \
                else (shared_gate_weight,)
            out, new, bias = F.GatedMoE(
                x, router_weight, router_bias, w1, w3, w2,
                shared_gate_up_weight, shared_down_weight, counters, *more,
                **self._attrs)
        stateful_write(self.counters, new)
        if self._attrs["bias_rate"] and autograd.is_training():
            stateful_write(self.router_bias, bias)
        return out


class LatentAttention(HybridBlock):
    """Causal multi-head latent attention (``ops.seq.latent_attention``)
    over the ``num_heads`` heads held here: queries ``nope_dim +
    rope_dim`` wide, straight from the input or, with ``q_latent_dim``,
    through a normed latent of that width (``q_down_weight``,
    ``q_norm_weight``, then ``q_weight`` from the latent); keys and
    values expanded from a ``latent_dim``-wide normed latent, one rotary
    key of ``rope_dim`` shared by all heads, values ``v_dim`` wide. The
    matrices' rows are grouped by part, not by head (the op's docstring).
    ``rope_scaling``: a configuration's YaRN group (``type: yarn``,
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``): the rotation's
    frequencies are ``ops.seq.rope_frequencies``' and, where
    ``mscale_all_dim`` is given, the softmax scale is ``(nope_dim +
    rope_dim) ** -0.5`` times ``yarn_mscale(factor, mscale_all_dim)``
    squared; where the group gives ``attention_factor``, or ``mscale``
    differs from ``mscale_all_dim``, the rotation's cos and sin carry
    that factor (``_yarn_attrs``, the one parser of the group).
    Where ``nope_dim == v_dim`` is a multiple of 128 and the program is
    lowered for a TPU, the softmax is the fused kernels of
    ``ops.attn_kernel``; ``block`` is the plain form's, as in
    ``GQAttention``."""

    def __init__(self, in_units, num_heads, nope_dim=128, rope_dim=64,
                 v_dim=128, latent_dim=512, rope_theta=10000.0,
                 epsilon=1e-5, block=1024, q_latent_dim=None,
                 rope_scaling=None, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"num_heads": num_heads, "nope_dim": nope_dim,
                       "rope_dim": rope_dim, "v_dim": v_dim,
                       "latent_dim": latent_dim,
                       "rope_theta": float(rope_theta), "eps": epsilon,
                       "block": block}
        if rope_scaling is not None:
            self._attrs.update(_yarn_attrs(rope_scaling,
                                           nope_dim + rope_dim))
        with self.name_scope():
            get = self.params.get
            if q_latent_dim is not None:
                self.q_down_weight = get("q_down_weight",
                                         shape=(q_latent_dim, in_units))
                self.q_norm_weight = get("q_norm_weight",
                                         shape=(q_latent_dim,), init="ones")
            self.q_weight = get(
                "q_weight", shape=(num_heads * (nope_dim + rope_dim),
                                   q_latent_dim or in_units))
            self.kv_down_weight = get(
                "kv_down_weight", shape=(latent_dim + rope_dim, in_units))
            self.kv_norm_weight = get("kv_norm_weight", shape=(latent_dim,),
                                      init="ones")
            self.kv_up_weight = get(
                "kv_up_weight", shape=(num_heads * (nope_dim + v_dim),
                                       latent_dim))
            self.o_weight = get("o_weight",
                                shape=(in_units, num_heads * v_dim))

    def hybrid_forward(self, F, x, q_weight, kv_down_weight, kv_norm_weight,
                       kv_up_weight, o_weight, q_down_weight=None,
                       q_norm_weight=None):
        more = () if q_down_weight is None else (q_down_weight,
                                                 q_norm_weight)
        return F.LatentAttention(x, q_weight, kv_down_weight, kv_norm_weight,
                                 kv_up_weight, o_weight, *more,
                                 **self._attrs)


def _yarn_attrs(group, score_dim=None):
    """``yarn``, ``mscale`` and ``scale`` of ``causal_gq_attention`` and
    ``latent_attention`` from a configuration's ``rope_scaling`` group
    (``type`` or ``rope_type`` ``yarn``, ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``
    and the factor's keys). The frequencies are
    ``ops.seq.rope_frequencies``'. YaRN's attention factor goes where the
    group puts it: ``attention_factor``, or without it ``yarn_mscale(
    factor, mscale) / yarn_mscale(factor, mscale_all_dim)`` where the two
    differ, on the rotation's cos and sin (``mscale``: the rotated
    elements of queries and keys alone carry it); with ``score_dim``,
    ``yarn_mscale(factor, mscale_all_dim)`` squared on the softmax
    (``scale`` = ``score_dim ** -0.5`` times it), which is all of it
    where ``mscale`` equals ``mscale_all_dim``."""
    from ...ops.seq import yarn_mscale
    if group.get("type", group.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {group!r}: only type yarn is known")
    factor = group["factor"]
    all_dim = group.get("mscale_all_dim", 0)
    attrs = {"yarn": (float(factor),
                      float(group["original_max_position_embeddings"]),
                      float(group.get("beta_fast", 32)),
                      float(group.get("beta_slow", 1)))}
    on_rotation = group.get("attention_factor")
    if on_rotation is None:
        on_rotation = yarn_mscale(factor, group.get("mscale", 1)) \
            / yarn_mscale(factor, all_dim or 1)
    if on_rotation != 1:
        attrs["mscale"] = float(on_rotation)
    if all_dim and score_dim is not None:
        attrs["scale"] = float(score_dim ** -0.5
                               * yarn_mscale(factor, all_dim) ** 2)
    return attrs


class GQAttention(HybridBlock):
    """Causal grouped-query attention (``ops.seq.causal_gq_attention``)
    between a fused ``[q | k | v]`` projection and the output projection,
    over the heads held here. ``rope_theta``: the base of the rotary
    position encoding applied to queries and keys; without it the layer
    has no positional encoding. Where ``head_dim`` is a multiple of 128
    and the program is lowered for a TPU the attention is the fused
    kernels of ``ops.attn_kernel``, which choose their own block size;
    everywhere else the blocked recurrence in plain JAX over blocks of
    ``block`` rows, which is all ``block`` means (no result depends on
    it). As a recomputation unit the layer keeps the packed rows, the
    attention's output and, with the kernels, a float32 log-sum-exp a
    row.

    ``rotary_dim``: the rotation takes a head's first ``rotary_dim``
    elements alone. ``qk_norm``: an RMSNorm of a head's width on every
    query head and every key head before the rotation
    (``q_norm_weight``, ``k_norm_weight``; ``epsilon``, and with
    ``norm_unit_offset`` the scale ``1 + w`` from ``w = 0``). ``gated``:
    the projection is ``[q | k | v | gate]``, the gate as wide as the
    queries, and every head's output is multiplied by ``sigmoid`` of its
    gate before the output projection. ``head_gate``: the projection
    holds ``num_heads`` more rows, ONE gate a head, and all of a head's
    output is multiplied by ``sigmoid`` of its gate (rows of the packed
    projection and no weight of their own: one product; ``(num_heads + 2
    num_kv_heads) * head_dim + num_heads`` is no whole lane tile, and the
    gates are its last columns, after every head).

    ``window``: a query sees that many keys, its own the last (sliding
    window attention); the kernels and the plain form skip the key blocks
    wholly before it. ``rope_scaling``: a configuration's YaRN group, as
    ``LatentAttention``'s, with ``attention_factor`` on the rotated
    elements' cos and sin (``_yarn_attrs``)."""

    def __init__(self, in_units, num_heads, num_kv_heads, head_dim=128,
                 block=1024, rope_theta=None, rotary_dim=None,
                 qk_norm=False, gated=False, epsilon=1e-6,
                 norm_unit_offset=False, window=None, head_gate=False,
                 rope_scaling=None, **kwargs):
        super().__init__(**kwargs)
        if gated and head_gate:
            raise ValueError("gated and head_gate: one gate on the output")
        self._attrs = {"num_heads": num_heads, "num_kv_heads": num_kv_heads,
                       "head_dim": head_dim, "block": block}
        if window is not None:
            self._attrs["window"] = int(window)
        if head_gate:
            self._attrs["head_gate"] = True
        if rope_scaling is not None:
            self._attrs.update(_yarn_attrs(rope_scaling))
        if rope_theta is not None:
            self._attrs["rope_theta"] = float(rope_theta)
        if rotary_dim is not None:
            self._attrs["rotary_dim"] = int(rotary_dim)
        if gated:
            self._attrs["gated"] = True
        if qk_norm:
            self._attrs.update(eps=epsilon,
                               unit_offset=bool(norm_unit_offset))
        rows = (num_heads * (2 if gated else 1) + 2 * num_kv_heads) \
            * head_dim + (num_heads if head_gate else 0)
        with self.name_scope():
            self.qkv_weight = self.params.get("qkv_weight",
                                              shape=(rows, in_units))
            self.o_weight = self.params.get(
                "o_weight", shape=(in_units, num_heads * head_dim))
            if qk_norm:
                init = "zeros" if norm_unit_offset else "ones"
                self.q_norm_weight = self.params.get(
                    "q_norm_weight", shape=(head_dim,), init=init)
                self.k_norm_weight = self.params.get(
                    "k_norm_weight", shape=(head_dim,), init=init)

    def hybrid_forward(self, F, x, qkv_weight, o_weight, q_norm_weight=None,
                       k_norm_weight=None):
        # the two products beside the attention's own scope
        with jax.named_scope("mx_attn_proj"):
            qkv = F.FullyConnected(x, qkv_weight, no_bias=True,
                                   flatten=False)
        norms = () if q_norm_weight is None else (q_norm_weight,
                                                  k_norm_weight)
        out = F.CausalGQAttention(qkv, *norms, **self._attrs)
        with jax.named_scope("mx_attn_proj"):
            return F.FullyConnected(out, o_weight, no_bias=True,
                                    flatten=False)


class GatedMLP(HybridBlock):
    """``W_down (silu(W_gate u) * W_up u)`` with no bias
    (``ops.seq.gated_mlp``); ``gate_up_weight`` holds ``[W_gate | W_up]``
    as the rows of one (2 ``units``, ``in_units``) matrix."""

    def __init__(self, in_units, units, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_up_weight = self.params.get(
                "gate_up_weight", shape=(2 * units, in_units))
            self.down_weight = self.params.get(
                "down_weight", shape=(in_units, units))

    def hybrid_forward(self, F, x, gate_up_weight, down_weight):
        return F.GatedMLP(x, gate_up_weight, down_weight)


class HybridLoop(HybridBlock):
    """Runs its children, in the order added, ``loops`` times over their
    own output with the same weights: ``h_t = children(h_{t-1})``. The
    trips are one ``lax.scan`` body, so a program holds one copy of the
    children whatever ``loops`` is, and a weight's gradient is the sum
    over its uses. One trip is no loop: the children run once, unscanned.

    Calling the block gives every trip's output stacked, (``loops``,
    ...); ``last(x)`` gives the final trip's alone. The children keep
    their input's shape and dtype. Recomputation units among them work
    inside the body (``remat::saved_bytes`` counts a unit's bytes times
    the trips). A child may not write a parameter (``stateful_write``:
    the next trip would not read it), and random draws repeat from trip
    to trip: the body is traced once."""

    def __init__(self, loops, **kwargs):
        super().__init__(**kwargs)
        self._loops = int(loops)
        #: how often the children's forward ran (was traced) in the last
        #: call: 1 when the trips are a scan
        self.body_traces = 0

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def _once(self, x):
        self.body_traces += 1
        for block in self._children.values():
            x = block(x)
        return x

    def _scan(self, x):
        """``(last, every)`` of ``loops`` scanned trips from ``x``."""
        outer = _TraceState.active()
        units = getattr(_TraceState._current, "remat_units", None)

        def body(h, _):
            inner = _TraceState()
            _TraceState._current.value = inner
            if units is not None:
                units.trips *= self._loops
            try:
                out = self._once(_wrap(h))._data
            finally:
                _TraceState._current.value = outer
                if units is not None:
                    units.trips //= self._loops
            if inner.writes:
                raise NotImplementedError(
                    f"{self.name}: a child wrote "
                    f"{[p.name for p in inner.writes]} inside the looped "
                    "body; the next trip would not read it")
            return out, out

        with jax.named_scope("mx_loop_body"):
            last, every = jax.lax.scan(body, x._data, None,
                                       length=self._loops)
        return _wrap(last), _wrap(every)

    def last(self, x):
        self.body_traces = 0
        return self._once(x) if self._loops == 1 else self._scan(x)[0]

    def forward(self, x):
        self.body_traces = 0
        if self._loops == 1:
            return self._once(x).expand_dims(0)
        return self._scan(x)[1]


class ExitGate(HybridBlock):
    """The gates of a stack run ``passes`` times (``ops.seq.exit_gate``):
    from the stack's output after each pass, (``passes``, ..., hidden),
    the logit ``h . w_g + b_g`` in float32 of leaving after each pass
    before the last, (``passes`` - 1, N). Weight and bias start at zero,
    so every gate starts at one half.

    ``counters`` (no gradient, written every forward): the mean exit
    probability of each pass, the mean expected number of passes and the
    mean entropy of the exit distribution (``publish_loop_counters``)."""

    def __init__(self, in_units, passes, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(1, in_units),
                                          init="zeros")
            self.bias = self.params.get("bias", shape=(1,), init="zeros")
            self.counters = self.params.get("counters", shape=(passes + 2,),
                                            init="zeros", grad_req="null")

    def hybrid_forward(self, F, x, weight, bias, counters):
        logits, stats = F.ExitGate(x, weight, bias)
        stateful_write(self.counters, stats)
        return logits


def publish_loop_counters(net):
    """Set the gauges of the ``HybridLoop`` and the ``ExitGate`` under
    ``net`` (one of each a net): ``loop::trips``, ``loop::stack_traces``
    (how often the looped children's forward was traced in the last call:
    1 when the trips are a scan), and, from the counters the last forward
    or ``TrainStep`` call wrote, ``loop::exit_mass::<t>``,
    ``loop::expected_steps`` and ``loop::gate_entropy``. Returns
    ``{gauge: value}``."""
    from ... import telemetry
    out = {}
    for block in _blocks_under(net):
        if isinstance(block, HybridLoop):
            out["loop::trips"] = float(block._loops)
            out["loop::stack_traces"] = float(block.body_traces)
        elif isinstance(block, ExitGate):
            *mass, steps, entropy = block.counters.data().asnumpy()
            for t, value in enumerate(mass, 1):
                out[f"loop::exit_mass::{t}"] = float(value)
            out["loop::expected_steps"] = float(steps)
            out["loop::gate_entropy"] = float(entropy)
    for name, value in out.items():
        telemetry.gauge(name).set(value)
    return out
