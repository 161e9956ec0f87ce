"""Sequence-model blocks: ``RMSNorm``, ``Mamba2Mixer``, ``LatentMoE`` and
``GQAttention`` over the ops of ``ops/seq.py``.

Each block is told what it holds of the layer: how many heads and groups,
which experts, how many of the shared expert's columns. A block that holds
a share returns that share's partial sum (``ops/seq.py``).
"""
from __future__ import annotations

from ... import autograd
from ..block import HybridBlock, stateful_write

__all__ = ["RMSNorm", "Mamba2Mixer", "LatentMoE", "GQAttention",
           "MOE_COUNTERS", "publish_moe_counters"]

#: what ``LatentMoE.counters`` holds, in order: the gauges
#: ``moe::<name>::<block>`` of ``publish_moe_counters``
MOE_COUNTERS = ("pairs_held", "overflow_pairs", "load_max_over_mean",
                "buffer_fill")


def publish_moe_counters(net):
    """Read the counters of every ``LatentMoE`` under ``net`` (what the
    last forward, or ``TrainStep`` call, wrote beside its output) and set
    the gauges ``moe::<counter>::<block>``: one read a layer, no work in
    the step. Returns ``{gauge: value}``."""
    from ... import telemetry
    out = {}
    todo = [net]
    while todo:
        block = todo.pop()
        todo.extend(block._children.values())
        if isinstance(block, LatentMoE):
            name = block.counters.name.rsplit("_", 1)[0]
            for counter, value in zip(MOE_COUNTERS,
                                      block.counters.data().asnumpy()):
                out[f"moe::{counter}::{name}"] = float(value)
                telemetry.gauge(f"moe::{counter}::{name}").set(float(value))
    return out


class RMSNorm(HybridBlock):
    def __init__(self, in_channels, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._epsilon)


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
    its statistics: the next call routes by the new bias."""

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


class GQAttention(HybridBlock):
    """Causal grouped-query attention with no positional encoding
    (``ops.seq.causal_gq_attention``) between a fused ``[q | k | v]``
    projection and the output projection, over the heads held here."""

    def __init__(self, in_units, num_heads, num_kv_heads, head_dim=128,
                 block=1024, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"num_heads": num_heads, "num_kv_heads": num_kv_heads,
                       "head_dim": head_dim, "block": block}
        with self.name_scope():
            self.qkv_weight = self.params.get(
                "qkv_weight",
                shape=((num_heads + 2 * num_kv_heads) * head_dim, in_units))
            self.o_weight = self.params.get(
                "o_weight", shape=(in_units, num_heads * head_dim))

    def hybrid_forward(self, F, x, qkv_weight, o_weight):
        qkv = F.FullyConnected(x, qkv_weight, no_bias=True, flatten=False)
        out = F.CausalGQAttention(qkv, **self._attrs)
        return F.FullyConnected(out, o_weight, no_bias=True, flatten=False)
