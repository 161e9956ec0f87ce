"""Ring attention: sequence/context parallelism over the device mesh.

This capability EXCEEDS the reference — MXNet ~1.1 has no attention op at
all and no sequence parallelism (SURVEY.md §5 long-context: its tools were
BucketingModule, sequence ops, and a fused RNN). On TPU, long sequences
shard over a mesh axis and attention walks the ring:

- each device holds a sequence block of Q, K, V;
- at every step it computes blockwise attention of its Q against the
  K/V block currently resident, accumulating with the numerically stable
  running-max/denominator recurrence (flash-attention style), then
  rotates K/V one hop around the ring with ``lax.ppermute`` — the
  collective rides ICI neighbor links, never gathering the full sequence
  on any chip;
- total memory per chip stays O(T/P), enabling contexts P× longer.

Public surface: ``ring_attention`` (shard_map'd full attention) and
``sequence_shard``/mesh helpers. Causal masking is computed from global
block offsets, and fully masked blocks are skipped numerically (their
contribution multiplies in as exp(-inf) = 0).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "local_attention_block", "sequence_shard"]

_NEG = -1e30


def local_attention_block(q, k, v, bias=None, scale=None):
    """Dense softmax attention for one (q-block, kv-block) pair.

    q: (B, Tq, H, D); k/v: (B, Tk, H, D). Returns (out, row_max, row_sum)
    for the stable-accumulation recurrence."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                          # (B, H, Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                          # (B, H, Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m, l


def _ring_attention_sharded(q, k, v, axis_name, causal, scale):
    """Per-device body under shard_map: q/k/v are local sequence blocks
    (B, T_local, H, D)."""
    p_size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, T, H, D = q.shape

    q_pos = my * T + jnp.arange(T)                   # global q positions

    def step(carry, i):
        k_blk, v_blk, o, m, l = carry
        # the block resident at step i originated on rank (my + i) % P
        src = (my + i) % p_size
        if causal:
            k_pos = src * T + jnp.arange(T)
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, _NEG)
            bias = bias[None, None, :, :]            # (1, 1, Tq, Tk)
        else:
            bias = None
        o_i, m_i, l_i = local_attention_block(q, k_blk, v_blk, bias, scale)
        # stable accumulation (flash recurrence)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        l_new = l * alpha + l_i * beta
        o_new = o * alpha.transpose(0, 2, 1)[..., None] \
            + o_i * beta.transpose(0, 2, 1)[..., None]
        # rotate K/V one hop: rank r sends to r-1 (so blocks advance +1)
        perm = [(r, (r - 1) % p_size) for r in range(p_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, o_new, m_new, l_new), None

    o0 = jnp.zeros_like(q, jnp.float32)
    # derive the running stats from q so they carry exactly q's varying
    # manual axes (required for the scan carry to type-check under
    # shard_map, whatever combination of mesh axes is in use)
    zero_bht = q.astype(jnp.float32).sum(-1).transpose(0, 2, 1) * 0.0
    m0 = zero_bht + _NEG
    l0 = zero_bht
    (k_f, v_f, o, m, l), _ = jax.lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(p_size))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(query, key, value, mesh: Mesh, seq_axis: str = "sp",
                   batch_axis: Optional[str] = None, causal: bool = False,
                   scale: Optional[float] = None):
    """Multi-head attention with the sequence axis sharded over ``seq_axis``.

    query/key/value: (B, T, H, D) arrays (global view). T must divide the
    size of ``seq_axis``. The result equals dense softmax attention to
    numerical accuracy while no device ever holds more than T/P of the
    sequence.
    """
    qspec = P(batch_axis, seq_axis, None, None)
    body = functools.partial(_ring_attention_sharded, axis_name=seq_axis,
                             causal=causal, scale=scale)
    fn = shard_map(body, mesh=mesh, in_specs=(qspec, qspec, qspec),
                   out_specs=qspec)
    with mesh:
        return fn(jnp.asarray(query), jnp.asarray(key), jnp.asarray(value))


def sequence_shard(array, mesh: Mesh, seq_axis: str = "sp", axis: int = 1,
                   batch_axis: Optional[str] = None):
    """Place an array with its sequence dimension sharded over the mesh."""
    spec = [None] * array.ndim
    spec[axis] = seq_axis
    if batch_axis is not None:
        spec[0] = batch_axis
    return jax.device_put(jnp.asarray(array), NamedSharding(mesh, P(*spec)))
