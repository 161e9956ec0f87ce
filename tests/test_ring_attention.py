"""Ring attention (sequence parallelism) tests on the virtual 8-device mesh.

Capability beyond the reference (it has no attention op); numerics are
checked against dense softmax attention.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.parallel import make_mesh, ring_attention, sequence_shard

import numerics


def dense_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        T = q.shape[1]
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -1e30)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    B, T, H, D = 2, 32, 4, 8       # T = 32 over 8 devices -> 4 per device
    q = rng.randn(B, T, H, D).astype(np.float32)
    k = rng.randn(B, T, H, D).astype(np.float32)
    v = rng.randn(B, T, H, D).astype(np.float32)
    return q, k, v


def test_ring_matches_dense(qkv):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})
    out = ring_attention(q, k, v, mesh, seq_axis="sp")
    expect = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-5)


def test_ring_causal_matches_dense(qkv):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})
    out = ring_attention(q, k, v, mesh, seq_axis="sp", causal=True)
    expect = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-5)


def test_sequence_actually_sharded(qkv):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})
    qs = sequence_shard(q, mesh, "sp")
    assert len(qs.sharding.device_set) == 8
    # per-device shard holds T/8 of the sequence
    shard = qs.addressable_shards[0]
    assert shard.data.shape[1] == q.shape[1] // 8
    out = ring_attention(qs, sequence_shard(k, mesh, "sp"),
                         sequence_shard(v, mesh, "sp"), mesh, seq_axis="sp")
    np.testing.assert_allclose(np.asarray(out), dense_attention(q, k, v),
                               rtol=2e-4, atol=2e-5)


def test_ring_with_batch_and_seq_axes(qkv):
    q, k, v = qkv
    mesh = make_mesh({"data": 2, "sp": 4})
    out = ring_attention(q, k, v, mesh, seq_axis="sp", batch_axis="data")
    np.testing.assert_allclose(np.asarray(out), dense_attention(q, k, v),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_differentiable(qkv):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh, seq_axis="sp") ** 2)


    def loss_dense(q_, k_, v_):
        d = q_.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) / jnp.sqrt(d * 1.0)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, v_) ** 2)

    # the gradient for ``q``
    g, g_ref = (numerics.traced(loss, (q, k, v), 1.0, 0)[1]
                for loss in (loss_ring, loss_dense))
    numerics.close(g, g_ref, numerics.Tol(rtol=5e-3, atol=5e-4))
