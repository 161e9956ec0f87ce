"""The sequence-model ops (ops/seq.py) against the plain reference
(tests/reference/nemotron_h.py) at a small size: forward and gradient of
each, sequence lengths that are and are not a multiple of the chunk, the
shares of a layer adding up to the uncut layer, the expert layer's static
buffer (same program for every routing, the whole buffer computed, pairs
beyond it counted)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "reference"))
import nemotron_h as ref  # noqa: E402
import numerics  # noqa: E402

from mxnet_tpu.ops import seq  # noqa: E402

SZ = dict(hidden_size=64, vocab_size=97, hybrid_override_pattern="ME*",
          mamba_num_heads=8, mamba_head_dim=16, n_groups=4,
          ssm_state_size=16, conv_kernel=4, chunk_size=8,
          num_attention_heads=8, num_key_value_heads=2, head_dim=16,
          moe_latent_size=32, moe_intermediate_size=48, router_experts=16,
          n_routed_experts=16, num_experts_per_tok=3,
          moe_shared_expert_intermediate_size=40,
          routed_scaling_factor=2.5, norm_topk_prob=True, norm_eps=1e-5)
M_LEAVES = ("in_proj_weight", "conv_weight", "conv_bias", "dt_bias", "a_log",
            "d", "gate_norm_weight", "out_proj_weight")
E_LEAVES = ("router_weight", "router_bias", "down_weight", "up_weight", "w1",
            "w2", "shared_w1", "shared_w2")


def _leaves(p, i, names):
    return [p[f"l{i}_{n}"] for n in names]


def _mamba(sz, x, *leaves):
    return seq.mamba2_mixer(
        x, *leaves, num_heads=sz["mamba_num_heads"],
        head_dim=sz["mamba_head_dim"], state_size=sz["ssm_state_size"],
        num_groups=sz["n_groups"], chunk_size=sz["chunk_size"])


def _moe(sz, x, *leaves, ids=None, rows=None, bias_rate=0.0):
    """``(out, counters)`` and, with a ``bias_rate``, the next bias."""
    ids = tuple(range(sz["router_experts"])) if ids is None else ids
    rows = len(ids) * x.shape[0] * x.shape[1] if rows is None else rows
    out = seq.latent_moe(
        x, *leaves, expert_ids=ids, top_k=sz["num_experts_per_tok"],
        buffer_rows=rows, scaling=sz["routed_scaling_factor"],
        bias_rate=bias_rate)
    return out if bias_rate else out[:2]


def _attn(sz, x, qkv_w, o_w, block=8):
    out = seq.causal_gq_attention(
        x @ qkv_w.T, num_heads=sz["num_attention_heads"],
        num_kv_heads=sz["num_key_value_heads"], head_dim=sz["head_dim"],
        block=block)
    return out @ o_w.T


def _x(length, seed=1, batch=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((batch, length, 64)), jnp.float32)


#: of the wanted value's largest entry, or of 1 where that is smaller
TOL = numerics.Tol(rtol=0.0, scaled=2e-5, floor=1.0)


def _grads_close(f_got, f_want, args, tol=2e-4):
    """Gradients of a scalar of the output with respect to every
    argument."""
    w = jnp.asarray(np.random.default_rng(7).standard_normal(
        jax.eval_shape(f_want, *args).shape), jnp.float32)
    got, want = (numerics.traced(fn, args, w, range(len(args)))[1]
                 for fn in (f_got, f_want))
    numerics.close(got, want, TOL._replace(scaled=tol))


def test_rms_norm_groups():
    x = _x(5)
    w = jnp.asarray(np.random.default_rng(2).standard_normal(64), jnp.float32)
    numerics.close(seq.rms_norm(x, w, eps=1e-5, num_groups=4),
                   ref._rms(x, w, 1e-5, 4), TOL)


@pytest.mark.parametrize("length", [24, 21, 5])
def test_mamba2_matches_time_step_scan(length):
    p = ref.random_params(SZ, 0)
    x = _x(length)
    numerics.agree(
        lambda x: _mamba(SZ, x, *_leaves(p, 0, M_LEAVES)),
        jax.vmap(lambda u: ref.mamba_layer(SZ, p, 0, u, "float32")), (x,),
        value=TOL)


def test_mamba2_gradients():
    p = ref.random_params(SZ, 0)
    args = (_x(21),) + tuple(_leaves(p, 0, M_LEAVES))

    def want(x, *leaves):
        q = {f"l0_{n}": v for n, v in zip(M_LEAVES, leaves)}
        return jax.vmap(lambda u: ref.mamba_layer(SZ, q, 0, u, "float32"))(x)

    _grads_close(lambda x, *lv: _mamba(SZ, x, *lv), want, args)


def test_scan_restarted_at_chunk_borders_is_told_apart():
    """What the comparison has to catch: a chunked scan that forgets the
    state between chunks."""
    p = ref.random_params(SZ, 0)
    x = _x(24)
    (whole, pieces), _ = numerics.traced(lambda x: (
        _mamba(SZ, x, *_leaves(p, 0, M_LEAVES)),
        jnp.concatenate(
            [_mamba(SZ, x[:, i:i + 8], *_leaves(p, 0, M_LEAVES))
             for i in range(0, 24, 8)], axis=1)), (x,))
    assert float(jnp.max(jnp.abs(whole - pieces)[:, 8:])) > 1e-3
    numerics.close(pieces[:, :8], whole[:, :8], TOL)


def test_latent_moe_matches_masked_loop():
    p = ref.random_params(SZ, 0)
    x = _x(21)
    (want, (got, stats)), _ = numerics.traced(lambda x: (
        ref.moe_layer(SZ, p, 1, x.reshape(-1, 64), "float32")[0],
        _moe(SZ, x, *_leaves(p, 1, E_LEAVES))), (x,))
    numerics.close(got.reshape(-1, 64), want, TOL)
    assert float(stats[0]) == 42 * 3 and float(stats[1]) == 0


@pytest.mark.parametrize("held", [tuple(range(16)), (4, 5)])
def test_bias_moves_one_step_towards_balance(held):
    """The third output is the reference's ``balance_step`` on the loads
    of the router's choice over all experts, whichever of them are held;
    at rate 0 the bias comes back as it went in."""
    p = ref.random_params(SZ, 0)
    x = _x(21)
    lo, hi = held[0], held[-1] + 1
    leaves = dict(zip(E_LEAVES, _leaves(p, 1, E_LEAVES)))
    leaves["w1"], leaves["w2"] = leaves["w1"][lo:hi], leaves["w2"][lo:hi]
    _, load = ref.moe_layer(SZ, p, 1, x.reshape(-1, 64), "float32")
    assert float(load.sum()) == 42 * 3 and float(load.max()) > load.mean()
    want = ref.balance_step(dict(SZ, router_bias_update_rate=0.01), p,
                            {"l1_router_bias": load})
    _, _, bias = _moe(SZ, x, *leaves.values(), ids=held, bias_rate=0.01)
    np.testing.assert_array_equal(np.asarray(bias),
                                  np.asarray(want["l1_router_bias"]))
    moved = np.asarray(bias - p["l1_router_bias"])
    assert (moved[np.asarray(load) > load.mean()] < 0).all()
    assert (moved[np.asarray(load) < load.mean()] > 0).all()
    assert want["l1_router_weight"] is p["l1_router_weight"]
    same = seq.latent_moe(x, *leaves.values(), expert_ids=held, top_k=3,
                          buffer_rows=len(held) * 42, scaling=2.5)[2]
    np.testing.assert_array_equal(np.asarray(same),
                                  np.asarray(p["l1_router_bias"]))


def test_latent_moe_gradients():
    p = ref.random_params(SZ, 0)
    names = [n for n in E_LEAVES if n != "router_bias"]
    bias = p["l1_router_bias"]
    args = (_x(13),) + tuple(p[f"l1_{n}"] for n in names)

    def full(leaves):
        q = dict(zip(names, leaves), router_bias=bias)
        return [q[n] for n in E_LEAVES]

    def want(x, *leaves):
        q = {f"l1_{n}": v for n, v in zip(E_LEAVES, full(leaves))}
        return ref.moe_layer(SZ, q, 1, x.reshape(-1, 64),
                             "float32")[0].reshape(x.shape)

    _grads_close(lambda x, *lv: _moe(SZ, x, *full(lv))[0], want, args)


@pytest.mark.parametrize("length,block", [(24, 8), (21, 8), (5, 8), (21, 64)])
def test_attention_matches_dense_softmax(length, block):
    p = ref.random_params(SZ, 0)
    x = _x(length)
    numerics.agree(
        lambda x: _attn(SZ, x, p["l2_qkv_weight"], p["l2_o_weight"], block),
        jax.vmap(lambda u: ref.attn_layer(SZ, p, 2, u, "float32", block=7)),
        (x,), value=TOL)


def test_attention_gradients():
    p = ref.random_params(SZ, 0)
    args = (_x(21), p["l2_qkv_weight"], p["l2_o_weight"])

    def want(x, qkv_w, o_w):
        q = {"l2_qkv_weight": qkv_w, "l2_o_weight": o_w}
        return jax.vmap(lambda u: ref.attn_layer(SZ, q, 2, u, "float32"))(x)

    _grads_close(lambda x, a, b: _attn(SZ, x, a, b), want, args)


# ---------------------------------------------------------------------------
# the shares of a layer add up to the layer
# ---------------------------------------------------------------------------
def test_mamba2_group_shares_add_up():
    """Four chips, one group (two heads) each: every chip's rows of the
    in-projection and the convolution, its heads' scalars and norm
    weights, its columns of the out-projection."""
    p = ref.random_params(SZ, 3)
    x = _x(21)
    h, hd, g, n = 8, 16, 4, 16
    d_in, r = h * hd, 2
    share_sz = dict(SZ, mamba_num_heads=r, n_groups=1)

    def share(x, s):
        ch = np.arange(s * r * hd, (s + 1) * r * hd)          # channels
        bn = np.arange(s * n, (s + 1) * n)
        hs = np.arange(s * r, (s + 1) * r)
        conv_rows = np.concatenate([ch, d_in + bn, d_in + g * n + bn])
        in_rows = np.concatenate([ch, d_in + conv_rows,
                                  2 * d_in + 2 * g * n + hs])
        return _mamba(
            share_sz, x, p["l0_in_proj_weight"][in_rows],
            p["l0_conv_weight"][conv_rows], p["l0_conv_bias"][conv_rows],
            p["l0_dt_bias"][hs], p["l0_a_log"][hs], p["l0_d"][hs],
            p["l0_gate_norm_weight"][ch], p["l0_out_proj_weight"][:, ch])

    numerics.agree(
        lambda x: sum(share(x, s) for s in range(g)),
        jax.vmap(lambda u: ref.mamba_layer(SZ, p, 0, u, "float32")), (x,),
        value=TOL)


def test_latent_moe_shares_add_up():
    """Four chips, four experts and ten of the shared expert's columns
    each; the router, the latent projections and the bias are on every
    chip and enter every share's result through its own experts only."""
    p = ref.random_params(SZ, 3)
    x = _x(21)

    def shares(x):
        total, pairs = 0, 0
        for s in range(4):
            ids = tuple(range(4 * s, 4 * s + 4))
            cols = np.arange(10 * s, 10 * s + 10)
            out, stats = _moe(
                SZ, x, p["l1_router_weight"], p["l1_router_bias"],
                p["l1_down_weight"], p["l1_up_weight"],
                p["l1_w1"][4 * s:4 * s + 4], p["l1_w2"][4 * s:4 * s + 4],
                p["l1_shared_w1"][cols], p["l1_shared_w2"][:, cols], ids=ids)
            total = total + out
            pairs += stats[0]
        return total.reshape(-1, 64), pairs

    ((total, pairs), want), _ = numerics.traced(lambda x: (
        shares(x), ref.moe_layer(SZ, p, 1, x.reshape(-1, 64), "float32")[0]),
        (x,))
    numerics.close(total, want, TOL)
    assert float(pairs) == 42 * 3   # every pair is on exactly one chip


def test_attention_head_shares_add_up():
    """Eight chips, one query head each; each key/value head is on four
    of them."""
    p = ref.random_params(SZ, 3)
    x = _x(21)
    hq, hk, dh = 8, 2, 16
    share_sz = dict(SZ, num_attention_heads=1, num_key_value_heads=1)

    def share(x, s):
        kv = s // (hq // hk)
        q_rows = np.arange(s * dh, (s + 1) * dh)
        k_rows = hq * dh + np.arange(kv * dh, (kv + 1) * dh)
        rows = np.concatenate([q_rows, k_rows, hk * dh + k_rows])
        return _attn(share_sz, x, p["l2_qkv_weight"][rows],
                     p["l2_o_weight"][:, q_rows])

    numerics.agree(
        lambda x: sum(share(x, s) for s in range(hq)),
        jax.vmap(lambda u: ref.attn_layer(SZ, p, 2, u, "float32")), (x,),
        value=TOL)


# ---------------------------------------------------------------------------
# the static buffer
# ---------------------------------------------------------------------------
def _routed_only(p, x, ids, rows, bias=None):
    zero = jnp.zeros((1, 64), jnp.float32)
    lo, hi = ids[0], ids[-1] + 1
    return _moe(SZ, x, p["l1_router_weight"],
                p["l1_router_bias"] if bias is None else bias,
                p["l1_down_weight"], p["l1_up_weight"], p["l1_w1"][lo:hi],
                p["l1_w2"][lo:hi], zero, zero.T, ids=ids, rows=rows)


def test_overflow_counter_counts_what_a_small_buffer_drops():
    p = ref.random_params(SZ, 0)
    x = _x(21)
    ids = (0, 1, 2, 3)
    full, stats = _routed_only(p, x, ids, 4 * 42)
    assert float(stats[1]) == 0
    _, chosen = ref.router(SZ, p, 1, x.reshape(-1, 64), "float32")
    counts = np.asarray(chosen[:, :4].sum(0))
    cap = int(counts.max()) - 2
    small, stats = _routed_only(p, x, ids, 4 * cap)
    dropped = int(np.maximum(counts - cap, 0).sum())
    assert dropped >= 2 and float(stats[1]) == dropped
    assert float(stats[0]) == counts.sum()
    assert abs(float(stats[3]) - (counts.sum() - dropped) / (4 * cap)) < 1e-6
    # the tokens that fit are computed as before, the others lose a term
    changed = np.asarray(jnp.any(jnp.abs(small - full) > 1e-6, axis=-1))
    assert 0 < changed.sum() <= dropped
    # and the count adds up from call to call
    again = seq.latent_moe(
        x, p["l1_router_weight"], p["l1_router_bias"], p["l1_down_weight"],
        p["l1_up_weight"], p["l1_w1"][:4], p["l1_w2"][:4],
        jnp.zeros((1, 64)), jnp.zeros((64, 1)), stats, expert_ids=ids,
        top_k=3, buffer_rows=4 * cap, scaling=2.5)[1]
    assert float(again[1]) == 2 * dropped


def test_every_routing_lowers_to_one_program_over_the_whole_buffer():
    """Two routers as different as they come (one bias sends nearly every
    token to the held experts, the other none) give the same program:
    same shapes, same instructions, the grouped product over every row of
    the buffer."""
    p = ref.random_params(SZ, 0)
    x = _x(21)
    ids, rows = (0, 1, 2, 3), 4 * 16
    crowd = jnp.zeros(16).at[:4].set(10.0)
    empty = jnp.zeros(16).at[:4].set(-10.0)

    def fn(bias):
        return _routed_only(p, x, ids, rows, bias)

    texts = [jax.jit(fn).lower(b).as_text() for b in (crowd, empty)]
    assert texts[0] == texts[1]
    assert "4x16x32" in texts[0] and "4x16x48" in texts[0]   # E x rows x width
    _, stats_crowd = fn(crowd)
    _, stats_empty = fn(empty)
    assert float(stats_crowd[0]) == 42 * 3 and float(stats_crowd[1]) > 0
    assert float(stats_empty[0]) == 0 and float(stats_empty[3]) == 0


def test_grouped_product_computes_unfilled_rows():
    """A row that holds no token is computed like any other (its result
    is dropped by the combine, not skipped by the product)."""
    rng = np.random.default_rng(0)
    buf = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((2, 32, 48)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((2, 48, 32)), jnp.float32)
    out = seq.grouped_product(buf, w1, w2)
    want = jnp.einsum("erf,efd->erd",
                      jnp.square(jnp.maximum(
                          jnp.einsum("erd,edf->erf", buf, w1), 0)), w2)
    numerics.close(out, want, TOL._replace(scaled=1e-4))
    assert bool(jnp.all(jnp.any(out != 0, axis=-1)))
