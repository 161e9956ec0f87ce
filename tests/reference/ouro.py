"""Plain reference of the Ouro family's looped language model
(``model_type: ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): a stack of layers, each an attention and a
gated-MLP sublayer between two RMSNorms, run ``total_ut_steps`` times
over its own output with the same weights; rotary heads; a language-model
head after every pass; an exit gate whose distribution over the passes
weighs the passes' losses. ``jax.numpy`` in float32 at
``Precision.HIGHEST``, dense softmax attention in blocks of queries, the
passes as a ``lax.scan``, each layer and each exit recomputed in the
backward pass, Adam written out. It imports nothing of ``mxnet_tpu``.

The block between the two marker lines is kept letter for letter equal to
the benchmark's own copy in ``benchmark/configs/ouro-2.6b.py``
(``tests/bench_harness/test_bench_ouro.py`` compares them).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def fp8(x):
    m, e = jnp.frexp(x)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + lax.stop_gradient(q - x)


def held(x, precision):
    return fp8(x) if precision == "fp8" else x


# --- reference: begin ------------------------------------------------------
_HI = lax.Precision.HIGHEST


def param_shapes(sz):
    d, v, f = sz["hidden_size"], sz["vocab_size"], sz["intermediate_size"]
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    shapes = {"embed_weight": (v, d)}
    for i in range(sz["num_hidden_layers"]):
        shapes[f"l{i}_attn_norm_weight"] = (d,)
        shapes[f"l{i}_qkv_weight"] = ((hq + 2 * hk) * dh, d)
        shapes[f"l{i}_o_weight"] = (d, hq * dh)
        shapes[f"l{i}_attn_post_norm_weight"] = (d,)
        shapes[f"l{i}_mlp_norm_weight"] = (d,)
        shapes[f"l{i}_gate_up_weight"] = (2 * f, d)
        shapes[f"l{i}_down_weight"] = (d, f)
        shapes[f"l{i}_mlp_post_norm_weight"] = (d,)
    shapes["final_norm_weight"] = (d,)
    shapes["head_weight"] = (v, d)
    shapes["gate_weight"] = (1, d)
    shapes["gate_bias"] = (1,)
    return shapes


def _matmul(x, w, precision):
    """``x @ w.T``."""
    return jnp.dot(held(x, precision), held(w, precision).T, precision=_HI)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rotate(x, theta):
    """Rotary position encoding of ``x`` (L, H, D) over the whole head,
    ``rotate_half`` convention: the angle of position ``t`` and pair ``i``
    is ``t * theta^(-2i/D)``; pair ``i`` is elements ``i`` and ``i + D/2``."""
    length, _, d = x.shape
    inv = jnp.asarray(1.0 / theta ** (np.arange(0, d, 2) / d), jnp.float32)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # (L, 1, D)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(sz, p, i, u, precision):
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    length, block = u.shape[0], sz.get("attention_block", 1024)
    qkv = held(_matmul(u, p[f"l{i}_qkv_weight"], precision), precision)
    q = qkv[:, :hq * dh].reshape(length, hq, dh)
    k = qkv[:, hq * dh:(hq + hk) * dh].reshape(length, hk, dh)
    v = qkv[:, (hq + hk) * dh:].reshape(length, hk, dh)
    q = held(rotate(q, sz["rope_theta"]), precision)
    k = held(rotate(k, sz["rope_theta"]), precision)
    k, v = (jnp.repeat(t, hq // hk, axis=1) for t in (k, v))
    outs = []
    for i0 in range(0, length, block):
        i1 = min(i0 + block, length)
        s = jnp.einsum("qhd,khd->hqk", q[i0:i1], k[:i1], precision=_HI) \
            * dh ** -0.5
        mask = jnp.arange(i0, i1)[:, None] >= jnp.arange(i1)[None, :]
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", held(pr, precision), v[:i1],
                               precision=_HI))
    out = jnp.concatenate(outs, axis=0).reshape(length, hq * dh)
    return _matmul(out, p[f"l{i}_o_weight"], precision)


def gated_mlp(sz, p, i, u, precision):
    f = sz["intermediate_size"]
    gu = held(_matmul(u, p[f"l{i}_gate_up_weight"], precision), precision)
    return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                   p[f"l{i}_down_weight"], precision)


def layer(sz, p, i, x, precision="float32"):
    """One layer on one sequence ``x`` (L, hidden): each sublayer between
    a norm before it and a norm after it, inside the residual."""
    eps = sz["rms_norm_eps"]
    a = x + _rms(attention(sz, p, i, _rms(x, p[f"l{i}_attn_norm_weight"],
                                          eps), precision),
                 p[f"l{i}_attn_post_norm_weight"], eps)
    return a + _rms(gated_mlp(sz, p, i, _rms(a, p[f"l{i}_mlp_norm_weight"],
                                             eps), precision),
                    p[f"l{i}_mlp_post_norm_weight"], eps)


def layer_params(p, i):
    return {k: v for k, v in p.items() if k.startswith(f"l{i}_")}


def hidden_states(sz, p, tokens, precision="float32"):
    """The stack's output after each pass, final norm applied, (T, B * L,
    hidden): pass ``t`` reads pass ``t - 1``'s, the first the embedding;
    the same weights every pass. Each layer's insides are recomputed in
    the backward pass."""
    def one_pass(h, _):
        for i in range(sz["num_hidden_layers"]):
            one = jax.checkpoint(
                lambda q, xs, i=i: layer(sz, q, i, xs, precision))
            h = jax.vmap(one, in_axes=(None, 0))(layer_params(p, i), h)
        h = _rms(h, p["final_norm_weight"], sz["rms_norm_eps"])
        return h, h

    x = jnp.take(p["embed_weight"], tokens, axis=0)          # (B, L, D)
    _, hidden = lax.scan(one_pass, x, None, length=sz["total_ut_steps"])
    return hidden.reshape(hidden.shape[0], -1, hidden.shape[-1])


def exit_probs(sz, p, hidden, precision="float32"):
    """``p(t)`` (T, N): the gate ``lambda_t = sigmoid(h_t . w_g + b_g)``
    of every pass but the last; a token leaves after pass ``t`` with
    ``lambda_t`` times the probability of not having left before, and
    after the last pass with what is left."""
    passes = hidden.shape[0]
    z = _matmul(hidden[:passes - 1], p["gate_weight"], precision)[..., 0] \
        + p["gate_bias"][0]
    lam = jax.nn.sigmoid(z)
    left, probs = jnp.ones(hidden.shape[1]), []
    for t in range(passes - 1):
        probs.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(probs + [left])


def reference_loss(sz, p, tokens, targets, precision="float32"):
    """``mean over tokens of [sum_t p(t) l_t - beta H(p)]``: ``l_t`` the
    next-token cross entropy of pass ``t``'s logits over ``tokens`` (B, L)
    against ``targets`` (B * L,), ``H`` the entropy of the exit
    distribution. One exit's logits at a time, computed again in the
    backward pass."""
    hidden = hidden_states(sz, p, tokens, precision)

    @jax.checkpoint
    def cross_entropy(h):
        logp = jax.nn.log_softmax(_matmul(h, p["head_weight"], precision),
                                  axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]

    ce = lax.map(cross_entropy, hidden)                       # (T, N)
    pt = exit_probs(sz, p, hidden, precision)
    entropy = -jnp.sum(pt * jnp.log(pt), axis=0)
    return jnp.mean(jnp.sum(pt * ce, axis=0)
                    - sz["exit_entropy_beta"] * entropy)


def adam_step(opt, p, m, v, t, grads):
    """Adam as ``mxnet_tpu``'s optimizer of that name applies it: the
    rate corrected for both moments' bias, epsilon outside the root."""
    b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
    eps, wd = opt.get("epsilon", 1e-8), opt.get("wd", 0.0)
    lr_t = opt["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for k in p:
        g = grads[k] + wd * p[k]
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g)
        new_p[k] = p[k] - lr_t * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
    return new_p, new_m, new_v
# --- reference: end --------------------------------------------------------
