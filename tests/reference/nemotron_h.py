"""Plain reference of the Nemotron-H family's layers (Mamba-2, LatentMoE,
grouped-query attention; ``model_type: nemotron_h``) for one chip's share
of a deployment: ``jax.numpy`` in float32 at ``Precision.HIGHEST``, a
time-step ``lax.scan`` for the state-space recurrence, dense softmax
attention in blocks of queries, the held experts as a loop with a mask
(no sort, no buffer). It imports nothing of ``mxnet_tpu``.

The block between the two marker lines is kept letter for letter equal to
the benchmark's own copy in
``benchmark/configs/nemotron3-super-120b-a12b.py``
(``tests/bench_harness/test_bench_nemotron.py`` compares them).
"""
from __future__ import annotations

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
FROZEN = ("router_bias",)          # leaves the optimizer does not touch


def kinds(sz):
    return list(sz["hybrid_override_pattern"])


def held_experts(sz):
    return list(sz.get("expert_ids", range(sz["n_routed_experts"])))


def shared_columns(sz):
    return sz["moe_shared_expert_intermediate_size"] \
        // sz.get("moe_shared_expert_shards", 1)


def param_shapes(sz):
    d, v = sz["hidden_size"], sz["vocab_size"]
    h, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n, k = sz["n_groups"], sz["ssm_state_size"], sz["conv_kernel"]
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    lat, ff = sz["moe_latent_size"], sz["moe_intermediate_size"]
    e_all, e = sz["router_experts"], len(held_experts(sz))
    shapes = {"embed_weight": (v, d)}
    for i, kind in enumerate(kinds(sz)):
        shapes[f"l{i}_norm_weight"] = (d,)
        if kind == "M":
            shapes[f"l{i}_in_proj_weight"] = (2 * h * p + 2 * g * n + h, d)
            shapes[f"l{i}_conv_weight"] = (h * p + 2 * g * n, k)
            shapes[f"l{i}_conv_bias"] = (h * p + 2 * g * n,)
            shapes[f"l{i}_dt_bias"] = (h,)
            shapes[f"l{i}_a_log"] = (h,)
            shapes[f"l{i}_d"] = (h,)
            shapes[f"l{i}_gate_norm_weight"] = (h * p,)
            shapes[f"l{i}_out_proj_weight"] = (d, h * p)
        elif kind == "E":
            shapes[f"l{i}_router_weight"] = (e_all, d)
            shapes[f"l{i}_router_bias"] = (e_all,)
            shapes[f"l{i}_down_weight"] = (lat, d)
            shapes[f"l{i}_up_weight"] = (d, lat)
            shapes[f"l{i}_w1"] = (e, lat, ff)
            shapes[f"l{i}_w2"] = (e, ff, lat)
            shapes[f"l{i}_shared_w1"] = (shared_columns(sz), d)
            shapes[f"l{i}_shared_w2"] = (d, shared_columns(sz))
        elif kind == "*":
            shapes[f"l{i}_qkv_weight"] = ((hq + 2 * hk) * dh, d)
            shapes[f"l{i}_o_weight"] = (d, hq * dh)
        else:
            raise ValueError(f"layer kind {kind!r}")
    shapes["final_norm_weight"] = (d,)
    shapes["head_weight"] = (v, d)
    return shapes


def _matmul(x, w, precision):
    """``x @ w.T``."""
    return jnp.dot(held(x, precision), held(w, precision).T, precision=_HI)


def _rms(x, w, eps, groups=1):
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x.reshape(shape) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _conv(x, w, b):
    """Causal depthwise convolution over time: ``x`` (L, C), ``w`` (C, K)
    with its last tap on the current step."""
    k = w.shape[1]
    pad = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return b + sum(pad[j:j + x.shape[0]] * w[:, j] for j in range(k))


def _ssm(x, dt, a, b, c, block=128):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
    C_t``, one step of time after another from a zero state. ``x`` (L, H,
    P), ``dt`` (L, H), ``a`` (H,), ``b``, ``c`` (L, G, N). The steps run
    in blocks whose insides are recomputed in the backward pass, which
    changes what is kept, not what is computed."""
    length, h, p = x.shape
    g, n = b.shape[1:]
    pad = (-length) % block
    x, dt, b, c = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                   for t in (x, dt, b, c))

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, h // g, axis=0)            # (H, N)
        c_h = jnp.repeat(c_t, h // g, axis=0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return s, jnp.sum(s * c_h[:, None, :], axis=-1)

    @jax.checkpoint
    def run_block(s, inp):
        return lax.scan(step, s, inp)

    blocks = tuple(t.reshape((-1, block) + t.shape[1:])
                   for t in (x, dt, b, c))
    _, y = lax.scan(run_block, jnp.zeros((h, p, n), jnp.float32), blocks)
    return y.reshape((-1, h, p))[:length]


def mamba_layer(sz, p, i, u, precision):
    h, hd = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n = sz["n_groups"], sz["ssm_state_size"]
    d_in = h * hd
    zxbcdt = _matmul(u, p[f"l{i}_in_proj_weight"], precision)
    z = zxbcdt[:, :d_in]
    xbc = zxbcdt[:, d_in:2 * d_in + 2 * g * n]
    dt = zxbcdt[:, 2 * d_in + 2 * g * n:]
    xbc = jax.nn.silu(_conv(held(xbc, precision), p[f"l{i}_conv_weight"],
                            p[f"l{i}_conv_bias"]))
    xbc = held(xbc, precision)
    x = xbc[:, :d_in].reshape(-1, h, hd)
    b = xbc[:, d_in:d_in + g * n].reshape(-1, g, n)
    c = xbc[:, d_in + g * n:].reshape(-1, g, n)
    dt = jax.nn.softplus(dt + p[f"l{i}_dt_bias"])
    y = _ssm(x, dt, -jnp.exp(p[f"l{i}_a_log"]), b, c)
    y = y + p[f"l{i}_d"][:, None] * x
    y = y.reshape(-1, d_in) * jax.nn.silu(z)
    y = _rms(y, p[f"l{i}_gate_norm_weight"], sz["norm_eps"], g)
    return _matmul(y, p[f"l{i}_out_proj_weight"], precision)


def router(sz, p, i, u, precision):
    """``(weights (T, E_all), zero where not chosen; chosen (T, E_all))``
    over every expert of the model."""
    s = jax.nn.sigmoid(_matmul(u, p[f"l{i}_router_weight"], precision))
    _, idx = lax.top_k(s + p[f"l{i}_router_bias"],
                       sz["num_experts_per_tok"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    if sz["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * sz["routed_scaling_factor"], chosen


def moe_layer(sz, p, i, u, precision):
    """``(the layer's output, every expert's load (E_all,))``: the load is
    the number of ``u``'s tokens whose choice holds the expert."""
    w, chosen = router(sz, p, i, u, precision)
    v = _matmul(u, p[f"l{i}_down_weight"], precision)

    def expert(routed, held_one):
        w1, w2, gate = held_one
        hid = _relu2(_matmul(v, w1.T, precision))
        return routed + gate[:, None] * _matmul(hid, w2.T, precision), None

    # one expert after another over all tokens, masked by its gate: a loop
    # whose body the compiled program holds once
    routed, _ = lax.scan(
        expert, jnp.zeros_like(v),
        (p[f"l{i}_w1"], p[f"l{i}_w2"], w[:, jnp.asarray(held_experts(sz))].T))
    shared = _matmul(_relu2(_matmul(u, p[f"l{i}_shared_w1"], precision)),
                     p[f"l{i}_shared_w2"], precision)
    return _matmul(routed, p[f"l{i}_up_weight"], precision) + shared, \
        jnp.sum(chosen, axis=0, dtype=jnp.float32)


def attn_layer(sz, p, i, u, precision, block=1024):
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    length = u.shape[0]
    qkv = held(_matmul(u, p[f"l{i}_qkv_weight"], precision), precision)
    q = qkv[:, :hq * dh].reshape(length, hq, dh)
    k = jnp.repeat(qkv[:, hq * dh:(hq + hk) * dh].reshape(length, hk, dh),
                   hq // hk, axis=1)
    v = jnp.repeat(qkv[:, (hq + hk) * dh:].reshape(length, hk, dh),
                   hq // hk, axis=1)
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


_LAYER = {"M": mamba_layer, "E": moe_layer, "*": attn_layer}


def layer(sz, p, i, x, precision="float32"):
    """``(x + Mixer_i(RMSNorm_i(x)), load)`` for one sequence ``x`` (L,
    hidden); ``load`` is an ``E`` layer's (``moe_layer``), else nothing."""
    u = _rms(x, p[f"l{i}_norm_weight"], sz["norm_eps"])
    out, load = _LAYER[kinds(sz)[i]](sz, p, i, u, precision), None
    if kinds(sz)[i] == "E":
        out, load = out
    return x + out, load


def layer_params(p, i):
    return {k: v for k, v in p.items() if k.startswith(f"l{i}_")}


def reference_loss(sz, p, tokens, targets, precision="float32"):
    """``(loss, loads)``: the mean cross entropy of the next token over
    ``tokens`` (B, L) against ``targets`` (B * L,), and each ``E`` layer's
    loads over the whole batch under the name of its correction bias.
    Each layer's insides are recomputed in the backward pass."""
    x = jnp.take(p["embed_weight"], tokens, axis=0)          # (B, L, D)
    loads = {}
    for i, kind in enumerate(kinds(sz)):
        one = jax.checkpoint(
            lambda q, xs, i=i: layer(sz, q, i, xs, precision))
        x, load = jax.vmap(one, in_axes=(None, 0))(layer_params(p, i), x)
        if kind == "E":
            loads[f"l{i}_router_bias"] = load.sum(0)
    x = _rms(x, p["final_norm_weight"], sz["norm_eps"])
    logits = _matmul(x.reshape(-1, x.shape[-1]), p["head_weight"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1)), \
        loads


def balance_step(sz, p, loads):
    """The routers' correction biases after one step of auxiliary-loss-free
    balancing on the step's own loads: ``b_e + rate * sign(mean load -
    load_e)``; every other leaf as it is."""
    rate = sz.get("router_bias_update_rate", 0.0)
    return {k: v + rate * jnp.sign(jnp.mean(loads[k]) - loads[k])
            if k in loads else v for k, v in p.items()}


def adam_step(opt, p, m, v, t, grads):
    """Adam as ``mxnet_tpu``'s optimizer of that name applies it: the
    rate corrected for both moments' bias, epsilon outside the root.
    Leaves named in ``FROZEN`` stay as they are (``balance_step`` moves
    them)."""
    b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
    eps, wd = opt.get("epsilon", 1e-8), opt.get("wd", 0.0)
    lr_t = opt["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for k in p:
        if k.endswith(FROZEN):
            new_p[k], new_m[k], new_v[k] = p[k], m[k], v[k]
            continue
        g = grads[k] + wd * p[k]
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g)
        new_p[k] = p[k] - lr_t * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
    return new_p, new_m, new_v
# --- reference: end --------------------------------------------------------


def random_params(sz, seed, scale=0.1):
    """Random parameters of the shapes above, for the tests: every leaf
    of a size that makes its part of the layer count."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in param_shapes(sz).items():
        if name.endswith("norm_weight"):
            value = 1 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("a_log"):
            value = np.log(rng.uniform(1, 16, shape))
        elif name.endswith("_d"):
            value = 1 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("dt_bias"):
            value = rng.uniform(-4, -1, shape)
        elif name.endswith("router_bias"):
            value = 0.05 * rng.standard_normal(shape)
        elif name.endswith("conv_weight"):
            value = rng.uniform(-0.5, 0.5, shape)
        else:
            value = scale * rng.standard_normal(shape)
        out[name] = jnp.asarray(value, jnp.float32)
    return out
