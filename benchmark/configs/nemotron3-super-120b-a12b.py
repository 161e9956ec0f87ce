"""One v5e chip's share of NVIDIA-Nemotron-3-Super-120B-A12B
(``model_type: nemotron_h``): one period of the layer pattern, Mamba-2
(``M``), LatentMoE (``E``) and grouped-query attention (``*``) layers at
the published widths, with an eighth of the heads, groups, shared-expert
columns and vocabulary and 8 of the 512 routed experts: what one of 64
chips that share each layer would hold. The cut, the deployment and every
assumed size are in ``nemotron3-super-120b-a12b.json``.

Two halves that share nothing but parameter names and layouts:

* the system under test (``build``): a gluon ``PatternLM`` driven by
  ``parallel.TrainStep`` with Adam, recomputation by layer and the net's
  own parameter buffers, the path ``lstm-lm-650x2.py`` takes;
* the plain reference (between the marker lines, a copy of
  ``tests/reference/nemotron_h.py``; ``reference_train``): ``jax.numpy``
  in float32 at ``Precision.HIGHEST``, a time-step ``lax.scan`` for the
  state-space recurrence, dense softmax attention in blocks of queries,
  the held experts as a loop with a mask, Adam written out. It imports
  nothing of ``mxnet_tpu``. ``precision="fp8"`` is the control: both
  operands of every matrix product, and the scan's inputs, rounded to the
  four significant bits of an 8-bit float.

``make_weights`` also sets each ``E`` layer's ``e_score_correction_bias``
by the auxiliary-loss-free balancing rule, run on the ring's batches
through the reference's forward for a fixed number of iterations, so that
no seed overflows the expert layers' static buffers; the vectors are kept
by seed, so the call that makes the reference's weights after the window
does not calibrate again.
"""
from __future__ import annotations

import functools
import json
import math
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from refutil import held, seed_key

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(_ROOT, "mxnet_tpu", "ops", "seq.py")):
    # a program from before these layers cannot run the cell: say so at
    # once, before any weight is made
    raise SystemExit("nemotron3-super-120b-a12b needs mxnet_tpu/ops/seq.py "
                     "(Mamba2Mixer, LatentMoE, CausalGQAttention) and "
                     "gluon.model_zoo.PatternLM: this program has neither")

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


# ---------------------------------------------------------------------------
# seeded weights and batches
# ---------------------------------------------------------------------------
OUT_PROJECTIONS = ("out_proj_weight", "o_weight", "w2", "shared_w2",
                   "up_weight")
_BIAS = {}        # (sizes, seed) -> {leaf: numpy vector}: no weight is kept
_BIAS_SPANS = []  # (start, seconds) of each calibration, for build() to report


def _init_leaf(sz, name, shape, key):
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)  # noqa
    if name.endswith(("norm_weight", "_d")):
        return jnp.ones(shape, jnp.float32)
    if name.endswith(("conv_bias", "router_bias")):
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("a_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name.endswith("dt_bias"):
        lo, hi = math.log(sz["time_step_min"]), math.log(sz["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, sz["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    if name.endswith("conv_weight"):
        bound = shape[1] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name.endswith(OUT_PROJECTIONS):
        return normal(sz["initializer_range"]
                      / math.sqrt(2 * sz["rescale_layers"]))
    return normal(sz["initializer_range"])


@functools.lru_cache(maxsize=None)
def _one_layer(sizes_json, kind):
    """The reference's layer of one kind over a batch of sequences, its
    parameters named as layer 0's."""
    sz = dict(json.loads(sizes_json), hybrid_override_pattern=kind)
    return jax.jit(jax.vmap(lambda q, x: layer(sz, q, 0, x)[0],
                            in_axes=(None, 0)))


@functools.lru_cache(maxsize=None)
def _balance(sizes_json, tokens_per_batch):
    """The balancing rule on one layer's router scores: ``b_e <- b_e + u
    sign(mean load - load_e)`` with the loads pooled over the ring, ``u``
    falling geometrically, for the configuration's fixed number of
    iterations. Returns the bias and, of the choice it gives, each ring
    batch's pairs of every expert."""
    sz = json.loads(sizes_json)
    k, n = sz["num_experts_per_tok"], sz["router_bias_iterations"]
    u0, u1 = sz["router_bias_step"]

    def loads(s, bias):
        biased = s + bias
        kth = lax.top_k(biased, k)[0][:, -1:]
        return jnp.sum((biased >= kth).reshape(
            -1, tokens_per_batch, s.shape[-1]), axis=1, dtype=jnp.float32)

    @jax.jit
    def run(x, norm_w, router_w):
        u = _rms(x, norm_w, sz["norm_eps"]).reshape(-1, x.shape[-1])
        s = jax.nn.sigmoid(jnp.dot(u, router_w.T, precision=_HI))

        def body(j, bias):
            load = jnp.sum(loads(s, bias), axis=0)
            step = u0 * (u1 / u0) ** (j / max(n - 1, 1))
            return bias + step * jnp.sign(jnp.mean(load) - load)

        bias = lax.fori_loop(0, n, body,
                             jnp.zeros(router_w.shape[0], jnp.float32))
        return bias, loads(s, bias)

    return run


def calibrate_router_bias(sz, weights, batches):
    """Each ``E`` layer's correction bias, layer by layer through the
    reference's forward on ``batches``; ends the run if a layer misses the
    criterion. Returns ``{leaf: numpy vector}``."""
    sizes_json = json.dumps(sz, sort_keys=True)
    tokens = jnp.stack([jnp.asarray(x) for x, _ in batches])  # (R, B, L)
    ring, bsz, length = tokens.shape
    x = jnp.take(weights["embed_weight"], tokens.reshape(ring * bsz, length),
                 axis=0)
    held_ids = np.asarray(held_experts(sz))
    cap = sz["moe_buffer_rows"] // len(held_ids)
    out = {}
    for i, kind in enumerate(kinds(sz)):
        lp = {"l0_" + k.split("_", 1)[1]: v
              for k, v in layer_params(weights, i).items()}
        if kind == "E":
            bias, per_batch = _balance(sizes_json, bsz * length)(
                x, lp["l0_norm_weight"], lp["l0_router_weight"])
            per_batch = np.asarray(per_batch)
            pooled = per_batch.sum(0)
            skew = float(pooled.max() / pooled.mean())
            worst = int(per_batch[:, held_ids].max())
            print(f"router bias, layer {i}: pooled max/mean load {skew:.3f}, "
                  f"largest held expert {worst} pairs a batch of {cap} rows")
            if skew > sz["router_bias_max_over_mean"] or worst > cap:
                raise SystemExit(
                    f"layer {i}: the router's bias misses its criterion "
                    f"after {sz['router_bias_iterations']} iterations "
                    f"(max/mean {skew:.3f} over "
                    f"{sz['router_bias_max_over_mean']}, or {worst} pairs "
                    f"over {cap} rows): the run ends, it does not iterate on")
            out[f"l{i}_router_bias"] = np.asarray(bias)
            lp["l0_router_bias"] = bias
        x = _one_layer(sizes_json, kind)(lp, x)
    return out


def make_weights(sizes, seed):
    """Every parameter from the seed in one jitted call on the device
    (``_init_leaf``), then the routers' correction bias, calibrated once a
    seed on the ring's batches."""
    shapes = param_shapes(sizes)

    @jax.jit
    def make(key):
        return {name: _init_leaf(sizes, name, shape,
                                 jax.random.fold_in(key, i))
                for i, (name, shape) in enumerate(shapes.items())}

    weights = make(seed_key(seed))
    cached = (json.dumps(sizes, sort_keys=True), int(seed))
    if cached not in _BIAS:
        t0 = time.perf_counter()
        _BIAS[cached] = calibrate_router_bias(
            sizes, weights,
            make_batches(sizes, seed, sizes["router_bias_batches"]))
        _BIAS_SPANS.append((t0, time.perf_counter() - t0))
    for name, bias in _BIAS[cached].items():
        weights[name] = jnp.asarray(bias)
    return weights


def make_batches(sizes, seed, n):
    """``n`` batches of ``(tokens (batch, seq_len), next tokens (batch *
    seq_len,))``, int32, uniform over the vocabulary held; a target is the
    next id of the same sequence, the last one drawn."""
    rng = np.random.default_rng([int(seed), 29])
    b, t, v = sizes["batch"], sizes["seq_len"], sizes["vocab_size"]
    out = []
    for _ in range(n):
        ids = rng.integers(0, v, (b, t + 1)).astype(np.int32)
        out.append((ids[:, :-1].copy(), ids[:, 1:].reshape(-1).copy()))
    return out


# ---------------------------------------------------------------------------
# the reference's first steps
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _train_program(sizes_json, opt_json, precision):
    sz, opt = json.loads(sizes_json), json.loads(opt_json)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, x, y):
        (loss, loads), g = jax.value_and_grad(
            lambda q: reference_loss(sz, q, x, y, precision),
            has_aux=True)(p)
        p, m, v = adam_step(opt, p, m, v, t, g)
        return balance_step(sz, p, loads), m, v, loss

    return step


def _norms(after, before, leaves):
    return {k: float(np.linalg.norm(
        (after[k] - before[k]).astype(np.float64))) for k in leaves}


def reference_train(sizes, opt, weights, batches, precision="float32"):
    """Adam, and the routers' balancing step, from ``weights`` over
    ``batches``, one batch a step: what ``refutil.first_steps`` returns
    for SGD, over the leaves the optimizer trains. The routers' bias is
    state the forward writes, like BatchNorm's statistics, which the
    benchmark's other cells do not compare either: what holds it is every
    later step's routing (the losses here, the window's overflow count).
    The system's device buffers are released first: the reference's three
    steps need the chip."""
    release_system()
    step = _train_program(json.dumps(sizes, sort_keys=True),
                          json.dumps(opt, sort_keys=True), precision)
    start = jax.device_get(weights)
    trained = [k for k in start if not k.endswith(FROZEN)]
    p = weights
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first, update = [], None, None
    for i, (x, y) in enumerate(batches):
        p, m, v, loss = step(p, m, v, jnp.float32(i + 1), jnp.asarray(x),
                             jnp.asarray(y))
        losses.append(float(loss))
        if i == 0:
            after = jax.device_get(p)
            update = {k: after[k] - start[k] for k in trained}
            first = {k: n / opt["learning_rate"]
                     for k, n in _norms(after, start, trained).items()}
            del after
    last = jax.device_get(p)
    return {"losses": losses, "first_grad_norms": first,
            "change_norms": _norms(last, start, trained),
            "first_update": update}


# ---------------------------------------------------------------------------
# the system under test, through the public API
# ---------------------------------------------------------------------------
_LIVE = []        # the system build() made last, until it is released
_LAST = []        # ... and after
_SCOPES = []      # [its step program's scope table], once one was asked for


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import PatternLM
    sz = sizes
    return PatternLM(
        sz["hybrid_override_pattern"], sz["vocab_size"], sz["hidden_size"],
        mamba=dict(num_heads=sz["mamba_num_heads"],
                   head_dim=sz["mamba_head_dim"],
                   state_size=sz["ssm_state_size"],
                   num_groups=sz["n_groups"], conv_kernel=sz["conv_kernel"],
                   chunk_size=sz["chunk_size"]),
        moe=dict(num_experts=sz["router_experts"],
                 expert_ids=held_experts(sz),
                 top_k=sz["num_experts_per_tok"],
                 latent_units=sz["moe_latent_size"],
                 expert_units=sz["moe_intermediate_size"],
                 shared_units=shared_columns(sz),
                 buffer_rows=sz["moe_buffer_rows"],
                 scaling=sz["routed_scaling_factor"],
                 norm_topk=sz["norm_topk_prob"],
                 bias_update_rate=sz["router_bias_update_rate"]),
        attention=dict(num_heads=sz["num_attention_heads"],
                       num_kv_heads=sz["num_key_value_heads"],
                       head_dim=sz["head_dim"], block=sz["attention_block"]),
        epsilon=sz["norm_eps"])


def _leaf_of(param_name):
    """gluon's ``patternlm0_l3_mamba2mixer0_conv_bias`` /
    ``..._l3_rmsnorm0_gamma`` / ``..._embedding0_weight`` -> the
    reference's leaf name; nothing for a layer's counters."""
    rest = param_name.split("_", 1)[1]
    if rest.startswith("embedding"):
        return "embed_weight"
    if rest.startswith("dense"):
        return "head_weight"
    if rest.startswith("rmsnorm"):
        return "final_norm_weight"
    layer_id, block, leaf = rest.split("_", 2)
    if block.startswith("rmsnorm"):
        return f"{layer_id}_norm_weight"
    return None if leaf == "counters" else f"{layer_id}_{leaf}"


_OVERFLOW = 1     # where nn.MOE_COUNTERS has "overflow_pairs"


@jax.jit
def _guard(loss, *counters):
    """``loss + inf * (pairs beyond a buffer > 0)``: the reference drops
    no token, so a step that did fails the run."""
    over = sum(c[_OVERFLOW] for c in counters)
    return jnp.where(over > 0, jnp.inf, loss)


class _StepSystem:
    """``TrainStep`` with its net: what the step driver calls and what
    ``read_params`` reads. Its loss is infinite once any expert layer has
    counted a pair beyond its buffer."""

    def __init__(self, net, step):
        from mxnet_tpu.gluon.nn import MOE_COUNTERS
        assert MOE_COUNTERS[_OVERFLOW] == "overflow_pairs"
        self.net, self.step = net, step
        self.specs = None        # the step program's arguments, as shapes
        self._counters = [p for name, p in net.collect_params().items()
                          if name.endswith("_counters")]

    def __call__(self, x, y):
        from mxnet_tpu.ndarray.ndarray import NDArray
        loss = self.step(x, y)
        if self.specs is None:
            st = self.step
            self.specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (st._pvals, st._opt_state, x._data, y._data, st._t_dev,
                 st._lr_cache[1]))
        return NDArray(_guard(loss._data, *[p.data()._data
                                            for p in self._counters]))


def build(cfg, sizes, role, weights):
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep
    from mxnet_tpu.telemetry import trace
    if role != "step":
        raise ValueError(f"nemotron3-super-120b-a12b has no role {role!r}")
    while _BIAS_SPANS:      # make_weights' calibration, on the program's record
        t0, seconds = _BIAS_SPANS.pop(0)
        mx.telemetry.timer("prof::setup::router_bias").record(seconds)
        trace.record_span("router_bias", "setup", t0, seconds)
    net = _net(sizes)
    net.initialize(mx.init.Zero())
    for name, p in net.collect_params().items():
        leaf = _leaf_of(name)
        if leaf is not None:
            p.set_data(NDArray(weights[leaf]))
    opt = dict(cfg["optimizer"])
    step = TrainStep(net, loss="softmax_ce", optimizer=opt.pop("name"),
                     optimizer_params=opt,
                     compute_dtype=cfg["compute_dtype"], remat="layer")
    system = _StepSystem(net, step)
    _LIVE[:] = [system]
    del _LAST[:], _SCOPES[:]
    mx.telemetry.remove("moe::")     # an earlier system's gauges
    return system


def read_params(system):
    named = ((_leaf_of(name), p)
             for name, p in system.net.collect_params().items())
    return {leaf: p.data().asnumpy().astype(np.float32, copy=False)
            for leaf, p in named if leaf is not None}


def release_system():
    """Publish the live system's counters (``moe::*`` gauges) and free
    its device buffers, the parameters the net and the step share and the
    optimizer's state: after the window nothing calls it again, and the
    reference needs the memory. What ``scope_table`` lowers from stays."""
    from mxnet_tpu.gluon.nn import publish_moe_counters
    while _LIVE:
        system = _LIVE.pop()
        publish_moe_counters(system.net)
        step = system.step
        for leaf in jax.tree_util.tree_leaves((step._pvals,
                                               step._opt_state)):
            if not leaf.is_deleted():
                leaf.delete()
        step._pvals = step._opt_state = None
        _LAST[:] = [system]


def scope_table():
    """HLO instruction name -> ``mx_*`` scope in the step program of the
    system built last, for the readers of the device trace. The program
    is compiled once more from the first call's shapes (its buffers may
    be gone; JAX's cache may have it) and its text read once, however
    many metrics ask."""
    from mxnet_tpu.telemetry import trace
    if not _SCOPES:
        systems = [s for s in _LIVE + _LAST if s.specs is not None]
        if not systems:
            return None
        text = systems[0].step._step_jit.lower(
            *systems[0].specs).compile().as_text()
        _SCOPES.append(trace.hlo_scopes(text))
    return _SCOPES[0]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------
def forward_macs(sz):
    """Multiply-accumulates of one token's forward pass, by part."""
    d, v, length = sz["hidden_size"], sz["vocab_size"], sz["seq_len"]
    h, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n, q = sz["n_groups"], sz["ssm_state_size"], sz["chunk_size"]
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    lat, ff = sz["moe_latent_size"], sz["moe_intermediate_size"]
    tokens = sz["batch"] * length
    per = {
        "M": {"projections": d * (2 * h * p + 2 * g * n + h) + h * p * d,
              "ssd": q * g * n + q * h * p + 2 * h * p * n},
        "E": {"router": d * sz["router_experts"],
              "latent": 2 * d * lat,
              "shared": 2 * d * shared_columns(sz),
              "routed": sz["moe_buffer_rows"] * 2 * lat * ff / tokens},
        "*": {"projections": d * (hq + 2 * hk) * dh + hq * dh * d,
              "attention": 2 * hq * dh * (length + 1) / 2},
    }
    out = {"head": v * d}
    for kind in kinds(sz):
        for part, macs in per[kind].items():
            out[f"{kind}.{part}"] = out.get(f"{kind}.{part}", 0) + macs
    return out


def flops_per_item(sizes, mode):
    macs = sum(forward_macs(sizes).values())
    return 2 * 3 * macs if mode == "train" else 2 * macs


def items_per_step(sizes):
    return sizes["batch"] * sizes["seq_len"]


def ssd_cost(sz):
    """``(operations, bytes)`` one trained step needs of the chunked scan
    (scope ``mx_ssd_fwd``, forward and backward) over all ``M`` layers:
    2 per multiply-accumulate, three passes; the bytes are the scan's
    inputs (x, B, C in the compute dtype, dt in float32) and its float32
    output once forward, and twice more backward (read again with the
    output's gradient, the inputs' gradients written)."""
    h, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n, q = sz["n_groups"], sz["ssm_state_size"], sz["chunk_size"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = kinds(sz).count("M")
    macs = tokens * (q * g * n + q * h * p + 2 * h * p * n)
    moved = tokens * ((h * p + 2 * g * n) * 2 + h * 4 + h * p * 4)
    return layers * 2 * 3 * macs, layers * 3 * moved


def moe_gmm_cost(sz):
    """``(operations, bytes)`` one trained step needs of the grouped
    product over the whole static buffer (scopes ``mx_moe_gmm_*``) over
    all ``E`` layers: three passes over both products; the bytes are both
    weights, the buffer, the hidden rows and the result in the compute
    dtype, once forward and twice backward."""
    lat, ff = sz["moe_latent_size"], sz["moe_intermediate_size"]
    rows, e = sz["moe_buffer_rows"], len(held_experts(sz))
    layers = kinds(sz).count("E")
    macs = rows * 2 * lat * ff
    moved = 2 * (e * 2 * lat * ff + rows * (2 * lat + ff))
    return layers * 2 * 3 * macs, layers * 3 * moved
