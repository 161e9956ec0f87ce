"""One v5e chip's share of LiquidAI's LFM2-24B-A2B (``model_type:
lfm2_moe``): the leading dense layer and one whole period of four expert
layers at the published widths. Three layers in four mix tokens by a
gated short convolution (a three-tap causal depthwise convolution between
two linear gates), the fourth by grouped-query attention over 64-wide
heads under head norms; 4 of the 32 query heads on 1 of the 8 key/value
heads, 8 of the 64 routed experts (4 a token by sigmoid scores under a
selection bias, no shared expert) with the router, the convolution
mixers and the dense MLP whole, and an eighth of the vocabulary: what one
of 8 chips that share each layer (tensor- and expert-parallel) would
hold. The cut, the deployment and every assumed size are in
``lfm2-24b-a2b.json``.

Two halves that share nothing but parameter names and layouts:

* the system under test (``build``): a gluon ``PatternLM`` (pattern
  ``CG`` the dense layer, then ``*F`` or ``CF`` a layer) driven by
  ``parallel.TrainStep`` with Adam, recomputation by layer and the net's
  own parameter buffers, the path ``xing4.0-29b-a4b.py`` takes;
* the plain reference (between the marker lines; ``reference_train``):
  ``jax.numpy`` in float32 at ``Precision.HIGHEST``, the convolution as
  its taps' shifted products one after another, the attention's whole
  score rows in blocks of queries, the held experts one at a time as a
  ``lax.scan`` with a dense mask and no buffer, Adam written out. It
  imports nothing of ``mxnet_tpu``. ``precision="fp8"`` is the control:
  both operands of every matrix product, the gated and the convolved
  channels, the rotated heads and the attention's probabilities rounded
  to the four significant bits of an 8-bit float.

``make_weights`` also sets each expert layer's ``router_bias`` (the
family's ``expert_bias``) by the auxiliary-loss-free balancing rule, run
on the ring's batches through the reference's forward for a fixed number
of iterations, as ``xing4.0-29b-a4b.py`` does; the vectors are kept by
seed.
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
with open(os.path.join(_ROOT, "mxnet_tpu", "gluon", "nn",
                       "seq_layers.py")) as _f:
    if "class GatedShortConv" not in _f.read():
        # a program from before the mixer cannot run the cell: say so at
        # once, before any weight is made
        raise SystemExit("lfm2-24b-a2b needs gluon.nn.GatedShortConv, "
                         "PatternLM's letter C and a GatedMoE without "
                         "shared experts: this program has none of them")

# --- reference: begin ------------------------------------------------------
_HI = lax.Precision.HIGHEST
FROZEN = ("router_bias",)          # leaves the optimizer does not touch


def held_experts(sz):
    return list(sz.get("expert_ids", range(sz["num_experts"])))


def is_conv(sz, i):
    return sz["layer_types"][i] == "conv"


def is_dense(sz, i):
    return i < sz["num_dense_layers"]


def param_shapes(sz):
    d, v = sz["hidden_size"], sz["vocab_size"]
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    f, ff = sz["intermediate_size"], sz["moe_intermediate_size"]
    e_all, e = sz["router_experts"], len(held_experts(sz))
    shapes = {"embed_weight": (v, d)}
    for i in range(sz["num_hidden_layers"]):
        shapes[f"l{i}_op_norm_weight"] = (d,)
        if is_conv(sz, i):
            shapes[f"l{i}_in_weight"] = (3 * d, d)      # rows [B | C | z]
            shapes[f"l{i}_conv_weight"] = (d, sz["conv_L_cache"])
            shapes[f"l{i}_out_weight"] = (d, d)
        else:
            shapes[f"l{i}_qkv_weight"] = ((ha + 2 * hkv) * dh, d)
            shapes[f"l{i}_o_weight"] = (d, ha * dh)
            shapes[f"l{i}_q_norm_weight"] = (dh,)
            shapes[f"l{i}_k_norm_weight"] = (dh,)
        shapes[f"l{i}_ffn_norm_weight"] = (d,)
        if is_dense(sz, i):
            shapes[f"l{i}_gate_up_weight"] = (2 * f, d)
            shapes[f"l{i}_down_weight"] = (d, f)
        else:
            shapes[f"l{i}_router_weight"] = (e_all, d)
            shapes[f"l{i}_router_bias"] = (e_all,)
            shapes[f"l{i}_w1"] = (e, d, ff)
            shapes[f"l{i}_w3"] = (e, d, ff)
            shapes[f"l{i}_w2"] = (e, ff, d)
    shapes["final_norm_weight"] = (d,)
    shapes["head_weight"] = (v, d)
    return shapes


def _matmul(x, w, precision):
    """``x @ w.T``."""
    return jnp.dot(held(x, precision), held(w, precision).T, precision=_HI)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _in_blocks(fn, limit, *xs):
    """``fn`` over equal blocks of at most ``limit`` rows of each of
    ``xs`` (the largest such block that divides their length), one block
    after another, each block's insides recomputed in the backward pass:
    what is held at a time is one block's. Changes what is kept, not what
    is computed."""
    n = xs[0].shape[0]
    rows = max(r for r in range(1, min(limit, n) + 1) if n % r == 0)
    out = lax.map(lambda block: jax.checkpoint(fn)(*block),
                  tuple(x.reshape((n // rows, rows) + x.shape[1:])
                        for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


# -- the gated short convolution ---------------------------------------------
def short_conv(sz, p, i, u, precision):
    """``W_out (C * conv(B * z))`` for one sequence ``u`` (L, hidden),
    ``[B | C | z] = W_in u``: tap ``j`` of ``conv_L_cache`` weighs the
    token ``conv_L_cache - 1 - j`` places back, zeros before the
    sequence, no bias, no activation."""
    d, taps = sz["hidden_size"], sz["conv_L_cache"]
    length = u.shape[0]
    bcz = held(_matmul(u, p[f"l{i}_in_weight"], precision), precision)
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    a = held(b * z, precision)
    w = p[f"l{i}_conv_weight"]
    conv = jnp.zeros_like(a)
    for j in range(taps):
        back = taps - 1 - j
        conv = conv + w[:, j] * jnp.concatenate(
            [jnp.zeros((back, d), a.dtype),
             a[:max(length - back, 0)]])[:length]
    return _matmul(c * held(conv, precision), p[f"l{i}_out_weight"],
                   precision)


# -- grouped-query attention under head norms --------------------------------
def rotate(x, theta):
    """Rotary position encoding of ``x`` (L, H, D) over its whole width,
    ``rotate_half`` convention: the angle of position ``t`` and pair ``i``
    is ``t * theta^(-2i/D)``; pair ``i`` is elements ``i`` and ``i +
    D/2``."""
    length, _, d = x.shape
    inv = jnp.asarray(1.0 / theta ** (np.arange(0, d, 2) / d), jnp.float32)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # (L, 1, D)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(sz, p, i, u, precision):
    """Causal softmax attention of one sequence ``u`` (L, hidden) over
    the heads held. The projection's rows are grouped by part: ``[q of
    every head | k | v]``; every query head and every key head is normed
    over its own width BEFORE the rotation."""
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    theta, eps = sz["rope_parameters"]["rope_theta"], sz["norm_eps"]
    length, block = u.shape[0], sz["reference_attention_block"]
    qkv = held(_matmul(u, p[f"l{i}_qkv_weight"], precision), precision)
    q = qkv[:, :ha * dh].reshape(length, ha, dh)
    k = qkv[:, ha * dh:(ha + hkv) * dh].reshape(length, hkv, dh)
    v = qkv[:, (ha + hkv) * dh:].reshape(length, hkv, dh)
    q = held(rotate(_rms(q, p[f"l{i}_q_norm_weight"], eps), theta),
             precision)
    k = held(rotate(_rms(k, p[f"l{i}_k_norm_weight"], eps), theta),
             precision)
    k, v = (jnp.repeat(t, ha // hkv, axis=1) for t in (k, v))

    def rows(qb, first):
        # a block of queries against every key, the later ones masked
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=_HI) * dh ** -0.5
        mask = (first + jnp.arange(qb.shape[0]))[:, None] \
            >= jnp.arange(length)[None, :]
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", held(pr, precision), v,
                          precision=_HI)

    out = _in_blocks(lambda qb, t: rows(qb, t[0]), block, q,
                     jnp.arange(length)).reshape(length, ha * dh)
    return _matmul(out, p[f"l{i}_o_weight"], precision)


# -- feed-forward sublayers -------------------------------------------------
def gated_mlp(sz, u, gate_up, down, precision):
    f = down.shape[1]

    def rows(x):
        gu = held(_matmul(x, gate_up, precision), precision)
        return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)

    return _in_blocks(rows, sz["reference_row_block"], u)


def router(sz, p, i, u, precision):
    """``(weights (T, E_all), zero where not chosen; chosen (T, E_all))``
    over every expert of the model: sigmoid scores, the
    ``num_experts_per_tok`` largest of score + bias chosen, the chosen
    scores over their sum (+ ``norm_topk_eps``) times the scaling
    factor."""
    s = jax.nn.sigmoid(_matmul(u, p[f"l{i}_router_weight"], precision))
    biased = s + p[f"l{i}_router_bias"] if sz["use_expert_bias"] else s
    _, idx = lax.top_k(biased, sz["num_experts_per_tok"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    if sz["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + sz["norm_topk_eps"])
    return w * sz["routed_scaling_factor"], chosen


def moe_layer(sz, p, i, u, precision):
    """``(the held experts' part, every expert's load (E_all,))``: the
    load is the number of ``u``'s tokens whose choice holds the expert.
    The family has no shared expert."""
    w, chosen = router(sz, p, i, u, precision)

    @jax.checkpoint
    def expert(routed, held_one):
        w1, w3, w2, gate = held_one
        hid = jax.nn.silu(held(_matmul(u, w1.T, precision), precision)) \
            * held(_matmul(u, w3.T, precision), precision)
        return routed + gate[:, None] * _matmul(hid, w2.T, precision), None

    # one expert after another over all tokens, masked by its gate
    routed, _ = lax.scan(
        expert, jnp.zeros_like(u),
        (p[f"l{i}_w1"], p[f"l{i}_w3"], p[f"l{i}_w2"],
         w[:, jnp.asarray(held_experts(sz))].T))
    return routed, jnp.sum(chosen, axis=0, dtype=jnp.float32)


def op_sublayer(sz, p, i, x, precision="float32"):
    """``x + Op(norm(x))`` for one sequence ``x`` (L, hidden)."""
    u = _rms(x, p[f"l{i}_op_norm_weight"], sz["norm_eps"])
    op = short_conv if is_conv(sz, i) else attention
    return x + op(sz, p, i, u, precision)


def ffn_input(sz, p, i, h):
    """What layer ``i``'s feed-forward sublayer reads: the router's
    input."""
    return _rms(h, p[f"l{i}_ffn_norm_weight"], sz["norm_eps"])


def layer(sz, p, i, x, precision="float32"):
    """``(hidden state, load)`` after both sublayers of layer ``i`` for
    one sequence ``x`` (L, hidden); ``load`` is an expert layer's
    (``moe_layer``), else nothing."""
    h = op_sublayer(sz, p, i, x, precision)
    u = ffn_input(sz, p, i, h)
    if is_dense(sz, i):
        return h + gated_mlp(sz, u, p[f"l{i}_gate_up_weight"],
                             p[f"l{i}_down_weight"], precision), None
    out, load = moe_layer(sz, p, i, u, precision)
    return h + out, load


def layer_params(p, i):
    return {k: v for k, v in p.items() if k.startswith(f"l{i}_")}


def reference_loss(sz, p, tokens, targets, precision="float32"):
    """``(loss, loads)``: the mean cross entropy of the next token over
    ``tokens`` (B, L) against ``targets`` (B * L,), and each expert
    layer's loads over the whole batch under the name of its bias. Each
    layer's insides are recomputed in the backward pass."""
    x = jnp.take(p["embed_weight"], tokens, axis=0)
    loads = {}
    for i in range(sz["num_hidden_layers"]):
        one = jax.checkpoint(
            lambda q, x, i=i: layer(sz, q, i, x, precision))
        x, load = jax.vmap(one, in_axes=(None, 0))(layer_params(p, i), x)
        if load is not None:
            loads[f"l{i}_router_bias"] = load.sum(0)
    x = _rms(x, p["final_norm_weight"], sz["norm_eps"])

    def cross_entropy(rows, labels):
        logp = jax.nn.log_softmax(_matmul(rows, p["head_weight"], precision),
                                  axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]

    return jnp.mean(_in_blocks(cross_entropy, sz["reference_row_block"],
                               x.reshape(-1, x.shape[-1]), targets)), loads


def balance_step(sz, p, loads):
    """The routers' biases after one step of auxiliary-loss-free
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
OUT_PROJECTIONS = ("o_weight", "out_weight", "down_weight", "w2")
_BIAS = {}        # (sizes, seed) -> {leaf: numpy vector}: no weight is kept
_BIAS_SPANS = []  # (start, seconds) of each calibration, for build() to report


def _init_leaf(sz, name, shape, key):
    if name.endswith(("q_norm_weight", "k_norm_weight")):
        # not all ones: a uniform scale passes through the rotation, and a
        # program with the head norms after it would compute the same
        return 1.0 + jax.random.uniform(
            key, shape, jnp.float32, -sz["head_norm_range"],
            sz["head_norm_range"])
    if name.endswith("norm_weight"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("router_bias"):
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("conv_weight"):
        return jax.random.uniform(key, shape, jnp.float32,
                                  -sz["conv_range"], sz["conv_range"])
    std = sz["initializer_range"]
    if name.endswith(OUT_PROJECTIONS):
        # a sublayer's last product, scaled down by the depth of the stack
        # it adds to (the .json's `assumed` says why)
        std /= math.sqrt(2 * sz["rescale_layers"])
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def _sublayers(sizes_json, conv, dense):
    """The reference's token-mixing sublayer, what its feed-forward
    sublayer then reads, and its whole layer of one kind, over a batch of
    sequences, the parameters named as layer 0's."""
    sz = dict(json.loads(sizes_json), num_dense_layers=int(dense),
              layer_types=["conv" if conv else "full_attention"])
    return (jax.jit(jax.vmap(lambda q, x: ffn_input(
                sz, q, 0, op_sublayer(sz, q, 0, x)), in_axes=(None, 0))),
            jax.jit(jax.vmap(lambda q, x: layer(sz, q, 0, x)[0],
                             in_axes=(None, 0))))


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
    def run(u, router_w):
        s = jax.nn.sigmoid(jnp.dot(u.reshape(-1, u.shape[-1]), router_w.T,
                                   precision=_HI))

        def body(j, bias):
            load = jnp.sum(loads(s, bias), axis=0)
            step = u0 * (u1 / u0) ** (j / max(n - 1, 1))
            return bias + step * jnp.sign(jnp.mean(load) - load)

        bias = lax.fori_loop(0, n, body,
                             jnp.zeros(router_w.shape[0], jnp.float32))
        return bias, loads(s, bias)

    return run


def calibrate_router_bias(sz, weights, batches):
    """Each expert layer's bias, layer by layer through the reference's
    forward on ``batches``; ends the run if a layer misses the criterion.
    Returns ``{leaf: numpy vector}``."""
    sizes_json = json.dumps(sz, sort_keys=True)
    tokens = jnp.stack([jnp.asarray(x) for x, _ in batches])  # (R, B, L)
    ring, bsz, length = tokens.shape
    x = jnp.take(weights["embed_weight"],
                 tokens.reshape(ring * bsz, length), axis=0)
    held_ids = np.asarray(held_experts(sz))
    cap = sz["moe_buffer_rows"]         # one pool, shared by the held
    out = {}
    for i in range(sz["num_hidden_layers"]):
        lp = {"l0_" + k.split("_", 1)[1]: v
              for k, v in layer_params(weights, i).items()}
        reads, whole = _sublayers(sizes_json, is_conv(sz, i),
                                  is_dense(sz, i))
        if not is_dense(sz, i):
            bias, per_batch = _balance(sizes_json, bsz * length)(
                reads(lp, x), lp["l0_router_weight"])
            per_batch = np.asarray(per_batch)
            pooled = per_batch.sum(0)
            skew = float(pooled.max() / pooled.mean())
            worst = int(per_batch[:, held_ids].sum(1).max())
            print(f"router bias, layer {i}: max/mean load over the ring "
                  f"{skew:.3f}, largest held expert "
                  f"{int(per_batch[:, held_ids].max())} pairs a batch, the "
                  f"held experts together at most {worst} of {cap} rows")
            if skew > sz["router_bias_max_over_mean"] or worst > cap:
                raise SystemExit(
                    f"layer {i}: the router's bias misses its criterion "
                    f"after {sz['router_bias_iterations']} iterations "
                    f"(max/mean {skew:.3f} over "
                    f"{sz['router_bias_max_over_mean']}, or {worst} pairs "
                    f"over {cap} rows): the run ends, it does not iterate on")
            out[f"l{i}_router_bias"] = np.asarray(bias)
            lp["l0_router_bias"] = bias
        x = whole(lp, x)
    return out


def make_weights(sizes, seed):
    """Every parameter from the seed in one jitted call on the device
    (``_init_leaf``), then the routers' bias, calibrated once a seed on
    the ring's batches."""
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
    rng = np.random.default_rng([int(seed), 47])
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
    for SGD, over the leaves the optimizer trains (the routers' bias is
    state the forward writes). The system's device buffers are released
    first: the reference's three steps need the chip."""
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


def pattern(sz):
    """``PatternLM``'s pattern: a layer is two units, its token mixer
    then its feed-forward network."""
    return "".join(("C" if is_conv(sz, i) else "*")
                   + ("G" if is_dense(sz, i) else "F")
                   for i in range(sz["num_hidden_layers"]))


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import PatternLM
    sz = sizes
    experts = dict(num_experts=sz["router_experts"],
                   expert_ids=held_experts(sz),
                   top_k=sz["num_experts_per_tok"],
                   expert_units=sz["moe_intermediate_size"], shared_units=0,
                   buffer_rows=sz["moe_buffer_rows"],
                   scaling=sz["routed_scaling_factor"],
                   norm_topk=sz["norm_topk_prob"],
                   norm_topk_eps=sz["norm_topk_eps"],
                   bias_update_rate=sz["router_bias_update_rate"])
    return PatternLM(
        pattern(sz), sz["vocab_size"], sz["hidden_size"],
        short_conv=dict(kernel=sz["conv_L_cache"]),
        attention=dict(num_heads=sz["num_attention_heads"],
                       num_kv_heads=sz["num_key_value_heads"],
                       head_dim=sz["head_dim"], block=sz["attention_block"],
                       rope_theta=sz["rope_parameters"]["rope_theta"],
                       qk_norm=True, epsilon=sz["norm_eps"]),
        mlp=dict(units=sz["intermediate_size"]), experts=experts,
        epsilon=sz["norm_eps"])


def _leaf_of(param_name):
    """gluon's ``patternlm0_l3_gatedmoe0_w1`` -> ``l1_w1``,
    ``..._l2_rmsnorm0_gamma`` -> ``l1_op_norm_weight``,
    ``..._l3_rmsnorm0_gamma`` -> ``l1_ffn_norm_weight``: the pattern's
    units ``2 l`` and ``2 l + 1`` are the reference's layer ``l``, token
    mixer then feed-forward; nothing for an expert layer's counters."""
    rest = param_name.split("_", 1)[1]
    if rest.startswith("embedding"):
        return "embed_weight"
    if rest.startswith("dense"):
        return "head_weight"
    if rest.startswith("rmsnorm"):
        return "final_norm_weight"
    unit, block, leaf = rest.split("_", 2)
    layer_id, kind = divmod(int(unit[1:]), 2)
    if block.startswith("rmsnorm"):
        return f"l{layer_id}_{('op', 'ffn')[kind]}_norm_weight"
    return None if leaf == "counters" else f"l{layer_id}_{leaf}"


_OVERFLOW = 1     # where nn.MOE_COUNTERS has "overflow_pairs"


@jax.jit
def _guard(loss, *counters):
    """``loss``, or infinity where a pair lay beyond an expert layer's
    pool: the reference drops no token, so that fails the run."""
    over = sum(c[_OVERFLOW] for c in counters)
    return jnp.where(over > 0, jnp.inf, loss)


class _StepSystem:
    """``TrainStep`` with its net: what the step driver calls and what
    ``read_params`` reads. Its loss is infinite once any expert layer has
    counted a pair beyond its pool."""

    def __init__(self, net, step):
        from mxnet_tpu.gluon.nn import MOE_COUNTERS
        assert MOE_COUNTERS[_OVERFLOW] == "overflow_pairs"
        self.net, self.step = net, step
        self._counters = [p for name, p in net.collect_params().items()
                          if name.endswith("_counters")]

    def __call__(self, x, y):
        from mxnet_tpu.ndarray.ndarray import NDArray
        loss = self.step(x, y)
        return NDArray(_guard(loss._data, *(p.data()._data
                                            for p in self._counters)))


def build(cfg, sizes, role, weights):
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep
    from mxnet_tpu.telemetry import trace
    if role != "step":
        raise ValueError(f"lfm2-24b-a2b has no role {role!r}")
    while _BIAS_SPANS:     # make_weights' calibration, on the program's record
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
    reference needs the memory."""
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


def scope_table():
    """The step program's own table (``mx.telemetry.trace.scope_table``:
    HLO instruction name -> ``mx_*`` scope path), for the readers that
    ask the configuration; none is built here."""
    from mxnet_tpu.telemetry import trace
    return trace.scope_table("jit_mx_train_step")


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------
def _layers(sz):
    """``(conv layers, attention layers, dense layers, expert layers)``
    held."""
    n = sz["num_hidden_layers"]
    conv = sum(is_conv(sz, i) for i in range(n))
    dense = sum(is_dense(sz, i) for i in range(n))
    return conv, n - conv, dense, n - dense


def forward_macs(sz):
    """Multiply-accumulates of one token's forward pass, by part."""
    d, length = sz["hidden_size"], sz["seq_len"]
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    conv, full, dense, expert = _layers(sz)
    tokens = sz["batch"] * length
    return {
        "sconv.projections": conv * 4 * d * d,
        "sconv.chain": conv * d * (sz["conv_L_cache"] + 2),
        "attn.projections": full * d * (2 * ha + 2 * hkv) * dh,
        "attn.scores": full * ha * 2 * dh * (length + 1) / 2,
        "dense.mlp": dense * 3 * d * sz["intermediate_size"],
        "experts.router": expert * d * sz["router_experts"],
        "experts.routed": expert * sz["moe_buffer_rows"] * 3 * d
        * sz["moe_intermediate_size"] / tokens,
        "head": sz["vocab_size"] * d,
    }


def flops_per_item(sizes, mode):
    """The need, not what a unit computes again."""
    macs = sum(forward_macs(sizes).values())
    return 2 * 3 * macs if mode == "train" else 2 * macs


def items_per_step(sizes):
    return sizes["batch"] * sizes["seq_len"]


def attn_cost(sz):
    """``(operations, bytes)`` one trained step needs of the attention
    between the projections (scope ``mx_attn_fwd``, forward and backward)
    over the attention layers: the causal half of the scores and of the
    weighted sums, both ``head_dim`` wide, 2 per multiply-accumulate,
    three passes (a second forming of the scores in a backward pass counts
    in the time, not in the need). The bytes are q, k, v and the output in
    the compute dtype and a float32 log-sum-exp a row, once forward and
    twice more backward: what a fused form moves; the blocks of
    probabilities the plain form writes count in the time."""
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = _layers(sz)[1]
    macs = tokens * ha * 2 * dh * (sz["seq_len"] + 1) / 2
    moved = tokens * ((2 * ha + 2 * hkv) * dh * 2 + ha * 4)
    return layers * 2 * 3 * macs, layers * 3 * moved


def sconv_cost(sz):
    """``(operations, bytes)`` one trained step needs of the gated short
    convolution between its two products (scopes ``mx_sconv_gate`` and
    ``mx_sconv_conv``, forward and backward) over the conv layers,
    whatever implements it. A token and channel at ``K`` taps, forward:
    ``B * z``, ``K`` products and ``K - 1`` sums, ``C * c``: ``2 K + 1``.
    Backward: ``dy * C`` and ``dy * c``, the taps transposed (``2 K -
    1``), ``da * z`` and ``da * B``, the taps' gradient (``2 K``), and
    ``a`` and ``c`` once more (``1 + 2 K - 1``), since no pass reads
    them: ``6 K + 3``. The bytes, in the compute dtype: forward ``B``,
    ``C``, ``z`` read once and ``C * c`` written once, 4 a channel;
    backward those three and the cotangent read once and the three
    cotangents written once, 7 a channel; the taps read in both passes
    and their gradient written. What a recomputation unit computes again
    (the forward, a second time) counts in the time, not in the need."""
    d, taps = sz["hidden_size"], sz["conv_L_cache"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = _layers(sz)[0]
    ops = tokens * d * (8 * taps + 4)
    moved = 2 * (tokens * d * 11 + 3 * d * taps)
    return layers * ops, layers * moved


def moe_gmm_cost(sz):
    """``(operations, bytes)`` one trained step needs of the grouped
    product over the whole static buffer (scopes ``mx_moe_gmm_*``) over
    all expert layers: three passes over the three products; the bytes
    are the three weights, the buffer, both hidden rows and the result in
    the compute dtype, once forward and twice backward."""
    d, ff = sz["hidden_size"], sz["moe_intermediate_size"]
    rows, e = sz["moe_buffer_rows"], len(held_experts(sz))
    layers = _layers(sz)[3]
    macs = rows * 3 * d * ff
    moved = 2 * (e * 3 * d * ff + rows * (2 * d + 2 * ff))
    return layers * 2 * 3 * macs, layers * 3 * moved
