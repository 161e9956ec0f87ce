"""One v5e chip's share of XingChen-AGI's Xing4.0-29B-A4B (``model_type:
xing4_0``): the leading dense layer and four of the 38 expert layers at
the published widths, every token's residual four streams mixed around
every sublayer by a manifold-constrained hyper-connection
(arXiv:2512.24880), 4 of the 32 heads of multi-head latent attention with
both latents whole (a 768-wide query latent, a 512-wide key/value latent)
under YaRN's rotary frequencies, 8 of the 64 routed experts with the
router, the shared expert and the dense MLP whole, and an eighth of the
vocabulary: what one of 8 chips that share each layer (tensor- and
expert-parallel) would hold. The cut, the deployment and every assumed
size are in ``xing4.0-29b-a4b.json``.

Two halves that share nothing but parameter names and layouts:

* the system under test (``build``): a gluon ``PatternLM`` (pattern ``LG``
  then ``LF`` a layer, ``residual_streams`` 4) driven by
  ``parallel.TrainStep`` with Adam, recomputation by layer and the net's
  own parameter buffers, the path ``moonlight-16b-a3b.py`` takes;
* the plain reference (between the marker lines; ``reference_train``):
  ``jax.numpy`` in float32 at ``Precision.HIGHEST``, the hyper-connection
  token by token (a ``vmap`` of the per-token form, its Sinkhorn a plain
  loop), the attention's whole score rows in blocks of queries, the held
  experts one at a time as a ``lax.scan`` with a dense mask and no
  buffer, Adam written out. It imports nothing of ``mxnet_tpu``.
  ``precision="fp8"`` is the control: both operands of every matrix
  product, the rotated parts, the attention's probabilities and the
  streams a sublayer writes rounded to the four significant bits of an
  8-bit float.

``make_weights`` also sets each expert layer's ``e_score_correction_bias``
by the auxiliary-loss-free balancing rule, run on the ring's batches
through the reference's forward for a fixed number of iterations, as
``moonlight-16b-a3b.py`` does; the vectors are kept by seed.
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
with open(os.path.join(_ROOT, "mxnet_tpu", "gluon", "model_zoo",
                       "pattern_lm.py")) as _f:
    if "residual_streams" not in _f.read():
        # a program from before the streams cannot run the cell: say so at
        # once, before any weight is made
        raise SystemExit("xing4.0-29b-a4b needs PatternLM's residual_streams "
                         "(hyper-connections) and nn.LatentAttention's "
                         "query latent: this program has neither")

# --- reference: begin ------------------------------------------------------
_HI = lax.Precision.HIGHEST
FROZEN = ("router_bias",)          # leaves the optimizer does not touch


def held_experts(sz):
    return list(sz.get("expert_ids", range(sz["n_routed_experts"])))


def is_dense(sz, i):
    return i < sz["first_k_dense_replace"]


def param_shapes(sz):
    d, v, n = sz["hidden_size"], sz["vocab_size"], sz["hc_mult"]
    h, dn, dr, dv = sz["num_attention_heads"], sz["qk_nope_head_dim"], \
        sz["qk_rope_head_dim"], sz["v_head_dim"]
    r, rq = sz["kv_lora_rank"], sz["q_lora_rank"]
    f, ff = sz["intermediate_size"], sz["moe_intermediate_size"]
    e_all, e = sz["router_experts"], len(held_experts(sz))
    shapes = {"embed_weight": (v, d)}
    for i in range(sz["num_hidden_layers"]):
        for sub in ("attn", "ffn"):
            # phi transposed, rows [pre (n) | post (n) | res (n n)]; one
            # alpha a map; b in phi's row order
            shapes[f"l{i}_{sub}_hc_weight"] = (n * (n + 2), n * d)
            shapes[f"l{i}_{sub}_hc_alpha"] = (3,)
            shapes[f"l{i}_{sub}_hc_bias"] = (n * (n + 2),)
        shapes[f"l{i}_attn_norm_weight"] = (d,)
        shapes[f"l{i}_q_down_weight"] = (rq, d)
        shapes[f"l{i}_q_norm_weight"] = (rq,)
        shapes[f"l{i}_q_weight"] = (h * (dn + dr), rq)
        shapes[f"l{i}_kv_down_weight"] = (r + dr, d)
        shapes[f"l{i}_kv_norm_weight"] = (r,)
        shapes[f"l{i}_kv_up_weight"] = (h * (dn + dv), r)
        shapes[f"l{i}_o_weight"] = (d, h * dv)
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
            shapes[f"l{i}_shared_gate_up_weight"] = (
                2 * sz["n_shared_experts"] * ff, d)
            shapes[f"l{i}_shared_down_weight"] = (
                d, sz["n_shared_experts"] * ff)
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
    what is held at a time is one block's, which is why the reference
    fits the chip beside its 10.5 GB of state. Changes what is kept, not
    what is computed."""
    n = xs[0].shape[0]
    rows = max(r for r in range(1, min(limit, n) + 1) if n % r == 0)
    out = lax.map(lambda block: jax.checkpoint(fn)(*block),
                  tuple(x.reshape((n // rows, rows) + x.shape[1:])
                        for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


# -- the hyper-connection, a token at a time --------------------------------
def token_maps(sz, phi, alpha, b, x, precision="float32"):
    """``(H_pre (n,), H_post (n,), H_res (n, n))`` of ONE token's streams
    ``x`` (n, C), steps 1 to 3 of the configuration's description."""
    n, eps = sz["hc_mult"], sz["hc_eps"]
    v = x.reshape(-1)
    v = v / jnp.sqrt(jnp.mean(jnp.square(v)) + eps)
    t = jnp.dot(held(phi, precision), held(v, precision), precision=_HI)
    h_pre = jax.nn.sigmoid(alpha[0] * t[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * t[n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * t[2 * n:] + b[2 * n:],
                         sz["mhc_h_res_clamp_min"],
                         sz["mhc_h_res_clamp_max"])).reshape(n, n)

    def normalise(m, _):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)     # columns
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)     # rows
        return m, None

    # a plain loop whose body the compiled program holds once
    m, _ = lax.scan(normalise, m, None, length=sz["hc_sinkhorn_iters"])
    return h_pre, h_post, m


def stream_maps(sz, p, name, xs, precision="float32"):
    """``token_maps`` over a sequence's tokens ``xs`` (L, n, C)."""
    phi, alpha, b = (p[f"{name}_hc_{leaf}"]
                     for leaf in ("weight", "alpha", "bias"))
    one = jax.vmap(lambda x: token_maps(sz, phi, alpha, b, x, precision))
    return _in_blocks(one, sz["reference_row_block"], xs)


def hyper_connection(sz, p, name, xs, f, precision="float32"):
    """Step 4 around the sublayer ``f`` (``u (L, C) -> (y (L, C), aux)``)
    for one sequence's streams ``xs`` (L, n, C): ``(streams, aux)``."""
    h_pre, h_post, h_res = stream_maps(sz, p, name, xs, precision)
    u = jnp.einsum("tj,tjc->tc", h_pre, xs, precision=_HI)
    y, aux = f(u)
    out = jnp.einsum("tij,tjc->tic", h_res, xs, precision=_HI) \
        + h_post[:, :, None] * y[:, None, :]
    return held(out, precision), aux


# -- latent attention -------------------------------------------------------
def yarn_frequencies(sz):
    """The rotary frequencies of the ``qk_rope_head_dim``-wide slices under
    the configuration's ``rope_scaling`` (YaRN, arXiv:2309.00071): pair
    ``i`` turns at ``theta^(-2i/d)``, divided by ``factor`` where it turns
    fewer than ``beta_slow`` times over the original length, kept where it
    turns more than ``beta_fast`` times, and mixed linearly between."""
    d, theta, group = sz["qk_rope_head_dim"], sz["rope_theta"], \
        sz["rope_scaling"]
    i = np.arange(d // 2)
    f = theta ** (-2.0 * i / d)

    def pair(beta):
        return d * math.log(group["original_max_position_embeddings"]
                            / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(pair(group["beta_fast"])), 0)
    high = min(math.ceil(pair(group["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1 - ramp) + f / group["factor"] * ramp


def softmax_scale(sz):
    """``(nope + rope)^-1/2`` times the square of YaRN's ``0.1
    mscale_all_dim ln(factor) + 1``."""
    group = sz["rope_scaling"]
    m = 0.1 * group["mscale_all_dim"] * math.log(group["factor"]) + 1.0
    return (sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]) ** -0.5 * m * m


def rotate(x, inv):
    """Rotary position encoding of ``x`` (L, H, D) over its whole width,
    ``rotate_half`` convention: the angle of position ``t`` and pair ``i``
    is ``t * inv[i]``; pair ``i`` is elements ``i`` and ``i + D/2``; cos
    and sin are not scaled."""
    length, _, d = x.shape
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # (L, 1, D)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def latent_attention(sz, p, i, u, precision):
    """``Attn(u)`` for one sequence ``u`` (L, hidden), the heads held. The
    matrices' rows are grouped by part: ``Wqb`` ``[q_nope of every head |
    q_pe of every head]``, ``Wkvb`` ``[k_nope of every head | v of every
    head]``."""
    h, dn, dr, dv = sz["num_attention_heads"], sz["qk_nope_head_dim"], \
        sz["qk_rope_head_dim"], sz["v_head_dim"]
    r, eps = sz["kv_lora_rank"], sz["rms_norm_eps"]
    length, block = u.shape[0], sz["reference_attention_block"]
    inv, scale = yarn_frequencies(sz), softmax_scale(sz)
    c_q = _rms(held(_matmul(u, p[f"l{i}_q_down_weight"], precision),
                    precision), p[f"l{i}_q_norm_weight"], eps)
    q = held(_matmul(c_q, p[f"l{i}_q_weight"], precision), precision)
    ckv = held(_matmul(u, p[f"l{i}_kv_down_weight"], precision), precision)
    c = _rms(ckv[:, :r], p[f"l{i}_kv_norm_weight"], eps)
    kv = held(_matmul(c, p[f"l{i}_kv_up_weight"], precision), precision)
    q_nope = q[:, :h * dn].reshape(length, h, dn)
    q_pe = held(rotate(q[:, h * dn:].reshape(length, h, dr), inv), precision)
    k_pe = held(rotate(ckv[:, r:].reshape(length, 1, dr), inv),
                precision)[:, 0]                                 # (L, dr)
    k_nope = kv[:, :h * dn].reshape(length, h, dn)
    v = kv[:, h * dn:].reshape(length, h, dv)

    def rows(qn, qp, first):
        # a block of queries against every key, the later ones masked
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=_HI)
             + jnp.einsum("qhd,kd->hqk", qp, k_pe, precision=_HI)) * scale
        mask = (first + jnp.arange(qn.shape[0]))[:, None] \
            >= jnp.arange(length)[None, :]
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", held(pr, precision), v,
                          precision=_HI)

    out = _in_blocks(lambda qn, qp, t: rows(qn, qp, t[0]), block, q_nope,
                     q_pe, jnp.arange(length)).reshape(length, h * dv)
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
    over every expert of the model (``noaux_tc`` with one group)."""
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
    """``(the held experts' part plus the shared expert, every expert's
    load (E_all,))``: the load is the number of ``u``'s tokens whose
    choice holds the expert."""
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
    shared = gated_mlp(sz, u, p[f"l{i}_shared_gate_up_weight"],
                       p[f"l{i}_shared_down_weight"], precision)
    return routed + shared, jnp.sum(chosen, axis=0, dtype=jnp.float32)


def attention_sublayer(sz, p, i, xs, precision="float32"):
    """The streams after layer ``i``'s attention sublayer."""
    def f(u):
        u = _rms(u, p[f"l{i}_attn_norm_weight"], sz["rms_norm_eps"])
        return latent_attention(sz, p, i, u, precision), None

    return hyper_connection(sz, p, f"l{i}_attn", xs, f, precision)[0]


def ffn_input(sz, p, i, xs, precision="float32"):
    """What layer ``i``'s feed-forward sublayer reads of the streams
    ``xs``, normed: the router's input."""
    h_pre, _, _ = stream_maps(sz, p, f"l{i}_ffn", xs, precision)
    u = jnp.einsum("tj,tjc->tc", h_pre, xs, precision=_HI)
    return _rms(u, p[f"l{i}_ffn_norm_weight"], sz["rms_norm_eps"])


def layer(sz, p, i, xs, precision="float32"):
    """``(streams, load)`` after both sublayers of layer ``i`` for one
    sequence's streams ``xs`` (L, n, C); ``load`` is an expert layer's
    (``moe_layer``), else nothing."""
    xs = attention_sublayer(sz, p, i, xs, precision)

    def f(u):
        u = _rms(u, p[f"l{i}_ffn_norm_weight"], sz["rms_norm_eps"])
        if is_dense(sz, i):
            return gated_mlp(sz, u, p[f"l{i}_gate_up_weight"],
                             p[f"l{i}_down_weight"], precision), None
        return moe_layer(sz, p, i, u, precision)

    return hyper_connection(sz, p, f"l{i}_ffn", xs, f, precision)


def layer_params(p, i):
    return {k: v for k, v in p.items() if k.startswith(f"l{i}_")}


def spread(sz, x):
    """A token's vector copied into each of its streams: (..., C) ->
    (..., n, C)."""
    return jnp.repeat(x[..., None, :], sz["hc_mult"], axis=-2)


def reference_loss(sz, p, tokens, targets, precision="float32"):
    """``(loss, loads)``: the mean cross entropy of the next token over
    ``tokens`` (B, L) against ``targets`` (B * L,), and each expert
    layer's loads over the whole batch under the name of its correction
    bias. Each layer's insides are recomputed in the backward pass."""
    xs = spread(sz, jnp.take(p["embed_weight"], tokens, axis=0))
    loads = {}
    for i in range(sz["num_hidden_layers"]):
        one = jax.checkpoint(
            lambda q, x, i=i: layer(sz, q, i, x, precision))
        xs, load = jax.vmap(one, in_axes=(None, 0))(layer_params(p, i), xs)
        if load is not None:
            loads[f"l{i}_router_bias"] = load.sum(0)
    x = _rms(jnp.sum(xs, axis=-2), p["final_norm_weight"],
             sz["rms_norm_eps"])

    def cross_entropy(rows, labels):
        logp = jax.nn.log_softmax(_matmul(rows, p["head_weight"], precision),
                                  axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]

    return jnp.mean(_in_blocks(cross_entropy, sz["reference_row_block"],
                               x.reshape(-1, x.shape[-1]), targets)), loads


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
OUT_PROJECTIONS = ("o_weight", "down_weight", "w2")
_BIAS = {}        # (sizes, seed) -> {leaf: numpy vector}: no weight is kept
_BIAS_SPANS = []  # (start, seconds) of each calibration, for build() to report


def _init_leaf(sz, name, shape, key):
    if name.endswith("norm_weight"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("router_bias"):
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("hc_alpha"):
        return jnp.full(shape, sz["hc_alpha_init"], jnp.float32)
    if name.endswith("hc_bias"):
        # of order 1, so that no map starts near the identity
        return jax.random.normal(key, shape, jnp.float32)
    std = sz["initializer_range"]
    if name.endswith(OUT_PROJECTIONS):
        # a sublayer's last product, scaled down by the depth of the stack
        # it adds to (the .json's `assumed` says why)
        std /= math.sqrt(2 * sz["rescale_layers"])
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def _sublayers(sizes_json, dense):
    """The reference's attention sublayer, what its feed-forward sublayer
    then reads, and its whole layer of one kind, over a batch of
    sequences' streams, the parameters named as layer 0's."""
    sz = dict(json.loads(sizes_json), first_k_dense_replace=int(dense))
    return (jax.jit(jax.vmap(lambda q, x: attention_sublayer(sz, q, 0, x),
                             in_axes=(None, 0))),
            jax.jit(jax.vmap(lambda q, x: ffn_input(sz, q, 0, x),
                             in_axes=(None, 0))),
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
    """Each expert layer's correction bias, layer by layer through the
    reference's forward on ``batches``; ends the run if a layer misses the
    criterion. Returns ``{leaf: numpy vector}``."""
    sizes_json = json.dumps(sz, sort_keys=True)
    tokens = jnp.stack([jnp.asarray(x) for x, _ in batches])  # (R, B, L)
    ring, bsz, length = tokens.shape
    xs = spread(sz, jnp.take(weights["embed_weight"],
                             tokens.reshape(ring * bsz, length), axis=0))
    held_ids = np.asarray(held_experts(sz))
    cap = sz["moe_buffer_rows"]         # one pool, shared by the held
    out = {}
    for i in range(sz["num_hidden_layers"]):
        lp = {"l0_" + k.split("_", 1)[1]: v
              for k, v in layer_params(weights, i).items()}
        attention, reads, whole = _sublayers(sizes_json, is_dense(sz, i))
        if not is_dense(sz, i):
            bias, per_batch = _balance(sizes_json, bsz * length)(
                reads(lp, attention(lp, xs)), lp["l0_router_weight"])
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
        xs = whole(lp, xs)
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
    rng = np.random.default_rng([int(seed), 43])
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
    """``PatternLM``'s pattern: a layer is two units, attention then its
    feed-forward network."""
    return "".join("LG" if is_dense(sz, i) else "LF"
                   for i in range(sz["num_hidden_layers"]))


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import PatternLM
    sz = sizes
    return PatternLM(
        pattern(sz), sz["vocab_size"], sz["hidden_size"],
        latent_attention=dict(num_heads=sz["num_attention_heads"],
                              nope_dim=sz["qk_nope_head_dim"],
                              rope_dim=sz["qk_rope_head_dim"],
                              v_dim=sz["v_head_dim"],
                              latent_dim=sz["kv_lora_rank"],
                              q_latent_dim=sz["q_lora_rank"],
                              rope_theta=sz["rope_theta"],
                              rope_scaling=sz["rope_scaling"],
                              block=sz["attention_block"]),
        mlp=dict(units=sz["intermediate_size"]),
        experts=dict(num_experts=sz["router_experts"],
                     expert_ids=held_experts(sz),
                     top_k=sz["num_experts_per_tok"],
                     expert_units=sz["moe_intermediate_size"],
                     shared_units=sz["n_shared_experts"]
                     * sz["moe_intermediate_size"],
                     buffer_rows=sz["moe_buffer_rows"],
                     scaling=sz["routed_scaling_factor"],
                     norm_topk=sz["norm_topk_prob"],
                     bias_update_rate=sz["router_bias_update_rate"]),
        epsilon=sz["rms_norm_eps"], residual_streams=sz["hc_mult"],
        hyper_connections=dict(
            iters=sz["hc_sinkhorn_iters"], eps=sz["hc_eps"],
            clamp=(sz["mhc_h_res_clamp_min"], sz["mhc_h_res_clamp_max"])))


def _leaf_of(param_name):
    """gluon's ``patternlm0_l3_gatedmoe0_w1`` -> ``l1_w1``,
    ``..._l2_rmsnorm0_gamma`` -> ``l1_attn_norm_weight``,
    ``..._l3_hc_weight`` -> ``l1_ffn_hc_weight``: the pattern's units ``2
    l`` and ``2 l + 1`` are the reference's layer ``l``, attention then
    feed-forward; nothing for an expert layer's counters and for what a
    hyper-connection's iterations left."""
    rest = param_name.split("_", 1)[1]
    if rest.startswith("embedding"):
        return "embed_weight"
    if rest.startswith("dense"):
        return "head_weight"
    if rest.startswith("rmsnorm"):
        return "final_norm_weight"
    unit, block, leaf = rest.split("_", 2)
    layer_id, kind = divmod(int(unit[1:]), 2)
    sub = ("attn", "ffn")[kind]
    if block.startswith("rmsnorm"):
        return f"l{layer_id}_{sub}_norm_weight"
    if block == "hc":
        return None if leaf == "dev" else f"l{layer_id}_{sub}_hc_{leaf}"
    return None if leaf == "counters" else f"l{layer_id}_{leaf}"


_OVERFLOW = 1     # where nn.MOE_COUNTERS has "overflow_pairs"


@jax.jit
def _guard(loss, limit, counters, left):
    """``loss``, or infinity where a pair lay beyond an expert layer's
    buffer (the reference drops no token) or where a sublayer's ``H_res``
    missed a row or column sum of 1 by more than ``limit`` (the
    reference's twenty iterations leave a millionth; a program that ran
    fewer computed another model): either fails the run."""
    over = sum(c[_OVERFLOW] for c in counters)
    worst = functools.reduce(jnp.maximum, [d[0] for d in left])
    return jnp.where((over > 0) | (worst > limit), jnp.inf, loss)


class _StepSystem:
    """``TrainStep`` with its net: what the step driver calls and what
    ``read_params`` reads. Its loss is infinite once any expert layer has
    counted a pair beyond its buffer, or any hyper-connection's ``H_res``
    is further from doubly stochastic than ``hc_res_sum_dev_max``."""

    def __init__(self, net, step, limit):
        from mxnet_tpu.gluon.nn import MOE_COUNTERS
        assert MOE_COUNTERS[_OVERFLOW] == "overflow_pairs"
        self.net, self.step, self.limit = net, step, float(limit)
        params = net.collect_params().items()
        self._counters = [p for name, p in params
                          if name.endswith("_counters")]
        self._left = [p for name, p in params if name.endswith("_hc_dev")]

    def __call__(self, x, y):
        from mxnet_tpu.ndarray.ndarray import NDArray
        loss = self.step(x, y)
        return NDArray(_guard(
            loss._data, self.limit,
            [p.data()._data for p in self._counters],
            [p.data()._data for p in self._left]))


def build(cfg, sizes, role, weights):
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep
    from mxnet_tpu.telemetry import trace
    if role != "step":
        raise ValueError(f"xing4.0-29b-a4b has no role {role!r}")
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
    system = _StepSystem(net, step, sizes["hc_res_sum_dev_max"])
    _LIVE[:] = [system]
    mx.telemetry.remove("moe::")     # an earlier system's gauges
    mx.telemetry.remove("mhc::res_sum_dev::")
    return system


def read_params(system):
    named = ((_leaf_of(name), p)
             for name, p in system.net.collect_params().items())
    return {leaf: p.data().asnumpy().astype(np.float32, copy=False)
            for leaf, p in named if leaf is not None}


def release_system():
    """Publish the live system's counters (``moe::*`` and
    ``mhc::res_sum_dev::*`` gauges) and free its device buffers, the
    parameters the net and the step share and the optimizer's state: after
    the window nothing calls it again, and the reference needs the
    memory."""
    from mxnet_tpu.gluon.nn import publish_mhc_counters, publish_moe_counters
    while _LIVE:
        system = _LIVE.pop()
        publish_moe_counters(system.net)
        left = publish_mhc_counters(system.net)
        print("hyper-connections: the largest |row or column sum of H_res "
              "- 1| at the last step, by sublayer, "
              + json.dumps({k.rsplit("::", 1)[1]: v
                            for k, v in sorted(left.items())}))
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
    """``(dense layers, expert layers)`` held."""
    dense = sum(is_dense(sz, i) for i in range(sz["num_hidden_layers"]))
    return dense, sz["num_hidden_layers"] - dense


def _mla_projection_macs(sz):
    """Both query products, both key/value products and the output's."""
    d, h, r, rq = sz["hidden_size"], sz["num_attention_heads"], \
        sz["kv_lora_rank"], sz["q_lora_rank"]
    dn, dr, dv = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    return d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) \
        + h * dv * d


def forward_macs(sz):
    """Multiply-accumulates of one token's forward pass, by part."""
    d, length, n = sz["hidden_size"], sz["seq_len"], sz["hc_mult"]
    h, dn, dr, dv = sz["num_attention_heads"], sz["qk_nope_head_dim"], \
        sz["qk_rope_head_dim"], sz["v_head_dim"]
    ff = sz["moe_intermediate_size"]
    dense, expert = _layers(sz)
    tokens = sz["batch"] * length
    return {
        "mhc.maps": 2 * (dense + expert) * n * (n + 2) * n * d,
        "mhc.mixes": 2 * (dense + expert) * (n + n * n + n) * d,
        "mla.projections": (dense + expert) * _mla_projection_macs(sz),
        "mla.scores": (dense + expert) * h * (dn + dr + dv)
        * (length + 1) / 2,
        "dense.mlp": dense * 3 * d * sz["intermediate_size"],
        "experts.router": expert * d * sz["router_experts"],
        "experts.shared": expert * 3 * d * sz["n_shared_experts"] * ff,
        "experts.routed": expert * sz["moe_buffer_rows"] * 3 * d * ff
        / tokens,
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
    over all layers: the causal half of the scores, 192 wide, and of the
    weighted sums, 128 wide, 2 per multiply-accumulate, three passes (the
    backward kernels' second forming of the scores counts in the time,
    not in the need). The bytes are q, the latent-expanded k and v, the
    shared rotary key and the output in the compute dtype and the float32
    log-sum-exp, once forward and twice more backward."""
    h, dn, dr, dv = sz["num_attention_heads"], sz["qk_nope_head_dim"], \
        sz["qk_rope_head_dim"], sz["v_head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = sum(_layers(sz))
    macs = tokens * h * (dn + dr + dv) * (sz["seq_len"] + 1) / 2
    moved = tokens * ((h * (dn + dr) + h * dn + dr + 2 * h * dv) * 2 + h * 4)
    return layers * 2 * 3 * macs, layers * 3 * moved


def mla_latent_cost(sz):
    """``(operations, bytes)`` one trained step needs of the latent
    path's five products (scopes ``mx_mla_q``, which holds the query's
    down- and up-product, ``mx_mla_kv_down``, ``mx_mla_kv_up``,
    ``mx_mla_out``) over all layers: three passes (the up-projection that
    a unit computes again counts in the time, not in the need); the bytes
    are the five weights and each product's input and output in the
    compute dtype, once forward and twice backward."""
    d, h, r, rq = sz["hidden_size"], sz["num_attention_heads"], \
        sz["kv_lora_rank"], sz["q_lora_rank"]
    dn, dr, dv = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = sum(_layers(sz))
    rows = (d + rq) + (rq + h * (dn + dr)) + (d + r + dr) \
        + (r + h * (dn + dv)) + (h * dv + d)
    moved = 2 * (_mla_projection_macs(sz) + tokens * rows)
    return layers * 2 * 3 * tokens * _mla_projection_macs(sz), \
        layers * 3 * moved


def moe_gmm_cost(sz):
    """``(operations, bytes)`` one trained step needs of the grouped
    product over the whole static buffer (scopes ``mx_moe_gmm_*``) over
    all expert layers: three passes over the three products; the bytes
    are the three weights, the buffer, both hidden rows and the result in
    the compute dtype, once forward and twice backward."""
    d, ff = sz["hidden_size"], sz["moe_intermediate_size"]
    rows, e = sz["moe_buffer_rows"], len(held_experts(sz))
    layers = _layers(sz)[1]
    macs = rows * 3 * d * ff
    moved = 2 * (e * 3 * d * ff + rows * (2 * d + 2 * ff))
    return layers * 2 * 3 * macs, layers * 3 * moved


def mhc_cost(sz):
    """``(operations, bytes)`` one trained step needs of the
    hyper-connections (scopes ``mx_mhc_maps``, ``mx_mhc_pre``,
    ``mx_mhc_post``) over all sublayers, whatever implements them. A
    token and sublayer forward: the streams' mean square (2 n C), the
    product with phi (2 n C n (n + 2)), the sigmoids and the Sinkhorn
    iterations (a division and an addition an entry, columns and rows,
    ``iters`` times), the mix read (2 n C) and the mix written (2 n n C +
    2 n C); three passes. The bytes forward, the streams in the compute
    dtype: the n C streams read once for the maps, once for the mix read
    and once for the mix written, and written once; ``u`` written and
    ``y`` read, C each; once forward and twice backward (the streams'
    gradient takes the streams' place). What a unit computes again counts
    in the time, not in the need."""
    d, n = sz["hidden_size"], sz["hc_mult"]
    tokens = sz["batch"] * sz["seq_len"]
    sublayers = 2 * sum(_layers(sz))
    ops = 2 * n * d * (1 + n * (n + 2)) + 2 * n * d + 2 * n * n * d \
        + 2 * n * d + sz["hc_sinkhorn_iters"] * 4 * n * n + 4 * n
    moved = 2 * (4 * n * d + 2 * d)
    return sublayers * 3 * tokens * ops, sublayers * 3 * tokens * moved
