"""One v5e chip's share of poolside's Laguna-S-2.1 (``model_type:
laguna``): the leading dense layer and one whole period of four expert
layers at the published widths. Three layers in four mix tokens by
causal attention under a window of 512 keys at 72 query heads, rotated
over the whole head at base 10,000; the fourth, and the dense layer, by
full causal attention at 48 query heads, rotated over half a head at
YaRN's frequencies with its attention factor on the rotated half. Every
layer's heads are 128 wide on 8 key/value heads and go through one
sigmoid gate a head and token before the output projection. Held: half
the heads (24 and 36 query heads on 4 key/value heads), 8 of the 256
routed experts (10 a token by a softmax router, scaled by 2.5) with the
router, the shared expert and the dense MLP whole, and an eighth of the
vocabulary: what one of 32 chips that share each layer would hold. The
cut, the deployment and every assumed size are in ``laguna-s-2.1.json``.

Two halves that share nothing but parameter names and layouts:

* the system under test (``build``): a gluon ``PatternLM`` (pattern
  ``*G`` the dense layer, then ``WF`` a sliding and ``*F`` a full expert
  layer) driven by ``parallel.TrainStep`` with Adam, recomputation by
  layer and the net's own parameter buffers, the path
  ``qwen3-next-80b-a3b.py`` takes;
* the plain reference (between the marker lines; ``reference_train``):
  ``jax.numpy`` in float32 at ``Precision.HIGHEST``, the rotation and
  YaRN's frequencies written out, the attention's whole score rows in
  blocks of queries under an explicit ``(t - j >= 0) & (t - j < W)``
  mask, the held experts one at a time as a ``lax.scan`` with a dense
  mask and no buffer, Adam written out. It imports nothing of
  ``mxnet_tpu``. ``precision="fp8"`` is the control: both operands of
  every matrix product, the rotated heads and the attention's
  probabilities rounded to the four significant bits of an 8-bit float.
"""
from __future__ import annotations

import functools
import json
import math
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from refutil import held, seed_key

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(_ROOT, "mxnet_tpu", "gluon", "model_zoo",
                       "pattern_lm.py")) as _f:
    if "window_attention" not in _f.read():
        # a program from before the window cannot run the cell: say so at
        # once, before any weight is made
        raise SystemExit("laguna-s-2.1 needs PatternLM's letter W and "
                         "GQAttention's window, head_gate and rope_scaling: "
                         "this program has none of them")

# --- reference: begin ------------------------------------------------------
_HI = lax.Precision.HIGHEST


def held_experts(sz):
    return list(sz.get("expert_ids", range(sz["num_experts"])))


def is_sliding(sz, i):
    return sz["layer_types"][i] == "sliding_attention"


def is_dense(sz, i):
    return sz["mlp_layer_types"][i] == "dense"


def heads_of(sz, i):
    """Query heads held of layer ``i``."""
    return sz["num_attention_heads_per_layer"][i]


def param_shapes(sz):
    d, v = sz["hidden_size"], sz["vocab_size"]
    hkv, dh = sz["num_key_value_heads"], sz["head_dim"]
    f, ff = sz["intermediate_size"], sz["moe_intermediate_size"]
    fs = sz["shared_expert_intermediate_size"]
    e_all, e = sz["router_experts"], len(held_experts(sz))
    shapes = {"embed_weight": (v, d)}
    for i in range(sz["num_hidden_layers"]):
        h = heads_of(sz, i)
        shapes[f"l{i}_attn_norm_weight"] = (d,)
        # rows [q of every head | k | v | one gate a head]
        shapes[f"l{i}_qkv_weight"] = ((h + 2 * hkv) * dh + h, d)
        shapes[f"l{i}_o_weight"] = (d, h * dh)
        shapes[f"l{i}_ffn_norm_weight"] = (d,)
        if is_dense(sz, i):
            shapes[f"l{i}_gate_up_weight"] = (2 * f, d)
            shapes[f"l{i}_down_weight"] = (d, f)
        else:
            shapes[f"l{i}_router_weight"] = (e_all, d)
            shapes[f"l{i}_w1"] = (e, d, ff)
            shapes[f"l{i}_w3"] = (e, d, ff)
            shapes[f"l{i}_w2"] = (e, ff, d)
            shapes[f"l{i}_shared_gate_up_weight"] = (2 * fs, d)
            shapes[f"l{i}_shared_down_weight"] = (d, fs)
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


# -- the rotation ---------------------------------------------------------------
def frequencies(width, group):
    """The ``width // 2`` rotary frequencies of a ``width``-wide rotated
    part under a ``rope_parameters`` group, float64: ``f_i = theta^(-2i /
    width)``; where the group's ``rope_type`` is ``yarn``, by YaRN's "NTK
    by parts" (arXiv:2309.00071): with ``d(beta) = width ln(original / (2
    pi beta)) / (2 ln theta)`` the pair at which a rotation turns ``beta``
    times over the original length, pairs up to ``low = floor(d(
    beta_fast))`` keep ``f_i``, pairs from ``high = ceil(d(beta_slow))``
    on get ``f_i / factor``, and between them the two are mixed along a
    linear ramp."""
    theta = float(group["rope_theta"])
    i = np.arange(0, width, 2, dtype=np.float64)
    f = theta ** (-i / width)
    if group.get("rope_type") != "yarn":
        return f
    original = float(group["original_max_position_embeddings"])

    def pair(beta):
        return width * math.log(original / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(group["beta_fast"])), 0)
    high = min(math.ceil(pair(group["beta_slow"])), width - 1)
    ramp = np.clip((i / 2 - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1 - ramp) + f / float(group["factor"]) * ramp


def rotate(x, group):
    """Rotary position encoding of ``x`` (L, H, D) under a
    ``rope_parameters`` group, ``rotate_half`` convention: the first
    ``width = D * partial_rotary_factor`` elements of every head are
    rotated as a head of that width (pair ``i`` is elements ``i`` and ``i
    + width / 2``, the angle of position ``t`` is ``t * f_i``), the
    others go through as they are; cos and sin are multiplied by the
    group's ``attention_factor`` (1 without), so the rotated elements,
    and they alone, carry it."""
    length, _, d = x.shape
    width = int(d * group.get("partial_rotary_factor", 1))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(
        frequencies(width, group), jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # (L, 1, w)
    a = jnp.float32(group.get("attention_factor", 1.0))
    r, rest = x[..., :width], x[..., width:]
    r1, r2 = r[..., :width // 2], r[..., width // 2:]
    r = r * (jnp.cos(ang) * a) + jnp.concatenate([-r2, r1], -1) \
        * (jnp.sin(ang) * a)
    return jnp.concatenate([r, rest], axis=-1)


# -- attention: full or under a window, one gate a head -----------------------------
def attention(sz, p, i, u, precision):
    """Layer ``i``'s causal softmax attention of one sequence ``u`` (L,
    hidden) over the heads held. The projection's rows are grouped by
    part: ``[q of every head | k | v | one gate a head]``. In a sliding
    layer query ``t`` sees key ``j`` where ``0 <= t - j <
    sliding_window``. Head ``h``'s output is multiplied by ``sigmoid`` of
    the token's gate ``h`` before the output projection."""
    h, hkv, dh = heads_of(sz, i), sz["num_key_value_heads"], sz["head_dim"]
    sliding = is_sliding(sz, i)
    group = sz["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    window = sz["sliding_window"] if sliding else None
    length, block = u.shape[0], sz["reference_attention_block"]
    qkv = held(_matmul(u, p[f"l{i}_qkv_weight"], precision), precision)
    q = qkv[:, :h * dh].reshape(length, h, dh)
    k = qkv[:, h * dh:(h + hkv) * dh].reshape(length, hkv, dh)
    v = qkv[:, (h + hkv) * dh:(h + 2 * hkv) * dh].reshape(length, hkv, dh)
    gate = jax.nn.sigmoid(qkv[:, (h + 2 * hkv) * dh:])           # (L, h)
    q = held(rotate(q, group), precision)
    k = held(rotate(k, group), precision)
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))

    def rows(qb, first):
        # a block of queries against every key under the mask
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=_HI) * dh ** -0.5
        back = (first + jnp.arange(qb.shape[0]))[:, None] \
            - jnp.arange(length)[None, :]                        # t - j
        mask = back >= 0
        if window is not None:
            mask = mask & (back < window)
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", held(pr, precision), v,
                          precision=_HI)

    out = _in_blocks(lambda qb, t: rows(qb, t[0]), block, q,
                     jnp.arange(length))
    return _matmul((out * gate[:, :, None]).reshape(length, h * dh),
                   p[f"l{i}_o_weight"], precision)


# -- feed-forward sublayers -------------------------------------------------
def gated_mlp(sz, u, gate_up, down, precision):
    """``W_down (silu(W_gate u) * W_up u)``, ``gate_up`` holding ``[W_gate
    | W_up]`` as its rows."""
    f = down.shape[1]

    def rows(x):
        gu = held(_matmul(x, gate_up, precision), precision)
        return _matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], down, precision)

    return _in_blocks(rows, sz["reference_row_block"], u)


def router(sz, p, i, u, precision):
    """``(weights (T, E_all), zero where not chosen; chosen (T, E_all))``
    over every expert of the model: a softmax over all of them in
    float32, the ``num_experts_per_tok`` largest chosen, the chosen
    scores over their sum, times ``moe_routed_scaling_factor``."""
    s = jax.nn.softmax(_matmul(u, p[f"l{i}_router_weight"], precision),
                       axis=-1)
    _, idx = lax.top_k(s, sz["num_experts_per_tok"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    if sz["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * sz["moe_routed_scaling_factor"], chosen


def moe_layer(sz, p, i, u, precision):
    """The held experts' part, each weighted on its output, plus the
    shared expert on every token, ungated."""
    w, _ = router(sz, p, i, u, precision)

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
    return routed + gated_mlp(sz, u, p[f"l{i}_shared_gate_up_weight"],
                              p[f"l{i}_shared_down_weight"], precision)


def attention_sublayer(sz, p, i, x, precision="float32"):
    """``x + Attn(norm(x))`` for one sequence ``x`` (L, hidden)."""
    u = _rms(x, p[f"l{i}_attn_norm_weight"], sz["rms_norm_eps"])
    return x + attention(sz, p, i, u, precision)


def ffn_sublayer(sz, p, i, h, precision="float32"):
    """``h + FFN(norm(h))``: the dense MLP or the experts."""
    u = _rms(h, p[f"l{i}_ffn_norm_weight"], sz["rms_norm_eps"])
    if is_dense(sz, i):
        return h + gated_mlp(sz, u, p[f"l{i}_gate_up_weight"],
                             p[f"l{i}_down_weight"], precision)
    return h + moe_layer(sz, p, i, u, precision)


def layer(sz, p, i, x, precision="float32"):
    return ffn_sublayer(sz, p, i, attention_sublayer(sz, p, i, x, precision),
                        precision)


def layer_params(p, i):
    return {k: v for k, v in p.items() if k.startswith(f"l{i}_")}


def reference_loss(sz, p, tokens, targets, precision="float32"):
    """The mean cross entropy of the next token over ``tokens`` (B, L)
    against ``targets`` (B * L,). Each layer's insides are recomputed in
    the backward pass."""
    x = jnp.take(p["embed_weight"], tokens, axis=0)          # (B, L, D)
    for i in range(sz["num_hidden_layers"]):
        one = jax.checkpoint(
            lambda q, xs, i=i: layer(sz, q, i, xs, precision))
        x = jax.vmap(one, in_axes=(None, 0))(layer_params(p, i), x)
    x = _rms(x, p["final_norm_weight"], sz["rms_norm_eps"])

    def cross_entropy(rows, labels):
        logp = jax.nn.log_softmax(_matmul(rows, p["head_weight"], precision),
                                  axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]

    return jnp.mean(_in_blocks(cross_entropy, sz["reference_row_block"],
                               x.reshape(-1, x.shape[-1]), targets))


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


# ---------------------------------------------------------------------------
# seeded weights and batches
# ---------------------------------------------------------------------------
def _init_leaf(sz, name, shape, key):
    if name.endswith("norm_weight"):
        return jnp.ones(shape, jnp.float32)
    # the plain start, a sublayer's last product too (the .json's `assumed`
    # says what scaling them down did to the held experts' loads)
    return sz["initializer_range"] * jax.random.normal(key, shape,
                                                      jnp.float32)


def make_weights(sizes, seed):
    """Every parameter from the seed in one jitted call on the device."""
    shapes = param_shapes(sizes)

    @jax.jit
    def make(key):
        return {name: _init_leaf(sizes, name, shape,
                                 jax.random.fold_in(key, i))
                for i, (name, shape) in enumerate(shapes.items())}

    return make(seed_key(seed))


def make_batches(sizes, seed, n):
    """``n`` batches of ``(tokens (batch, seq_len), next tokens (batch *
    seq_len,))``, int32, uniform over the vocabulary held; a target is the
    next id of the same sequence, the last one drawn."""
    rng = np.random.default_rng([int(seed), 50])
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
        loss, g = jax.value_and_grad(
            lambda q: reference_loss(sz, q, x, y, precision))(p)
        p, m, v = adam_step(opt, p, m, v, t, g)
        return p, m, v, loss

    return step


def _norms(after, before):
    return {k: float(np.linalg.norm(
        (after[k] - before[k]).astype(np.float64))) for k in before}


def reference_train(sizes, opt, weights, batches, precision="float32"):
    """Adam from ``weights`` over ``batches``, one batch a step: what
    ``refutil.first_steps`` returns for SGD. The system's device buffers
    are released first: the reference's three steps need the chip."""
    release_system()
    step = _train_program(json.dumps(sizes, sort_keys=True),
                          json.dumps(opt, sort_keys=True), precision)
    start = jax.device_get(weights)
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
            update = {k: after[k] - start[k] for k in start}
            first = {k: n / opt["learning_rate"]
                     for k, n in _norms(after, start).items()}
            del after
    last = jax.device_get(p)
    return {"losses": losses, "first_grad_norms": first,
            "change_norms": _norms(last, start), "first_update": update}


# ---------------------------------------------------------------------------
# the system under test, through the public API
# ---------------------------------------------------------------------------
_LIVE = []        # the system build() made last, until it is released


def pattern(sz):
    """``PatternLM``'s pattern: a layer is two units, its attention (``W``
    under the window, ``*`` full) then its feed-forward network."""
    return "".join(("W" if is_sliding(sz, i) else "*")
                   + ("G" if is_dense(sz, i) else "F")
                   for i in range(sz["num_hidden_layers"]))


def _heads(sz, sliding):
    """The query heads held of the layers of one kind, which agree."""
    (held_heads,) = {heads_of(sz, i) for i in range(sz["num_hidden_layers"])
                     if is_sliding(sz, i) == sliding}
    return held_heads


def _attention(sz, sliding):
    """``nn.GQAttention``'s keyword arguments for the layers of one
    kind."""
    group = sz["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    kw = dict(num_heads=_heads(sz, sliding),
              num_kv_heads=sz["num_key_value_heads"],
              head_dim=sz["head_dim"], block=sz["attention_block"],
              rope_theta=group["rope_theta"], head_gate=True)
    if group.get("partial_rotary_factor", 1) != 1:
        kw["rotary_dim"] = int(sz["head_dim"]
                               * group["partial_rotary_factor"])
    if group["rope_type"] != "default":
        kw["rope_scaling"] = group
    if sliding:
        kw["window"] = sz["sliding_window"]
    return kw


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import PatternLM
    sz = sizes
    return PatternLM(
        pattern(sz), sz["vocab_size"], sz["hidden_size"],
        attention=_attention(sz, False),
        window_attention=_attention(sz, True),
        mlp=dict(units=sz["intermediate_size"]),
        experts=dict(num_experts=sz["router_experts"],
                     expert_ids=held_experts(sz),
                     top_k=sz["num_experts_per_tok"],
                     expert_units=sz["moe_intermediate_size"],
                     shared_units=sz["shared_expert_intermediate_size"],
                     buffer_rows=sz["moe_buffer_rows"],
                     scaling=sz["moe_routed_scaling_factor"],
                     norm_topk=sz["norm_topk_prob"], scoring="softmax"),
        epsilon=sz["rms_norm_eps"])


def _leaf_of(param_name):
    """gluon's ``patternlm0_l3_gatedmoe0_w1`` -> ``l1_w1``,
    ``..._l2_rmsnorm0_gamma`` -> ``l1_attn_norm_weight``,
    ``..._l3_rmsnorm0_gamma`` -> ``l1_ffn_norm_weight``: the pattern's
    units ``2 l`` and ``2 l + 1`` are the reference's layer ``l``,
    attention then feed-forward; nothing for an expert layer's counters
    and for its correction bias, which stays at zero."""
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
        return f"l{layer_id}_{('attn', 'ffn')[kind]}_norm_weight"
    return None if leaf in ("counters", "router_bias") \
        else f"l{layer_id}_{leaf}"


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
    if role != "step":
        raise ValueError(f"laguna-s-2.1 has no role {role!r}")
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
        gauges = publish_moe_counters(system.net)
        print("experts: pairs held at the last step, by layer, "
              + json.dumps({k.rsplit("::", 1)[1]: v
                            for k, v in sorted(gauges.items())
                            if "::pairs_held::" in k}))
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
def _layers(sz, sliding):
    """The layers held of one kind of attention."""
    return [i for i in range(sz["num_hidden_layers"])
            if is_sliding(sz, i) == sliding]


def _pairs(sz, sliding):
    """(query, key) pairs a head and sequence that the mask lets through:
    ``sum_t (t + 1)`` under the causal order alone, ``sum_t min(t + 1,
    W)`` under the window."""
    length = sz["seq_len"]
    w = min(sz["sliding_window"], length) if sliding else length
    return w * (w + 1) // 2 + (length - w) * w


def forward_macs(sz):
    """Multiply-accumulates of one token's forward pass, by part."""
    d, hkv, dh = sz["hidden_size"], sz["num_key_value_heads"], sz["head_dim"]
    fs = sz["shared_expert_intermediate_size"]
    n = sz["num_hidden_layers"]
    dense = sum(is_dense(sz, i) for i in range(n))
    expert = n - dense
    tokens = sz["batch"] * sz["seq_len"]

    def scores(sliding):
        return sum(heads_of(sz, i) for i in _layers(sz, sliding)) * 2 * dh \
            * _pairs(sz, sliding) / sz["seq_len"]

    return {
        "attn.projections": sum(
            d * ((2 * heads_of(sz, i) + 2 * hkv) * dh + heads_of(sz, i))
            for i in range(n)),
        "attn.scores.full": scores(False),
        "attn.scores.sliding": scores(True),
        "dense.mlp": dense * 3 * d * sz["intermediate_size"],
        "experts.router": expert * d * sz["router_experts"],
        "experts.shared": expert * 3 * d * fs,
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


def _attention_cost(sz, sliding):
    """``(operations, bytes)`` one trained step needs of the attention
    between the projections over the layers of one kind, whatever
    implements it: the pairs the mask lets through, scores and weighted
    sums both ``head_dim`` wide, 2 per multiply-accumulate, three passes
    (a second forming of the scores in a backward pass, and under the
    window a block's masked part, count in the time, not in the need).
    The bytes are q, k, v and the output in the compute dtype and a
    float32 log-sum-exp a row, once forward and twice more backward."""
    hkv, dh = sz["num_key_value_heads"], sz["head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    ops = moved = 0
    for i in _layers(sz, sliding):
        h = heads_of(sz, i)
        ops += 2 * 3 * sz["batch"] * h * 2 * dh * _pairs(sz, sliding)
        moved += 3 * tokens * ((2 * h + 2 * hkv) * dh * 2 + h * 4)
    return ops, moved


def attn_cost(sz):
    """The full-attention layers' (scope ``mx_attn_fwd``, forward and
    backward): ``_attention_cost``."""
    return _attention_cost(sz, False)


def swa_cost(sz):
    """The sliding layers' (scope ``mx_swa_fwd``, forward and backward):
    ``_attention_cost`` over the band alone, ``sum_t min(t + 1, 512)``
    pairs a head."""
    return _attention_cost(sz, True)


def moe_gmm_cost(sz):
    """``(operations, bytes)`` one trained step needs of the grouped
    product over the whole static buffer (scopes ``mx_moe_gmm_*``) over
    all expert layers: three passes over the three products; the bytes
    are the three weights, the buffer, both hidden rows and the result in
    the compute dtype, once forward and twice backward."""
    d, ff = sz["hidden_size"], sz["moe_intermediate_size"]
    rows, e = sz["moe_buffer_rows"], len(held_experts(sz))
    layers = sum(not is_dense(sz, i) for i in range(sz["num_hidden_layers"]))
    macs = rows * 3 * d * ff
    moved = 2 * (e * 3 * d * ff + rows * (2 * d + 2 * ff))
    return layers * 2 * 3 * macs, layers * 3 * moved
