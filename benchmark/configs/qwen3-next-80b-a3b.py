"""One v5e chip's share of Qwen's Qwen3-Next-80B-A3B (``model_type:
qwen3_next``): one whole period of four layers at the published widths
(three Gated DeltaNet linear-attention layers and one gated
softmax-attention layer, a mixture of experts after each), all 16 + 32
linear heads and all 16 + 2 attention heads, 32 of the 512 routed
experts with the router and the shared expert whole, and an eighth of the
vocabulary: what one of 16 chips that share each layer (expert parallel
under data-parallel token mixers) would hold. The cut, the deployment and
every assumed size are in ``qwen3-next-80b-a3b.json``.

Two halves that share nothing but parameter names and layouts:

* the system under test (``build``): a gluon ``PatternLM`` (pattern
  ``DFDFDF*F``) driven by ``parallel.TrainStep`` with Adam, recomputation
  by layer and the net's own parameter buffers, the path
  ``moonlight-16b-a3b.py`` takes;
* the plain reference (between the marker lines; ``reference_train``):
  ``jax.numpy`` in float32 at ``Precision.HIGHEST``, the delta rule as
  its token-by-token recurrence (a ``lax.scan`` over tokens, in blocks
  whose insides are recomputed in the backward pass), the attention's
  whole score rows in blocks of queries, the held experts one at a time
  with a dense mask and no buffer, Adam written out. It imports nothing
  of ``mxnet_tpu``. ``precision="fp8"`` is the control: both operands of
  every matrix product, the convolved and the normalised heads, the
  rotated parts and the attention's probabilities rounded to the four
  significant bits of an 8-bit float.
"""
from __future__ import annotations

import functools
import json
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from refutil import held, seed_key

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(_ROOT, "mxnet_tpu", "gluon", "nn",
                       "seq_layers.py")) as _f:
    if "class GatedDeltaNet" not in _f.read():
        # a program from before these layers cannot run the cell: say so
        # at once, before any weight is made
        raise SystemExit("qwen3-next-80b-a3b needs gluon.nn.GatedDeltaNet, "
                         "GQAttention's head norms, partial rotation and "
                         "gate, and GatedMoE's softmax router: this program "
                         "has none of them")

# --- reference: begin ------------------------------------------------------
_HI = lax.Precision.HIGHEST


def held_experts(sz):
    return list(sz.get("expert_ids", range(sz["num_experts"])))


def is_linear(sz, i):
    """Layer ``i`` mixes tokens by the delta rule; every
    ``full_attention_interval``-th layer by softmax attention."""
    return (i + 1) % sz["full_attention_interval"] != 0


def param_shapes(sz):
    d, v = sz["hidden_size"], sz["vocab_size"]
    hk, hv = sz["linear_num_key_heads"], sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    ff, fs = sz["moe_intermediate_size"], \
        sz["shared_expert_intermediate_size"]
    e_all, e = sz["router_experts"], len(held_experts(sz))
    conv = 2 * hk * dk + hv * dv
    shapes = {"embed_weight": (v, d)}
    for i in range(sz["num_hidden_layers"]):
        shapes[f"l{i}_mixer_norm_weight"] = (d,)
        if is_linear(sz, i):
            shapes[f"l{i}_qkvz_weight"] = (conv + hv * dv, d)
            shapes[f"l{i}_ba_weight"] = (2 * hv, d)
            shapes[f"l{i}_conv_weight"] = (conv, sz["linear_conv_kernel_dim"])
            shapes[f"l{i}_dt_bias"] = (hv,)
            shapes[f"l{i}_a_log"] = (hv,)
            shapes[f"l{i}_gate_norm_weight"] = (dv,)
            shapes[f"l{i}_out_weight"] = (d, hv * dv)
        else:
            shapes[f"l{i}_qkv_weight"] = ((2 * ha + 2 * hkv) * dh, d)
            shapes[f"l{i}_q_norm_weight"] = (dh,)
            shapes[f"l{i}_k_norm_weight"] = (dh,)
            shapes[f"l{i}_o_weight"] = (d, ha * dh)
        shapes[f"l{i}_ffn_norm_weight"] = (d,)
        shapes[f"l{i}_router_weight"] = (e_all, d)
        shapes[f"l{i}_w1"] = (e, d, ff)
        shapes[f"l{i}_w3"] = (e, d, ff)
        shapes[f"l{i}_w2"] = (e, ff, d)
        shapes[f"l{i}_shared_gate_up_weight"] = (2 * fs, d)
        shapes[f"l{i}_shared_down_weight"] = (d, fs)
        shapes[f"l{i}_shared_gate_weight"] = (1, d)
    shapes["final_norm_weight"] = (d,)
    shapes["head_weight"] = (v, d)
    return shapes


def _matmul(x, w, precision):
    """``x @ w.T``."""
    return jnp.dot(held(x, precision), held(w, precision).T, precision=_HI)


def _rms(x, w, eps):
    """The family's norm: ``x / rms(x) * (1 + w)``."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + w)


def _block_rows(n, limit):
    """The largest block of at most ``limit`` rows that divides ``n``."""
    return max(r for r in range(1, min(limit, n) + 1) if n % r == 0)


def _in_blocks(fn, limit, *xs):
    """``fn`` over equal blocks of at most ``limit`` rows of each of
    ``xs`` (the largest such block that divides their length), one block
    after another, each block's insides recomputed in the backward pass:
    what is held at a time is one block's. Changes what is kept, not what
    is computed."""
    n = xs[0].shape[0]
    rows = _block_rows(n, limit)
    out = lax.map(lambda block: jax.checkpoint(fn)(*block),
                  tuple(x.reshape((n // rows, rows) + x.shape[1:])
                        for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def delta_rule(sz, q, k, v, beta, g):
    """The gated delta rule, token by token from a zero state: ``S' =
    exp(g_t) S``, ``u_t = beta_t (v_t - S'^T k_t)``, ``S = S' + k_t
    u_t^T``, ``o_t = S^T q_t``. ``q``, ``k``: (L, H, N); ``v``: (L, H, P);
    ``beta``, ``g``: (L, H). Returns (L, H, P). A scan over blocks of
    tokens around a scan over a block's tokens; a block is recomputed in
    the backward pass, so that what is held is the state entering each
    block and one block's steps."""
    length, h, n = q.shape
    p = v.shape[-1]
    block = _block_rows(length, sz["reference_scan_block"])

    def token(state, x):
        q_t, k_t, v_t, b_t, g_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hnp,hn->hp", state, k_t,
                                               precision=_HI))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hnp,hn->hp", state, q_t, precision=_HI)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    _, out = lax.scan(tokens, jnp.zeros((h, n, p), jnp.float32), tuple(
        t.reshape((length // block, block) + t.shape[1:])
        for t in (q, k, v, beta, g)))
    return out.reshape(length, h, p)


def gated_delta_net(sz, p, i, u, precision):
    """The linear-attention mixer of one sequence ``u`` (L, hidden). The
    in-projection's rows are grouped by part, ``[q | k | v | z]`` and ``[b
    | a]``; key head ``j`` serves value heads ``2 j`` and ``2 j + 1``."""
    hk, hv = sz["linear_num_key_heads"], sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    taps, length = sz["linear_conv_kernel_dim"], u.shape[0]
    conv = 2 * hk * dk + hv * dv
    qkvz = held(_matmul(u, p[f"l{i}_qkvz_weight"], precision), precision)
    ba = held(_matmul(u, p[f"l{i}_ba_weight"], precision), precision)
    qkv, z = qkvz[:, :conv], qkvz[:, conv:]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = sum(padded[j:j + length] * p[f"l{i}_conv_weight"][:, j]
              for j in range(taps))
    qkv = held(jax.nn.silu(qkv), precision)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p[f"l{i}_a_log"]) * jax.nn.softplus(
        ba[:, hv:] + p[f"l{i}_dt_bias"])

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)

    q = unit(qkv[:, :hk * dk].reshape(length, hk, dk)) * dk ** -0.5
    k = unit(qkv[:, hk * dk:2 * hk * dk].reshape(length, hk, dk))
    q, k = (jnp.repeat(held(t, precision), hv // hk, axis=1) for t in (q, k))
    v = qkv[:, 2 * hk * dk:].reshape(length, hv, dv)
    o = delta_rule(sz, q, k, v, beta, g)
    y = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                     + sz["rms_norm_eps"]) * p[f"l{i}_gate_norm_weight"] \
        * jax.nn.silu(z.reshape(length, hv, dv))
    return _matmul(y.reshape(length, hv * dv), p[f"l{i}_out_weight"],
                   precision)


def rotate(x, theta, width):
    """Rotary position encoding of the first ``width`` elements of every
    head of ``x`` (L, H, D), ``rotate_half`` convention: the angle of
    position ``t`` and pair ``i`` is ``t * theta^(-2i/width)``; pair ``i``
    is elements ``i`` and ``i + width/2``; the other elements as they
    are."""
    length = x.shape[0]
    inv = jnp.asarray(1.0 / theta ** (np.arange(0, width, 2) / width),
                      jnp.float32)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # (L, 1, w)
    r, rest = x[..., :width], x[..., width:]
    r1, r2 = r[..., :width // 2], r[..., width // 2:]
    r = r * jnp.cos(ang) + jnp.concatenate([-r2, r1], -1) * jnp.sin(ang)
    return jnp.concatenate([r, rest], axis=-1)


def gated_attention(sz, p, i, u, precision):
    """The gated softmax attention of one sequence ``u`` (L, hidden). The
    projection's rows are grouped by part: ``[q of every head | k | v |
    gate of every head]``."""
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    width = int(dh * sz["partial_rotary_factor"])
    length, block = u.shape[0], sz["reference_attention_block"]
    eps = sz["rms_norm_eps"]
    qkv = held(_matmul(u, p[f"l{i}_qkv_weight"], precision), precision)
    q = qkv[:, :ha * dh].reshape(length, ha, dh)
    k = qkv[:, ha * dh:(ha + hkv) * dh].reshape(length, hkv, dh)
    v = qkv[:, (ha + hkv) * dh:(ha + 2 * hkv) * dh].reshape(length, hkv, dh)
    gate = qkv[:, (ha + 2 * hkv) * dh:]
    q = _rms(q, p[f"l{i}_q_norm_weight"], eps)
    k = _rms(k, p[f"l{i}_k_norm_weight"], eps)
    q = held(rotate(q, sz["rope_theta"], width), precision)
    k = held(rotate(k, sz["rope_theta"], width), precision)
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
    return _matmul(out * jax.nn.sigmoid(gate), p[f"l{i}_o_weight"],
                   precision)


def router(sz, p, i, u, precision):
    """``(weights (T, E_all), zero where not chosen; chosen (T, E_all))``:
    a softmax over every expert of the model, the largest
    ``num_experts_per_tok``, normalised to sum 1."""
    s = jax.nn.softmax(_matmul(u, p[f"l{i}_router_weight"], precision),
                       axis=-1)
    _, idx = lax.top_k(s, sz["num_experts_per_tok"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    if sz["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, chosen


def moe_layer(sz, p, i, u, precision):
    """The held experts' part plus the gated shared expert."""
    w, _ = router(sz, p, i, u, precision)
    fs = sz["shared_expert_intermediate_size"]

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

    def shared(x):
        gu = held(_matmul(x, p[f"l{i}_shared_gate_up_weight"], precision),
                  precision)
        out = _matmul(jax.nn.silu(gu[:, :fs]) * gu[:, fs:],
                      p[f"l{i}_shared_down_weight"], precision)
        return jax.nn.sigmoid(
            _matmul(x, p[f"l{i}_shared_gate_weight"], precision)) * out

    return routed + _in_blocks(shared, sz["reference_row_block"], u)


def mixer_sublayer(sz, p, i, x, precision="float32"):
    mixer = gated_delta_net if is_linear(sz, i) else gated_attention
    return x + mixer(
        sz, p, i, _rms(x, p[f"l{i}_mixer_norm_weight"], sz["rms_norm_eps"]),
        precision)


def layer(sz, p, i, x, precision="float32"):
    """``a + MoE(N(a))`` with ``a = x + Mixer(N(x))`` for one sequence
    ``x`` (L, hidden)."""
    a = mixer_sublayer(sz, p, i, x, precision)
    return a + moe_layer(
        sz, p, i, _rms(a, p[f"l{i}_ffn_norm_weight"], sz["rms_norm_eps"]),
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
    if name.endswith("gate_norm_weight"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("norm_weight"):        # 1 + w, from w = 0
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("dt_bias"):
        return jnp.full(shape, sz["dt_bias"], jnp.float32)
    if name.endswith("a_log"):
        low, high = sz["a_range"]           # A over (low, high]
        return jnp.log(high - (high - low)
                       * jax.random.uniform(key, shape, jnp.float32))
    if name.endswith("conv_weight"):
        return jax.random.uniform(key, shape, jnp.float32,
                                  -sz["conv_range"], sz["conv_range"])
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
    rng = np.random.default_rng([int(seed), 41])
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
    """``PatternLM``'s pattern: a layer is two units, its token mixer
    then its experts."""
    return "".join(("D" if is_linear(sz, i) else "*") + "F"
                   for i in range(sz["num_hidden_layers"]))


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import PatternLM
    sz = sizes
    return PatternLM(
        pattern(sz), sz["vocab_size"], sz["hidden_size"],
        linear_attention=dict(num_k_heads=sz["linear_num_key_heads"],
                              num_v_heads=sz["linear_num_value_heads"],
                              key_dim=sz["linear_key_head_dim"],
                              value_dim=sz["linear_value_head_dim"],
                              conv_kernel=sz["linear_conv_kernel_dim"],
                              chunk_size=sz["gdn_chunk"]),
        attention=dict(num_heads=sz["num_attention_heads"],
                       num_kv_heads=sz["num_key_value_heads"],
                       head_dim=sz["head_dim"], block=sz["attention_block"],
                       rope_theta=sz["rope_theta"],
                       rotary_dim=int(sz["head_dim"]
                                      * sz["partial_rotary_factor"]),
                       qk_norm=True, gated=True,
                       epsilon=sz["rms_norm_eps"], norm_unit_offset=True),
        experts=dict(num_experts=sz["router_experts"],
                     expert_ids=held_experts(sz),
                     top_k=sz["num_experts_per_tok"],
                     expert_units=sz["moe_intermediate_size"],
                     shared_units=sz["shared_expert_intermediate_size"],
                     buffer_rows=sz["moe_buffer_rows"],
                     norm_topk=sz["norm_topk_prob"], scoring="softmax",
                     shared_gate=True),
        epsilon=sz["rms_norm_eps"], norm_unit_offset=True)


def _leaf_of(param_name):
    """gluon's ``patternlm0_l1_gatedmoe0_w1`` -> ``l0_w1``,
    ``..._l0_rmsnorm0_gamma`` -> ``l0_mixer_norm_weight``,
    ``..._l1_rmsnorm0_gamma`` -> ``l0_ffn_norm_weight``: the pattern's
    units ``2 l`` and ``2 l + 1`` are the reference's layer ``l``, mixer
    then experts; nothing for an expert layer's counters and for its
    correction bias, which stays at zero."""
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
        return f"l{layer_id}_{('mixer', 'ffn')[kind]}_norm_weight"
    return None if leaf in ("counters", "router_bias") \
        else f"l{layer_id}_{leaf}"


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
        self._counters = [p for name, p in net.collect_params().items()
                          if name.endswith("_counters")]

    def __call__(self, x, y):
        from mxnet_tpu.ndarray.ndarray import NDArray
        loss = self.step(x, y)
        return NDArray(_guard(loss._data, *[p.data()._data
                                            for p in self._counters]))


def build(cfg, sizes, role, weights):
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep
    if role != "step":
        raise ValueError(f"qwen3-next-80b-a3b has no role {role!r}")
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
def _layers(sz):
    """``(linear-attention layers, gated-attention layers)`` held."""
    linear = sum(is_linear(sz, i) for i in range(sz["num_hidden_layers"]))
    return linear, sz["num_hidden_layers"] - linear


def _rule_macs(sz):
    """Multiply-accumulates a token of the delta rule itself, as its
    recurrence needs them: the state read for the key (``S'^T k``),
    written (``k u^T``) and read for the query (``S^T q``), a value
    head."""
    return 3 * sz["linear_num_value_heads"] * sz["linear_key_head_dim"] \
        * sz["linear_value_head_dim"]


def forward_macs(sz):
    """Multiply-accumulates of one token's forward pass, by part."""
    d, length = sz["hidden_size"], sz["seq_len"]
    hk, hv = sz["linear_num_key_heads"], sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    ff, fs = sz["moe_intermediate_size"], \
        sz["shared_expert_intermediate_size"]
    linear, full = _layers(sz)
    tokens = sz["batch"] * length
    return {
        "gdn.projections": linear * d * (2 * hk * dk + 3 * hv * dv + 2 * hv),
        "gdn.conv": linear * (2 * hk * dk + hv * dv)
        * sz["linear_conv_kernel_dim"],
        "gdn.rule": linear * _rule_macs(sz),
        "attn.projections": full * d * (3 * ha + 2 * hkv) * dh,
        "attn.scores": full * ha * 2 * dh * (length + 1) / 2,
        "experts.router": (linear + full) * d * sz["router_experts"],
        "experts.shared": (linear + full) * (3 * d * fs + d),
        "experts.routed": (linear + full) * sz["moe_buffer_rows"] * 3 * d
        * ff / tokens,
        "head": sz["vocab_size"] * d,
    }


def flops_per_item(sizes, mode):
    macs = sum(forward_macs(sizes).values())
    return 2 * 3 * macs if mode == "train" else 2 * macs


def items_per_step(sizes):
    return sizes["batch"] * sizes["seq_len"]


def attn_cost(sz):
    """``(operations, bytes)`` one trained step needs of the attention
    between the projections (scope ``mx_attn_fwd``, forward and backward)
    over the gated-attention layers: the causal half of the scores and of
    the weighted sums, both ``head_dim`` wide, 2 per multiply-accumulate,
    three passes (the backward kernels' second forming of the scores
    counts in the time, not in the need). The bytes are q, k, v and the
    output in the compute dtype and the float32 log-sum-exp, once forward
    and twice more backward."""
    ha, hkv, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = _layers(sz)[1]
    macs = tokens * ha * 2 * dh * (sz["seq_len"] + 1) / 2
    moved = tokens * ((2 * ha + 2 * hkv) * dh * 2 + ha * 4)
    return layers * 2 * 3 * macs, layers * 3 * moved


def gdn_cost(sz):
    """``(operations, bytes)`` one trained step needs of the delta rule
    (scope ``mx_gdn_rule``, forward and backward) over the
    linear-attention layers: the recurrence's three products with the
    state a token and value head, 2 per multiply-accumulate, three passes
    (what the chunked form adds, the triangular system and its solve, and
    the rule computed again inside a recomputation unit count in the
    time, not in the need). The bytes are q, k and v in the compute
    dtype, beta and the decay in float32 and the output in float32, once
    a pass, three passes."""
    hk, hv = sz["linear_num_key_heads"], sz["linear_num_value_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    layers = _layers(sz)[0]
    moved = tokens * ((2 * hk * dk + hv * dv) * 2 + 2 * hv * 4
                      + hv * dv * 4)
    return layers * 2 * 3 * tokens * _rule_macs(sz), layers * 3 * moved


def moe_gmm_cost(sz):
    """``(operations, bytes)`` one trained step needs of the grouped
    product over the whole static buffer (scopes ``mx_moe_gmm_*``) over
    all expert layers: three passes over the three products; the bytes
    are the three weights, the buffer, both hidden rows and the result in
    the compute dtype, once forward and twice backward."""
    d, ff = sz["hidden_size"], sz["moe_intermediate_size"]
    rows, e = sz["moe_buffer_rows"], len(held_experts(sz))
    layers = sum(_layers(sz))
    macs = rows * 3 * d * ff
    moved = 2 * (e * 3 * d * ff + rows * (2 * d + 2 * ff))
    return layers * 2 * 3 * macs, layers * 3 * moved
