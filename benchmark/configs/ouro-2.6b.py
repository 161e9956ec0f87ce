"""One v5e chip's stage of ByteDance's Ouro-2.6B (``model_type: ouro``;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741):
four of the 48 layers at the published widths, all 16 heads and the whole
vocabulary, run ``total_ut_steps`` = 4 times over their own output with
the same weights, a language-model head after every pass and an exit gate
whose distribution over the passes weighs the four losses. The cut, the
deployment and every assumed size are in ``ouro-2.6b.json``.

Two halves that share nothing but parameter names and layouts:

* the system under test (``build``): a gluon ``PatternLM`` (pattern
  ``*G`` a layer, ``post_norm``, ``loops``, ``exit_gate``) driven by
  ``parallel.TrainStep`` with the exit-weighted loss, Adam, recomputation
  by layer and the net's own parameter buffers, the path
  ``nemotron3-super-120b-a12b.py`` takes;
* the plain reference (between the marker lines, a copy of
  ``tests/reference/ouro.py``; ``reference_train``): ``jax.numpy`` in
  float32 at ``Precision.HIGHEST``, the passes as a ``lax.scan``, dense
  softmax attention in blocks of queries, each layer and each exit
  recomputed in the backward pass, Adam written out. It imports nothing
  of ``mxnet_tpu``. ``precision="fp8"`` is the control: both operands of
  every matrix product, the rotated heads and the attention's
  probabilities rounded to the four significant bits of an 8-bit float.
"""
from __future__ import annotations

import functools
import json
import os
import re

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from refutil import held, seed_key

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(_ROOT, "mxnet_tpu", "gluon", "nn",
                       "seq_layers.py")) as _f:
    if "class HybridLoop" not in _f.read():
        # a program from before these layers cannot run the cell: say so
        # at once, before any weight is made
        raise SystemExit("ouro-2.6b needs gluon.nn.HybridLoop, GatedMLP and "
                         "ExitGate, rotary heads in GQAttention and "
                         "parallel.exit_weighted_loss: this program has "
                         "none of them")

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


# ---------------------------------------------------------------------------
# seeded weights and batches
# ---------------------------------------------------------------------------
def _init_leaf(sz, name, shape, key):
    if name.endswith("norm_weight"):
        return jnp.ones(shape, jnp.float32)
    if name.startswith("gate_"):     # every gate starts at one half
        return jnp.zeros(shape, jnp.float32)
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
    seq_len,))``, int32, uniform over the vocabulary; a target is the
    next id of the same sequence, the last one drawn."""
    rng = np.random.default_rng([int(seed), 33])
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
        return adam_step(opt, p, m, v, t, g) + (loss,)

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
    return {"losses": losses, "first_grad_norms": first,
            "change_norms": _norms(jax.device_get(p), start),
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
        "*G" * sz["num_hidden_layers"], sz["vocab_size"], sz["hidden_size"],
        attention=dict(num_heads=sz["num_attention_heads"],
                       num_kv_heads=sz["num_key_value_heads"],
                       head_dim=sz["head_dim"], block=sz["attention_block"],
                       rope_theta=sz["rope_theta"]),
        mlp=dict(units=sz["intermediate_size"]), epsilon=sz["rms_norm_eps"],
        post_norm=True, loops=sz["total_ut_steps"], exit_gate=True)


_BLOCK_LEAF = {"embedding0_weight": "embed_weight",
               "dense0_weight": "head_weight",
               "rmsnorm0_gamma": "final_norm_weight",
               "exitgate0_weight": "gate_weight",
               "exitgate0_bias": "gate_bias"}
_NORM_LEAF = {"rmsnorm0_gamma": "norm_weight",
              "rmsnorm1_gamma": "post_norm_weight"}


def _leaf_of(param_name):
    """gluon's ``patternlm0_l3_gatedmlp0_down_weight`` ->
    ``l1_down_weight``, ``..._l2_rmsnorm1_gamma`` ->
    ``l1_attn_post_norm_weight``: the pattern's sublayers ``2 l`` and ``2
    l + 1`` are the reference's layer ``l``, attention then MLP; nothing
    for the gate's counters."""
    rest = param_name.split("_", 1)[1]
    if rest in _BLOCK_LEAF:
        return _BLOCK_LEAF[rest]
    if rest.endswith("_counters"):
        return None
    sub, leaf = rest.split("_", 1)
    layer_id, kind = divmod(int(sub[1:]), 2)
    if leaf in _NORM_LEAF:
        return f"l{layer_id}_{('attn', 'mlp')[kind]}_{_NORM_LEAF[leaf]}"
    return f"l{layer_id}_{leaf.split('_', 1)[1]}"


class _StepSystem:
    """``TrainStep`` with its net: what the step driver calls and what
    ``read_params`` reads."""

    def __init__(self, net, step):
        self.net, self.step = net, step
        self.specs = None        # the step program's arguments, as shapes

    def __call__(self, x, y):
        loss = self.step(x, y)
        if self.specs is None:
            st = self.step
            self.specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (st._pvals, st._opt_state, x._data, y._data, st._t_dev,
                 st._lr_cache[1]))
        return loss


def build(cfg, sizes, role, weights):
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep, exit_weighted_loss
    if role != "step":
        raise ValueError(f"ouro-2.6b has no role {role!r}")
    net = _net(sizes)
    net.initialize(mx.init.Zero())
    for name, p in net.collect_params().items():
        leaf = _leaf_of(name)
        if leaf is not None:
            p.set_data(NDArray(weights[leaf]))
    opt = dict(cfg["optimizer"])
    step = TrainStep(net, loss=exit_weighted_loss(sizes["exit_entropy_beta"]),
                     optimizer=opt.pop("name"), optimizer_params=opt,
                     compute_dtype=cfg["compute_dtype"], remat="layer")
    system = _StepSystem(net, step)
    _LIVE[:] = [system]
    del _LAST[:], _SCOPES[:]
    mx.telemetry.remove("loop::")     # an earlier system's gauges
    return system


def read_params(system):
    named = ((_leaf_of(name), p)
             for name, p in system.net.collect_params().items())
    return {leaf: p.data().asnumpy().astype(np.float32, copy=False)
            for leaf, p in named if leaf is not None}


def release_system():
    """Publish the live system's counters (``loop::*`` gauges) and free
    its device buffers, the parameters the net and the step share and the
    optimizer's state: after the window nothing calls it again, and the
    reference needs the memory. What ``scope_table`` lowers from stays."""
    from mxnet_tpu.gluon.nn import publish_loop_counters
    while _LIVE:
        system = _LIVE.pop()
        publish_loop_counters(system.net)
        step = system.step
        for leaf in jax.tree_util.tree_leaves((step._pvals,
                                               step._opt_state)):
            if not leaf.is_deleted():
                leaf.delete()
        step._pvals = step._opt_state = None
        _LAST[:] = [system]


_LOOP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? while\(", re.M)


def scope_table():
    """HLO instruction name -> ``mx_*`` scopes, outermost first and joined
    by ``/`` (``mx_loop_body/mx_attn_fwd``), in the step program of the
    system built last, for the readers of the device trace. The ``while``
    instructions themselves are left out: the trace holds an event for a
    loop and one for each operation of its every trip, and a sum over
    both would count the loop twice. The program is compiled once more
    from the first call's shapes (its buffers may be gone; JAX's cache may
    have it) and its text read once, however many metrics ask."""
    from mxnet_tpu.telemetry import trace
    if not _SCOPES:
        systems = [s for s in _LIVE + _LAST if s.specs is not None]
        if not systems:
            return None
        text = systems[0].step._step_jit.lower(
            *systems[0].specs).compile().as_text()
        table = trace.hlo_scopes(text, path=True)
        for loop in _LOOP.findall(text):
            table.pop(loop, None)
        _SCOPES.append(table)
    return _SCOPES[0]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------
def forward_macs(sz):
    """Multiply-accumulates of one token's forward pass, by part: the
    stack's products and its causal attention over all layers and passes,
    and the head after every pass."""
    d, f = sz["hidden_size"], sz["intermediate_size"]
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    uses = sz["num_hidden_layers"] * sz["total_ut_steps"]
    return {
        "products": uses * (d * (hq + 2 * hk) * dh + hq * dh * d
                            + 3 * d * f),
        "attention": uses * 2 * hq * dh * (sz["seq_len"] + 1) / 2,
        "heads": sz["total_ut_steps"] * sz["vocab_size"] * d,
    }


def flops_per_item(sizes, mode):
    macs = sum(forward_macs(sizes).values())
    return 2 * 3 * macs if mode == "train" else 2 * macs


def items_per_step(sizes):
    return sizes["batch"] * sizes["seq_len"]


def attn_cost(sz):
    """``(operations, bytes)`` one trained step needs of the attention
    between the projections (scope ``mx_attn_fwd``, forward and backward)
    over all layers and passes: the causal half of the scores and of the
    weighted sums, 2 per multiply-accumulate, three passes (the blocks the
    program forms beyond the diagonal, and the forward it computes again,
    count in the time, not in the need); the bytes are q, k, v and the
    output in the compute dtype, once forward, and twice more backward
    (read again with the output's gradient, the three gradients
    written)."""
    hq, hk, dh = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    tokens = sz["batch"] * sz["seq_len"]
    uses = sz["num_hidden_layers"] * sz["total_ut_steps"]
    macs = tokens * 2 * hq * dh * (sz["seq_len"] + 1) / 2
    moved = tokens * (2 * hq + 2 * hk) * dh * 2
    return uses * 2 * 3 * macs, uses * 3 * moved


def exit_head_cost(sz):
    """``(operations, bytes)`` one trained step needs of the heads with
    their cross entropy (scope ``mx_exit_head``) over all passes: the
    logits, the hidden state's gradient and the weight's gradient, 2 per
    multiply-accumulate (the logits computed again in the backward pass
    count in the time, not in the need); the bytes are the head's weight
    and the pass's hidden state in the compute dtype, read forward, read
    again backward with both gradients written. The float32 logits are
    not among them: a head fused with its cross entropy never writes
    them."""
    tokens = sz["batch"] * sz["seq_len"]
    v, d = sz["vocab_size"], sz["hidden_size"]
    return sz["total_ut_steps"] * 2 * 3 * tokens * v * d, \
        sz["total_ut_steps"] * 3 * (v * d + tokens * d) * 2
