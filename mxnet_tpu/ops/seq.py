"""Sequence-model operators: RMSNorm, the Mamba-2 mixer (the chunked
state-space scan, SSD), the Gated DeltaNet mixer (the gated delta rule in
chunks, this repo's kernels where the program is lowered for a TPU,
``ops.gdn_kernel``), the gated short convolution (a few causal taps
between two linear gates), a mixture of experts that is told which experts
it holds (one routing path, two expert bodies: relu2 in a latent, or
gated SiLU on the full hidden vector, its grouped products this repo's
kernels where the program is lowered for a TPU, ``ops.gmm_kernel``; with
or without shared experts),
causal grouped-query attention in blocks (fused kernels where the program
is lowered for a TPU, ``ops.attn_kernel``) with or without rotary
position encoding, a window and a gate a head, multi-head latent
attention over the same kernels, a
gated MLP, and the exit gate and exit-weighted loss of a stack that is
run several times.

Every op here is one chip's share of a layer: it is told how many heads
and groups it holds and which experts, computes with what it holds, and
returns its partial result. No code stands in for the chips that hold the
rest; ``tests/test_seq_ops.py`` adds the shares up to the uncut layer.

The step's device time is a function of shapes alone: the expert layer's
receive buffer is static and every row of it is computed, filled or not.

Named scopes (``mx_norm``, ``mx_mamba_proj``, ``mx_ssd_*``, ``mx_gdn_*``,
``mx_sconv_*``, ``mx_moe_*``, ``mx_attn_*``, ``mx_swa_fwd``, ``mx_mla_*``,
``mx_rope``,
``mx_mhc_*``, ``mx_gated_mlp``, ``mx_exit_head``, ``mx_exit_gate``) mark
each mechanism
in the compiled program, and each operator's registration lists its own;
``telemetry.trace.scope_table`` maps the program's instructions back to
them. The expert layer's matrix
products have scopes of their own (``mx_moe_score``: the router's;
``mx_moe_latent``: both latent projections; ``mx_moe_gmm_*``;
``mx_moe_shared``), so that ``mx_moe_route``, ``mx_moe_dispatch`` and
``mx_moe_combine`` hold the choice, the sort, the gathers and the
scatter-add alone.

What a recomputation unit around these ops holds for its backward pass
is said here, where the values are computed (``remat.kept``): the
outputs of the matrix products a backward pass reads, a gated MLP's
2 f wide first product among them (not a unit's last ones, nor the
attention's score blocks, which grow with the square of the length), the
threshold of the routing's choice, what the dispatch's
sort gave, the convolution's, the scan's and the attention's outputs,
the attention's log-sum-exp a row where its kernels run, and a norm's sum
of squares. Activations, gates, decay masks, casts,
rotated heads, the scaled rows of a norm and the whole of the delta rule
(its chain's backward wants every chunk's entering state) are computed
again. An
exit's logits are never held: each exit's head and cross entropy is a
unit of its own that keeps its hidden state (``exit_weighted_ce``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import (attn_kernel, gdn_conv_kernel, gdn_kernel, gmm_kernel,
               mhc_kernel, moe_rows_kernel)
from .registry import register_op
from .remat import kept

_F32 = jnp.float32
_NEG = -1e30


def _mm(x, w):
    """``x @ w.T`` for a ``(out, in)`` weight, summed in float32, returned
    in ``x``'s dtype."""
    return lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=_F32).astype(x.dtype)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0))


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
@register_op("RMSNorm", names_its_parts=True)
def rms_norm(data, gamma, eps=1e-5, num_groups=1, keep_input=False,
             unit_offset=False, **kw):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, or over
    each of ``num_groups`` equal slices of it; computed in float32.
    ``keep_input``: the unit around this norm holds its input (a norm
    *after* a sublayer reads that sublayer's last product).
    ``unit_offset``: the scale is ``1 + gamma``, a weight that starts at
    zero (the ``qwen3_next`` family's norms). Scope
    ``mx_norm``; the two norms that are a part of another mechanism (the
    Mamba-2 gate's, the key/value latent's) are ``_rms_norm`` inside
    that mechanism's scope."""
    with jax.named_scope("mx_norm"):
        return _rms_norm(data, gamma, eps, num_groups, keep_input,
                         unit_offset)


def _rms_norm(data, gamma, eps=1e-5, num_groups=1, keep_input=False,
              unit_offset=False):
    if keep_input:
        data = kept(data)
    shape = data.shape
    g = int(num_groups)
    x = data.astype(_F32).reshape(shape[:-1] + (g, shape[-1] // g))
    # the reduction's result, a float a row, is kept; the scaling is not
    x = x * lax.rsqrt(kept(jnp.mean(jnp.square(x), -1, keepdims=True) + eps))
    x = x.reshape(shape)
    if unit_offset:
        return (x * (1.0 + gamma.astype(_F32))).astype(data.dtype)
    return (x * gamma.astype(_F32)).astype(data.dtype)


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------
def causal_conv1d(x, weight, bias):
    """Depthwise causal convolution over time. ``x``: (B, L, C);
    ``weight``: (C, K), its last tap on the current step; ``bias``: (C,),
    or None for a convolution without one."""
    k = weight.shape[1]
    length = x.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(x.dtype)
    out = None if bias is None else bias.astype(x.dtype)
    for j in range(k):
        tap = pad[:, j:j + length, :] * w[:, j]
        out = tap if out is None else out + tap
    return out


def ssd_chunked(x, dt, a, b, c, chunk):
    """The state-space recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    (x) B_t``, ``y_t = S_t C_t`` from a zero state, in chunks: inside a
    chunk the masked product ``(C B^T o L) (dt x)``, between chunks the
    states carried by their decay.

    ``x``: (B, L, H, P); ``dt``: (B, L, H) float32, after softplus;
    ``a``: (H,) float32, negative; ``b``, ``c``: (B, L, G, N) with
    ``H % G == 0``. Any ``L``: the tail is padded with steps of ``dt =
    0``, which neither move the state nor reach an earlier output.
    Returns (B, L, H, P) float32."""
    bsz, length, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    q = int(chunk)
    pad = (-length) % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (length + pad) // q
    with jax.named_scope("mx_ssd_fwd"):
        dtype = x.dtype
        dt = dt.reshape(bsz, nc, q, g, r)
        xdt = (x.reshape(bsz, nc, q, g, r, p).astype(_F32)
               * dt[..., None])
        b = b.reshape(bsz, nc, q, g, n)
        c = c.reshape(bsz, nc, q, g, n)
        la = dt * a.reshape(g, r)                       # log decay a step
        cs = jnp.cumsum(la, axis=2)                     # (B, nc, q, g, r)
        # inside a chunk
        cb = kept(jnp.einsum("zcign,zcjgn->zcgij", c, b,
                             preferred_element_type=_F32))
        seg = cs[:, :, :, None] - cs[:, :, None, :]     # (B,nc,i,j,g,r)
        tri = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        decay = jnp.exp(jnp.where(tri[:, :, None, None], seg, -jnp.inf))
        scores = (cb.transpose(0, 1, 3, 4, 2)[..., None] * decay)
        y = jnp.einsum("zcijgr,zcjgrp->zcigrp", scores.astype(dtype),
                       xdt.astype(dtype), preferred_element_type=_F32)
        # each chunk's own state at its end
        to_end = jnp.exp(cs[:, :, -1:] - cs)            # (B, nc, q, g, r)
        own = kept(jnp.einsum("zcjgrp,zcjgn->zcgrpn",
                              (xdt * to_end[..., None]).astype(dtype), b,
                              preferred_element_type=_F32))
        # the state entering each chunk: the earlier chunks' states,
        # each decayed by the chunks between
        tot = jnp.cumsum(cs[:, :, -1], axis=1)          # (B, nc, g, r)
        between = tot[:, :-1, None] - tot[:, None, :-1]  # (B, c-1, c', g, r)
        low = jnp.arange(nc - 1)[:, None] >= jnp.arange(nc - 1)[None, :]
        carry = jnp.exp(jnp.where(low[:, :, None, None], between, -jnp.inf))
        entering = jnp.einsum("zcdgr,zdgrpn->zcgrpn", carry, own[:, :-1],
                              precision=lax.Precision.HIGHEST)
        entering = jnp.pad(entering, ((0, 0), (1, 0)) + ((0, 0),) * 4)
        y = y + kept(jnp.einsum("zcign,zcgrpn->zcigrp", c,
                                kept(entering.astype(dtype)),
                                preferred_element_type=_F32)) \
            * jnp.exp(cs)[..., None]
    return y.reshape(bsz, nc * q, h, p)[:, :length]


@register_op("Mamba2Mixer", names_its_parts=True)
def mamba2_mixer(data, in_proj_weight, conv_weight, conv_bias, dt_bias,
                 a_log, d, norm_weight, out_proj_weight, num_heads=1,
                 head_dim=64, state_size=128, num_groups=1, chunk_size=128,
                 eps=1e-5, **kw):
    """The Mamba-2 mixer over the heads and groups held here.

    ``data``: (B, L, hidden). ``in_proj_weight``: (2 d_inner + 2 G N + H,
    hidden) with ``d_inner = H * head_dim``, rows ``[z | x B C | dt]``;
    ``conv_weight``: (d_inner + 2 G N, K); ``out_proj_weight``: (hidden,
    d_inner). The gated norm is over each group's ``d_inner / G``
    channels, so a chip that holds whole groups computes it locally.
    Returns this share's partial sum of the layer's output."""
    h, p, n, g = int(num_heads), int(head_dim), int(state_size), \
        int(num_groups)
    bsz, length, _ = data.shape
    d_in = h * p
    # not mx_ssd_*: the in- and out-projections are no part of what
    # ssd_time_share.train has read under that prefix since PR 29
    with jax.named_scope("mx_mamba_proj"):
        zxbcdt = kept(_mm(data, in_proj_weight))
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:2 * d_in + 2 * g * n]
    dt = zxbcdt[..., 2 * d_in + 2 * g * n:]
    with jax.named_scope("mx_ssd_conv"):
        xbc = jax.nn.silu(kept(causal_conv1d(xbc, conv_weight, conv_bias)))
    x = xbc[..., :d_in].reshape(bsz, length, h, p)
    b = xbc[..., d_in:d_in + g * n].reshape(bsz, length, g, n)
    c = xbc[..., d_in + g * n:].reshape(bsz, length, g, n)
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = -jnp.exp(a_log.astype(_F32))
    y = kept(ssd_chunked(x, dt, a, b, c, chunk_size))
    with jax.named_scope("mx_ssd_gate"):
        y = y + d.astype(_F32)[:, None] * x.astype(_F32)
        y = y.reshape(bsz, length, d_in) * jax.nn.silu(z.astype(_F32))
        y = _rms_norm(y, norm_weight, eps=eps, num_groups=g)
    with jax.named_scope("mx_mamba_proj"):
        return _mm(y.astype(data.dtype), out_proj_weight)


# ---------------------------------------------------------------------------
# Gated DeltaNet
# ---------------------------------------------------------------------------
def _l2_norm(x, eps):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(_F32)
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def gated_delta_rule(q, k, v, beta, g, chunk=64):
    """The gated delta rule from a zero state, ``S' = exp(g_t) S_{t-1}``,
    ``u_t = beta_t (v_t - S'^T k_t)``, ``S_t = S' + k_t u_t^T``, ``o_t =
    S_t^T q_t``, in chunks. What a step writes depends on what the state
    holds for its key, so the steps of a chunk are tied by a unit lower
    triangular system: with ``G_i`` the sum of ``g`` up to step ``i`` of
    the chunk, ``A_ij = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i``
    and ``(I + A) [W | U] = [beta k exp(G) | beta v]`` solved by forward
    substitution (``lax.linalg.triangular_solve``), a chunk entered with
    state ``S`` writes ``V' = U - W S``, reads ``O = (q exp(G)) S +
    tril[(q_i . k_j) exp(G_i - G_j)] V'`` and leaves ``exp(G_last) S + (k
    exp(G_last - G))^T V'``. The system and every product that does not
    read the state are formed for all chunks at once; the chunks are then
    chained by a ``lax.scan`` that carries the state.

    ``q``, ``k``: (B, L, G, N), as the rule reads them (normalised, ``q``
    scaled); ``v``: (B, L, H, P) with ``H % G == 0``, key head ``j``
    serving value heads ``j H/G`` to ``(j + 1) H/G - 1``; ``beta``, ``g``:
    (B, L, H) float32, ``g <= 0``. The decays, the solve, the state and
    every sum in float32, the products' operands in ``v``'s dtype. Any
    ``L``: the tail is padded with steps of ``beta = 0`` and ``g = 0``,
    which write nothing and decay nothing. Returns (B, L, H, P) float32.

    Plain JAX, differentiated by JAX. The mixer around it,
    ``gated_delta_net``, takes this repo's kernels for the rule and the
    gated norm after it together (``ops.gdn_kernel``) where their rule of
    shapes takes them and the program is lowered for a TPU."""
    bsz, length, h, p = v.shape
    gk, n = k.shape[2], k.shape[3]
    r = h // gk
    c = int(chunk)
    pad = (-length) % c
    if pad:
        q, k, v, beta, g = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, beta, g))
    nc = (length + pad) // c
    dtype = v.dtype

    def heads(t):       # (B, L, H) -> (B, nc, G, r, c)
        return t.astype(_F32).reshape(bsz, nc, c, gk, r).transpose(
            0, 1, 3, 4, 2)

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=_F32)

    beta, cs = heads(beta), jnp.cumsum(heads(g), axis=-1)
    q = q.reshape(bsz, nc, c, gk, n).transpose(0, 1, 3, 2, 4)
    k = k.reshape(bsz, nc, c, gk, n).transpose(0, 1, 3, 2, 4)
    v = v.reshape(bsz, nc, c, gk, r, p).transpose(0, 1, 3, 4, 2, 5)
    seg = cs[..., :, None] - cs[..., None, :]           # (B,nc,G,r,i,j)
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    upto = jnp.exp(jnp.where(row >= col, seg, -jnp.inf))
    below = jnp.where(row > col, upto, 0.0)
    kk = dot("zcgin,zcgjn->zcgij", k, k)[:, :, :, None]
    system = beta[..., None] * kk * below               # strictly lower
    k32 = k.astype(_F32)[:, :, :, None]                 # (B,nc,G,1,c,N)
    rhs = jnp.concatenate(
        [k32 * (beta * jnp.exp(cs))[..., None],
         v.astype(_F32) * beta[..., None]], axis=-1)
    wu = lax.linalg.triangular_solve(system, rhs, left_side=True, lower=True,
                                     unit_diagonal=True)
    w, u = wu[..., :n], wu[..., n:]
    inside = dot("zcgin,zcgjn->zcgij", q, k)[:, :, :, None] * upto
    q_in = q.astype(_F32)[:, :, :, None] * jnp.exp(cs)[..., None]
    to_end = jnp.exp(cs[..., -1:] - cs)                 # (B,nc,G,r,c)
    k_out = k32 * to_end[..., None]
    leave = jnp.exp(cs[..., -1])                        # (B,nc,G,r)

    def one_chunk(state, xs):
        w, u, inside, q_in, k_out, leave = xs
        written = u - dot("zgrin,zgrnp->zgrip", w, state)
        out = dot("zgrin,zgrnp->zgrip", q_in, state) \
            + dot("zgrij,zgrjp->zgrip", inside, written)
        state = leave[..., None, None] * state \
            + dot("zgrin,zgrip->zgrnp", k_out, written)
        return state, out

    _, out = lax.scan(
        one_chunk, jnp.zeros((bsz, gk, r, n, p), _F32),
        tuple(jnp.moveaxis(t, 1, 0)
              for t in (w, u, inside, q_in, k_out, leave)))
    # (nc, B, G, r, c, P) -> (B, L, H, P)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(bsz, nc * c, h, p)[
        :, :length]


def _operands_plain(qkvz, conv_weight, heads):
    """The rule's ``(q, k, v)`` from the packed projection, plain JAX:
    ``silu(conv([q | k | v]))``, then ``q`` and ``k`` (B, L, G, N)
    L2-normalised a head in float32, ``q`` scaled by ``N ** -0.5``, in the
    projection's dtype; ``v`` (B, L, H, P) as it is."""
    bsz, length, _ = qkvz.shape
    wide = heads.keys * heads.n
    conv = 2 * wide + heads.values * heads.p
    qkv = jax.nn.silu(causal_conv1d(qkvz[..., :conv], conv_weight, None))
    q, k = (_l2_norm(t.reshape(bsz, length, heads.keys, heads.n), 1e-6)
            for t in (qkv[..., :wide], qkv[..., wide:2 * wide]))
    v = qkv[..., 2 * wide:].reshape(bsz, length, heads.values, heads.p)
    return (q * heads.n ** -0.5).astype(qkvz.dtype), k.astype(qkvz.dtype), v


def _gated_norm(o, qkvz, norm_weight, eps):
    """``rmsnorm(o) * norm_weight * silu(z)`` a value head in float32,
    ``z`` the last columns of the packed projection: (B, L, H P) in the
    projection's dtype."""
    bsz, length, hv, dv = o.shape
    with jax.named_scope("mx_gdn_gate"):
        z = qkvz[..., qkvz.shape[-1] - hv * dv:].astype(_F32).reshape(o.shape)
        y = _rms_norm(o, norm_weight, eps=eps) * jax.nn.silu(z)
    return y.reshape(bsz, length, hv * dv).astype(qkvz.dtype)


def _mixer_plain(qkvz, conv_weight, beta, g, norm_weight, heads, chunk, eps):
    """The mixer between its input products and its output product, plain
    JAX, each part under its scope."""
    with jax.named_scope("mx_gdn_conv"):
        q, k, v = _operands_plain(qkvz, conv_weight, heads)
    with jax.named_scope("mx_gdn_rule"):
        o = gated_delta_rule(q, k, v, beta, g, chunk)
    return _gated_norm(o, qkvz, norm_weight, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _mixer_kernels(qkvz, conv_weight, beta, g, norm_weight, heads, chunk, eps):
    """The mixer between its input products and its output product, (B, L,
    H P) in the projection's dtype, a program that takes its form when it
    is lowered. For a TPU two pairs of kernels: ``ops.gdn_conv_kernel``
    reads ``[q | k | v]`` where the projection wrote them and convolves,
    activates and normalises in one pass; ``ops.gdn_kernel`` runs the rule
    with a head's state and a chunk's system in VMEM and, where a chunk's
    output is still there, the gated norm, reading ``z`` from the packed
    rows in place. Backward in reverse: the rule's kernel goes back
    through the gated norm as it loads the output's cotangent and gives
    ``dz`` beside ``dq``, ``dk``, ``dv``; the convolution's kernel takes
    those three back to the rows; the projection's cotangent is put
    together once, ``[dq | dk | dv | dz]``. For any other platform
    ``_mixer_plain`` and JAX's own derivative of it. A recomputation unit
    keeps nothing of it: both passes read the kept projection, and what
    the backward kernels read of the forward (``q``, ``k``, ``v``, every
    chunk's entering state and inverse) lives from the forward to the
    backward inside the unit's backward pass, one layer at a time."""
    return _mixer_kernels_fwd(qkvz, conv_weight, beta, g, norm_weight, heads,
                              chunk, eps)[0]


def _mixer_kernels_fwd(qkvz, conv_weight, beta, g, norm_weight, heads, chunk,
                       eps):
    lead = qkvz.shape[:-1]
    keys, values = lead + (heads.keys, heads.n), lead + (heads.values, heads.p)
    chunks = gdn_kernel.steps(lead[1], chunk)[0] // chunk

    def kernels(x, w, beta, g, gamma):
        with jax.named_scope("mx_gdn_conv"):
            q, k, v = gdn_conv_kernel.forward(
                attn_kernel.counted_site(x, gdn_conv_kernel.GAUGE), w, heads)
        q, k, v = q.reshape(keys), k.reshape(keys), v.reshape(values)
        with jax.named_scope("mx_gdn_rule"):
            y, states, inverses = gdn_kernel.forward(
                attn_kernel.counted_site(q, gdn_kernel.GAUGE), k, v, beta, g,
                x, gamma, chunk=chunk, eps=eps)
        return y, q, k, v, states, inverses

    def plain(x, w, beta, g, gamma):
        # what the kernels keep has no part in this form's derivative
        return (_mixer_plain(x, w, beta, g, gamma, heads, chunk, eps),
                jnp.zeros(keys, x.dtype), jnp.zeros(keys, x.dtype),
                jnp.zeros(values, x.dtype),
                jnp.zeros(lead[:1] + (heads.values, chunks, heads.n, heads.p),
                          _F32),
                jnp.zeros(lead[:1] + (heads.values, chunks, chunk, chunk),
                          _F32))

    operands = (qkvz, conv_weight, beta, g, norm_weight)
    y, *kept_here = lax.platform_dependent(*operands, tpu=kernels,
                                           default=plain)
    return y, operands + tuple(kept_here)


def _mixer_kernels_bwd(heads, chunk, eps, res, dy):
    # the scopes are opened here because the call site stands under none: a
    # backward rule carries the scope its forward was called under, and a
    # kernel filed under ``mx_gdn_rule/mx_gdn_rule`` would stand outside
    # what ``^mx_gdn_rule$`` reads
    def kernels(x, w, beta, g, gamma, q, k, v, states, inverses, dy):
        with jax.named_scope("mx_gdn_rule"):
            dq, dk, dv, d_beta, d_g, dz, d_gamma = gdn_kernel.backward(
                q, k, v, beta, g, x, gamma, states, inverses, dy,
                chunk=chunk, eps=eps)
        with jax.named_scope("mx_gdn_conv"):
            d_rows, d_w = gdn_conv_kernel.backward(
                x, w, *(d.reshape(x.shape[:-1] + (-1,))
                        for d in (dq, dk, dv)), heads)
            d_x = jnp.concatenate(d_rows + (dz,), axis=-1)
        return d_x, d_w, d_beta, d_g, d_gamma

    def plain(x, w, beta, g, gamma, *rest):
        return jax.vjp(
            lambda *a: _mixer_plain(*a, heads, chunk, eps),
            x, w, beta, g, gamma)[1](rest[-1])

    return lax.platform_dependent(*res, dy, tpu=kernels, default=plain)


_mixer_kernels.defvjp(_mixer_kernels_fwd, _mixer_kernels_bwd)


@register_op("GatedDeltaNet", names_its_parts=True)
def gated_delta_net(data, qkvz_weight, ba_weight, conv_weight, dt_bias,
                    a_log, norm_weight, out_weight, num_k_heads=1,
                    num_v_heads=1, key_dim=128, value_dim=128, chunk_size=64,
                    eps=1e-6, **kw):
    """The Gated DeltaNet mixer (linear attention by the gated delta
    rule) over the ``num_k_heads`` key heads, ``key_dim`` wide, and the
    ``num_v_heads`` value heads, ``value_dim`` wide, held here.

    ``data``: (B, L, hidden). ``qkvz_weight``: (2 Hk key_dim + 2 Hv
    value_dim, hidden), rows ``[q | k | v | z]`` grouped by part (a fixed
    permutation of a layout that interleaves them by key head);
    ``ba_weight``: (2 Hv, hidden), rows ``[b | a]``; ``conv_weight``: (2
    Hk key_dim + Hv value_dim, K), a causal depthwise convolution
    without bias over ``[q | k | v]``, not over the gate ``z``;
    ``dt_bias``, ``a_log``: (Hv,); ``norm_weight``: (value_dim,), the
    gated norm's, one for all heads; ``out_weight``: (hidden, Hv
    value_dim).

    ``[q | k | v] = silu(conv(.))``; a value head's ``beta =
    sigmoid(b)`` and log decay ``g = -exp(a_log) softplus(a + dt_bias)``
    in float32; ``q`` and ``k`` L2-normalised over a head, ``q`` scaled by
    ``key_dim ** -0.5``; ``gated_delta_rule`` in chunks of
    ``chunk_size``; ``rmsnorm(o) * norm_weight * silu(z)`` a head in
    float32; the output product. Two forms of one algorithm, chosen by
    what the program can see, not by the caller. Where both kernel pairs'
    rules of shapes take the mixer (``gdn_conv_kernel.takes``,
    ``gdn_kernel.takes``: heads whole lane tiles of 128, 2 to 9 taps, the
    chunk whole sublane tiles and at most 128, ``z`` whole blocks of a key
    head's value columns into the packed rows, one dtype, the blocks
    within VMEM) and the program is lowered for a TPU, everything between
    the input products and the output product is this repo's kernels under
    one ``custom_vjp`` (``_mixer_kernels``): the convolution, SiLU and the
    heads' norm read from the packed projection in place, then the rule
    with a head's state and a chunk's system in VMEM and the gated norm
    where a chunk's output is still there, ``z`` read from the same packed
    rows; backward the same kernels' derivatives in reverse and the
    projection's cotangent put together once. Everywhere else (other
    shapes, another backend) the same lines in plain JAX, differentiated
    by JAX. The gauges ``gdn::kernel_sites`` and
    ``gdn::conv_kernel_sites`` say which. Scopes: ``mx_gdn_proj`` (the
    three products), ``mx_gdn_conv``, ``mx_gdn_rule`` (decays and the
    rule; through the kernels the gated norm too, which is inside them),
    ``mx_gdn_gate`` (the gated norm in plain JAX: it names no instruction
    of a program that took the kernels). A recomputation unit around it
    keeps both input products; the convolution and the rule are computed
    again (the chain's own backward wants every chunk's entering state,
    which no unit holds between its passes: the kernels write them in the
    unit's recomputation and read them in its backward).

    Returns (B, L, hidden), this share's partial sum."""
    hk, hv, dk, dv = int(num_k_heads), int(num_v_heads), int(key_dim), \
        int(value_dim)
    chunk = int(chunk_size)
    with jax.named_scope("mx_gdn_proj"):
        qkvz = kept(_mm(data, qkvz_weight))
        ba = kept(_mm(data, ba_weight))
    heads = gdn_conv_kernel.Heads(hk, dk, hv, dv)
    with jax.named_scope("mx_gdn_rule"):
        beta = jax.nn.sigmoid(ba[..., :hv].astype(_F32))
        g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
            ba[..., hv:].astype(_F32) + dt_bias.astype(_F32))
    taken = qkvz.dtype == data.dtype and gdn_conv_kernel.takes(
        heads, conv_weight.shape[1], qkvz.dtype, conv_weight.dtype) \
        and gdn_kernel.takes(dk, dv, chunk, qkvz.dtype, hv // hk,
                             gate_offset=2 * hk * dk + hv * dv)
    y = (_mixer_kernels if taken else _mixer_plain)(
        qkvz, conv_weight, beta, g, norm_weight, heads, chunk, float(eps))
    with jax.named_scope("mx_gdn_proj"):
        return _mm(y, out_weight)


# ---------------------------------------------------------------------------
# gated short convolution
# ---------------------------------------------------------------------------
@register_op("GatedShortConv", names_its_parts=True)
def gated_short_conv(data, in_weight, conv_weight, out_weight, **kw):
    """The gated short convolution (the ``lfm2`` family's ``conv``
    layers' mixer): ``[B | C | z] = W_in u``, ``c = conv(B * z)``, ``W_out
    (C * c)``, the convolution depthwise and causal over
    ``conv_weight.shape[1]`` taps, the last on the current token, zeros
    before the sequence, no bias. Both gates are linear: there is no
    activation anywhere in it.

    ``data``: (B, L, hidden). ``in_weight``: (3 C, hidden), rows ``[B | C
    | z]`` in that order; ``conv_weight``: (C, K); ``out_weight``:
    (hidden, C). The channels are no share of a deployment's: every chip
    that shares the layer holds the mixer whole.

    Both products sum in float32 and round to ``data``'s dtype
    (``_mm``, as every mixer's here). Between them both gates, the taps
    and the taps' sum are in ``data``'s dtype, as the sibling mixers'
    convolutions are (``mamba2_mixer``, ``_operands_plain``): ``B * z`` is
    what memory holds between the gate and the taps (every tap reads it
    at another shift, so the compiler writes it out), and in float32 it
    would be twice the bytes of a chain that is bound by them.

    Scopes: ``mx_sconv_proj`` (both products), ``mx_sconv_gate`` (``B *
    z`` and ``C * c``), ``mx_sconv_conv`` (the taps, ``causal_conv1d``).
    A recomputation unit around it keeps the packed projection (3 C a
    token: a second forward would pay the 3 C x hidden product again); the
    gates and the taps are computed again.

    Returns (B, L, hidden)."""
    with jax.named_scope("mx_sconv_proj"):
        bcz = kept(_mm(data, in_weight))
    width = bcz.shape[-1] // 3
    b, c, z = (bcz[..., i * width:(i + 1) * width] for i in range(3))
    with jax.named_scope("mx_sconv_gate"):
        gated = b * z
    with jax.named_scope("mx_sconv_conv"):
        conv = causal_conv1d(gated, conv_weight, None)
    with jax.named_scope("mx_sconv_gate"):
        y = c * conv
    with jax.named_scope("mx_sconv_proj"):
        return _mm(y, out_weight)


# ---------------------------------------------------------------------------
# LatentMoE
# ---------------------------------------------------------------------------
def grouped_product(buf, w1, w2):
    """Every held expert's ``relu2(rows W1) W2`` over its slice of the
    receive buffer: ``buf`` (E, rows, latent), ``w1`` (E, latent, ff),
    ``w2`` (E, ff, latent). The whole buffer is computed, whether a row
    holds a token or nothing: the work is the same for every routing, and
    a product that skipped empty tiles would make a step's time depend on
    the seed. Do not write one. (The other expert body, gated on the full
    hidden vector: ``pooled_gated_product``, under the same scopes and
    the same rule.)"""
    with jax.named_scope("mx_moe_gmm_up"):
        hid = _relu2(kept(jnp.einsum("erd,edf->erf", buf, w1,
                                     preferred_element_type=_F32))
                     ).astype(buf.dtype)
    with jax.named_scope("mx_moe_gmm_down"):
        return kept(jnp.einsum("erf,efd->erd", hid, w2,
                               preferred_element_type=_F32
                               ).astype(buf.dtype))


def _gating(gate, up):
    """``silu(gate) * up`` in float32, in the products' dtype."""
    return (jax.nn.silu(gate.astype(_F32)) * up.astype(_F32)
            ).astype(gate.dtype)


def _ragged(rows, w, sizes):
    return lax.ragged_dot(rows, w, sizes, preferred_element_type=rows.dtype)


def pooled_gated_product(buf, w1, w3, w2, sizes):
    """Every held expert's ``(silu(rows W1) * rows W3) W2`` over its rows
    of the pool: ``buf`` (rows, hidden) sorted by expert, ``sizes`` (E,)
    int32 the rows each expert has there, summing to ``rows``; ``w1``,
    ``w3`` (E, hidden, ff), ``w2`` (E, ff, hidden); the activation and the
    gating in float32 on the products as the compute dtype holds them
    (``gated_mlp``'s arithmetic), every product summed in float32 and
    rounded once.

    Two forms of the three grouped products, chosen by what the program
    can see, not by the caller. Where the kernels' tiling rule takes the
    shapes (``gmm_kernel.tile_rows``: both widths whole lane tiles, the
    pool whole tiles of rows, the blocks within VMEM) and the program is
    lowered for a TPU, this repo's own kernels under one ``custom_vjp``
    (``ops.gmm_kernel``): the pool walked in tiles, group by group, an
    expert's matrices in VMEM while its rows pass, forward, the rows'
    gradients (contracted over the weights' stored minor dimension: no
    weight is turned) and the weights' gradients (a group's float32 sum
    held in VMEM over its tiles). Everywhere else (other shapes, another
    backend) three ``lax.ragged_dot`` and JAX's derivative of them.

    A recomputation unit around it keeps both up-products and the
    down-product and, where the kernels may run, ``sizes`` and the walk
    (a few hundred bytes); ``hid`` is formed again, the products are not.

    Every row of the pool is some expert's and is computed, whether it
    holds a token or nothing: ``grouped_product``'s rule."""
    tile = gmm_kernel.tile_rows(buf.shape[0], buf.shape[1], w1.shape[2],
                                buf.dtype)
    if tile is not None:
        return _pooled_kernels(buf, w1, w3, w2, sizes, tile)
    with jax.named_scope("mx_moe_gmm_up"):
        hid = _gating(*(kept(_ragged(buf, w, sizes)) for w in (w1, w3)))
    with jax.named_scope("mx_moe_gmm_down"):
        return kept(_ragged(hid, w2, sizes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _pooled_kernels(buf, w1, w3, w2, sizes, tile):
    """``pooled_gated_product`` whose program takes its form when it is
    lowered: for a TPU the kernels of ``ops.gmm_kernel`` over tiles of
    ``tile`` rows, forward and backward, for any other platform the
    ragged products and JAX's derivative of each. Either way the unit
    around it keeps ``gate``, ``up`` and the result, and its backward pass
    runs no forward product a second time."""
    return _pooled_kernels_fwd(buf, w1, w3, w2, sizes, tile)[0]


def _pooled_kernels_fwd(buf, w1, w3, w2, sizes, tile):
    sizes = kept(sizes)
    with jax.named_scope("mx_moe_gmm_up"):
        walk = tuple(kept(t) for t in gmm_kernel.visits(
            sizes, buf.shape[0], tile))
        gate, up, hid = lax.platform_dependent(
            buf, w1, w3, sizes, *walk,
            tpu=lambda buf, w1, w3, sizes, *walk: gmm_kernel.up(
                attn_kernel.counted_site(buf, gmm_kernel.GAUGE), w1, w3,
                walk, tile),
            default=lambda buf, w1, w3, sizes, *walk: _ragged_up(
                buf, w1, w3, sizes))
        gate, up = kept(gate), kept(up)
    with jax.named_scope("mx_moe_gmm_down"):
        out = kept(lax.platform_dependent(
            hid, w2, sizes, *walk,
            tpu=lambda hid, w2, sizes, *walk: gmm_kernel.down(
                hid, w2, walk, tile),
            default=lambda hid, w2, sizes, *walk: _ragged(hid, w2, sizes)))
    return out, (buf, w1, w3, w2, sizes, walk, gate, up)


def _ragged_up(buf, w1, w3, sizes):
    gate, up = (_ragged(buf, w, sizes) for w in (w1, w3))
    return gate, up, _gating(gate, up)


def _ragged_down_backward(d_out, gate, up, w2, sizes):
    hid, gating_vjp = jax.vjp(_gating, gate, up)
    d_hid, dw2 = jax.vjp(lambda h, w: _ragged(h, w, sizes), hid, w2)[1](d_out)
    return gating_vjp(d_hid) + (dw2,)


def _ragged_up_backward(buf, d_gate, d_up, w1, w3, sizes):
    (d_buf, dw1), (d_buf3, dw3) = (
        jax.vjp(lambda b, w: _ragged(b, w, sizes), buf, w)[1](d)
        for w, d in ((w1, d_gate), (w3, d_up)))
    return d_buf + d_buf3, dw1, dw3


def _pooled_kernels_bwd(tile, res, d_out):
    buf, w1, w3, w2, sizes, walk, gate, up = res
    with jax.named_scope("mx_moe_gmm_down"):
        d_gate, d_up, dw2 = lax.platform_dependent(
            d_out, gate, up, w2, sizes, *walk,
            tpu=lambda d_out, gate, up, w2, sizes, *walk:
                gmm_kernel.down_backward(d_out, gate, up, w2, walk, tile),
            default=lambda d_out, gate, up, w2, sizes, *walk:
                _ragged_down_backward(d_out, gate, up, w2, sizes))
    with jax.named_scope("mx_moe_gmm_up"):
        d_buf, dw1, dw3 = lax.platform_dependent(
            buf, d_gate, d_up, w1, w3, sizes, *walk,
            tpu=lambda buf, d_gate, d_up, w1, w3, sizes, *walk:
                gmm_kernel.up_backward(buf, d_gate, d_up, w1, w3, walk, tile),
            default=lambda buf, d_gate, d_up, w1, w3, sizes, *walk:
                _ragged_up_backward(buf, d_gate, d_up, w1, w3, sizes))
    return d_buf, dw1, dw3, dw2, None


_pooled_kernels.defvjp(_pooled_kernels_fwd, _pooled_kernels_bwd)


def route(scores_in, router_weight, router_bias, top_k, scaling,
          norm_topk=True, scoring="sigmoid", norm_topk_eps=0.0):
    """Scores over every expert of the model (``scoring``: each expert's
    ``sigmoid``, or a ``softmax`` over all of them, float32), the
    ``top_k`` by ``score + bias`` chosen, the chosen scores normalised to
    sum 1 (divided by their sum ``+ norm_topk_eps``, which the
    ``lfm2_moe`` family adds) and scaled. Returns ``(gate (T, E_all)
    float32, zero where not chosen; chosen (T, E_all) bool)``. Ties at
    the ``top_k``-th place are all taken (between floats they do not
    occur)."""
    with jax.named_scope("mx_moe_score"):
        logits = kept(lax.dot_general(
            scores_in, router_weight, (((1,), (1,)), ((), ())),
            preferred_element_type=_F32))
    with jax.named_scope("mx_moe_route"):
        s = {"sigmoid": jax.nn.sigmoid,
             "softmax": jax.nn.softmax}[scoring](logits)
        biased = s + router_bias.astype(_F32)
        # the threshold alone stands for the choice: a unit that keeps it
        # (T floats) finds gate and mask again without a second top_k
        kth = kept(lax.top_k(biased, int(top_k))[0][:, -1:])
        chosen = biased >= lax.stop_gradient(kth)
        gate = jnp.where(chosen, s, 0.0)
        if norm_topk:
            total = jnp.sum(gate, -1, keepdims=True)
            # no addition of zero: without the option the text it had
            gate = gate / (total + norm_topk_eps if norm_topk_eps else total)
        return gate * scaling, chosen


def balanced_bias(router_bias, load, rate):
    """Auxiliary-loss-free balancing, one step of it: ``b_e + rate *
    sign(mean load - load_e)`` from the loads the router's choice gave
    every expert (here: over the tokens of this call; a deployment sums
    them over its data-parallel groups first)."""
    load = load.astype(_F32)
    return router_bias.astype(_F32) \
        + rate * jnp.sign(jnp.mean(load) - load)


@register_op("LatentMoE", num_outputs=3, names_its_parts=True)
def latent_moe(data, router_weight, router_bias, down_weight, up_weight,
               w1, w2, shared_w1, shared_w2, counters=None, expert_ids=(0,),
               top_k=1, buffer_rows=0, scaling=1.0, norm_topk=True,
               bias_rate=0.0, **kw):
    """A mixture of experts that works in a latent, for the experts held
    here.

    ``data``: (B, L, hidden). The router (``router_weight`` (E_all,
    hidden), ``router_bias`` (E_all,)) sees every expert of the model;
    ``expert_ids`` names the ones whose weights ``w1`` (E, latent, ff),
    ``w2`` (E, ff, latent) are here. The (token, expert) pairs that chose
    a held expert are sorted into a static buffer of ``buffer_rows`` rows,
    ``buffer_rows / E`` an expert; pairs beyond an expert's rows are
    counted, not computed. The rows' way into the buffer and back, each
    times its gate and added up by token in float32 (``_rows_to_pool``,
    ``_combine``), is the kernels of ``ops.moe_rows_kernel`` where the
    program is lowered for a TPU and their tiling rule takes the shapes
    (the slices are one pool of equal sizes to them), ``jnp.take`` and a
    scatter-add everywhere else; the gauge ``moe::rows_kernel_sites``
    says which. ``shared_w1`` (ff_s, hidden), ``shared_w2`` (hidden,
    ff_s): the shared expert's columns held here.

    Returns ``(out (B, L, hidden), counters (4,) float32, bias (E_all,)
    float32)``. The counters: the pairs that chose a held expert, the
    pairs beyond the buffer (added to ``counters``' own, when given: they
    add up from call to call), the largest expert's load over the mean
    load (over all experts), and the share of the buffer's rows that hold
    a pair. The bias: ``router_bias`` after one step of the balancing
    rule at ``bias_rate`` on this call's loads (``balanced_bias``), for
    the caller to keep for the next call; no gradient reaches it."""
    bsz, length, hidden = data.shape
    u = data.reshape(bsz * length, hidden)
    gate_all, chosen_all = route(u, router_weight, router_bias, top_k,
                                 scaling, norm_topk)
    with jax.named_scope("mx_moe_latent"):
        v = _mm(u, down_weight)                         # (T, latent)
    buf, token, row_gate, load, count, cap = _dispatch(
        v, gate_all, chosen_all, expert_ids, buffer_rows)
    routed = _combine(grouped_product(buf, w1, w2), row_gate, token,
                      u.shape[0], _slice_starts(len(expert_ids), cap))
    with jax.named_scope("mx_moe_latent"):
        routed = _mm(kept(routed.astype(data.dtype)), up_weight)
    with jax.named_scope("mx_moe_shared"):
        shared = _mm(_relu2(kept(_mm(u, shared_w1))), shared_w2)
    stats = _moe_stats(load, count, cap, counters)
    return (routed + shared).reshape(bsz, length, hidden), stats, \
        lax.stop_gradient(balanced_bias(router_bias, load, bias_rate))


def _dispatch(rows, gate_all, chosen_all, expert_ids, buffer_rows):
    """Sort the (token, expert) pairs that chose a held expert into the
    static buffer. ``rows`` (T, width): what an expert reads of a token.
    Returns the buffer (E, cap, width), each of its rows' token (E, cap;
    T where a row holds none) and gate (E, cap), every expert's load
    (E_all,), the held experts' pair counts (E,) and ``cap``, the rows an
    expert has."""
    t = rows.shape[0]
    ids = jnp.asarray(tuple(int(e) for e in expert_ids), jnp.int32)
    n_held = ids.shape[0]
    cap = int(buffer_rows) // n_held
    with jax.named_scope("mx_moe_dispatch"):
        load = jnp.sum(chosen_all, axis=0, dtype=_F32)
        gate = jnp.take(gate_all, ids, axis=1)          # (T, E)
        chosen = jnp.take(chosen_all, ids, axis=1)
        count = jnp.sum(chosen, axis=0, dtype=jnp.int32)
        # an expert's tokens in order: a stable sort brings them first
        order = jnp.argsort(jnp.logical_not(chosen), axis=0, stable=True)
        order = order[:cap]
        if cap > t:
            order = jnp.pad(order, ((0, cap - t), (0, 0)))
        valid = jnp.arange(cap)[None, :] < count[:, None]
        # what the sort gave and the rows it gathered, kept: no backward
        # pass sorts again, nor projects to the latent for the rows' sake
        token = kept(jnp.where(valid, order.T, t))      # (E, cap); t: none
        buf = kept(_rows_to_pool(rows, token, _slice_starts(n_held, cap)))
        pair = jnp.where(valid, jnp.arange(n_held)[:, None] * t + token,
                         n_held * t)
        row_gate = kept(jnp.take(gate.T.reshape(-1), pair, mode="fill",
                                 fill_value=0))
    return buf, token, row_gate, load, count, cap


def _dispatch_pooled(rows, gate_all, chosen_all, expert_ids, buffer_rows):
    """Sort the (token, expert) pairs that chose a held expert, expert by
    expert, into ONE pool of ``buffer_rows`` rows that the held experts
    share: an expert takes as many rows as it drew pairs, so a pair is
    beyond the buffer only when the held experts' pairs together are more
    than its rows (``_dispatch`` gives every expert a slice of its own,
    and one expert's slice overflows while its neighbours' stand empty).
    Returns the pool (rows, width), each row's token (T where it holds
    none) and gate, every expert's load (E_all,), the held experts' pair
    counts (E,) and the rows each has in the pool (E,) int32: the rows no
    pair took are zeros and go to the last expert, so the sizes sum to
    ``buffer_rows`` whatever the routing."""
    t = rows.shape[0]
    ids = jnp.asarray(tuple(int(e) for e in expert_ids), jnp.int32)
    n_held = ids.shape[0]
    cap = int(buffer_rows)
    with jax.named_scope("mx_moe_dispatch"):
        load = jnp.sum(chosen_all, axis=0, dtype=_F32)
        gate = jnp.take(gate_all, ids, axis=1)          # (T, E)
        chosen = jnp.take(chosen_all, ids, axis=1)
        count = jnp.sum(chosen, axis=0, dtype=jnp.int32)
        # pairs expert-major, e * T + token: a stable sort brings the
        # chosen first, expert by expert and each expert's in token order
        order = jnp.argsort(jnp.logical_not(chosen.T.reshape(-1)),
                            stable=True)[:cap]
        if cap > n_held * t:
            order = jnp.pad(order, (0, cap - n_held * t))
        ends = jnp.minimum(jnp.cumsum(count), cap)
        valid = jnp.arange(cap) < ends[-1]
        # kept as ``_dispatch`` keeps them: no backward pass sorts again
        token = kept(jnp.where(valid, order % t, t))    # (cap,); t: none
        taken = jnp.diff(ends, prepend=0)       # an expert's rows with a pair
        buf = kept(_rows_to_pool(rows, token, ends - taken))
        row_gate = kept(jnp.take(gate.T.reshape(-1),
                                 jnp.where(valid, order, n_held * t),
                                 mode="fill", fill_value=0))
        sizes = taken.at[-1].add(cap - ends[-1])
    return buf, token, row_gate, load, count, sizes


def _take_rows(rows, token):
    return jnp.take(rows, token, axis=0, mode="fill", fill_value=0)


def _slice_starts(n_held, cap):
    """The first row of each expert's slice of ``cap`` rows."""
    return jnp.arange(n_held, dtype=jnp.int32) * cap


def _rows_to_pool(rows, token, starts):
    """``rows[token]`` with zeros where ``token`` names no row (``rows``'
    count): the buffer in ``token``'s shape. Two forms, chosen by what the
    program can see. Where ``moe_rows_kernel.takes`` takes the shapes and
    the program is lowered for a TPU, that module's kernels under one
    ``custom_vjp``: ``rows_by_index`` forward, and backward
    ``rows_by_token``, a token's rows of the cotangent added up in float32
    and rounded once (no scatter-add; ``jnp.take``'s derivative sums them
    in the compute dtype). Everywhere else ``jnp.take`` and its
    derivative. ``starts`` (E,) int32: the first row of each held
    expert's, by which the buffer is sorted."""
    tile = moe_rows_kernel.takes(token.size, rows.shape[0], rows.shape[1],
                                 rows.dtype)
    if tile is None:
        return _take_rows(rows, token)
    buf = _pool_rows(rows, token.reshape(-1), starts, rows.shape[0], tile)
    return buf.reshape(token.shape + rows.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pool_rows(rows, token, starts, tokens, tile):
    return _pool_rows_fwd(rows, token, starts, tokens, tile)[0]


def _pool_rows_fwd(rows, token, starts, tokens, tile):
    buf = lax.platform_dependent(
        rows, token,
        tpu=lambda rows, token: moe_rows_kernel.rows_by_index(
            token, attn_kernel.counted_site(rows, moe_rows_kernel.GAUGE),
            tile),
        default=_take_rows)
    return buf, (token, starts)


def _pool_rows_bwd(tokens, tile, res, d_buf):
    token, starts = res
    plain = jax.linear_transpose(
        lambda rows: _take_rows(rows, token),
        jax.ShapeDtypeStruct((tokens,) + d_buf.shape[1:], d_buf.dtype))
    # under ``mx_moe_dispatch``, the scope the forward was called in
    d_rows = lax.platform_dependent(
        d_buf, token, starts,
        tpu=lambda d_buf, token, starts: moe_rows_kernel.rows_by_token(
            token, None, d_buf, tokens, starts, tile),
        default=lambda d_buf, token, starts: plain(d_buf)[0])
    return d_rows, None, None


_pool_rows.defvjp(_pool_rows_fwd, _pool_rows_bwd)


def _scatter_add(out_buf, row_gate, token, t):
    """``_combine`` by a scatter-add into a zeroed float32 array."""
    weighted = out_buf.astype(_F32) * row_gate[..., None]
    return jnp.zeros((t, out_buf.shape[-1]), _F32).at[
        token.reshape(-1)].add(
            weighted.reshape(-1, out_buf.shape[-1]), mode="drop").astype(
                out_buf.dtype)


def _combine(out_buf, row_gate, token, t, starts):
    """The buffer's rows, each times its gate, added up by token: every
    product and every token's sum in float32, rounded once to the buffer's
    dtype; (T, width). Two forms, as ``_rows_to_pool``'s: where
    ``moe_rows_kernel.takes`` takes the shapes and the program is lowered
    for a TPU, ``rows_by_token`` forward (the sums live in VMEM; no zeroed
    float32 (T, width) array, no scatter, no pass that rounds it), and
    backward ``jnp.take`` of the cotangent's rows by token, times the gates
    for the buffer's and times the buffer, added up over the width in
    float32, for the gates'; everywhere else the scatter-add forward.
    ``starts``: ``_rows_to_pool``'s."""
    with jax.named_scope("mx_moe_combine"):
        width = out_buf.shape[-1]
        tile = moe_rows_kernel.takes(token.size, t, width, out_buf.dtype)
        if tile is None:
            return _scatter_add(out_buf, row_gate, token, t)
        return _pool_sum(out_buf.reshape(-1, width), row_gate.reshape(-1),
                         token.reshape(-1), starts, t, tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pool_sum(out_buf, row_gate, token, starts, t, tile):
    return _pool_sum_fwd(out_buf, row_gate, token, starts, t, tile)[0]


def _pool_sum_fwd(out_buf, row_gate, token, starts, t, tile):
    routed = lax.platform_dependent(
        out_buf, row_gate, token, starts,
        tpu=lambda out_buf, row_gate, token, starts:
            moe_rows_kernel.rows_by_token(token, row_gate, out_buf, t, starts,
                                          tile),
        default=lambda out_buf, row_gate, token, starts: _scatter_add(
            out_buf, row_gate, token, t))
    return routed, (out_buf, row_gate, token)


def _pool_sum_bwd(t, tile, res, d_routed):
    # under ``mx_moe_combine``, the scope the forward was called in; on
    # every platform what JAX's derivative of the scatter-add computes,
    # with the cotangent's rows gathered before they are widened
    out_buf, row_gate, token = res
    rows = _take_rows(d_routed, token).astype(_F32)
    return (rows * row_gate[:, None]).astype(out_buf.dtype), \
        jnp.sum(out_buf.astype(_F32) * rows, -1).astype(row_gate.dtype), \
        None, None


_pool_sum.defvjp(_pool_sum_fwd, _pool_sum_bwd)


def _moe_stats(load, count, cap, counters):
    """``nn.MOE_COUNTERS`` of one call (the pairs beyond the buffer added
    to ``counters``' own, when given); no gradient reaches them."""
    placed = jnp.sum(jnp.minimum(count, cap)).astype(_F32)
    held = jnp.sum(count).astype(_F32)
    before = 0.0 if counters is None else counters[1].astype(_F32)
    return lax.stop_gradient(jnp.stack([
        held, before + held - placed,
        jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
        placed / (count.shape[0] * cap)]))


@register_op("GatedMoE", num_outputs=3, names_its_parts=True)
def gated_moe(data, router_weight, router_bias, w1, w3, w2,
              shared_gate_up_weight=None, shared_down_weight=None,
              counters=None, shared_gate_weight=None, expert_ids=(0,),
              top_k=1, buffer_rows=0, scaling=1.0, norm_topk=True,
              bias_rate=0.0, scoring="sigmoid", norm_topk_eps=0.0, **kw):
    """A mixture of gated experts on the full hidden vector, for the
    experts held here: ``latent_moe``'s router, combine, counters and
    balancing step (one copy of each: ``route``, ``_combine``,
    ``_moe_stats``, ``balanced_bias``) around another expert body and
    another buffer. An expert is ``W2 (silu(W1 u) * W3 u)``: ``w1``,
    ``w3`` (E, hidden, ff), ``w2`` (E, ff, hidden), no latent projection
    on either side (``pooled_gated_product``: the grouped-matmul kernels
    of ``ops.gmm_kernel`` where the program is lowered for a TPU and the
    widths are whole lane tiles, ``lax.ragged_dot`` everywhere else; the
    gauge ``moe::gmm_kernel_sites`` says which; the rows reach the pool
    and leave it as ``latent_moe``'s do, by the row kernels or the plain
    moves, under the gauge ``moe::rows_kernel_sites``). The ``buffer_rows``
    rows are ONE pool the held experts share (``_dispatch_pooled``): the
    family trains without dropping a token, and a slice of its own for
    each expert overflowed on the chip while the pool stood a quarter
    empty (PERF.md, PR 37), so a pair is beyond the buffer only when the
    held experts' pairs together outnumber its rows; the counters read
    the pool (``buffer_fill`` its filled share). The shared experts are
    one gated MLP, ``shared_gate_up_weight`` (2 ff_s, hidden) and
    ``shared_down_weight`` (hidden, ff_s), as ``gated_mlp`` takes them;
    with ``shared_gate_weight`` (1, hidden) a token's share of them is
    ``sigmoid(u . w)``, a gate of their own. A layer with no shared
    expert (the ``lfm2_moe`` family's) gives ``None`` for both shared
    weights: the routed sum alone, and no ``mx_moe_shared`` scope
    (``routed_moe`` is that call under an operator's name of its own).
    ``scoring``, ``norm_topk_eps``: ``route``'s. Arguments, counters and
    returns as ``latent_moe``'s."""
    bsz, length, hidden = data.shape
    u = data.reshape(bsz * length, hidden)
    gate_all, chosen_all = route(u, router_weight, router_bias, top_k,
                                 scaling, norm_topk, scoring, norm_topk_eps)
    buf, token, row_gate, load, count, sizes = _dispatch_pooled(
        u, gate_all, chosen_all, expert_ids, buffer_rows)
    routed = _combine(pooled_gated_product(buf, w1, w3, w2, sizes), row_gate,
                      token, u.shape[0], jnp.cumsum(sizes) - sizes)
    shared = None
    if shared_gate_up_weight is not None:
        with jax.named_scope("mx_moe_shared"):
            shared = gated_mlp(u, shared_gate_up_weight, shared_down_weight)
            if shared_gate_weight is not None:
                open_ = jax.nn.sigmoid(lax.dot_general(
                    u, shared_gate_weight, (((1,), (1,)), ((), ())),
                    preferred_element_type=_F32))
                shared = (shared.astype(_F32) * open_).astype(shared.dtype)
    # one pool: the held experts' pairs together against all its rows
    stats = _moe_stats(load, jnp.sum(count)[None], int(buffer_rows),
                       counters)
    out = routed.astype(data.dtype)
    if shared is not None:
        out = out + shared
    return out.reshape(bsz, length, hidden), stats, \
        lax.stop_gradient(balanced_bias(router_bias, load, bias_rate))


@register_op("RoutedMoE", num_outputs=3, names_its_parts=True)
def routed_moe(data, router_weight, router_bias, w1, w3, w2, counters=None,
               **attrs):
    """``gated_moe`` of a layer with no shared expert: its operands
    without the shared weights, for callers that pass operands by
    position alone (``nn.GatedMoE(shared_units=0)``)."""
    return gated_moe(data, router_weight, router_bias, w1, w3, w2, None,
                     None, counters, **attrs)


# ---------------------------------------------------------------------------
# causal grouped-query attention
# ---------------------------------------------------------------------------
def _attention_block(q, k, v, bias, scale):
    """One (query block, key block) pair of ``parallel.ring``'s
    recurrence, with the scores and the sums in float32 whatever the
    inputs' dtype: ``(out, row_max, row_sum)``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=_F32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=_F32)
    return o, m, jnp.sum(p, axis=-1)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 for a
    factor of 1 or less): its square scales the softmax where a
    configuration gives ``mscale_all_dim`` (``nn.LatentAttention``)."""
    factor = float(factor)
    return 0.1 * float(mscale) * np.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(dim, theta=10000.0, yarn=None):
    """The ``dim // 2`` rotary frequencies of a ``dim``-wide head, float32:
    ``f_i = theta^(-2i/dim)``. With ``yarn = (factor, original length,
    beta_fast, beta_slow)`` the YaRN rule (arXiv:2309.00071, "NTK by
    parts"): a pair that turns more than ``beta_fast`` times over the
    original length keeps ``f_i``, one that turns fewer than ``beta_slow``
    times gets ``f_i / factor``, and between the two pairs ``low =
    floor(d(beta_fast))`` and ``high = ceil(d(beta_slow))``, ``d(beta) =
    dim ln(original / (2 pi beta)) / (2 ln theta)``, the two are mixed by
    ``ramp_i = clip((i - low) / (high - low), 0, 1)``: ``f_i (1 - ramp_i)
    + f_i / factor * ramp_i``."""
    i = np.arange(0, dim, 2)
    f = 1.0 / float(theta) ** (i / dim)
    if yarn is not None:
        factor, original, fast, slow = (float(v) for v in yarn)

        def pair(beta):
            return dim * np.log(original / (2 * np.pi * beta)) \
                / (2 * np.log(float(theta)))

        low = max(np.floor(pair(fast)), 0)
        high = min(np.ceil(pair(slow)), dim - 1)
        ramp = np.clip((i / 2 - low) / max(high - low, 1e-3), 0, 1)
        f = f * (1 - ramp) + f / factor * ramp
    return np.asarray(f, np.float32)


@register_op("RoPE", names_its_parts=True)
def rope(data, theta=10000.0, rotary_dim=None, yarn=None, mscale=None,
         **kw):
    """Rotary position encoding over the whole head, in the
    ``rotate_half`` convention: with ``x = [x1 | x2]`` the two halves of
    a head, position ``t`` and ``angle_i = t * theta^(-2i/D)`` for ``i <
    D/2``, ``[x1 cos - x2 sin | x2 cos + x1 sin]``. With ``rotary_dim``
    below ``D`` the head's first ``rotary_dim`` elements are rotated so,
    as a head of that width, and the rest go through as they are. With
    ``yarn = (factor, original length, beta_fast, beta_slow)`` the
    frequencies are YaRN's (``rope_frequencies``). With ``mscale`` cos
    and sin are multiplied by it in float32 (YaRN's attention factor
    where a configuration puts it on the rotation): the rotated elements
    alone are scaled, so under ``rotary_dim`` the rotated part of a score
    carries its square and the rest does not. ``data``: (B, L, H, D),
    position = index along ``L``; the angles and the rotation in float32,
    the result in ``data``'s dtype."""
    if rotary_dim is not None and int(rotary_dim) < data.shape[-1]:
        r = int(rotary_dim)
        return jnp.concatenate([rope(data[..., :r], theta, yarn=yarn,
                                     mscale=mscale),
                                data[..., r:]], axis=-1)
    length, d = data.shape[1], data.shape[-1]
    half = d // 2
    with jax.named_scope("mx_rope"):
        inv = rope_frequencies(d, theta, yarn)
        ang = jnp.arange(length, dtype=_F32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        if mscale is not None:
            cos, sin = cos * _F32(mscale), sin * _F32(mscale)
        x = data.astype(_F32)
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
        return out.astype(data.dtype)


def _blocked_attention(q, k, v, blk, scale, with_lse=False, window=None):
    """The blocked recurrence in plain JAX: blocks of ``blk`` queries
    against the blocks of keys at or before them, accumulated by the
    running maximum and denominator; blocks past the diagonal are never
    formed. With ``window`` a query sees that many keys, its own the
    last: key blocks wholly before a query block's window are not formed
    either, and the block its edge crosses is masked (scope
    ``mx_swa_fwd``). ``q``, ``k``, ``v``: (B, L, H, D), as many heads
    each. Returns (B, L, H, D) float32, and ``with_lse`` each row's
    log-sum-exp (B, H, L) beside it."""
    length = q.shape[1]
    outs, lses = [], []
    with _attention_scope(window):
        for i0 in range(0, length, blk):
            i1 = min(i0 + blk, length)
            qi = q[:, i0:i1]
            o = m = l = None
            # the block that holds the first key the block's first query sees
            start = 0 if window is None \
                else max(i0 - window + 1, 0) // blk * blk
            for j0 in range(start, i1, blk):
                j1 = min(j0 + blk, i1)
                seen = None
                if j1 > i0:     # the block on the diagonal
                    seen = jnp.arange(i0, i1)[:, None] \
                        >= jnp.arange(j0, j1)[None, :]
                if window is not None and i1 - 1 - j0 >= window:
                    inside = jnp.arange(i0, i1)[:, None] \
                        - jnp.arange(j0, j1)[None, :] < window
                    seen = inside if seen is None else seen & inside
                bias = None if seen is None else jnp.where(seen, 0.0, _NEG)
                o_j, m_j, l_j = _attention_block(
                    qi, k[:, j0:j1], v[:, j0:j1], bias, scale)
                if o is None:
                    o, m, l = o_j, m_j, l_j
                    continue
                m_new = jnp.maximum(m, m_j)
                alpha, beta = jnp.exp(m - m_new), jnp.exp(m_j - m_new)
                l = l * alpha + l_j * beta
                o = o * alpha.transpose(0, 2, 1)[..., None] \
                    + o_j * beta.transpose(0, 2, 1)[..., None]
                m = m_new
            outs.append(o / l.transpose(0, 2, 1)[..., None])
            if with_lse:
                lses.append(m + jnp.log(l))
    out = jnp.concatenate(outs, axis=1)
    return (out, jnp.concatenate(lses, axis=-1)) if with_lse else out


def _blocked_rows(q, k, v, hq, hk, scale, blk, extra=None, window=None):
    """``_blocked_attention`` over rows of heads: ``q`` (B, L, hq * D),
    ``k``, ``v`` (B, L, hk * D); with ``extra = (q2 (B, L, hq * D2), k2
    (B, L, D2))`` every head's query and key are ``[q | q2]`` and ``[k |
    k2]``, ``k2`` the same for all heads. Returns the output as such rows
    in ``q``'s dtype and the log-sum-exp (B, hq, L)."""
    bsz, length, _ = q.shape
    k, v = (jnp.repeat(t.reshape(bsz, length, hk, -1), hq // hk, axis=2)
            for t in (k, v))
    qh = q.reshape(bsz, length, hq, -1)
    if extra is not None:
        q2, k2 = extra
        qh = jnp.concatenate([qh, q2.reshape(bsz, length, hq, -1)], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            k2[:, :, None], (bsz, length, hq, k2.shape[-1]))], axis=-1)
    out, lse = _blocked_attention(qh, k, v, blk, scale, with_lse=True,
                                  window=window)
    return out.reshape(bsz, length, -1).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused_attention(q, k, v, extra, hq, hk, scale, blk, window=None):
    """Attention over rows of heads whose program takes its form when it
    is lowered: for a TPU the kernels of ``ops.attn_kernel``, forward and
    backward, for any other platform the blocked recurrence and JAX's own
    derivative of it. ``extra``: nothing, or the scores' second part
    ``(q2, k2)`` (``_blocked_rows``). ``window``: nothing, or how many
    keys a query sees, its own the last; both forms then skip the key
    blocks before it, under the scope ``mx_swa_fwd`` in ``mx_attn_fwd``'s
    place. Either way the unit around it keeps
    the output and one float32 log-sum-exp a row, and its backward pass
    runs no forward a second time where the kernels are."""
    return _fused_attention_fwd(q, k, v, extra, hq, hk, scale, blk,
                                window)[0]


def _attention_scope(window):
    return jax.named_scope("mx_attn_fwd" if window is None else "mx_swa_fwd")


def _fused_attention_fwd(q, k, v, extra, hq, hk, scale, blk, window=None):
    def kernels(q, k, v, *extra):
        q = attn_kernel.counted_site(q)
        if window is not None:
            q = attn_kernel.counted_site(q, attn_kernel.WINDOW_GAUGE)
        return attn_kernel.forward(q, k, v, hq, hk, scale,
                                   extra=extra or None, window=window)

    with _attention_scope(window):
        out, lse = lax.platform_dependent(
            q, k, v, *(extra or ()), tpu=kernels,
            default=lambda q, k, v, *extra: _blocked_rows(
                q, k, v, hq, hk, scale, blk, extra or None, window))
    out, lse = kept(out), kept(lse)
    return out, (q, k, v, extra, out, lse)


def _fused_attention_bwd(hq, hk, scale, blk, window, res, dout):
    q, k, v, extra, out, lse = res
    with _attention_scope(window):
        grads = lax.platform_dependent(
            q, k, v, out, lse, dout, *(extra or ()),
            tpu=lambda *a: attn_kernel.backward(
                *a[:6], hq, hk, scale, extra=a[6:] or None, window=window),
            default=lambda q, k, v, out, lse, dout, *extra: jax.vjp(
                lambda q, k, v, *extra: _blocked_rows(
                    q, k, v, hq, hk, scale, blk, extra or None, window)[0],
                q, k, v, *extra)[1](dout))
    return tuple(grads[:3]) + (tuple(grads[3:]) or None,)


_fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


@register_op("CausalGQAttention", names_its_parts=True)
def causal_gq_attention(data, q_norm_weight=None, k_norm_weight=None,
                        num_heads=1, num_kv_heads=1, head_dim=128,
                        block=1024, scale=None, rope_theta=None,
                        rotary_dim=None, gated=False, eps=1e-6,
                        unit_offset=False, window=None, head_gate=False,
                        yarn=None, mscale=None, **kw):
    """Causal attention over packed ``[q | k | v]`` rows, ``num_heads``
    query heads sharing ``num_kv_heads`` key/value heads; with
    ``rope_theta`` the queries and keys are rotated by their position
    first (``rope``; over a head's first ``rotary_dim`` elements alone
    where that is given; with ``yarn`` at YaRN's frequencies, with
    ``mscale`` the rotated elements multiplied by it), without it there
    is no positional encoding. The
    scores and every sum in float32, the probabilities in ``data``'s
    dtype for the weighted sum, scale ``head_dim ** -0.5`` unless given.
    ``window``: query ``t`` sees the keys ``t - window < j <= t`` alone
    (``window`` keys, its own among them); a window that reaches the
    whole length is none.

    With ``q_norm_weight`` and ``k_norm_weight`` (head_dim,) every query
    head and every key head goes through an RMSNorm of its own width
    before the rotation (``eps``, ``unit_offset``: ``rms_norm``'s; scope
    ``mx_attn_qk_norm``). ``gated``: the rows are ``[q | k | v | gate]``,
    the gate as wide as the queries, and each head's output is multiplied
    by ``sigmoid`` of its gate, in float32 (scope ``mx_attn_gate``).
    ``head_gate``: the rows are ``[q | k | v | gate]`` with ONE gate a
    head, ``num_heads`` wide, and all of a head's output is multiplied by
    ``sigmoid`` of it, in float32, under the same scope (the head-wise
    gate after the weighted sum of arXiv:2505.06708; rows of the packed
    projection and no weight of their own: one product, and the unit
    keeps the gates' logits with the packed rows).

    Two forms of one recurrence (blocks of queries against the blocks of
    keys at or before them, a running maximum and denominator, blocks
    past the diagonal never formed, nor under a ``window`` those wholly
    before it), chosen by what the program can see,
    not by the caller. Where ``head_dim`` is a multiple of 128 and the
    program is lowered for a TPU, one fused kernel forward and, backward,
    one fused kernel or, where a group's float32 ``dQ`` over all the rows
    would not fit in VMEM (``attn_kernel.resident_bytes``), two by side
    (``ops.attn_kernel``): no block of scores reaches memory,
    the heads are read where the projection wrote them, grouped heads
    share keys and values through the block index, and the block size is
    the kernel's (``attn_kernel.block_size``; ``block`` is not read).
    Everywhere else (another ``head_dim``, another backend) the
    recurrence in plain JAX over blocks of ``block`` rows
    (``parallel.ring.local_attention_block``'s), differentiated by JAX. No
    result depends on ``block``. So a site's backward takes one of three
    forms, with a window and without: the fused kernel, the kernel pair
    by side, or JAX's derivative of the plain recurrence; under a window
    each walks the band alone (the kernels' grid is the band's; the plain
    form starts at the band's first block), under the scope
    ``mx_swa_fwd`` where a site without one has ``mx_attn_fwd``.

    A recomputation unit around it keeps the packed rows, the output and,
    where ``head_dim`` is a multiple of 128, one float32 log-sum-exp a
    row; the head norms, the rotation and the output's gating are
    computed again, the kernel's forward is not.
    (Heads whose queries and keys are wider than their values and share a
    part of the key go through the same ``_fused_attention`` with a
    second score part: ``latent_attention``.)

    ``data``: (B, L, (num_heads + 2 num_kv_heads) * head_dim), and
    ``num_heads * head_dim`` more where ``gated``, ``num_heads`` more
    where ``head_gate``. Returns (B, L, num_heads * head_dim)."""
    hq, hk, dh = int(num_heads), int(num_kv_heads), int(head_dim)
    bsz, length, _ = data.shape
    data = kept(data)       # the projection's output, named where it is read
    q = data[..., :hq * dh].reshape(bsz, length, hq, dh)
    k = data[..., hq * dh:(hq + hk) * dh].reshape(bsz, length, hk, dh)
    v = data[..., (hq + hk) * dh:(hq + 2 * hk) * dh].reshape(
        bsz, length, hk, dh)
    if q_norm_weight is not None:
        with jax.named_scope("mx_attn_qk_norm"):
            q = _rms_norm(q, q_norm_weight, eps, unit_offset=unit_offset)
            k = _rms_norm(k, k_norm_weight, eps, unit_offset=unit_offset)
    if rope_theta is not None:
        q, k = (rope(t, rope_theta, rotary_dim, yarn, mscale)
                for t in (q, k))
    blk = min(int(block), length)
    scale = scale if scale is not None else dh ** -0.5
    if window is not None:
        window = int(window) if int(window) < length else None
    if dh % 128 == 0:
        out = _fused_attention(
            q.reshape(bsz, length, hq * dh), k.reshape(bsz, length, hk * dh),
            v.reshape(bsz, length, hk * dh), None, hq, hk, float(scale), blk,
            window)
    else:
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
        out = _blocked_attention(q, k, v, blk, scale, window=window)
        out = kept(out.reshape(bsz, length, hq * dh).astype(data.dtype))
    if gated or head_gate:
        with jax.named_scope("mx_attn_gate"):
            gate = jax.nn.sigmoid(data[..., (hq + 2 * hk) * dh:].astype(_F32))
            if head_gate:       # one number a head: over its elements
                gate = jnp.repeat(gate, dh, axis=-1)
            out = (out.astype(_F32) * gate).astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# multi-head latent attention
# ---------------------------------------------------------------------------
@register_op("LatentAttention", names_its_parts=True)
def latent_attention(data, q_weight, kv_down_weight, kv_norm_weight,
                     kv_up_weight, o_weight, q_down_weight=None,
                     q_norm_weight=None, num_heads=1, nope_dim=128,
                     rope_dim=64, v_dim=128, latent_dim=512,
                     rope_theta=10000.0, eps=1e-5, block=1024, scale=None,
                     yarn=None, mscale=None, **kw):
    """Causal multi-head latent attention over the ``num_heads`` heads
    held here. Keys and values are expanded from one ``latent_dim``-wide
    vector a token; a head's score is ``q_nope . k_nope + q_pe . k_pe``
    with ``k_pe`` (``rope_dim`` wide, rotated by position as ``q_pe`` is)
    one vector shared by all heads, scaled by ``scale``, ``(nope_dim +
    rope_dim) ** -0.5`` unless given (a model whose positions are
    stretched multiplies it by ``yarn_mscale`` squared); values are
    ``v_dim`` wide. ``yarn``: the rotation's frequencies are YaRN's
    (``rope_frequencies``); ``mscale``: cos and sin are multiplied by it
    (``rope``).

    With ``q_down_weight`` (q_latent, hidden) and ``q_norm_weight``
    (q_latent,) the queries come through a latent of their own: ``c_q =
    RMSNorm(x W_qa)``, ``q = c_q W_qb``, and ``q_weight`` is (H * (nope +
    rope), q_latent); the three are one scope, ``mx_mla_q``, and a unit
    keeps both products. A share of the heads holds both latents whole
    and its heads' rows of ``q_weight`` and ``kv_up_weight`` and columns
    of ``o_weight``: the shares' outputs add up to the uncut layer's.

    ``data`` (B, L, hidden). ``q_weight`` (H * (nope + rope), hidden),
    rows ``[every head's q_nope | every head's q_pe]``;
    ``kv_down_weight`` (latent + rope, hidden), rows ``[c | k_pe]``;
    ``kv_norm_weight`` (latent,), an RMSNorm over ``c`` alone;
    ``kv_up_weight`` (H * (nope + v), latent), rows ``[every head's
    k_nope | every head's v]``; ``o_weight`` (hidden, H * v). Grouping
    the rows by part and not by head is a layout: a fixed permutation of
    the rows of a matrix that is stored head by head.

    The softmax goes through ``_fused_attention`` with ``(q_pe, k_pe)``
    as the scores' second part: lowered for a TPU with ``nope_dim ==
    v_dim`` a multiple of 128, the kernels of ``ops.attn_kernel`` (no
    score block in memory, ``k_pe`` not repeated a head, 192 not padded
    to 256), everywhere else the blocked recurrence over the
    concatenated heads. A recomputation unit keeps ``q``, the latent
    before and after its norm with ``k_pe`` (576 + 512 wide), the
    attention's output and its log-sum-exp; the up-projection to H *
    (nope + v) is computed again (2.1 M multiply-accumulates a token at
    16 heads, against 8 times the latent's bytes to hold).

    Returns (B, L, hidden), this share's partial sum."""
    h, dn, dr, dv = int(num_heads), int(nope_dim), int(rope_dim), int(v_dim)
    bsz, length, _ = data.shape
    with jax.named_scope("mx_mla_q"):
        if q_down_weight is None:
            q = kept(_mm(data, q_weight))
        else:
            c_q = _rms_norm(kept(_mm(data, q_down_weight)), q_norm_weight,
                            eps=eps)
            q = kept(_mm(c_q, q_weight))
    with jax.named_scope("mx_mla_kv_down"):
        ckv = kept(_mm(data, kv_down_weight))
        c = kept(_rms_norm(ckv[..., :latent_dim], kv_norm_weight, eps=eps))
    with jax.named_scope("mx_mla_kv_up"):
        kv = _mm(c, kv_up_weight)
    with jax.named_scope("mx_mla_rope"):
        q_pe = rope(q[..., h * dn:].reshape(bsz, length, h, dr), rope_theta,
                    yarn=yarn, mscale=mscale)
        k_pe = rope(ckv[..., latent_dim:].reshape(bsz, length, 1, dr),
                    rope_theta, yarn=yarn, mscale=mscale)
    blk = min(int(block), length)
    scale = float((dn + dr) ** -0.5 if scale is None else scale)
    q_nope, k_nope, v = q[..., :h * dn], kv[..., :h * dn], kv[..., h * dn:]
    extra = (q_pe.reshape(bsz, length, h * dr),
             k_pe.reshape(bsz, length, dr))
    if dn == dv and dn % 128 == 0:
        out = _fused_attention(q_nope, k_nope, v, extra, h, h, scale, blk)
    else:
        out = kept(_blocked_rows(q_nope, k_nope, v, h, h, scale, blk,
                                 extra)[0])
    with jax.named_scope("mx_mla_out"):
        return _mm(out, o_weight)


# ---------------------------------------------------------------------------
# several residual streams: manifold-constrained hyper-connections
# ---------------------------------------------------------------------------
#: one for every sublayer whose maps a program lowers (and one more where
#: a recomputation unit lowers them again); ``TrainStep`` sets it to zero
#: where it traces its step
MHC_GAUGE = "mhc::sites"


def _sum_over(m, axis):
    """The sum over a short axis as additions of its slices: elementwise
    work that fuses with what surrounds it, where a ``reduce`` a
    normalisation would cut the 20 iterations into 40 programs."""
    parts = [lax.index_in_dim(m, i, axis, keepdims=True)
             for i in range(m.shape[axis])]
    return functools.reduce(lambda a, b: a + b, parts)


def _product(x, phi):
    """``phi X^T`` (n (n + 2), T) float32 of the streams ``x`` (T, n C)."""
    return lax.dot_general(phi.astype(x.dtype), x, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _mean_square(x):
    return jnp.mean(jnp.square(x.astype(_F32)), axis=-1)


def _maps_of(raw, mean_sq, alpha, bias, n, iters, eps, clamp):
    """The per-token arithmetic of ``mhc_maps`` on its product and mean
    square, tokens minor: ``(H_pre (n, T), H_post (n, T), H_res (n, n, T),
    dev ())``."""
    part = np.repeat(np.arange(3), [n, n, n * n])
    h = raw * lax.rsqrt(mean_sq + eps)[None, :] \
        * alpha.astype(_F32)[part][:, None] \
        + bias.astype(_F32)[:, None]
    pre = jax.nn.sigmoid(h[:n])
    post = 2.0 * jax.nn.sigmoid(h[n:2 * n])
    m = jnp.exp(jnp.clip(h[2 * n:], float(clamp[0]),
                         float(clamp[1]))).reshape(n, n, -1)
    for _ in range(int(iters)):
        m = m / (_sum_over(m, 0) + eps)       # columns
        m = m / (_sum_over(m, 1) + eps)       # rows
    dev = lax.stop_gradient(jnp.maximum(
        jnp.max(jnp.abs(_sum_over(m, 0) - 1.0)),
        jnp.max(jnp.abs(_sum_over(m, 1) - 1.0))))
    return pre, post, m, dev


@register_op("HyperConnectionMaps", num_outputs=4, names_its_parts=True)
def mhc_maps(data, phi, alpha, bias, streams=4, iters=20, eps=1e-6,
             clamp=(-30.0, 30.0), **kw):
    """The three maps of a hyper-connected sublayer (manifold-constrained
    hyper-connections, arXiv:2512.24880) from a token's ``n = streams``
    residual streams, ``data`` (B, L, n * C), stream ``j`` the columns ``j
    C`` to ``(j + 1) C``:

    ``x^ = vec(X) / sqrt(mean(vec(X)^2) + eps)``; ``H~ = alpha * (x^ phi)
    + bias`` in three parts, ``phi`` (n (n + 2), n C) rows ``[pre (n) |
    post (n) | res (n n, row-major)]``, ``alpha`` (3,) one scalar a part,
    ``bias`` (n (n + 2),); ``H_pre = sigmoid(H~_pre)``, ``H_post = 2
    sigmoid(H~_post)``, ``H_res`` the matrix ``exp(clip(H~_res, clamp))``
    after ``iters`` Sinkhorn iterations, each its columns divided by
    their sums (+ ``eps``) and then its rows by theirs: doubly stochastic
    to what the iterations leave.

    Everything in float32 and with the tokens minor: the product is
    ``phi X^T`` (n (n + 2), T), divided by the streams' root mean square
    after it (the streams are read for both at once), and a token's 4 x 4
    matrix is 16 rows of a (16, T) array, never a padded tile of its
    own. A recomputation unit keeps the product and the mean square (n
    (n + 2) + 1 floats a token); sigmoids and iterations are computed
    again. Scope ``mx_mhc_maps``.

    This op is the plain form on every platform, one pass of XLA's over
    the streams for the product and one for the mean square. A layer
    calls ``mhc_read``, which is this op and ``mhc_pre`` in one and, in a
    program lowered for a TPU, reads the streams once for both.

    Returns ``(H_pre (n, B, L), H_post (n, B, L), H_res (n, n, B, L),
    dev (1,))``, float32; ``dev`` is the largest ``|row or column sum of
    H_res - 1|`` over the tokens, and no gradient reaches it."""
    n = int(streams)
    bsz, length, width = data.shape
    with jax.named_scope("mx_mhc_maps"):
        x = attn_kernel.counted_site(
            data.reshape(bsz * length, width), MHC_GAUGE)
        raw = kept(_product(x, phi))
        mean_sq = kept(_mean_square(x))
        pre, post, m, dev = _maps_of(raw, mean_sq, alpha, bias, n, iters,
                                     eps, clamp)
        return (pre.reshape(n, bsz, length), post.reshape(n, bsz, length),
                m.reshape(n, n, bsz, length), dev.reshape(1))


def _streams_of(data, n):
    width = data.shape[-1] // n
    return [data[..., j * width:(j + 1) * width].astype(_F32)
            for j in range(n)]


def _mix(data, pre):
    """``sum_j pre[j] X_j`` in float32, rounded to ``data``'s dtype."""
    xs = _streams_of(data, pre.shape[0])
    u = sum(pre[j][..., None] * x for j, x in enumerate(xs))
    return u.astype(data.dtype)


@register_op("HyperConnectionPre", names_its_parts=True)
def mhc_pre(data, pre, **kw):
    """What a hyper-connected sublayer reads: ``u = sum_j H_pre[j] X_j``,
    ``data`` (B, L, n * C) and ``pre`` (n, B, L) -> (B, L, C) in
    ``data``'s dtype, the sum in float32. A unit computes it again (it
    is as wide as a stream). Scope ``mx_mhc_pre``. The plain form on
    every platform, a pass of its own over the streams; a layer calls
    ``mhc_read``."""
    with jax.named_scope("mx_mhc_pre"):
        return _mix(data, pre)


def _read_plain(x, phi, alpha_pre, bias_pre, n, eps):
    """``mhc_kernel.read`` in plain JAX: the streams' product and mean
    square and the mix under ``H_pre``, which is ``_maps_of``'s expression
    on the product's first rows."""
    raw, mean_sq = _product(x, phi), _mean_square(x)
    pre = jax.nn.sigmoid(raw[:n] * lax.rsqrt(mean_sq + eps)[None, :]
                         * alpha_pre + bias_pre[:, None])
    return raw, mean_sq, _mix(x, pre)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _read_kernels(x, phi, alpha_pre, bias_pre, n, eps):
    """``(x, raw, mean_sq, u)``: a sublayer's read side whose program takes
    its form when it is lowered: for a TPU ``mhc_kernel.read`` and
    ``read_backward``, for any other platform ``_read_plain`` and JAX's
    derivative of it. The streams go through unchanged so that what the
    write side's backward leaves for them comes back here as a cotangent
    and is added where the one ``dX`` is summed, not in a pass of its
    own."""
    return _read_kernels_fwd(x, phi, alpha_pre, bias_pre, n, eps)[0]


def _read_kernels_fwd(x, phi, alpha_pre, bias_pre, n, eps):
    raw, mean_sq, u = lax.platform_dependent(
        x, phi, alpha_pre, bias_pre,
        tpu=lambda x, *a: mhc_kernel.read(
            attn_kernel.counted_site(x, mhc_kernel.GAUGE), *a, n=n, eps=eps),
        default=lambda *a: _read_plain(*a, n, eps))
    return (x, raw, mean_sq, u), (x, phi, alpha_pre, bias_pre, raw, mean_sq)


def _read_kernels_bwd(n, eps, res, cts):
    # no scope of its own: the backward rule carries the scope its forward
    # was called under (``mx_mhc_pre``)
    def kernels(x, phi, alpha_pre, bias_pre, raw, mean_sq, dxp, d_raw, d_ms,
                du):
        dx, d_phi, d_logits = mhc_kernel.read_backward(
            x, dxp, du, raw, mean_sq, d_raw, d_ms, phi, alpha_pre, bias_pre,
            n=n, eps=eps)
        scaled = raw[:n] * lax.rsqrt(mean_sq + eps)[None, :]
        return (dx, d_phi.astype(phi.dtype), jnp.sum(d_logits * scaled),
                jnp.sum(d_logits, axis=1))

    def plain(x, phi, alpha_pre, bias_pre, raw, mean_sq, dxp, *cts):
        dx, *rest = jax.vjp(lambda *a: _read_plain(*a, n, eps),
                            x, phi, alpha_pre, bias_pre)[1](cts)
        return (dxp + dx, *rest)

    return lax.platform_dependent(*res, *cts, tpu=kernels, default=plain)


_read_kernels.defvjp(_read_kernels_fwd, _read_kernels_bwd)


@register_op("HyperConnectionRead", num_outputs=5, names_its_parts=True)
def mhc_read(data, phi, alpha, bias, streams=4, iters=20, eps=1e-6,
             clamp=(-30.0, 30.0), **kw):
    """A hyper-connected sublayer's read side: ``mhc_maps`` and ``mhc_pre``
    in one. ``data`` (B, L, n * C), ``phi``, ``alpha``, ``bias`` and the
    keywords as ``mhc_maps`` takes them.

    Two forms. Where ``mhc_kernel.takes`` the shapes (2 to 8 streams of
    whole lane tiles, the tokens whole blocks of 128, bfloat16 or
    float32) and the program is lowered for a TPU, the streams are read
    once: ``mhc_kernel.read`` gives the product, the mean square and ``u``
    (``H_pre`` formed in the kernel), under ``mx_mhc_pre``; the
    per-token arithmetic of ``mhc_maps`` stays XLA's, on the product and
    the mean square the unit keeps, under ``mx_mhc_maps``; the backward
    pass is ``mhc_kernel.read_backward``, which writes ONE cotangent of
    the streams. Everywhere else ``mhc_maps`` then ``mhc_pre``, the plain
    form as JAX differentiates it. Shapes and the platform choose; no
    option does. ``mhc::kernel_sites`` counts the sites that took the
    kernels, ``mhc::sites`` every site's maps.

    Returns ``(data, u (B, L, C), H_post (n, B, L), H_res (n, n, B, L),
    dev (1,))``: the streams as they came, for ``mhc_post`` to read (so
    that its backward's share of their cotangent passes through this
    op's), what the sublayer reads, and ``mhc_maps``' last three."""
    n = int(streams)
    bsz, length, width = data.shape
    if not mhc_kernel.takes(bsz * length, n, width, data.dtype):
        pre, post, res, dev = mhc_maps(data, phi, alpha, bias, streams=n,
                                       iters=iters, eps=eps, clamp=clamp)
        return data, mhc_pre(data, pre), post, res, dev
    with jax.named_scope("mx_mhc_pre"):
        x, raw, mean_sq, u = _read_kernels(
            data.reshape(bsz * length, width), phi.astype(data.dtype),
            alpha.astype(_F32)[0], bias.astype(_F32)[:n], n, float(eps))
    with jax.named_scope("mx_mhc_maps"):
        raw = attn_kernel.counted_site(raw, MHC_GAUGE)
        _, post, res, dev = _maps_of(kept(raw), kept(mean_sq), alpha, bias,
                                     n, iters, eps, clamp)
    return (x.reshape(data.shape), u.reshape(bsz, length, width // n),
            post.reshape(n, bsz, length), res.reshape(n, n, bsz, length),
            dev.reshape(1))


def _post_plain(data, y, res, post, keep=False):
    """``X'_i = sum_j res[i, j] X_j + post[i] y``, the sums in float32;
    ``keep``: the unit around it holds ``y``."""
    xs = _streams_of(data, post.shape[0])
    y = (kept(y) if keep else y).astype(_F32)
    return jnp.concatenate(
        [(sum(res[i, j][..., None] * x for j, x in enumerate(xs))
          + post[i][..., None] * y).astype(data.dtype)
         for i in range(post.shape[0])], axis=-1)


@jax.custom_vjp
def _post_kernels(x, y, res, post):
    """``mhc_post`` on (T, n C) streams, ``res`` (n n, T) and ``post`` (n,
    T), whose program takes its form when it is lowered: for a TPU
    ``mhc_kernel.post`` and ``post_backward``, for any other platform
    ``_post_plain`` and JAX's derivative of it."""
    return _post_kernels_fwd(x, y, res, post)[0]


def _post_rows(x, y, res, post):
    n = post.shape[0]
    return _post_plain(x, y, res.reshape(n, n, -1), post)


def _post_kernels_fwd(x, y, res, post):
    out = lax.platform_dependent(x, y, res, post, tpu=mhc_kernel.post,
                                 default=_post_rows)
    return out, (x, y, res, post)


def _post_kernels_bwd(saved, g):
    # under the scope of its forward too (``mx_mhc_post``)
    return lax.platform_dependent(
        *saved, g,
        tpu=lambda x, y, res, post, g: mhc_kernel.post_backward(
            g, x, y, res, post),
        default=lambda *a: jax.vjp(_post_rows, *a[:4])[1](a[4]))


_post_kernels.defvjp(_post_kernels_fwd, _post_kernels_bwd)


@register_op("HyperConnectionPost", names_its_parts=True)
def mhc_post(data, out, res, post, **kw):
    """What a hyper-connected sublayer writes: ``X'_i = sum_j H_res[i, j]
    X_j + H_post[i] y``, ``data`` (B, L, n * C), ``out`` = ``y`` (B, L,
    C), ``res`` (n, n, B, L), ``post`` (n, B, L) -> (B, L, n * C) in
    ``data``'s dtype, the sums in float32. A unit keeps ``y``, the
    mixer's last product, which ``H_post``'s gradient reads. Scope
    ``mx_mhc_post``.

    Two forms, by ``mhc_kernel.takes`` and the platform the program is
    lowered for, as ``mhc_read``'s: on a TPU ``mhc_kernel.post`` reads
    ``data`` and ``y`` once and writes the streams once, and its backward,
    ``mhc_kernel.post_backward``, reads the cotangent, ``data`` and ``y``
    once for the streams' partial cotangent, ``dy`` and both maps'
    gradients a token; everywhere else the plain sums."""
    with jax.named_scope("mx_mhc_post"):
        n = post.shape[0]
        bsz, length, width = data.shape
        tokens = bsz * length
        if not mhc_kernel.takes(tokens, n, width, data.dtype) \
                or out.dtype != data.dtype:
            return _post_plain(data, out, res, post, keep=True)
        return _post_kernels(
            data.reshape(tokens, width),
            kept(out).reshape(tokens, width // n),
            res.reshape(n * n, tokens), post.reshape(n, tokens)
        ).reshape(data.shape)


@register_op("HyperConnectionSpread", names_its_parts=True)
def mhc_spread(data, streams=4, **kw):
    """A token's vector copied into each of its ``streams`` residual
    streams: (B, L, C) -> (B, L, n * C). Scope ``mx_mhc_in``."""
    with jax.named_scope("mx_mhc_in"):
        return jnp.tile(data, (1, 1, int(streams)))


@register_op("HyperConnectionMerge", names_its_parts=True)
def mhc_merge(data, streams=4, **kw):
    """The sum of a token's residual streams, in float32: (B, L, n * C) ->
    (B, L, C) in ``data``'s dtype. Scope ``mx_mhc_out``."""
    with jax.named_scope("mx_mhc_out"):
        return sum(_streams_of(data, int(streams))).astype(data.dtype)


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------
@register_op("GatedMLP", names_its_parts=True)
def gated_mlp(data, gate_up_weight, down_weight, **kw):
    """``(silu(u W_gate) * (u W_up)) W_down``. ``gate_up_weight``: (2 f,
    hidden), rows ``[gate | up]``, one product for both; ``down_weight``:
    (hidden, f). The activation and the gating in float32. A unit around
    it keeps the first product, as it keeps every other first product of
    this file: 2 f wide, the widest value of a layer (5.5 times the
    layer's input at f = 2.75 hidden) and the dearest to form again (2 of
    the MLP's 11 products a unit). The gated rows, the activation and the
    casts are computed again from it; the down product is kept only
    where a norm after the sublayer reads it (``rms_norm``'s
    ``keep_input``). On the chip (PERF.md, section 6): dropping the
    product bought 1.8 GB for 17 ms of ``ouro-2.6b-train-4k``'s step when
    that step did not fit (PR 33); with the attention's score blocks off
    the chip's memory, keeping it costs 1.47 GB there and gives back 11.2
    of 299.6 ms, and 8.6 of 186.0 ms for 0.77 GB in
    ``lfm2-24b-a2b-train-8k`` (PR 49). ``TrainStep(remat=...)`` is the
    lever on memory."""
    with jax.named_scope("mx_gated_mlp"):
        gu = kept(_mm(data, gate_up_weight))
        f = gu.shape[-1] // 2
        hid = jax.nn.silu(gu[..., :f].astype(_F32)) * gu[..., f:].astype(_F32)
        return _mm(hid.astype(data.dtype), down_weight)


# ---------------------------------------------------------------------------
# a stack run several times: the exit gate and the exit-weighted loss
# ---------------------------------------------------------------------------
@register_op("ExitGate", num_outputs=2, names_its_parts=True)
def exit_gate(data, weight, bias, **kw):
    """The gates of a stack run ``T`` times. ``data``: (T, ..., hidden),
    the stack's output after each pass. Returns ``(logits (T - 1, N),
    stats (T + 2,))``, float32: the logit ``h . w_g + b_g`` of every row
    of the passes before the last (``lambda = sigmoid`` of it is the
    probability of leaving after that pass, given that no earlier pass
    was left), and, over the rows, the mean ``p(t)`` of each pass
    (``exit_log_probs``), the mean expected number of passes and the mean
    entropy of ``p``; no gradient reaches the stats."""
    passes, hidden = data.shape[0], data.shape[-1]
    with jax.named_scope("mx_exit_gate"):
        # all gated passes' rows as one axis: one program for every T
        h = data[:passes - 1].reshape(-1, hidden).astype(_F32)
        logits = jnp.sum(h * weight.astype(_F32).reshape(-1), axis=-1) \
            + bias.astype(_F32).reshape(())
        logits = logits.reshape(passes - 1, data[0].size // hidden)
        logp = lax.stop_gradient(exit_log_probs(logits))
        p = jnp.exp(logp)
        mass = jnp.mean(p, axis=1)
        stats = jnp.concatenate([
            mass, jnp.stack([jnp.sum(mass * jnp.arange(1, passes + 1)),
                             -jnp.mean(jnp.sum(p * logp, axis=0))])])
        return logits, stats


def exit_log_probs(gate_logits):
    """``log p(t)`` of leaving after pass ``t`` of ``T``, from the gates'
    logits of passes ``1..T-1``: ``p(t) = lambda_t prod_{j<t} (1 -
    lambda_j)``, and the last pass takes what is left, ``p(T) =
    prod_{j<T} (1 - lambda_j)``. ``gate_logits``: (T - 1, N) -> (T, N)
    float32; the rows of ``exp`` of it sum to one. One program for every
    ``T``: no loop over the passes."""
    z = gate_logits.astype(_F32)
    passes = z.shape[0] + 1
    leave, stay = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
    shape = (passes, passes - 1)
    earlier = (lax.broadcasted_iota(jnp.int32, shape, 0)
               > lax.broadcasted_iota(jnp.int32, shape, 1)).astype(_F32)
    stayed = jnp.einsum("tj,jn->tn", earlier, stay,
                        precision=lax.Precision.HIGHEST)
    return stayed + jnp.pad(leave, ((0, 1), (0, 0)))


def softmax_ce_rows(logits, labels):
    """Every row's softmax cross entropy with its integer label, in
    float32: ``parallel.step.softmax_ce_loss`` before its mean (the label
    picked by a compare and a masked row sum, not a gather)."""
    x = logits.astype(_F32)
    s = x - lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(s), axis=-1))
    hit = jnp.arange(s.shape[-1]) == labels.astype(jnp.int32)[:, None]
    picked = jnp.sum(jnp.where(hit, s, 0.0), axis=-1)
    return lse - picked


def exit_weighted_ce(hidden, gate_logits, head_weight, labels, beta=0.0):
    """The expected next-token loss under the exit distribution, less
    ``beta`` times that distribution's entropy: ``mean_n [sum_t p_n(t)
    l_n(t) - beta H(p_n)]`` with ``l(t)`` the cross entropy of ``hidden[t]
    head_weight^T`` and ``p`` from ``exit_log_probs``.

    ``hidden``: (T, N, hidden), the stack's output after each pass;
    ``gate_logits``: (T - 1, N); ``head_weight``: (vocab, hidden);
    ``labels``: (N,). One exit after another as a scan whose body is a
    recomputation unit: it keeps its hidden state and computes its
    float32 logits again in the backward pass, so no two exits' logits
    are ever held together."""

    @jax.checkpoint
    def one_exit(h, w, y):
        logits = lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32)
        return softmax_ce_rows(logits, y)

    with jax.named_scope("mx_exit_head"):
        _, ce = lax.scan(
            lambda _, h: (None, one_exit(h, head_weight, labels)),
            None, hidden)                               # (T, N)
    with jax.named_scope("mx_exit_gate"):
        logp = exit_log_probs(gate_logits)
        p = jnp.exp(logp)
        return jnp.mean(jnp.sum(p * ce, axis=0)
                        + beta * jnp.sum(p * logp, axis=0))
