"""Multi-head latent attention: ``ops.seq.latent_attention`` against the
plain reference's equations (``benchmark/configs/moonlight-16b-a3b.py``),
values and gradients; the kernels of ``ops.attn_kernel`` with the scores'
second part (a second query part a head, one key part shared by all
heads), interpreted here on the CPU, against the blocked recurrence over
the concatenated heads, forward and the three-plus-two gradients; where
the op takes the kernels; what a recomputation unit keeps; and the
kernels' lowered text at heads of 128 at the three cells' shapes, pinned.
Nothing here is a time."""
import base64
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu.ops import attn_kernel, remat, seq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import harness  # noqa: E402
import numerics  # noqa: E402
from numerics import Tol, kernels_here  # noqa: E402, F401

D = 128


def _reference():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "configs", "moonlight-16b-a3b.py"))


# -- the op against the reference's equations ----------------------------------
SMALL = {"hidden_size": 48, "num_attention_heads": 3, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 6, "v_head_dim": 10, "kv_lora_rank": 20,
         "rope_theta": 50000, "rms_norm_eps": 1e-5,
         "reference_attention_block": 8}
LEAVES = ("q_weight", "kv_down_weight", "kv_norm_weight", "kv_up_weight",
          "o_weight")


def _small_weights(sz, seed=0):
    d, h, r = sz["hidden_size"], sz["num_attention_heads"], sz["kv_lora_rank"]
    dn, dr, dv = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    shapes = {"q_weight": (h * (dn + dr), d), "kv_down_weight": (r + dr, d),
              "kv_norm_weight": (r,), "kv_up_weight": (h * (dn + dv), r),
              "o_weight": (d, h * dv)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    w = {k: 0.2 * jax.random.normal(key, s, jnp.float32)
         for key, (k, s) in zip(keys, shapes.items())}
    w["kv_norm_weight"] = 1.0 + w["kv_norm_weight"]
    return w


def _op(sz, w, x, block=8):
    return seq.latent_attention(
        x, *(w[k] for k in LEAVES), num_heads=sz["num_attention_heads"],
        nope_dim=sz["qk_nope_head_dim"], rope_dim=sz["qk_rope_head_dim"],
        v_dim=sz["v_head_dim"], latent_dim=sz["kv_lora_rank"],
        rope_theta=sz["rope_theta"], eps=sz["rms_norm_eps"], block=block)


@pytest.mark.parametrize("length,block", [(24, 8), (21, 8), (16, 1024)])
def test_op_is_the_reference_s_equations(length, block):
    """``qk_rope`` 6, ``qk_nope`` 16, values 10 wide, 3 heads: no two
    widths alike and none a lane tile; a length that is no multiple of the
    block; the output and every gradient."""
    ref = _reference()
    w = _small_weights(SMALL)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, length, SMALL["hidden_size"]), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)

    def want(w, x):
        p = {"l0_" + k: v for k, v in w.items()}
        return jax.vmap(lambda u: ref.latent_attention(
            SMALL, p, 0, u, "float32"))(x)

    with jax.default_matmul_precision("highest"):
        numerics.agree(
            lambda w, x: _op(SMALL, w, x, block), want, (w, x), cot, (0, 1),
            value=Tol(atol=2e-5), grads=Tol(scaled=3e-5))


def test_the_rotary_key_is_one_vector_for_all_heads():
    """Moving ``k_pe``'s rows of the down-projection moves every head's
    output; the rotation acts on the 6-wide slices alone: with those rows
    of ``Wq`` and ``Wkva`` zero the op has no positional signal and a
    reversed causal prefix gives the same last row."""
    w = _small_weights(SMALL)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 48), jnp.float32)
    h, dn, r = 3, 16, 20
    ones = jnp.ones_like(w["o_weight"])

    def forms(w, x):
        base = _op(SMALL, w, x)
        moved = dict(w, kv_down_weight=w["kv_down_weight"].at[r:].multiply(
            2.0))
        per_head = jnp.abs(_op(SMALL, dict(moved, o_weight=ones), x)
                           - _op(SMALL, dict(w, o_weight=ones), x)).max()
        flat = dict(w, q_weight=w["q_weight"].at[h * dn:].set(0.0),
                    kv_down_weight=w["kv_down_weight"].at[r:].set(0.0))
        a = _op(SMALL, flat, x)[0, -1]
        b = _op(SMALL, flat, jnp.concatenate(
            [x[:, :-1][:, ::-1], x[:, -1:]], axis=1))[0, -1]
        return base, per_head, a, b

    (base, per_head, a, b), _ = numerics.traced(forms, (w, x))
    assert per_head > 1e-3
    np.testing.assert_allclose(a, b, atol=1e-5)
    assert jnp.abs(base - a).max() > 1e-3


# -- the kernels with the second part ------------------------------------------
def _parts(length, hq, hk, d2, dtype, seed=0, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((batch, length, hq * D), (batch, length, hk * D),
              (batch, length, hk * D), (batch, length, hq * d2),
              (batch, length, d2), (batch, length, hq * D))
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(ks, shapes)]


def _blocked(q, k, v, q2, k2, hq, hk, scale, blk=128):
    return seq._blocked_rows(q, k, v, hq, hk, scale, blk, (q2, k2))


CASES = [(256, 4, 4, 64, "float32"), (256, 4, 4, 64, "bfloat16"),
         (200, 2, 2, 64, "float32"), (384, 4, 2, 64, "float32"),
         (384, 4, 2, 32, "bfloat16")]


@pytest.mark.parametrize("length,hq,hk,d2,dtype", CASES)
def test_kernels_with_a_second_part_agree_with_the_blocked_form(
        length, hq, hk, d2, dtype):
    """Forward, and ``dq, dk, dv`` with ``dq2`` and the shared ``dk2``
    (summed over every head in the kernel's grid), against JAX's
    derivative of the blocked recurrence over ``[q | q2]`` and ``[k |
    k2]``; grouped key/value heads and a padded length among the cases."""
    q, k, v, q2, k2, cot = _parts(length, hq, hk, d2, dtype)
    scale = (D + d2) ** -0.5
    tol = 2e-5 if dtype == "float32" else 4e-2

    def kernels(q, k, v, q2, k2):
        out, lse = attn_kernel.forward(q, k, v, hq, hk, scale, interpret=True,
                                       extra=(q2, k2))
        return out, lse, attn_kernel.backward(
            q, k, v, out, lse, cot, hq, hk, scale, interpret=True,
            extra=(q2, k2))

    (out, lse, got), _ = numerics.traced(kernels, (q, k, v, q2, k2))
    (want, want_lse), grads = numerics.traced(
        lambda *a: _blocked(*a, hq, hk, scale), (q, k, v, q2, k2),
        (cot.astype(jnp.float32), 0.0), (0, 1, 2, 3, 4))
    numerics.close(out, want, Tol(rtol=0.0, scaled=tol), "out")
    numerics.close(lse, want_lse, Tol(atol=2e-5 if dtype == "float32"
                                      else 5e-2), "lse")
    # dq, dk, dv, dq2, dk2
    numerics.close(got, grads, Tol(rtol=0.0, scaled=tol), "d",
                   same_dtype=True)


def test_without_a_second_part_the_kernels_return_what_they_did():
    q, k, v, _, _, cot = _parts(256, 4, 2, 64, "float32", seed=4)
    out, lse = attn_kernel.forward(q, k, v, 4, 2, D ** -0.5, interpret=True)
    got = attn_kernel.backward(q, k, v, out, lse, cot, 4, 2, D ** -0.5,
                               interpret=True)
    assert len(got) == 3
    # a second part of zeros adds nothing to any score
    zeros = (jnp.zeros((2, 256, 4 * 64)), jnp.zeros((2, 256, 64)))
    out2, lse2 = attn_kernel.forward(q, k, v, 4, 2, D ** -0.5,
                                     interpret=True, extra=zeros)
    np.testing.assert_allclose(out, out2, atol=1e-6)
    np.testing.assert_allclose(lse, lse2, atol=1e-6)


# -- where the op takes the kernels ---------------------------------------------
WIDE = {"hidden_size": 256, "num_attention_heads": 2, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 64,
        "rope_theta": 50000, "rms_norm_eps": 1e-5}


def _wide_case(nope=128, dtype=jnp.float32):
    sz = dict(WIDE, qk_nope_head_dim=nope, v_head_dim=nope)
    w = {k: v.astype(dtype) for k, v in _small_weights(sz, seed=5).items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 256, 256),
                          jnp.float32).astype(dtype)
    return sz, w, x


def test_op_through_the_kernels_is_the_plain_form(kernels_here):
    sz, w, x = _wide_case()

    def loss(w, x):
        return jnp.sum(_op(sz, w, x, block=128) ** 2)

    got = numerics.traced(loss, (w, x), 1.0, (0, 1))
    with pytest.MonkeyPatch.context() as plain:
        plain.setattr(lax, "platform_dependent",
                      lambda *args, tpu, default: default(*args))
        want = numerics.traced(loss, (w, x), 1.0, (0, 1))
    numerics.close(got, want, numerics.kernel_tol(2e-4))


def _lowered_for(platform, fn, *args):
    mx.telemetry.gauge(attn_kernel.GAUGE).set(0)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(attn_kernel.GAUGE).get()


@pytest.mark.parametrize("nope,platform,sites", [
    (128, "tpu", 1), (256, "tpu", 1), (64, "tpu", 0), (128, "cpu", 0)])
def test_kernel_sites_follow_the_platform_and_the_head(nope, platform, sites):
    sz, w, x = _wide_case(nope, jnp.bfloat16)

    def loss(w, x):
        return jnp.sum(_op(sz, w, x, block=1024).astype(jnp.float32))

    text, counted = _lowered_for(platform, jax.grad(loss), w, x)
    assert counted == sites
    assert len(re.findall(r"tpu_custom_call", text)) == 2 * sites
    # a (heads, block, block) float32 score value is in the text where the
    # plain form is, and nowhere where the kernels are
    assert ("tensor<1x2x256x256xf32>" in text) == (not sites)
    # with the kernels the rotary key goes in as it lies, (B, L, 64), and
    # no key is ever 192 wide
    if sites:
        assert f"tensor<1x256x2x{nope + 64}xbf16>" not in text


def test_a_unit_keeps_the_latent_and_not_the_up_projection():
    sz, w, x = _wide_case(128, jnp.bfloat16)
    unit = jax.checkpoint(lambda x: _op(sz, w, x), policy=remat.POLICY)
    got = remat.kept_bytes(jax.make_jaxpr(unit)(x).jaxpr)
    tokens, h = 256, 2
    q = tokens * h * 192 * 2
    latent = tokens * (64 + 64) * 2 + tokens * 64 * 2    # [c | k_pe], N(c)
    out = tokens * h * 128 * 2
    lse, norm_sum = h * tokens * 4, tokens * 4
    assert got == q + latent + out + lse + norm_sum
    # the (tokens, H * 256) up-projection is not among what is kept
    assert got < q + latent + out + lse + norm_sum + tokens * h * 256 * 2


# -- the kernels' lowered text with no second part ------------------------------
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _without_locations(text):
    """A lowered text with each Mosaic kernel's serialized module, which
    carries the source lines it was traced from, replaced by that
    module's own text without them."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        bodies = [ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False) for body in _BODY.findall(text)]
    return _BODY.sub("BODY", text) + "\n".join(bodies)


#: sha256 of ``jax.grad`` of the attention at heads of 128, lowered for a
#: TPU (two Mosaic calls: the forward kernel and the fused backward),
#: locations stripped, computed with the function below. PR 38 changed
#: the text (the backward's two kernels became one; the forward kernel's
#: body is what PR 37's parent pinned) and re-anchored it here: Ouro's
#: heads (16 of 16 at 4096, rotary), Nemotron's (4 on 1 at 8192), a padded
#: length, and Moonlight's (16 heads at 8192 with a second part 64 wide),
#: so that each cell's own kernels are guarded outside the harness's tests
KERNEL_TEXT_SHA256 = {
    (4096, 16, 16, 1e6, 0):
        "bf98d2b5c543dbc0b907b06e9d4fde3ac92e5d4517e93365ffb05f2c19c90a59",
    (8192, 4, 1, None, 0):
        "73868229bf765dbc8f013038c2cb9e7a6d65c7dc8726c71c8fccc9e7225387dd",
    (200, 4, 2, None, 0):
        "585fb073a6e4335cf8c90299e4741f052012caf06475f6e56cfb20176af76e4c",
    (8192, 16, 16, None, 64):
        "942b13d4667adb433b25b08cbe4a3a8f834b6ba0d031d48218e09df680b64e2f",
}


def _lowered_attention(length, hq, hk, theta, d2):
    """``jax.grad`` of the attention over bfloat16 rows of one sequence,
    lowered for a TPU: ``causal_gq_attention``, or with a second part
    ``_fused_attention`` over ``(q2, k2)`` ``d2`` wide."""
    def rows(width):
        return jax.ShapeDtypeStruct((1, length, width), jnp.bfloat16)

    if not d2:
        def loss(d):
            return jnp.sum(seq.causal_gq_attention(
                d, num_heads=hq, num_kv_heads=hk, head_dim=D,
                rope_theta=theta).astype(jnp.float32))
        args = (rows((hq + 2 * hk) * D),)
    else:
        def loss(q, k, v, q2, k2):
            return jnp.sum(seq._fused_attention(
                q, k, v, (q2, k2), hq, hk, (D + d2) ** -0.5,
                1024).astype(jnp.float32))
        args = (rows(hq * D), rows(hk * D), rows(hk * D), rows(hq * d2),
                rows(d2))
    return jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("length,hq,hk,theta,d2", list(KERNEL_TEXT_SHA256))
def test_kernels_at_heads_of_128_are_the_program_they_were(length, hq, hk,
                                                           theta, d2):
    """The rehearsal sizes of the ``PatternLM`` cells have heads of 16
    and never reach the kernels, so their pinned steps do not guard them:
    this does, at the cells' own shapes."""
    text = _lowered_attention(length, hq, hk, theta, d2)
    assert len(_BODY.findall(text)) == 2
    assert hashlib.sha256(_without_locations(text).encode()).hexdigest() \
        == KERNEL_TEXT_SHA256[length, hq, hk, theta, d2]
