"""The Gated DeltaNet mixer's operand kernels (``ops/gdn_conv_kernel.py``:
the convolution over ``[q | k | v]``, SiLU and the heads' L2 norm, read
from the packed projection in place) against the plain form
(``causal_conv1d``, ``silu``, ``_l2_norm``) and JAX's own derivative of it,
interpreted on the CPU; the rule of shapes they are taken by; and which
form ``gated_delta_net`` takes: the kernels (these and the rule's,
``ops/gdn_kernel.py``, under one ``custom_vjp``) where both rules take the
shapes and the program is lowered for a TPU, the plain form everywhere
else, with the gauge ``gdn::conv_kernel_sites`` counting the sites.
Nothing here is a time."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import gdn_conv_kernel, seq

import numerics
from numerics import kernel_tol

N = P = 128
TAPS = 4


def _heads(group):
    return gdn_conv_kernel.Heads(1, N, group, P)


def _operands(bsz, length, group, dtype, seed=0, taps=TAPS, keys=1):
    """``(qkvz, weight)``: the packed projection (B, L, [q | k | v | z])
    and the taps, in ``dtype``."""
    heads = gdn_conv_kernel.Heads(keys, N, keys * group, P)
    conv = 2 * keys * N + heads.values * P
    rng = np.random.default_rng(seed)
    qkvz = rng.normal(size=(bsz, length, conv + heads.values * P)) * 0.7
    weight = rng.normal(size=(conv, taps)) * 0.5
    return jnp.asarray(qkvz, dtype), jnp.asarray(weight, dtype), heads


def _flat(outs):
    return tuple(o.reshape(o.shape[:2] + (-1,)) for o in outs)


def _cots(qkvz, heads, seed=9):
    """Cotangents for ``q``, ``k`` and ``v``, (B, L, .) in the
    projection's dtype."""
    rng = np.random.default_rng(seed)
    wide_k = heads.keys * heads.n
    return tuple(jnp.asarray(rng.normal(size=qkvz.shape[:2] + (wide,)),
                             qkvz.dtype)
                 for wide in (wide_k, wide_k, heads.values * heads.p))


@functools.cache
def _plain_program(heads):
    """The plain form's ``(q, k, v)`` and JAX's gradients of it for the
    projection and the taps as one jitted function of float32 operands:
    the cases of one shape, whatever their dtype, share its trace."""
    def both(x, w, *cots):
        out, vjp = jax.vjp(
            lambda x, w: _flat(seq._operands_plain(x, w, heads)), x, w)
        return out, vjp(cots)

    return jax.jit(both)


def _plain(qkvz, weight, heads, cots):
    """The plain form's ``(q, k, v)`` and JAX's gradients of it for the
    projection and the taps, in float32 from the operands as given."""
    f32 = jnp.float32
    return _plain_program(heads)(qkvz.astype(f32), weight.astype(f32),
                                 *(c.astype(f32) for c in cots))


def _kernels(qkvz, weight, heads, seed=9):
    """``(q, k, v)`` of the forward kernel, cotangents drawn for them, and
    the backward kernel's ``(d_rows, d_weight)``, both interpreted, one
    program."""
    cots = _cots(qkvz, heads, seed)
    (got, grads), _ = numerics.traced(
        lambda x, w, *c: (
            gdn_conv_kernel.forward(x, w, tuple(heads), interpret=True),
            gdn_conv_kernel.backward(x, w, *c, tuple(heads),
                                     interpret=True)),
        (qkvz, weight, *cots))
    return got, cots, grads


# one block of 256 rows and a row (257 rows show both edges of a block, the
# halo after it and the carried rows before it, as two whole blocks would);
# one step of a group and a padded tail; shorter than the taps
@functools.cache
def _batch_of_two(dtype, group, length):
    """The operands of a batch of two entries and what ``_kernels`` gives
    for them: one traced program serves the cases of both entries."""
    qkvz, weight, heads = _operands(2, length, group, jnp.dtype(dtype),
                                    seed=length + group)
    return (qkvz, weight, heads) + _kernels(qkvz, weight, heads)


@pytest.mark.parametrize("length", [257, 33, 2])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("entry", [0, 1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_are_the_plain_form_and_its_derivative(dtype, entry, group,
                                                       length):
    """``q``, ``k``, ``v`` and the gradients for the projection's
    convolved columns and for the taps: in float32 to 1e-5 of the largest
    value, in bfloat16 within the rounding of one output. An entry of a
    batch of two against the plain form of that entry alone: its first
    rows see zeros before them, not the entry before, and its last rows'
    cotangent nothing of the entry after; a block's first rows see the
    block before through the halo, and its last rows' cotangent the block
    after through the carried rows. The taps' gradient is the sum of both
    entries'."""
    qkvz, weight, heads, got, cots, (d_rows, d_weight) = _batch_of_two(
        dtype, group, length)
    (want, (want_dx, want_dw)), (_, (_, other_dw)) = (
        _plain(qkvz[e:e + 1], weight, heads, tuple(c[e:e + 1] for c in cots))
        for e in (entry, 1 - entry))
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    assert {a.dtype for a in got} == {qkvz.dtype}
    numerics.close(tuple(a[entry:entry + 1] for a in got), want,
                   kernel_tol(tol), "qkv")
    assert d_weight.dtype == weight.dtype
    conv = weight.shape[0]
    assert not np.asarray(want_dx[..., conv:]).any()    # the gate's columns
    numerics.close(jnp.concatenate(d_rows, axis=-1)[entry:entry + 1],
                   want_dx[..., :conv], kernel_tol(tol), "dx")
    numerics.close(d_weight, want_dw + other_dw, kernel_tol(4 * tol), "dw")


def test_a_second_sequence_does_not_see_the_first_one_s_rows():
    """The halo and the carried rows are zeros where a batch entry begins
    and ends: the second entry gives what it gives alone, to the bit."""
    qkvz, weight, heads = _operands(2, 300, 2, jnp.bfloat16, seed=3)
    both, cots, (d_both, _) = _kernels(qkvz, weight, heads)
    alone, _ = numerics.traced(lambda x, w: gdn_conv_kernel.forward(
        x, w, tuple(heads), interpret=True), (qkvz[1:], weight))
    (d_alone, _), _ = numerics.traced(
        lambda x, w, *c: gdn_conv_kernel.backward(
            x, w, *c, tuple(heads), interpret=True),
        (qkvz[1:], weight, *(c[1:] for c in cots)))
    for a, b in zip(both + d_both, alone + d_alone):
        np.testing.assert_array_equal(np.asarray(a[1:], np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("taps", [2, 3])
def test_other_filters_than_four_taps(taps):
    qkvz, weight, heads = _operands(1, 70, 1, jnp.float32, seed=taps,
                                    taps=taps)
    got, cots, (d_rows, d_weight) = _kernels(qkvz, weight, heads)
    want, (want_dx, want_dw) = _plain(qkvz, weight, heads, cots)
    numerics.close(got, want, kernel_tol(1e-5), "qkv")
    numerics.close(jnp.concatenate(d_rows, axis=-1),
                   want_dx[..., :weight.shape[0]], kernel_tol(1e-5), "dx")
    numerics.close(d_weight, want_dw, kernel_tol(4e-5), "dw")


def test_two_key_heads_are_normalised_each_over_its_own_lanes():
    """Two heads of ``q`` side by side in one part: each row's norm is
    over one head's 128 lanes, and ``q`` is scaled by ``N ** -0.5``."""
    qkvz, weight, heads = _operands(1, 40, 2, jnp.float32, seed=8, keys=2)
    q, k, v = gdn_conv_kernel.forward(qkvz, weight, tuple(heads),
                                      interpret=True)
    norms = np.linalg.norm(np.asarray(k).reshape(1, 40, 2, N), axis=-1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-4)
    norms = np.linalg.norm(np.asarray(q).reshape(1, 40, 2, N), axis=-1)
    np.testing.assert_allclose(norms, N ** -0.5, atol=1e-5)
    numerics.close((q, k, v), _flat(seq._operands_plain(qkvz, weight, heads)),
                   kernel_tol(1e-5), "qkv")


def test_the_rule_of_shapes_reads_shapes_alone():
    """Key and value heads whole lane tiles, 2 to 9 taps, one dtype for
    the projection and the taps, column parts that exist and the blocks
    under the VMEM budget: the Qwen3-Next cell's shapes are taken (two
    parts of 4096 columns); heads that are no lane tile, mixed dtypes, a
    long filter and rows that would not fit are not."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    heads = gdn_conv_kernel.Heads
    cell = heads(16, 128, 32, 128)
    assert gdn_conv_kernel.takes(cell, 4, bf16, bf16)
    assert gdn_conv_kernel.parts(cell) == 2
    assert gdn_conv_kernel.takes(heads(1, 128, 1, 128), 4, f32, f32)
    assert gdn_conv_kernel.takes(heads(2, 256, 4, 128), 2, bf16, bf16)
    assert not gdn_conv_kernel.takes(heads(2, 8, 4, 6), 4, bf16, bf16)
    assert not gdn_conv_kernel.takes(heads(2, 128, 4, 6), 4, bf16, bf16)
    assert not gdn_conv_kernel.takes(heads(2, 192, 4, 128), 4, bf16, bf16)
    assert not gdn_conv_kernel.takes(cell, 4, bf16, f32)     # mixed dtypes
    assert not gdn_conv_kernel.takes(cell, 4, jnp.float16, jnp.float16)
    assert not gdn_conv_kernel.takes(cell, 1, bf16, bf16)
    assert not gdn_conv_kernel.takes(cell, 10, bf16, bf16)
    # ``v``'s window has to begin at a whole multiple of its width
    assert gdn_conv_kernel.parts(heads(1, 128, 3, 128)) is None
    assert not gdn_conv_kernel.takes(heads(1, 128, 3, 128), 4, bf16, bf16)
    # one key head 4096 wide cannot be cut into parts: over the budget
    assert not gdn_conv_kernel.takes(heads(1, 32768, 1, 32768), 4, f32, f32)
    held = gdn_conv_kernel.held_bytes(cell, 2)
    assert 8e6 < held < gdn_conv_kernel._BUDGET_BYTES \
        < gdn_conv_kernel._VMEM_LIMIT_BYTES
    # a long sequence in steps of 256 rows, a short one in one step of
    # whole groups
    assert gdn_conv_kernel.block_rows(8192) == (8192, 256)
    assert gdn_conv_kernel.block_rows(257) == (512, 256)
    assert gdn_conv_kernel.block_rows(100) == (128, 128)
    assert gdn_conv_kernel.block_rows(2) == (32, 32)


# ---------------------------------------------------------------------------
# the mixer's two forms
# ---------------------------------------------------------------------------
def _mixer(head, dtype, length=40, bsz=2, seed=4, chunk=16):
    """``loss(data, *weights)`` of a ``gated_delta_net`` with one key head
    and two value heads ``head`` wide, in chunks of ``chunk`` rows, and
    its arguments."""
    hidden = 32
    conv = 4 * head
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    args = (draw(bsz, length, hidden),
            draw(conv + 2 * head, hidden, scale=hidden ** -0.5),
            draw(4, hidden, scale=hidden ** -0.5), draw(conv, TAPS, scale=0.5),
            draw(2), draw(2, scale=0.1), 1 + draw(head, scale=0.1),
            draw(hidden, 2 * head, scale=head ** -0.5))
    cot = jnp.asarray(rng.normal(size=(bsz, length, hidden)), jnp.float32)

    def loss(*a):
        out = seq.gated_delta_net(*a, num_k_heads=1, num_v_heads=2,
                                  key_dim=head, value_dim=head,
                                  chunk_size=chunk)
        return jnp.sum(out * cot)

    return loss, args


@pytest.fixture()
def kernels_here(monkeypatch):
    """``ops.seq`` takes its TPU branches on this backend, both kernel
    pairs interpreted."""
    monkeypatch.setattr(seq, "lax", numerics.LoweredForATpu())
    for module in (gdn_conv_kernel, seq.gdn_kernel):
        for name in ("forward", "backward"):
            monkeypatch.setattr(module, name, functools.partial(
                getattr(module, name), interpret=True))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_mixer_through_the_kernels_is_the_plain_form_with_every_gradient(
        dtype, monkeypatch, kernels_here):
    """The TPU's branch of the mixer's ``custom_vjp`` on this CPU, both
    kernel pairs interpreted: the value and the gradients for the input
    and all seven weights (the norm's, which is not all ones, among
    them), the projection's cotangent put together from the operands'
    kernels' three parts and the rule's kernel's ``dz``."""
    loss, args = _mixer(N, jnp.dtype(dtype))
    with monkeypatch.context() as m:
        m.setattr(gdn_conv_kernel, "takes", lambda *a: False)
        want = numerics.traced(loss, args, 1.0, range(len(args)))
    got = numerics.traced(loss, args, 1.0, range(len(args)))
    tol = 2e-4 if dtype == "float32" else 2.0 ** -5
    numerics.close(got, want, kernel_tol(tol), same_dtype=True)


def test_the_projection_s_cotangent_is_four_parts_side_by_side(kernels_here):
    """``[dq | dk | dv | dz]``, put together once: the convolved columns
    hold what the operands' backward kernel gives for the rule's three
    cotangents, the gate's columns hold the rule's backward kernel's
    ``dz``, each to the bit, nothing added to either. (One chunk of one
    value head: the kernels' programs at their smallest.)"""
    chunk, eps = 16, 1e-6
    qkvz, weight, heads = _operands(1, 16, 1, jnp.bfloat16, seed=6)
    rng = np.random.default_rng(7)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (1, 16, 1)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0, 1, (1, 16, 1)), jnp.float32)
    gamma = jnp.asarray(1 + 0.3 * rng.normal(size=(P,)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(1, 16, P)), jnp.bfloat16)

    def by_hand(x, w, beta, g, gamma, dy):
        q, k, v = gdn_conv_kernel.forward(x, w, tuple(heads))
        q, k, v = (t.reshape(t.shape[:2] + (-1, N)) for t in (q, k, v))
        _, states, inverses = seq.gdn_kernel.forward(
            q, k, v, beta, g, x, gamma, chunk=chunk, eps=eps)
        dq, dk, dv, _, _, dz, _ = seq.gdn_kernel.backward(
            q, k, v, beta, g, x, gamma, states, inverses, dy, chunk=chunk,
            eps=eps)
        d_rows, _ = gdn_conv_kernel.backward(
            x, w, *(d.reshape(x.shape[:2] + (-1,)) for d in (dq, dk, dv)),
            tuple(heads))
        return d_rows, dz

    def through(x, w, beta, g, gamma, dy):
        return jax.vjp(lambda x: seq._mixer_kernels(
            x, w, beta, g, gamma, heads, chunk, eps), x)[1](dy)[0]

    args = (qkvz, weight, beta, g, gamma, dy)
    (d_rows, dz), _ = numerics.traced(by_hand, args)
    d_x, _ = numerics.traced(through, args)
    conv = weight.shape[0]
    assert d_x.shape == qkvz.shape and d_x.dtype == qkvz.dtype
    assert dz.shape == qkvz.shape[:2] + (qkvz.shape[2] - conv,)
    assert np.asarray(dz, np.float32).any()
    np.testing.assert_array_equal(
        np.asarray(d_x[..., conv:], np.float32), np.asarray(dz, np.float32))
    np.testing.assert_array_equal(
        np.asarray(d_x[..., :conv], np.float32),
        np.asarray(jnp.concatenate(d_rows, axis=-1), np.float32))


def _lowered(head, platform, dtype=jnp.bfloat16):
    """The text of the mixer's value and gradients lowered for
    ``platform`` at heads ``head`` wide, and what the gauge counted."""
    loss, args = _mixer(head, dtype)
    mx.telemetry.gauge(gdn_conv_kernel.GAUGE).set(0)
    text = jax.jit(jax.value_and_grad(loss, argnums=range(len(args)))).trace(
        *args).lower(lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(gdn_conv_kernel.GAUGE).get()


@pytest.mark.parametrize("head,platform,sites", [
    (128, "tpu", 1),    # the kernels: one forward, one backward
    (128, "cpu", 0),    # another platform: the plain form
    (8, "tpu", 0)])     # heads the rule of shapes refuses: the same
def test_kernel_sites_follow_the_platform_and_the_rule_of_shapes(
        head, platform, sites):
    text, counted = _lowered(head, platform)
    assert counted == sites
    assert ("gdn_conv_fwd_kernel" in text) \
        == ("gdn_conv_bwd_kernel" in text) == bool(sites)
    # the plain form pads the rows for its taps; the kernels read a halo
    assert ("stablehlo.pad" in text) or sites


def test_taps_of_another_dtype_stay_the_plain_form():
    """A float32 filter over a bfloat16 projection is rounded by the
    plain form where it multiplies: no kernel."""
    loss, args = _mixer(N, jnp.bfloat16)
    args = args[:3] + (args[3].astype(jnp.float32),) + args[4:]
    mx.telemetry.gauge(gdn_conv_kernel.GAUGE).set(0)
    text = jax.jit(loss).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "gdn_conv" not in text
    assert mx.telemetry.gauge(gdn_conv_kernel.GAUGE).get() == 0


def _parent_s_mixer(data, qkvz_weight, ba_weight, conv_weight, dt_bias, a_log,
                    norm_weight, out_weight, hk, hv, dk, dv, chunk):
    """``gated_delta_net`` as it stood before the kernels, line for
    line (but ``beta`` and ``g``, which it formed after the convolution
    and forms before it since the mixer's middle is one function: the
    same operations, two lines higher in the text)."""
    _F32 = jnp.float32
    bsz, length, _ = data.shape
    qkvz = seq.kept(seq._mm(data, qkvz_weight))
    ba = seq.kept(seq._mm(data, ba_weight))
    conv = 2 * hk * dk + hv * dv
    beta = jax.nn.sigmoid(ba[..., :hv].astype(_F32))
    g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
        ba[..., hv:].astype(_F32) + dt_bias.astype(_F32))
    qkv = jax.nn.silu(seq.causal_conv1d(qkvz[..., :conv], conv_weight, None))
    q, k = (seq._l2_norm(t.reshape(bsz, length, hk, dk), 1e-6)
            for t in (qkv[..., :hk * dk], qkv[..., hk * dk:2 * hk * dk]))
    v = qkv[..., 2 * hk * dk:].reshape(bsz, length, hv, dv)
    o = seq.gated_delta_rule((q * dk ** -0.5).astype(data.dtype),
                             k.astype(data.dtype), v, beta, g, chunk)
    z = qkvz[..., conv:].astype(_F32).reshape(bsz, length, hv, dv)
    y = seq._rms_norm(o, norm_weight, eps=1e-6) * jax.nn.silu(z)
    return seq._mm(y.reshape(bsz, length, hv * dv).astype(data.dtype),
                   out_weight)


def _without_locations(text):
    import re
    return re.sub(r"\s*loc\([^\n]*\)|#loc[^\n]*\n", "", text)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_refused_shapes_lower_to_the_parent_s_text(platform):
    """At heads the rule of shapes refuses, the mixer's lowered text,
    value and gradients, is the text of the lines it had before the
    kernels, for either platform."""
    loss, args = _mixer(8, jnp.bfloat16)
    cot = jnp.ones(args[0].shape, jnp.float32)

    def text(fn):
        def loss(*a):
            return jnp.sum(fn(*a) * cot)

        return _without_locations(
            jax.jit(jax.value_and_grad(loss, argnums=range(len(args)))).trace(
                *args).lower(lowering_platforms=(platform,)).as_text(
                    debug_info=False))

    assert text(lambda *a: seq.gated_delta_net(
        *a, num_k_heads=1, num_v_heads=2, key_dim=8, value_dim=8,
        chunk_size=16)) == text(lambda *a: _parent_s_mixer(*a, 1, 2, 8, 8, 16))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_off_a_tpu_the_values_are_the_parent_s_to_the_bit(dtype):
    """Where the rule takes the shapes and the platform is not a TPU, the
    mixer's value and every gradient are what the lines it had before the
    kernels give."""
    loss, args = _mixer(N, jnp.dtype(dtype))
    cot = jnp.asarray(np.random.default_rng(2).normal(size=args[0].shape),
                      jnp.float32)
    assert gdn_conv_kernel.takes(_heads(2), TAPS, jnp.dtype(dtype),
                                 jnp.dtype(dtype))
    numerics.agree(
        lambda *a: seq.gated_delta_net(
            *a, num_k_heads=1, num_v_heads=2, key_dim=N, value_dim=N,
            chunk_size=16),
        lambda *a: _parent_s_mixer(*a, 1, 2, N, N, 16), args, cot,
        range(len(args)), value=numerics.TO_THE_BIT)


def test_a_unit_through_the_kernels_keeps_what_the_plain_form_keeps(
        monkeypatch):
    """Both input products and the gated norm's statistics, nothing of
    the convolution: the kernels read the kept projection in both
    passes."""
    from mxnet_tpu.ops import remat
    loss, args = _mixer(N, jnp.bfloat16)

    def unit(data):
        return seq.gated_delta_net(data, *args[1:], num_k_heads=1,
                                   num_v_heads=2, key_dim=N, value_dim=N,
                                   chunk_size=16)

    got = remat.kept_bytes(jax.make_jaxpr(unit)(args[0]))
    monkeypatch.setattr(gdn_conv_kernel, "takes", lambda *a: False)
    want = remat.kept_bytes(jax.make_jaxpr(unit)(args[0]))
    assert got == want > 0


def test_a_train_step_sets_the_gauge_to_zero_where_it_traces():
    """``TrainStep`` resets ``gdn::conv_kernel_sites`` beside the other
    kernels' gauges, so that a step's reading is that step's: a model
    with a delta-rule layer traced for this CPU reads 0 whatever stood
    there."""
    from mxnet_tpu.gluon.model_zoo import PatternLM
    from mxnet_tpu.parallel import TrainStep
    net = PatternLM("D", 31, 16,
                    linear_attention=dict(num_k_heads=1, num_v_heads=2,
                                          key_dim=8, value_dim=8,
                                          chunk_size=8),
                    mlp=dict(units=24))
    net.initialize(mx.init.Normal(0.3))
    step = TrainStep(net, loss="softmax_ce", optimizer="sgd",
                     optimizer_params=dict(learning_rate=1e-2))
    gauge = mx.telemetry.gauge(gdn_conv_kernel.GAUGE)
    gauge.set(7)
    step(mx.nd.array(np.zeros((2, 6), np.int32)),
         mx.nd.array(np.zeros((12,), np.int32)))
    assert gauge.get() == 0
