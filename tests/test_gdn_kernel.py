"""The gated delta rule's kernels (``ops/gdn_kernel.py``: the rule and,
where a chunk's output is still in VMEM, the gated norm ``rmsnorm(o) * w *
silu(z)``) against the plain form (``ops.seq.gated_delta_rule`` followed by
``_gated_norm``) and JAX's own derivative of it, interpreted on the CPU;
the rule of shapes they are taken by; and which form the mixer around them
takes: the kernels where the rule takes the shapes and the program is
lowered for a TPU, the plain form everywhere else, with the gauge
``gdn::kernel_sites`` counting the sites. Nothing here is a time."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import gdn_conv_kernel, gdn_kernel, seq

import numerics
from test_gdn_conv_kernel import _mixer

N = P = 128
CHUNK = 16
EPS = 1e-6
NAMES = "y dq dk dv dbeta dg dz dw".split()


def _operands(length, dtype, group=1, bsz=1, seed=0, decay=1.0, n=N, p=P):
    """``(q, k, v, beta, g, z, w)`` as the mixer hands them over: unit
    keys, scaled unit queries, ``beta`` in (0, 1), ``g <= 0``; one key
    head, ``group`` value heads; ``z`` the last ``group * p`` columns of
    rows that hold one block of other numbers before them; a norm's
    weight that is not all ones (a weight of 1 hides a missing factor)."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(bsz, length, 1, n))) * n ** -0.5
    k = unit(rng.normal(size=(bsz, length, 1, n)))
    v = rng.normal(size=(bsz, length, group, p))
    beta = 1 / (1 + np.exp(-rng.normal(size=(bsz, length, group))))
    g = -rng.uniform(0, decay, size=(bsz, length, group))
    z = rng.normal(size=(bsz, length, 2 * group * p))
    w = 1 + 0.3 * rng.normal(size=(p,))
    f32 = jnp.float32
    return tuple(jnp.asarray(t, d) for t, d in zip(
        (q, k, v, beta, g, z, w), (dtype, dtype, dtype, f32, f32, dtype, f32)))


def _cot(args, seed=9, ones=False):
    """A cotangent for ``y``, (B, L, H P) in its dtype."""
    bsz, length, group, p = args[2].shape
    shape = (bsz, length, group * p)
    if ones:
        return jnp.ones(shape, args[2].dtype)
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       args[2].dtype)


def _plain_form(q, k, v, beta, g, z, w, chunk=CHUNK):
    return seq._gated_norm(seq.gated_delta_rule(q, k, v, beta, g, chunk), z,
                           w, EPS)


def _plain(args, cot, chunk=CHUNK):
    """The plain form's value and gradients, float32 products at full
    precision."""
    def both(*a):
        out, vjp = jax.vjp(lambda *a: _plain_form(*a, chunk), *a)
        return out, vjp(cot)

    with jax.default_matmul_precision("highest"):
        return numerics.traced(both, args)[0]


def _kernels(args, cot, chunk=CHUNK):
    def both(*a):
        y, states, inverses = gdn_kernel.forward(*a, chunk=chunk, eps=EPS,
                                                 interpret=True)
        return y, gdn_kernel.backward(*a, states, inverses, cot, chunk=chunk,
                                      eps=EPS, interpret=True)

    return numerics.traced(both, args)[0]


def _same(got, want, tol):
    """``(y, gradients)`` of the kernels to the plain form's, by name; the
    plain form's cotangent of the rows that hold ``z`` is nothing before
    the gate's columns, and the kernels give those columns alone."""
    (y, grads), (want_y, want_grads) = got, want
    *others, want_dz, want_dw = want_grads
    wide = grads[5].shape[-1]
    assert not np.asarray(want_dz[..., :-wide], np.float32).any()
    want_grads = (*others, want_dz[..., -wide:], want_dw)
    numerics.close(dict(zip(NAMES, (y, *grads))),
                   dict(zip(NAMES, (want_y, *want_grads))),
                   numerics.kernel_tol(tol), same_dtype=True)


@pytest.mark.parametrize("length", [32, 40])    # whole chunks; a padded tail
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_are_the_plain_form_and_its_derivative(dtype, group, length):
    """``y`` and the gradients for ``q``, ``k``, ``v``, ``beta``, ``g``,
    the gate's ``z`` and the norm's weight: in float32 to 1e-5 of the
    largest value, in bfloat16 within the rounding of one output. One grid
    step of two chunks, and one of three whose last is half padding (the
    gate's columns are then cut out of their rows and padded; whole chunks
    read them in place, one block into the rows)."""
    args = _operands(length, jnp.dtype(dtype), group, seed=length + group)
    cot = _cot(args)
    _same(_kernels(args, cot), _plain(args, cot),
           1e-5 if dtype == "float32" else 2.0 ** -7)


def test_the_state_crosses_grid_steps_forward_and_backward():
    """130 rows in chunks of 16 are two grid steps of eight chunks (the
    second mostly padding): the state leaves the first step in the VMEM
    scratch and ``dS`` comes back through it; the weight's row sums add up
    over both steps in one block."""
    assert gdn_kernel.steps(130, CHUNK) == (256, 128)
    args = _operands(130, jnp.bfloat16, 1, seed=11, decay=0.1)
    cot = _cot(args, 4)
    got = _kernels(args, cot)
    _same(got, _plain(args, cot), 2.0 ** -7)
    # the second step's rows read what the first step wrote
    assert float(jnp.max(jnp.abs(got[0][:, 128:]))) > 0


def test_a_strong_decay_underflows_to_zero_and_not_to_nan():
    """``g`` near -20 a step, -300 over a chunk: ``exp`` of differences
    ``G_i - G_j <= 0`` only, never a quotient of two underflowed
    numbers."""
    args = _operands(40, jnp.float32, 2, seed=5, decay=25.0)
    cot = _cot(args, ones=True)
    got = _kernels(args, cot)
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in jax.tree_util.tree_leaves(got))
    _same(got, _plain(args, cot), 1e-5)


def test_a_second_sequence_does_not_see_the_first_one_s_state():
    """The states' scratch is set to zero where a sequence begins: the
    second sequence of a batch gives what it gives alone, to the bit. But
    two of the gradients: the weight's is a sum over both sequences, and
    ``dg`` holds a sum over a head's (N, P) numbers, whose order is the
    compiled program's, another for another batch: to 1e-5."""
    both = _operands(32, jnp.bfloat16, 1, bsz=2, seed=3)
    alone = tuple(t[1:] for t in both[:-1]) + both[-1:]
    cot = _cot(both, ones=True)
    got, want = ((y, dict(zip(NAMES[1:], grads))) for y, grads in (
        _kernels(both, cot), _kernels(alone, cot[1:])))
    np.testing.assert_array_equal(np.asarray(got[0][1:], np.float32),
                                  np.asarray(want[0], np.float32))
    for name in "dq dk dv dbeta dz".split():
        np.testing.assert_array_equal(
            np.asarray(got[1][name][1:], np.float32),
            np.asarray(want[1][name], np.float32), err_msg=name)
    np.testing.assert_allclose(got[1]["dg"][1:], want[1]["dg"], atol=1e-5)


def test_a_padded_tail_writes_nothing():
    """The same first 20 outputs whether 20 steps are given (padded to
    two chunks) or 32; beside the output, every chunk's entering state
    and inverse for the backward kernel."""
    args = _operands(32, jnp.float32, 1, seed=1)
    (whole, states, inverses), _ = numerics.traced(
        lambda *a: gdn_kernel.forward(*a, chunk=CHUNK, eps=EPS,
                                      interpret=True), args)
    short = numerics.traced(
        lambda *a: gdn_kernel.forward(*a, chunk=CHUNK, eps=EPS,
                                      interpret=True)[0],
        tuple(t[:, :20] for t in args[:-1]) + args[-1:])[0]
    np.testing.assert_allclose(short, whole[:, :20], atol=1e-6)
    assert states.shape == (1, 1, 2, N, P) and inverses.shape == (
        1, 1, 2, CHUNK, CHUNK)
    assert not np.asarray(states[:, :, 0]).any()    # from a zero state


def test_every_level_of_the_inverse_at_the_cell_s_chunk():
    """Chunks of 64, the Qwen3-Next cell's: diagonal blocks of 8 merged
    three times, the matrices twice side by side in a lane tile."""
    args = _operands(100, jnp.bfloat16, 2, seed=13, decay=0.05)
    cot = _cot(args, 6)
    _same(_kernels(args, cot, 64), _plain(args, cot, 64), 2.0 ** -7)


def test_the_rule_of_shapes_reads_shapes_alone():
    """``N`` and ``P`` whole lane tiles, the chunk whole sublane tiles of
    the dtype and at most 128, the gate's ``z`` whole blocks of a key
    head's value columns into its rows, the blocks under the VMEM budget:
    the Qwen3-Next cell's shapes are taken (``z`` 8192 columns, 32 blocks
    of 256, into the packed projection); heads that are no lane tile, a
    chunk over 128 or off the tiles, a gate off the blocks and states that
    would not fit are not."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert gdn_kernel.takes(128, 128, 64, bf16, group=2)
    assert gdn_kernel.takes(128, 128, 64, bf16, group=2, gate_offset=8192)
    assert gdn_kernel.takes(128, 128, 64, bf16, group=1, gate_offset=128)
    assert not gdn_kernel.takes(128, 128, 64, bf16, group=2, gate_offset=128)
    assert not gdn_kernel.takes(128, 128, 64, bf16, group=4, gate_offset=768)
    assert gdn_kernel.takes(256, 128, 64, bf16)
    assert gdn_kernel.takes(128, 128, 128, bf16)
    assert gdn_kernel.takes(128, 128, 8, f32)
    assert not gdn_kernel.takes(128, 128, 8, bf16)      # half a bf16 tile
    assert not gdn_kernel.takes(128, 128, 48, bf16)     # 8 doubled: no
    assert not gdn_kernel.takes(128, 128, 256, bf16)
    assert not gdn_kernel.takes(8, 128, 64, bf16)
    assert not gdn_kernel.takes(128, 6, 64, bf16)
    assert not gdn_kernel.takes(128, 192, 64, bf16)
    assert not gdn_kernel.takes(128, 128, 64, jnp.int8)
    assert not gdn_kernel.takes(128, 128, 64, jnp.float16)
    assert not gdn_kernel.takes(1024, 1024, 64, bf16, group=4)
    held = max(gdn_kernel.forward_bytes(128, 128, 64, 2, 2),
               gdn_kernel.backward_bytes(128, 128, 64, 2, 2))
    assert 3e6 < held < gdn_kernel._BUDGET_BYTES \
        < gdn_kernel._VMEM_LIMIT_BYTES
    # a long sequence in steps of whole lane tiles of rows, a short one in
    # one step of all its chunks
    assert gdn_kernel.steps(8192, 64) == (8192, 256)
    assert gdn_kernel.steps(8200, 64) == (8448, 256)
    assert gdn_kernel.steps(200, 64) == (256, 256)
    assert gdn_kernel.steps(100, 64) == (128, 128)
    assert gdn_kernel.steps(130, 16) == (256, 128)
    assert gdn_kernel.steps(40, 16) == (48, 48)


def _lowered(head, platform, chunk=CHUNK):
    """The text of the mixer's value and gradients lowered for
    ``platform`` at heads ``head`` wide, and what the rule's gauge
    counted."""
    loss, args = _mixer(head, jnp.bfloat16, chunk=chunk)
    mx.telemetry.gauge(gdn_kernel.GAUGE).set(0)
    text = jax.jit(jax.value_and_grad(loss, argnums=range(len(args)))).trace(
        *args).lower(lowering_platforms=(platform,)).as_text()
    return text, mx.telemetry.gauge(gdn_kernel.GAUGE).get()


@pytest.mark.parametrize("head,platform,sites,calls", [
    # the kernels: the operands' and the rule's, forward and backward
    (128, "tpu", 1, 4),
    (128, "cpu", 0, 0),     # another platform: the plain form
    (8, "tpu", 0, 0)])      # heads the rule of shapes refuses: the same
def test_kernel_sites_follow_the_platform_and_the_rule_of_shapes(
        head, platform, sites, calls):
    text, counted = _lowered(head, platform)
    assert counted == sites
    assert text.count("tpu_custom_call") == calls
    assert ("gdn_fwd_kernel" in text) == ("gdn_bwd_kernel" in text) \
        == bool(calls)
    # the plain form's chain of chunks is a loop; the kernels' is their grid
    assert ("stablehlo.while" in text) == (calls == 0)


def test_a_chunk_the_rule_refuses_leaves_the_whole_mixer_plain():
    """Chunks of 48 rows are no diagonal block of 8 doubled: the operands'
    kernels would take the heads, the rule's do not take the chunk, and
    the mixer's middle is one program: no kernel of either pair."""
    assert gdn_conv_kernel.takes(gdn_conv_kernel.Heads(1, N, 2, P), 4,
                                 jnp.bfloat16, jnp.bfloat16)
    mx.telemetry.gauge(gdn_conv_kernel.GAUGE).set(0)
    text, counted = _lowered(N, "tpu", chunk=48)
    assert "tpu_custom_call" not in text
    assert counted == mx.telemetry.gauge(gdn_conv_kernel.GAUGE).get() == 0


def test_off_a_tpu_the_program_is_the_plain_form_to_the_bit():
    """Where the rule takes the shapes and the platform is not a TPU,
    value and gradients of the mixer's middle are its plain lines' as JAX
    differentiates them: the convolution, the rule and the gated norm."""
    *_, beta, g, _, w = _operands(40, jnp.bfloat16, 2, bsz=2, seed=7)
    heads = gdn_conv_kernel.Heads(1, N, 2, P)
    rng = np.random.default_rng(2)
    qkvz = jnp.asarray(rng.normal(size=(2, 40, 6 * N)), jnp.bfloat16)
    taps = jnp.asarray(rng.normal(size=(4 * N, 4)) * 0.5, jnp.bfloat16)
    args = (qkvz, taps, beta, g, w)
    cot = jnp.asarray(rng.normal(size=(2, 40, 2 * P)), jnp.float32)
    assert gdn_kernel.takes(N, P, CHUNK, jnp.bfloat16, 2, gate_offset=4 * N)
    numerics.agree(lambda *a: seq._mixer_kernels(*a, heads, CHUNK, EPS),
                   lambda *a: seq._mixer_plain(*a, heads, CHUNK, EPS), args,
                   cot, range(5), value=numerics.TO_THE_BIT)
