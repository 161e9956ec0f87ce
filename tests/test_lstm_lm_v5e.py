"""The step of ``lstm-lm-train`` compiled for a v5e that is described and
not attached (``tests/described_v5e.py``), at the sizes the cell times:
each of the ``RNN`` operator's four loops holds one matrix product, and
the step takes no more of the chip than it did. Nothing runs here: counts
by XLA, not times."""
import re

import jax
import jax.numpy as jnp

from described_v5e import (harness, no_jax_cache, one_chip,  # noqa: F401
                           peak_bytes)

#: XLA's memory for the ``lstm-lm-train`` step while the ``RNN`` operator's
#: scans still held the input's product and both weight gradients
LSTM_STEP_BYTES_BEFORE = 5.01e9


def _computations(text):
    """``{name: [instruction lines]}`` of a compiled program's text."""
    out, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if head and not line.startswith(" "):
            lines = out.setdefault(head.group(1), [])
        elif lines is not None:
            lines.append(line)
    return out


def _products_under(computations, name):
    """How many ``convolution`` instructions (a matrix product on the TPU)
    a computation and the fusions it calls hold."""
    return sum(
        bool(re.search(r"=\s*\S+\s+convolution\(", line))
        + sum(_products_under(computations, called)
              for called in re.findall(r"calls=%?([\w.\-]+)", line))
        for line in computations[name])


def test_lstm_lm_loops_hold_one_product_each(one_chip, no_jax_cache):
    cell = harness.load_cell("lstm-lm-train")
    sizes = cell.sizes
    step = cell.model.build(cell.config, sizes, "step",
                            cell.model.make_weights(sizes, 0)).step
    step._init_state()
    step._build_step()
    args = (step._pvals, step._opt_state,
            jnp.zeros((sizes["batch"], sizes["bptt"]), jnp.int32),
            jnp.zeros((sizes["batch"] * sizes["bptt"],), jnp.int32),
            step._t_dev, jnp.asarray(0.1, jnp.float32))
    compiled = step._step_jit.lower(*jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)).compile()
    peak = peak_bytes(compiled)
    computations = _computations(compiled.as_text())
    bodies = [body for lines in computations.values() for line in lines
              for body in re.findall(r"\bwhile\(.*body=%?([\w.\-]+)", line)]
    per_body = [_products_under(computations, b) for b in bodies]
    print(f"lstm-lm-train step: {peak / 1e9:.2f} GB, {len(bodies)} loops "
          f"with {per_body} products")
    # two layers, forward and backward; the backward bodies held four
    # products and the forward ones two before the scan was cut down
    assert len(bodies) == sizes["layers"] * 2 == 4
    assert per_body == [1, 1, 1, 1]
    assert peak <= LSTM_STEP_BYTES_BEFORE, peak
