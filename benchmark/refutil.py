"""What the plain references share: a JAX key from any seed, the rounding
that makes a reference the control, and the loop over the first steps."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed):
    """A key for any non-negative whole seed (the driver's exceed
    2**31, which ``PRNGKey`` alone does not take)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def fp8(x):
    """Round to the four significant bits of an 8-bit float (e4m3), by
    arithmetic, so that it runs wherever float32 does; its range is not
    imitated. Gradients pass straight through."""
    m, e = jnp.frexp(x)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + lax.stop_gradient(q - x)


def held(x, precision):
    """A tensor as the network holds it between operations: as it is in
    the reference; rounded to an 8-bit float in the control, where the
    program holds bfloat16."""
    return fp8(x) if precision == "fp8" else x


@jax.jit
def _change(p, p0):
    delta = {k: p[k] - p0[k] for k in p}
    return delta, {k: jnp.sqrt(jnp.sum(jnp.square(d)))
                   for k, d in delta.items()}


def first_steps(step, params, batches, learning_rate):
    """Drive ``step(p, m, x, y) -> (p, m, loss)`` from ``params`` and zero
    momentum over ``batches``, one batch a step. Returns the loss of every
    step, and by parameter the norm of the first step's change over the
    learning rate (the gradient as the optimizer gets it, weight decay
    included), the norm of the change after the last step, and the first
    step's change itself."""
    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first, update = [], None, None
    for i, (x, y) in enumerate(batches):
        p, m, loss = step(p, m, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if i == 0:
            update, norms = jax.device_get(_change(p, params))
            first = {k: float(v) / learning_rate for k, v in norms.items()}
    _, norms = jax.device_get(_change(p, params))
    return {"losses": losses, "first_grad_norms": first,
            "change_norms": {k: float(v) for k, v in norms.items()},
            "first_update": update}
