"""Registry-wide gradient sweep driver.

Walks every distinct registered op, instantiates inputs (defaults by
signature arity + per-op overrides), and checks jax.grad against central
finite differences — the registry-scale analog of the reference's
check_numeric_gradient coverage in its own
incubator-mxnet/tests/python/unittest/test_operator.py.

Run directly to see the status table; the frozen CI version lives in
tests/test_op_gradients.py (same case table, imported from here).
"""
from __future__ import annotations

import inspect
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402

from mxnet_tpu.ops.registry import _OPS       # noqa: E402


def _rng(seed=0):
    return np.random.RandomState(seed)


def _pos(shape, seed=0, lo=0.4, hi=1.3):
    """Positive inputs away from 0/1 kinks — safe for log/sqrt/ratio."""
    return _rng(seed).uniform(lo, hi, shape).astype(np.float32)


def _signed(shape, seed=0):
    """|x| in [0.4, 1.3] with random sign — keeps away from the kinks of
    abs/relu/sign while exercising both branches."""
    r = _rng(seed)
    return (_pos(shape, seed) *
            np.where(r.rand(*shape) < 0.5, -1, 1)).astype(np.float32)


# ---------------------------------------------------------------------------
# case table: name -> dict(inputs=[np arrays], attrs={}, grad_args=[idx],
#                          tol=(rtol, atol), mode='grad'|'fwd'|'skip',
#                          reason=str for skips)
# names not listed fall back to arity-based defaults.
# ---------------------------------------------------------------------------
S = (2, 3)


def default_case(opdef):
    sig = inspect.signature(opdef.fn)
    params = list(sig.parameters.values())
    if any(p.kind == p.VAR_POSITIONAL for p in params):
        return {"inputs": [_signed(S, 1), _signed(S, 2)]}
    req = [p for p in params
           if p.default is inspect.Parameter.empty and
           p.kind in (p.POSITIONAL_OR_KEYWORD, p.POSITIONAL_ONLY)]
    return {"inputs": [_signed(S, i + 1) for i in range(len(req))]}


def run_case(opdef, case, eps=1e-2, rtol=5e-2, atol=5e-3):
    """Returns (status, detail). status: ok / fwd_ok / fail / error.

    Runs under matmul precision 'highest' (scoped, not a global config
    write): this CPU backend's default-precision matmuls carry ~5e-3
    relative error, which central differences amplify ~1/eps-fold."""
    with jax.default_matmul_precision("highest"):
        return _run_case_inner(opdef, case, eps, rtol, atol)


def _run_case_inner(opdef, case, eps, rtol, atol):
    inputs = [jnp.asarray(v) for v in case["inputs"]]
    attrs = case.get("attrs", {})
    mode = case.get("mode", "grad")
    if "tol" in case:
        rtol, atol = case["tol"]
    grad_args = case.get("grad_args")
    if grad_args is None:
        grad_args = [i for i, v in enumerate(inputs)
                     if np.issubdtype(np.asarray(v).dtype, np.floating)]

    def f(*xs):
        full = list(inputs)
        for i, x in zip(grad_args, xs):
            full[i] = x
        out = opdef.fn(*full, **attrs)
        outs = out if isinstance(out, tuple) else (out,)
        # an op may declare its later outputs to take no gradient
        # (statistics for a gauge): the case says how many do
        outs = outs[:case.get("outputs", len(outs))]
        tot = 0.0
        for o in outs:
            o = jnp.asarray(o)
            if jnp.issubdtype(o.dtype, jnp.floating):
                # cos-weighted sum: a plain sum has zero gradient
                # through mean-removing ops (softmax, norms)
                w = jnp.cos(jnp.arange(o.size,
                                       dtype=jnp.float32)).reshape(
                    o.shape)
                tot = tot + jnp.sum(o.astype(jnp.float32) * w)
        return tot

    try:
        xs = [inputs[i] for i in grad_args]
        jf = jax.jit(f)
        base = jf(*xs)
        if not np.isfinite(float(base)):
            return "error", "non-finite forward"
        if mode == "fwd" or opdef.no_grad or not grad_args:
            return "fwd_ok", ""
        analytic = jax.jit(jax.grad(
            f, argnums=tuple(range(len(xs)))))(*xs)
        # directional derivative check: <grad_k, v> vs central finite
        # difference along 3 fixed random directions per argument —
        # O(evals) instead of O(elements), same bug-catching power for
        # wrong-formula gradients
        for k, i in enumerate(grad_args):
            a = np.asarray(analytic[k], np.float64)
            if not np.isfinite(a).all():
                return "fail", f"arg{i}: non-finite analytic grad"
            x0 = np.asarray(inputs[i], np.float64)
            for d in range(3):
                v = _rng(100 + 7 * i + d).uniform(
                    -1, 1, x0.shape).astype(np.float64)
                proj = float((a * v).sum())
                args_p = list(xs)
                args_m = list(xs)
                args_p[k] = jnp.asarray((x0 + eps * v), jnp.float32)
                args_m[k] = jnp.asarray((x0 - eps * v), jnp.float32)
                num = (float(jf(*args_p)) - float(jf(*args_m))) / (2 * eps)
                denom = max(abs(num), abs(proj))
                if abs(proj - num) > atol + rtol * denom:
                    return "fail", (f"arg{i} dir{d}: analytic={proj:.5g} "
                                    f"numeric={num:.5g}")
        return "ok", ""
    except Exception as e:  # noqa: BLE001 - sweep collects every failure
        return "error", f"{type(e).__name__}: {str(e)[:110]}"


def sweep(cases, only=None):
    seen = {}
    for name, od in _OPS.items():
        seen.setdefault(id(od), od)
    results = {}
    verbose = os.environ.get("GRAD_SWEEP_VERBOSE")
    for od in sorted(seen.values(), key=lambda o: o.name):
        name = od.name
        if only and name not in only:
            continue
        case = cases.get(name) or default_case(od)
        if case.get("mode") == "skip":
            results[name] = ("skip", case.get("reason", ""))
            continue
        if verbose:
            print(f"... {name}", flush=True)
        import time
        t0 = time.perf_counter()
        results[name] = run_case(od, case)
        if verbose and time.perf_counter() - t0 > 2:
            print(f"    slow: {time.perf_counter() - t0:.1f}s",
                  flush=True)
    dump = os.environ.get("GRAD_SWEEP_DUMP")
    if dump:
        import json
        with open(dump, "w") as f:
            json.dump({n: list(v) for n, v in results.items()
                       if v[0] in ("fail", "error")}, f, indent=1)
    return results


def main():
    from op_grad_cases import CASES
    only = set(sys.argv[1:]) or None
    res = sweep(CASES, only)
    from collections import Counter
    c = Counter(s for s, _ in res.values())
    print(c)
    for name in sorted(res):
        s, d = res[name]
        if s in ("fail", "error"):
            print(f"{s:6} {name:40} {d}")


if __name__ == "__main__":
    main()
