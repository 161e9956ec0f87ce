"""Training through a step object called in a loop (``TrainStep``): a
ring of seeded batches on the device, the calls chained without a host
sync, one sync when the clock has run out.

Set-up builds one step object and drives it through its first steps on
the ring; the window goes on calling that same object. The comparison
with the plain reference runs after the window.
"""
from __future__ import annotations

import math
import time

import jax

from training import check, control, first_steps  # noqa: F401


def setup(cell, seed):
    import mxnet_tpu as mx
    sizes, traffic = cell.sizes, cell.traffic
    weights = cell.model.make_weights(sizes, seed)
    batches = cell.model.make_batches(sizes, seed, traffic["ring"])
    system = cell.model.build(cell.config, sizes, "step", weights)
    ring = [(mx.nd.array(x), mx.nd.array(y)) for x, y in batches]
    params0 = {k: jax.device_get(v) for k, v in weights.items()}
    del weights
    first = traffic["first_steps"]
    losses = [float(system(*ring[0]).asnumpy())]
    params1 = cell.model.read_params(system)
    for i in range(1, first):
        losses.append(float(system(*ring[i % len(ring)]).asnumpy()))
    params_last = cell.model.read_params(system)
    return {"seed": seed, "system": system, "ring": ring, "next": first,
            "first": first_steps(cell, losses, params0, params1,
                                 params_last)}


def window(cell, session, seconds):
    system, ring = session["system"], session["ring"]
    i = session["next"]
    steps = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation("bench:step_call"):
            loss = system(*ring[i % len(ring)])
        i += 1
        steps += 1
    with jax.profiler.TraceAnnotation("bench:final_sync"):
        last = float(loss.asnumpy())
    t1 = time.perf_counter()
    session["next"] = i
    items = steps * cell.model.items_per_step(cell.sizes)
    return {
        "metrics": {"train_throughput": items / (t1 - t0)},
        "attempted": steps,
        "failed": 0 if math.isfinite(last) else steps,
        "facts": {"steps": steps, "items": items, "seconds": t1 - t0,
                  "mode": "train"},
    }


def close(session):
    session.clear()
