"""Training through ``Module.fit``: the framework's own loop over a
benchmark-owned ``DataIter`` that cycles a ring of seeded batches.

Set-up builds one module, drives it through its first steps with the
same ``fit`` call and the same kind of iterator that the window uses
(so the step program, the data-pipeline wrap and the metric are the
window's), and keeps the losses and the parameters those steps gave.
The window is one more ``fit`` call on that module over the ring until
the clock runs out. The comparison with the plain reference runs after
the window.
"""
from __future__ import annotations

import time

import numpy as np

import jax

from training import check, control, first_steps  # noqa: F401


def _ring(batches):
    """The ring as ``DataBatch``es of NDArrays on the chip, as
    ``NDArrayIter`` holds its data. (Placed on ``mx.cpu()`` for ``fit``'s
    data pipeline to stage, the same ring hangs the chip within 20
    chained steps: PERF.md section 6, PR 23.)"""
    import mxnet_tpu as mx
    return [mx.io.DataBatch([mx.nd.NDArray(x)], [mx.nd.NDArray(y)])
            for x, y in batches]


def _make_iter(session, stop):
    import mxnet_tpu as mx
    ring = session["ring"]

    class RingIter(mx.io.DataIter):
        """Goes on round ``ring`` from the session's cursor until
        ``stop(served)`` says so."""

        def __init__(self):
            super().__init__(batch_size=session["provide_data"][0].shape[0])
            self.provide_data = session["provide_data"]
            self.provide_label = session["provide_label"]
            self.served = 0

        def reset(self):
            pass

        def next(self):
            with jax.profiler.TraceAnnotation("bench:data_next"):
                if stop(self.served):
                    raise StopIteration
                batch = ring[session["cursor"] % len(ring)]
                session["cursor"] += 1
                self.served += 1
                return batch

    return RingIter()


def _cross_entropy(probs, labels):
    p = probs[np.arange(len(labels)), labels.astype(int)]
    return float(-np.log(np.maximum(p.astype(np.float64), 1e-300)).mean())


def _fit(cell, session, stop, on_batch):
    opt = dict(cell.config["optimizer"])
    name = opt.pop("name")
    it = _make_iter(session, stop)
    # one metric object for every call: the step program counts into its
    # slot, and a new object would be a new program
    session["module"].fit(it, eval_metric=session["metric"], num_epoch=1,
                          kvstore=None,
                          optimizer=name, optimizer_params=opt,
                          batch_end_callback=on_batch)
    return it


def setup(cell, seed):
    import mxnet_tpu as mx
    sizes, traffic = cell.sizes, cell.traffic
    t0 = time.perf_counter()

    def note(what):
        print(f"fit_loop: {time.perf_counter() - t0:7.2f} s  {what}")

    weights = cell.model.make_weights(sizes, seed)
    batches = cell.model.make_batches(sizes, seed, traffic["ring"])
    note("weights and batches made")
    module = cell.model.build(cell.config, sizes, "fit", weights)
    del weights
    note("module bound, parameters set")
    session = {
        "seed": seed, "module": module, "cursor": 0,
        "metric": mx.metric.Accuracy(),
        "ring": _ring(batches),
        "provide_data": [mx.io.DataDesc("data", batches[0][0].shape)],
        "provide_label": [mx.io.DataDesc("softmax_label",
                                         batches[0][1].shape)],
    }
    # the first steps, through fit: one step, then the rest, so that the
    # parameters after the first are there to read
    first = traffic["first_steps"]
    losses = []

    def record(param):
        probs = module.get_outputs()[0].asnumpy()
        losses.append(_cross_entropy(
            probs, np.asarray(batches[len(losses)][1])))

    params0 = cell.model.read_params(module)
    note("ring placed")
    _fit(cell, session, lambda served: served >= 1, record)
    params1 = cell.model.read_params(module)
    note("first step through fit (optimizer bound, pass gate, step "
         "program)")
    _fit(cell, session, lambda served: served >= first - 1, record)
    params_last = cell.model.read_params(module)
    note("first steps done (step program again, with the metric's slot)")
    session["first"] = first_steps(cell, losses, params0, params1,
                                   params_last)
    return session


def window(cell, session, seconds):
    module = session["module"]
    steps = [0]

    every = cell.traffic["disp_batches"]

    def on_batch(param):
        # what mx.callback.Speedometer does: read the metric every few
        # batches. The read waits for the device, and it is the only
        # thing in fit() that does: without it a ring that is always
        # ready lets the host run ahead of the chip without bound, one
        # staged batch a step
        steps[0] += 1
        if steps[0] % every == 0:
            with jax.profiler.TraceAnnotation("bench:metric_read"):
                param.eval_metric.get()

    t0 = time.perf_counter()
    deadline = t0 + seconds
    _fit(cell, session, lambda served: time.perf_counter() >= deadline,
         on_batch)
    with jax.profiler.TraceAnnotation("bench:final_sync"):
        last = module.get_outputs()[0].asnumpy()
    t1 = time.perf_counter()
    items = steps[0] * cell.model.items_per_step(cell.sizes)
    finite = bool(np.isfinite(last).all())
    return {
        "metrics": {"train_throughput": items / (t1 - t0)},
        "attempted": steps[0],
        "failed": 0 if finite else steps[0],
        "facts": {"steps": steps[0], "items": items, "seconds": t1 - t0,
                  "mode": "train"},
    }


def close(session):
    session.clear()
