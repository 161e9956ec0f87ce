"""What the training drivers share: the plain reference's first steps on
a cell's seeded weights and batches, the comparison that decides
``correct``, and its control."""
from __future__ import annotations

import time

import numpy as np

import harness


def reference(cell, seed, precision="float32"):
    sizes = cell.sizes
    t0 = time.perf_counter()
    weights = cell.model.make_weights(sizes, seed)
    batches = cell.model.make_batches(sizes, seed, cell.traffic["ring"])
    out = cell.model.reference_train(
        sizes, cell.config["optimizer"], weights,
        batches[:cell.traffic["first_steps"]], precision)
    print(f"reference: {cell.traffic['first_steps']} steps in {precision} "
          f"took {time.perf_counter() - t0:.2f} s")
    return out


def first_steps(cell, losses, params0, params1, params_last):
    """The program's first steps in the shape the reference returns
    them, from its losses and its parameters before the first step,
    after it and after the last."""
    lr = cell.config["optimizer"]["learning_rate"]
    update = {k: np.asarray(params1[k], np.float32) - params0[k]
              for k in params0}
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa
    return {"losses": losses,
            "first_grad_norms": {k: norm(u) / lr for k, u in update.items()},
            "change_norms": {k: norm(params_last[k] - params0[k])
                             for k in params0},
            "first_update": update}


def check(cell, session, result):
    """Runs after the window, so that neither set-up nor the peak of
    memory counts the reference."""
    want = reference(cell, session["seed"])
    return harness.compare_training(session["first"], want, cell.limits)


def control(cell, seed):
    """The reference in the precision below the configuration's, in the
    program's place."""
    want = reference(cell, seed)
    got = reference(cell, seed, cell.config["control_precision"])
    return harness.compare_training(got, want, cell.limits)
