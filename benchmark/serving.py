"""What the request drivers share: the predictor behind its batcher, the
pool of seeded rows that requests are cut from, the sample of finished
requests that the plain reference is run over, and the control."""
from __future__ import annotations

import numpy as np

import harness
import traffic as traffic_mod


def make_pool(cell, seed):
    """A pool of seeded rows on the host. A request is a view of it: the
    generator does no work but ``submit``."""
    pool, _ = cell.model.make_rows(cell.sizes, seed, cell.traffic["pool"])
    return np.asarray(pool)


def request_offsets(pool, seed, rows):
    """For every request the offset of its rows in the pool."""
    return traffic_mod.rng_for(seed, 5).integers(
        0, len(pool) - np.asarray(rows) + 1)


def setup(cell, seed):
    from mxnet_tpu import serving
    weights = cell.model.make_weights(cell.sizes, seed)
    predictor = cell.model.build(cell.config, cell.sizes, "serve", weights)
    del weights
    t = cell.traffic
    batcher = serving.DynamicBatcher(
        predictor, max_wait_us=t["max_wait_us"], max_queue=t["max_queue"],
        name=cell.name)
    batcher.start()          # warms every bucket
    return {"seed": seed, "predictor": predictor, "batcher": batcher,
            "pool": make_pool(cell, seed)}


def facts(session, before, extra):
    after = session["batcher"].report()
    launched = rows = 0
    for b, pb in after["per_bucket"].items():
        n = pb["batches"] - before["per_bucket"][b]["batches"]
        launched += n * int(b)
        rows += pb["rows"] - before["per_bucket"][b]["rows"]
    batches = sum(pb["batches"] - before["per_bucket"][b]["batches"]
                  for b, pb in after["per_bucket"].items())
    return {"mode": "serve", "batches": batches, "rows_real": rows,
            "rows_launched": launched,
            "retraces": after["retraces"] - before["retraces"],
            "shed": after["shed_requests"] - before["shed_requests"],
            "deadline_missed": after["deadline_missed"]
            - before["deadline_missed"], **extra}


def reference_outputs(cell, seed, requests, precision="float32"):
    """The reference's answers to ``requests`` (one array of rows each),
    one array a request."""
    weights = cell.model.make_weights(cell.sizes, seed)
    out = cell.model.reference_forward(
        cell.sizes, weights, np.concatenate(requests), precision)
    return np.split(out, np.cumsum([len(r) for r in requests])[:-1])


def check(cell, session, result):
    """The sampled requests' outputs, as the batcher returned them,
    against the reference's forward pass over the same rows."""
    sample = result["sample"]          # [(rows array, output array), ...]
    if not sample:
        return [("output_rel_l2", float("inf"),
                 cell.limits["output_rel_l2"]["limit"],
                 "no sampled request finished")]
    want = reference_outputs(cell, session["seed"], [r for r, _ in sample])
    rows = harness.compare_outputs([o for _, o in sample], want, cell.limits)
    rows.append(("retraces_in_window", result["facts"]["retraces"], 0,
                 "bucket programs traced inside the window"))
    return rows


def control(cell, seed):
    """The reference in the precision below the configuration's, over a
    sample of the size a run compares."""
    n = cell.traffic["sample"]
    rows = traffic_mod.request_rows(cell.traffic["rows_mix"], 8 * n, seed)
    pool = make_pool(cell, seed)
    offsets = request_offsets(pool, seed, rows)
    requests = [pool[offsets[i]:offsets[i] + rows[i]]
                for i in traffic_mod.sample_indices(rows, n, seed)]
    return harness.compare_outputs(
        reference_outputs(cell, seed, requests,
                          cell.config["control_precision"]),
        reference_outputs(cell, seed, requests), cell.limits)


def close(session):
    session["batcher"].stop()
    session.clear()
