"""Independent users: requests sent on a schedule, whether or not
earlier ones have come back. Latency runs from the moment a request was
*due* to the moment its result is readable on the host, so a stall is
charged to every request it delays; how late the generator itself sent
is reported beside it."""
from __future__ import annotations

import time

import numpy as np

import jax

import serving
import traffic as traffic_mod
from serving import setup, check, control, close  # noqa: F401


def window(cell, session, seconds, rate_rps=None):
    t = cell.traffic
    batcher = session["batcher"]
    seed = session["seed"]
    rate = rate_rps or t["rate_rps"]
    due = traffic_mod.arrival_times(rate, seconds, t["base_seed"], seed,
                                    t.get("profile"))
    n = len(due)
    rows = traffic_mod.request_rows(t["rows_mix"], n, seed)
    pool = session["pool"]
    offsets = serving.request_offsets(pool, seed, rows)
    wanted = set(traffic_mod.sample_indices(rows, t["sample"], seed))
    sent = np.zeros(n)
    done = np.full(n, np.nan)
    outputs = {}
    failed = [0]

    def on_done(i, fut):
        done[i] = time.perf_counter()
        try:
            out = fut.result(0)
        except Exception:               # shed, missed or errored: failed
            done[i] = np.nan
            failed[0] += 1
            return
        if i in wanted:
            outputs[i] = out

    before = batcher.report()
    depth_mid = None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:generate"):
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if depth_mid is None and due[i] >= seconds / 2:
                depth_mid = batcher.queue_depth
            sent[i] = time.perf_counter()
            req = pool[offsets[i]:offsets[i] + rows[i]]
            try:
                fut = batcher.submit(req)
            except Exception:           # Overloaded: shed at the door
                failed[0] += 1
                continue
            fut.add_done_callback(lambda f, i=i: on_done(i, f))
    depth_end = batcher.queue_depth
    with jax.profiler.TraceAnnotation("bench:drain"):
        limit = time.perf_counter() + 60
        while np.isnan(done).sum() > failed[0] and \
                time.perf_counter() < limit:
            time.sleep(0.002)
    t1 = time.perf_counter()
    late = np.isnan(done)
    n_failed = int(late.sum())
    # a request that failed or never came back missed every limit
    latency_ms = np.where(late, seconds * 1e3, (done - t0 - due) * 1e3)
    lag_ms = (sent - t0 - due) * 1e3
    rows_done = int(np.asarray(rows)[~late].sum())
    sample = [(pool[offsets[i]:offsets[i] + rows[i]], outputs[i])
              for i in sorted(outputs)]
    return {
        "metrics": {
            "serve_p50_ms": traffic_mod.percentile(latency_ms, 50),
            "serve_p95_ms": traffic_mod.percentile(latency_ms, 95),
        },
        "attempted": n,
        "failed": n_failed,
        "sample": sample,
        "facts": serving.facts(session, before, {
            "requests": n, "seconds": t1 - t0, "rate_rps": rate,
            "rows_per_s": rows_done / (t1 - t0),
            "queue_depth_mid": depth_mid, "queue_depth_end": depth_end,
            "generator_lag_p95_ms": traffic_mod.percentile(lag_ms, 95),
            "request_p95_ms": traffic_mod.percentile(latency_ms, 95),
        }),
    }
