"""Callers that each wait for a reply: ``clients`` threads, each sending
its next request when the last has returned, so the batcher is kept
saturated and the number is rows completed per second. Latency is
recorded, not judged."""
from __future__ import annotations

import itertools
import threading
import time

import numpy as np

import jax

import serving
import traffic as traffic_mod
from serving import setup, check, control, close  # noqa: F401


def window(cell, session, seconds):
    t = cell.traffic
    batcher = session["batcher"]
    seed = session["seed"]
    n = t["requests_drawn"]
    rows = traffic_mod.request_rows(t["rows_mix"], n, seed)
    pool = session["pool"]
    offsets = serving.request_offsets(pool, seed, rows)
    wanted = set(traffic_mod.sample_indices(rows, t["sample"], seed))
    counter = itertools.count()
    outputs = {}
    lock = threading.Lock()
    latencies, rows_done, failed, errors = [], [0], [0], []

    before = batcher.report()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def client():
        mine, my_rows, my_failed = [], 0, 0
        try:
            while time.perf_counter() < deadline:
                i = next(counter)
                k = i % n
                req = pool[offsets[k]:offsets[k] + rows[k]]
                t_send = time.perf_counter()
                try:
                    out = batcher.predict(req, timeout=120)
                except Exception:       # shed, missed or errored: failed
                    my_failed += 1
                    continue
                mine.append((time.perf_counter() - t_send) * 1e3)
                my_rows += len(req)
                if i in wanted:
                    outputs[i] = (req, out)
        except BaseException as e:      # re-raised on the main thread
            errors.append(e)
        with lock:
            latencies.extend(mine)
            rows_done[0] += my_rows
            failed[0] += my_failed

    threads = [threading.Thread(target=client, name=f"client{c}")
               for c in range(t["clients"])]
    with jax.profiler.TraceAnnotation("bench:clients"):
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=seconds + 180)
    t1 = time.perf_counter()
    if errors:
        raise errors[0]
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a client never returned")
    sample = [outputs[i] for i in sorted(outputs)]
    return {
        "metrics": {"serve_throughput": rows_done[0] / (t1 - t0)},
        "attempted": len(latencies) + failed[0],
        "failed": failed[0],
        "sample": sample,
        "facts": serving.facts(session, before, {
            "requests": len(latencies), "seconds": t1 - t0,
            "rows_per_s": rows_done[0] / (t1 - t0),
            "request_p95_ms": traffic_mod.percentile(latencies, 95),
        }),
    }
