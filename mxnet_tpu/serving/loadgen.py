"""Closed-loop load generator for serving measurements.

One implementation of the barrier-synchronized concurrent-client
driver shared by the tuner's serving workloads (``tune/workloads.py``),
the fleet drills (``tools/chaos_drill.py``) and the serving SLO test — the
measurement methodology (barrier start, per-request latency under a
lock, wall-clock window from barrier release to last join) must not
fork across them, or their ``batcher_efficiency`` numbers stop being
comparable. The benchmark's own generator is ``benchmark/traffic.py``.

Clients are also where RETRY policy lives (round 17): a server that
sheds with ``Overloaded`` is telling the client "back off and come
back", and the correct client answer is deadline-aware jittered
exponential backoff — never a tight retry storm (which re-creates the
overload it is escaping), never a sleep past the request's own
deadline (which turns a shed into a timeout). Both closed-loop
harnesses implement the policy behind ``retries=``/``backoff_ms=``;
retried requests are counted separately from server-side sheds (a
retry the server absorbed is load smoothing; a give-up is lost work)
and surface in the ``clients`` section of
``mxnet_tpu.serving.serving_report()``.
"""
from __future__ import annotations

import random
import threading
import time

import numpy as np

from . import Overloaded

__all__ = ["closed_loop", "ramp", "raw_predict_rate",
           "token_closed_loop", "mixed_prompts", "client_report"]

# client-side retry ledger (process-wide; serving_report()'s "clients"
# section reads it, reset=True starts a fresh window)
_client_lock = threading.Lock()
_retries = 0      # Overloaded submissions retried after backoff
_gave_up = 0      # Overloaded submissions abandoned (budget/deadline)


def client_report(reset: bool = False) -> dict:
    global _retries, _gave_up
    with _client_lock:
        out = {"retries": _retries, "gave_up": _gave_up}
        if reset:
            _retries = _gave_up = 0
    return out


def _note_retry():
    global _retries
    with _client_lock:
        _retries += 1


def _note_give_up():
    global _gave_up
    with _client_lock:
        _gave_up += 1


def _backoff_s(attempt, backoff_ms, jitter):
    """Jittered exponential backoff: base * 2^attempt, multiplied by a
    uniform draw from [1-jitter, 1+jitter] so retry waves decorrelate."""
    base = (backoff_ms / 1e3) * (2 ** attempt)
    return base * random.uniform(1.0 - jitter, 1.0 + jitter)


def _call_with_retry(fn, deadline, retries, backoff_ms, jitter):
    """Run ``fn()`` retrying ONLY on ``Overloaded``, sleeping the
    jittered exponential backoff between attempts, never sleeping past
    ``deadline`` (a perf_counter timestamp, or None). Re-raises the
    last ``Overloaded`` once the retry budget or the deadline is
    exhausted."""
    attempt = 0
    while True:
        try:
            return fn()
        except Overloaded:
            if attempt >= retries:
                _note_give_up()
                raise
            wait = _backoff_s(attempt, backoff_ms, jitter)
            if deadline is not None:
                room = deadline - time.perf_counter()
                if room <= 0:
                    _note_give_up()
                    raise
                wait = min(wait, room)
            _note_retry()
            time.sleep(wait)
            attempt += 1


def closed_loop(batcher, x_req, clients, per_client, timeout=300,
                deadline_ms=None, retries=0, backoff_ms=25, jitter=0.5):
    """Drive ``clients`` closed-loop threads, each submitting ``x_req``
    (one request of ``x_req.shape[0]`` rows) ``per_client`` times
    through ``batcher.predict``. Returns a dict with rows/s and
    client-observed latency percentiles.

    ``retries`` > 0 arms the deadline-aware retry policy: an
    ``Overloaded`` rejection is retried after jittered exponential
    backoff (``backoff_ms`` base, doubled per attempt, scaled by a
    uniform ``1 ± jitter`` draw), at most ``retries`` times and never
    sleeping past the request's ``deadline_ms``. A request that
    exhausts the budget counts as a client give-up and its latency is
    excluded (it produced no answer). ``deadline_ms`` is also passed
    through to the server when the batcher accepts it."""
    rows = x_req.shape[0] if hasattr(x_req, "shape") else 1
    lats = []
    failed = [0]
    lock = threading.Lock()
    barrier = threading.Barrier(clients + 1)
    kw = {"deadline_ms": deadline_ms} if deadline_ms is not None else {}

    def client():
        barrier.wait()
        mine, mine_failed = [], 0
        for _ in range(per_client):
            t_r = time.perf_counter()
            deadline = t_r + deadline_ms / 1e3 \
                if deadline_ms is not None else None
            try:
                _call_with_retry(
                    lambda: batcher.predict(x_req, timeout=timeout,
                                            **kw),
                    deadline, retries, backoff_ms, jitter)
            except Overloaded:
                mine_failed += 1
                continue
            mine.append(time.perf_counter() - t_r)
        with lock:
            lats.extend(mine)
            failed[0] += mine_failed

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    n_reqs = clients * per_client
    n_ok = len(lats)
    return {
        "rows_s": n_ok * rows / dt,
        "req_s": n_ok / dt,
        "p50_ms": float(np.percentile(lats, 50)) * 1e3 if lats else None,
        "p99_ms": float(np.percentile(lats, 99)) * 1e3 if lats else None,
        "wall_s": dt,
        "submitted": n_reqs,
        "completed": n_ok,
        "gave_up": failed[0],
    }


def _expand_profile(profile):
    """Expand a ramp profile dict into ``[(duration_s, clients), ...]``
    steps.

    ``{"shape": "step", "steps": [(dur_s, clients), ...]}`` is taken
    verbatim; ``{"shape": "sine", "period_s": P, "min_clients": lo,
    "max_clients": hi, "duration_s": D, "step_s": S}`` samples a raised
    cosine (starting at ``lo``) every ``S`` seconds — the diurnal-ish
    traffic wave the autoscaler drills ride."""
    shape = profile.get("shape", "step")
    if shape == "step":
        steps = [(float(d), int(c)) for d, c in profile["steps"]]
    elif shape == "sine":
        import math
        period = float(profile["period_s"])
        lo = int(profile["min_clients"])
        hi = int(profile["max_clients"])
        dur = float(profile.get("duration_s", period))
        step_s = float(profile.get("step_s", period / 8.0))
        steps = []
        t = 0.0
        while t < dur:
            frac = 0.5 - 0.5 * math.cos(2.0 * math.pi * t / period)
            steps.append((min(step_s, dur - t),
                          max(0, int(round(lo + (hi - lo) * frac)))))
            t += step_s
    else:
        raise ValueError(f"unknown ramp profile shape {shape!r}")
    if not steps:
        raise ValueError("ramp profile expands to zero steps")
    return steps


def ramp(batcher, x_req, profile, tenants=None, timeout=300,
         deadline_ms=None, retries=0, backoff_ms=25, jitter=0.5):
    """Closed-loop load with a TIME-VARYING client count — the traffic
    ramp the autoscaler drills drive against a FleetRouter.

    ``profile`` is expanded by :func:`_expand_profile` (stepped or
    sine). A pool of ``max(clients)`` worker threads runs for the whole
    profile; only the first ``clients``-of-the-current-step workers
    submit, the rest idle — stepping the active count up and down
    without thread churn. ``tenants`` (``{name: weight}``) turns each
    worker into a deterministic weighted wheel over tenant names, so a
    70/30 latency/batch mix is exactly 70/30, not a coin flip.

    The same ``retries``/``backoff_ms``/``jitter`` Overloaded-retry
    policy as :func:`closed_loop` applies per request. Returns overall,
    per-step, and per-tenant stats; a request that exhausted its retry
    budget counts in ``gave_up`` (and per-tenant ``gave_up``), never in
    the latency percentiles."""
    steps = _expand_profile(profile)
    max_clients = max(c for _, c in steps)
    if max_clients < 1:
        raise ValueError("ramp profile never activates a client")
    wheel = []
    if tenants:
        for tname, weight in tenants.items():
            wheel.extend([tname] * max(1, int(weight)))
    rows = x_req.shape[0] if hasattr(x_req, "shape") else 1
    stop = threading.Event()
    target = [0]
    step_idx = [0]
    lock = threading.Lock()
    recs = []                      # (t_rel, lat_s, tenant, step_idx)
    counts = {"submitted": 0, "gave_up": 0}
    by_tenant = {t: {"submitted": 0, "gave_up": 0, "lats": []}
                 for t in (tenants or {})}
    t0 = time.perf_counter()

    def worker(idx):
        k = 0
        while not stop.is_set():
            if idx >= target[0]:
                time.sleep(0.002)
                continue
            tname = wheel[(idx + k) % len(wheel)] if wheel else None
            k += 1
            kw = {}
            if deadline_ms is not None:
                kw["deadline_ms"] = deadline_ms
            if tname is not None:
                kw["tenant"] = tname
            si = step_idx[0]
            t_r = time.perf_counter()
            deadline = t_r + deadline_ms / 1e3 \
                if deadline_ms is not None else None
            with lock:
                counts["submitted"] += 1
                if tname is not None:
                    by_tenant[tname]["submitted"] += 1
            try:
                _call_with_retry(
                    lambda: batcher.predict(x_req, timeout=timeout,
                                            **kw),
                    deadline, retries, backoff_ms, jitter)
            except Overloaded:
                with lock:
                    counts["gave_up"] += 1
                    if tname is not None:
                        by_tenant[tname]["gave_up"] += 1
                continue
            lat = time.perf_counter() - t_r
            with lock:
                recs.append((t_r - t0, lat, tname, si))
                if tname is not None:
                    by_tenant[tname]["lats"].append(lat)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(max_clients)]
    for t in threads:
        t.start()
    for i, (dur, c) in enumerate(steps):
        step_idx[0] = i
        target[0] = c
        time.sleep(dur)
    stop.set()
    target[0] = 0
    for t in threads:
        t.join(timeout=timeout)
    wall = time.perf_counter() - t0

    def _pct(xs, q):
        return float(np.percentile(xs, q)) * 1e3 if xs else None

    phases = []
    for i, (dur, c) in enumerate(steps):
        lats = [lat for _, lat, _, si in recs if si == i]
        phases.append({
            "clients": c, "duration_s": dur, "completed": len(lats),
            "req_s": len(lats) / dur if dur > 0 else None,
            "p50_ms": _pct(lats, 50), "p99_ms": _pct(lats, 99),
        })
    tenant_stats = {}
    for tname, d in by_tenant.items():
        tenant_stats[tname] = {
            "submitted": d["submitted"],
            "completed": len(d["lats"]),
            "gave_up": d["gave_up"],
            "p50_ms": _pct(d["lats"], 50),
            "p99_ms": _pct(d["lats"], 99),
        }
    all_lats = [lat for _, lat, _, _ in recs]
    return {
        "wall_s": wall,
        "max_clients": max_clients,
        "steps": [[d, c] for d, c in steps],
        "submitted": counts["submitted"],
        "completed": len(all_lats),
        "gave_up": counts["gave_up"],
        "req_s": len(all_lats) / wall if wall > 0 else None,
        "rows_s": len(all_lats) * rows / wall if wall > 0 else None,
        "p50_ms": _pct(all_lats, 50),
        "p99_ms": _pct(all_lats, 99),
        "phases": phases,
        "tenants": tenant_stats,
    }


def mixed_prompts(dist, vocab_size, n=None, seed=0):
    """Build a MIXED prompt-length workload from ``dist``
    (``{length: weight}``): ``n`` prompts (default ``sum(weights)``)
    whose lengths follow the weighted wheel exactly — a 3:1
    short:long distribution is exactly 3:1 across any window of
    ``sum(weights)`` consecutive draws, not a coin flip (same
    determinism idiom as :func:`ramp`'s tenant wheel). Token ids are
    drawn from a seeded RNG so the workload is reproducible and the
    bit-identity harnesses can replay it."""
    wheel = []
    for length, weight in sorted(dist.items()):
        if int(length) < 1:
            raise ValueError(f"prompt length must be >= 1, got {length}")
        wheel.extend([int(length)] * max(1, int(weight)))
    if not wheel:
        raise ValueError("mixed_prompts needs a non-empty distribution")
    if n is None:
        n = len(wheel)
    rs = np.random.RandomState(seed)
    return [rs.randint(int(vocab_size),
                       size=wheel[i % len(wheel)]).astype(np.int32)
            for i in range(int(n))]


def token_closed_loop(batcher, prompts, clients, per_client,
                      max_new_tokens=8, timeout=300, deadline_ms=None,
                      retries=0, backoff_ms=25, jitter=0.5):
    """Token-granularity twin of :func:`closed_loop` for a
    ``DecodeBatcher``: each client thread submits a prompt (drawn
    round-robin from ``prompts``), ITERATES the returned stream, and
    records time-to-first-token plus every inter-token gap. Returns
    tokens/s and the two SLO percentile families (TTFT, inter-token)
    the decode autotuning objective is built from. The same
    ``retries``/``backoff_ms``/``jitter`` admission-retry policy as
    :func:`closed_loop` applies to the submit call (``Overloaded``
    only — a stream that already produced tokens is never replayed).

    ``prompts`` may mix lengths freely (see :func:`mixed_prompts`);
    the result's ``by_length`` section breaks TTFT/ITL percentiles
    down PER PROMPT-LENGTH BUCKET — the aggregate p99 of a mixed
    workload hides exactly the effect disaggregated prefill exists to
    fix (a long prompt's prefill landing between a short stream's
    tokens), so the per-bucket view is what the disagg-vs-unified
    comparison gates on."""
    ttfts, itls = [], []            # (prompt_len, seconds)
    tokens = [0]
    failed = [0]
    lock = threading.Lock()
    barrier = threading.Barrier(clients + 1)

    def client(cid):
        barrier.wait()
        my_ttft, my_itl, my_toks, my_failed = [], [], 0, 0
        for i in range(per_client):
            prompt = prompts[(cid + i * clients) % len(prompts)]
            plen = len(prompt)
            t_r = time.perf_counter()
            deadline = t_r + deadline_ms / 1e3 \
                if deadline_ms is not None else None
            try:
                stream = _call_with_retry(
                    lambda: batcher.submit(
                        prompt, max_new_tokens=max_new_tokens),
                    deadline, retries, backoff_ms, jitter)
            except Overloaded:
                my_failed += 1
                continue
            t_last = None
            for _ in stream:
                now = time.perf_counter()
                if t_last is None:
                    my_ttft.append((plen, now - t_r))
                else:
                    my_itl.append((plen, now - t_last))
                t_last = now
                my_toks += 1
        with lock:
            ttfts.extend(my_ttft)
            itls.extend(my_itl)
            tokens[0] += my_toks
            failed[0] += my_failed

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    deadline = t0 + timeout
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    dt = time.perf_counter() - t0

    def _pct(xs, q):
        return float(np.percentile(xs, q)) * 1e3 if xs else None

    by_length = {}
    for plen in sorted({p for p, _ in ttfts} | {p for p, _ in itls}):
        bt = [s for p, s in ttfts if p == plen]
        bi = [s for p, s in itls if p == plen]
        by_length[plen] = {
            "streams": len(bt),
            "ttft_p50_ms": _pct(bt, 50),
            "ttft_p99_ms": _pct(bt, 99),
            "inter_token_p50_ms": _pct(bi, 50),
            "inter_token_p99_ms": _pct(bi, 99),
        }
    all_ttft = [s for _, s in ttfts]
    all_itl = [s for _, s in itls]
    return {
        "tok_s": tokens[0] / dt,
        "gen_s": clients * per_client / dt,
        "ttft_p50_ms": _pct(all_ttft, 50),
        "ttft_p99_ms": _pct(all_ttft, 99),
        "inter_token_p50_ms": _pct(all_itl, 50),
        "inter_token_p99_ms": _pct(all_itl, 99),
        "tokens": tokens[0],
        "wall_s": dt,
        "gave_up": failed[0],
        "by_length": by_length,
    }


def raw_predict_rate(predictor, x_full, steps=10, warm=2):
    """Rows/s of the RAW compiled predict step on ``x_full`` (sized to
    a bucket) — the ceiling ``batcher_efficiency`` is measured
    against."""
    for _ in range(warm):
        predictor.predict(x_full)
    t0 = time.perf_counter()
    for _ in range(steps):
        predictor.predict(x_full)
    return x_full.shape[0] * steps / (time.perf_counter() - t0)
