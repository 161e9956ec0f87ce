"""The one traffic generator: from a traffic file's parameters and a seed
to what is sent.

A traffic file (``traffic/<name>.json``) holds parameters only. Requests
are described by ``rows_mix`` (rows per request -> share), arrivals by
``rate_rps`` with an optional periodic ``profile`` of
``[seconds, multiplier]`` phases (bursts), or by ``clients`` for a closed
loop. The *set* of request sizes and of gaps between arrivals is fixed
by the file's ``base_seed`` and the window's length; ``--seed`` only
puts them in another order, so that two seeds offer the same work and
differ in nothing but its order.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed, stream):
    """A numpy generator for one named stream of one seed. ``seed`` may
    be any non-negative whole number (the driver's exceed 2**31)."""
    return np.random.default_rng([int(seed), int(stream)])


def rows_multiset(mix, n):
    """``n`` request sizes in the shares of ``mix`` (largest remainder),
    sorted: the same list for every seed."""
    sizes = sorted(int(k) for k in mix)
    shares = np.array([float(mix[str(s)]) for s in sizes])
    shares = shares / shares.sum()
    counts = np.floor(shares * n).astype(int)
    remainder = shares * n - counts
    for i in np.argsort(-remainder)[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(sizes, counts)


def request_rows(mix, n, seed):
    """The sizes of ``n`` requests for ``seed``: the fixed multiset in an
    order drawn from the seed."""
    return rng_for(seed, 1).permutation(rows_multiset(mix, n))


def _warp(times, seconds, profile):
    """Map arrival times of a unit-rate schedule through the inverse of
    the cumulative rate of a periodic ``profile`` whose mean multiplier
    is scaled to 1, so that the mean rate is kept."""
    period = sum(d for d, _ in profile)
    mean = sum(d * m for d, m in profile) / period
    edges, cum = [0.0], [0.0]
    t = 0.0
    while t < seconds:
        for d, m in profile:
            t += d
            edges.append(t)
            cum.append(cum[-1] + d * m / mean)
    edges, cum = np.array(edges), np.array(cum)
    # the cumulative rate at the end of the window stands for `seconds`
    total = np.interp(seconds, edges, cum)
    return np.interp(times * (total / seconds), cum, edges)


def arrival_times(rate_rps, seconds, base_seed, seed, profile=None):
    """Due times, in seconds from the start of the window, of
    ``int(rate_rps * seconds)`` requests. The gaps are exponential draws
    fixed by ``base_seed``, scaled to fill the window; ``seed`` orders
    them."""
    n = int(rate_rps * seconds)
    if n < 1:
        raise ValueError(f"rate {rate_rps}/s over {seconds} s offers no "
                         "request")
    gaps = rng_for(base_seed, 2).exponential(1.0, n + 1)
    gaps = rng_for(seed, 3).permutation(gaps)
    times = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    if profile:
        times = _warp(times, seconds, profile)
    return times


def sample_indices(rows, k, seed):
    """``k`` request indices drawn from the seed, the first of the
    largest requests always among them."""
    n = len(rows)
    k = min(k, n)
    chosen = set(rng_for(seed, 4).choice(n, size=k, replace=False)
                 .tolist())
    largest = int(np.argmax(rows))
    if largest not in chosen:
        chosen.pop()
        chosen.add(largest)
    return sorted(chosen)


def percentile(values, q):
    """The ``q``-th percentile (nearest rank) of ``values``."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if not len(v):
        raise ValueError("no values")
    rank = int(np.ceil(q / 100.0 * len(v))) - 1
    return float(v[min(max(rank, 0), len(v) - 1)])
