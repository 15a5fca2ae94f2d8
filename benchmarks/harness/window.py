"""Window arithmetic of a served run, on plain numbers: which requests
count, the rate, the tails. Checked on synthetic logs
(`tests/benchmarks/test_traffic_and_arithmetic.py`).

A request's log entry: `due` (when it was due, on the run's clock),
`tokens` (arrival time of each output token's event), `done` (arrival of
its `done` event or None), `failed` (refused, answered with an `error`
event, or not finished when the drain ended).
"""

from __future__ import annotations

import math


def percentile(values, q: float):
    """The q-th percentile (0-100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it. None of an empty
    sample."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def in_window(entries, w0: float, w1: float):
    """The requests due inside the window."""
    return [e for e in entries if w0 <= e["due"] < w1]


def tokens_per_s(entries, w0: float, w1: float) -> float:
    """Output tokens whose events arrived inside the window, of every
    request (also those due before it), over the window's seconds."""
    n = sum(1 for e in entries for t in e["tokens"] if w0 <= t < w1)
    return n / (w1 - w0)


def ttfts(entries, w0: float, w1: float, miss_at: float):
    """First-token arrival minus due time of every request due in the
    window. A failed request is a miss: it reads as if its first token
    came at `miss_at` (the end of the drain), later than any real one."""
    out = []
    for e in in_window(entries, w0, w1):
        if e["failed"] or not e["tokens"]:
            out.append(miss_at - e["due"])
        else:
            out.append(e["tokens"][0] - e["due"])
    return out


def token_gaps(entries, w0: float, w1: float):
    """Every gap between consecutive tokens of every request due in the
    window."""
    out = []
    for e in in_window(entries, w0, w1):
        ts = e["tokens"]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out
