"""The program's own spans, as the per-layer readers take them: the
`serve_tick` records of a served run (one per busy engine tick: tick
number, start, end and its phases as `[name, start, end, request id]` on
the host's monotonic clock) and the set-up path's `span` records. Plain
arithmetic on plain records, checked on hand-made ones
(`tests/benchmarks/test_span_readers.py`). A program without the spans
(the parent of the PR that brought them) leaves nothing to read, and every
function here then gives an empty answer.

What a tick's device work is: every `prefill` phase from its start to the
end of the `prefill_wait` inside it (its own end where it fetched
nothing), and every `dispatch` from its start to the end of the
`decode_wait` that follows it (its own end where none does before the next
`dispatch`). The rest of the tick is the host's.
"""

from __future__ import annotations

import statistics

from harness.trace_reduce import merge


def tick_records(obs) -> list:
    """The run's `serve_tick` records in which a decode step ran."""
    return [r for r in obs.get("records") or ()
            if r.get("record") == "serve_tick" and r.get("decode_active")]


def prefill_intervals(phases) -> list:
    """`(start, end)` of each prefill: its start to its wait's end."""
    out = []
    for name, t0, t1, *_ in phases:
        if name != "prefill":
            continue
        waits = [w1 for wname, w0, w1, *_ in phases
                 if wname == "prefill_wait" and t0 <= w0 and w1 <= t1]
        out.append((t0, max(waits) if waits else t1))
    return out


def decode_intervals(phases) -> list:
    """`(start, end)` of each dispatch: its start to the end of the
    `decode_wait` that follows it before another dispatch does."""
    starts = sorted(t0 for name, t0, *_ in phases if name == "dispatch")
    out = []
    for name, t0, t1, *_ in phases:
        if name != "dispatch":
            continue
        nxt = min((s for s in starts if s > t0), default=float("inf"))
        waits = [w1 for wname, w0, w1, *_ in phases
                 if wname == "decode_wait" and t1 <= w0 < nxt]
        out.append((t0, min(waits) if waits else t1))
    return out


def tick_host_s(record) -> float:
    """The tick less the union of its device intervals."""
    phases = record["phases"]
    device = merge(prefill_intervals(phases) + decode_intervals(phases))
    return (record["t1_s"] - record["t0_s"]) - sum(b - a for a, b in device)


def median_ms(seconds) -> float | None:
    seconds = list(seconds)
    return 1e3 * statistics.median(seconds) if seconds else None


def setup_span_s(records, name: str) -> float | None:
    """Seconds of the newest set-up span called `name`."""
    found = [r["dur_s"] for r in records or ()
             if r.get("record") == "span" and r.get("name") == name
             and r.get("component") == "setup"]
    return found[-1] if found else None
