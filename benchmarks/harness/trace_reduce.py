"""From a profiler trace to numbers: busy and idle time of the device,
time per operation name, the top-ten breakdown and the longest idle gaps.

An event is `(name, start_ns, duration_ns)`. The arithmetic works on plain
lists, so it is checked on hand-made events and on a recorded trace
(`tests/benchmarks/test_trace_reduce.py`); only `load` touches the
profiler's file, through `jax.profiler.ProfileData`.
"""

from __future__ import annotations

import glob
import os
import re

#: the device line whose events are single operations, never overlapping
#: on one core; the other device lines ("XLA Modules", "Steps") enclose them
OPS_LINE = "XLA Ops"
DEVICE_PLANE_PREFIX = "/device:TPU:"


def load(trace_dir: str) -> dict:
    """`{plane: {line: [(name, start_ns, duration_ns), ...]}}` of the
    newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events
            )
    return out


def device_ops(planes: dict) -> dict:
    """`{device plane: [op events]}` for every TPU core in the trace."""
    return {
        name: sorted(lines[OPS_LINE], key=lambda e: e[1])
        for name, lines in planes.items()
        if name.startswith(DEVICE_PLANE_PREFIX) and lines.get(OPS_LINE)
    }


def merge(intervals) -> list[tuple[int, int]]:
    """Union of `(start, end)` intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events, t0: int, t1: int):
    """Events cut to the window `[t0, t1)`."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def leaves(events):
    """The events that enclose no other: a `while` or a `call` spans the
    operations of its body on the same line, and counting it would hide
    every gap inside the loop."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [event, has_child]
    for ev in ordered:
        while stack and stack[-1][0][1] + stack[-1][0][2] <= ev[1]:
            top, parent = stack.pop()
            if not parent:
                out.append(top)
        if stack and ev[1] + ev[2] <= stack[-1][0][1] + stack[-1][0][2]:
            stack[-1][1] = True
        stack.append([ev, False])
    out.extend(top for top, parent in stack if not parent)
    return sorted(out, key=lambda e: e[1])


_HLO = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = (.+?) ([a-z][\w\-]*)\(")


def group_key(name: str) -> str:
    """A readable key that groups the same operation of every layer: an
    event's name is its whole HLO instruction (`%fusion.7230 =
    bf16[8,128,1024]{...} fusion(...)`); the key keeps the instruction's
    base name and its result type without layouts (`fusion
    bf16[8,128,1024]`). Names of another form stay as they are."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    base, result, _ = m.groups()
    result = re.sub(r"\{[^{}]*\}", "", result)
    return f"{base} {result}"[:120]


def short_name(name: str) -> str:
    """`%attention_norm.528` of `%attention_norm.528 = (...) custom-call(`."""
    return name.split(" = ", 1)[0].lstrip("%")


def busy_ns(events) -> int:
    return sum(e - s for s, e in merge((s, s + d) for _, s, d in events))


def per_name_ns(events, key=lambda name: name) -> dict:
    out = {}
    for name, _, d in events:
        k = key(name)
        out[k] = out.get(k, 0) + d
    return out


def top(events, n: int = 10, key=lambda name: name) -> list[list]:
    """The `n` operation names with most device time: `[name, seconds]`."""
    rank = sorted(per_name_ns(events, key).items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rank]


def gaps(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """Idle intervals of `[t0, t1)`: where no event runs."""
    out, cursor = [], t0
    for s, e in merge((s, s + d) for _, s, d in events):
        if s > cursor:
            out.append((cursor, min(s, t1)))
        cursor = max(cursor, e)
    if cursor < t1:
        out.append((cursor, t1))
    return [(a, b) for a, b in out if b > a]


def host_label(host_events, a: int, b: int) -> str:
    """What the host was doing in `[a, b)`: the shortest host span that
    covers at least half of it (the most specific: an enclosing `wait` of
    the whole trace says nothing), `unattributed` where there is none."""
    best, best_d = "unattributed", None
    for name, s, d in host_events:
        cover = min(s + d, b) - max(s, a)
        if 2 * cover >= b - a and (best_d is None or d < best_d):
            best, best_d = name, d
    return best


#: the program's own phase spans (`telemetry.spans.Phase`); everything
#: else on a host line is a frame of the profiler's Python tracer or a
#: runtime thread's event, whose names have no place in a result line
PHASE_NAMES = re.compile(
    r"(serve_tick|serve_setup|warm_start)(\..*)?|train_step|eval_step")


def host_spans(planes: dict) -> list:
    """The program's phase spans on the host, all threads together: every
    line of every `/host:` plane (the line that holds them is named after
    the command, `python3` on the chip, `python` elsewhere), kept to the
    program's phase names."""
    return [
        ev
        for name, lines in planes.items() if name.startswith("/host:")
        for events in lines.values()
        for ev in events if PHASE_NAMES.fullmatch(ev[0])
    ]


SHORT_GAP_NS = 20_000
LABELLED_GAPS = 200


def idle_gaps(events, t0: int, t1: int, host_events=(), n: int = 10) -> list[list]:
    """The idle time of the window by what the host was doing, the `n`
    labels with most: `[label, seconds]`. A device runs hundreds of
    thousands of operations a second with a sliver between each: gaps
    under 20 us are summed under one label, the 200 longest are labelled
    by the host's spans, the rest go under `other gaps`."""
    by = {}
    long_gaps = []
    for a, b in gaps(events, t0, t1):
        if b - a < SHORT_GAP_NS:
            by["gaps under 20 us"] = by.get("gaps under 20 us", 0) + (b - a)
        else:
            long_gaps.append((a, b))
    long_gaps.sort(key=lambda g: g[0] - g[1])
    for i, (a, b) in enumerate(long_gaps):
        label = (host_label(host_events, a, b) if i < LABELLED_GAPS
                 else "other gaps")
        by[label] = by.get(label, 0) + (b - a)
    rank = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rank]


def idle_share(obs):
    """Per-layer reader shared by the cells' `*.device_idle` metrics: the
    share (%) of the traced window in which no operation ran on the
    device; nothing where there is no device trace."""
    trace = obs.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def window_of(events) -> tuple[int, int]:
    """First start to last end of the device's own events."""
    return min(s for _, s, _ in events), max(s + d for _, s, d in events)


def reduce(planes: dict, window: tuple[int, int] | None = None,
           host_events=()) -> dict:
    """The numbers every traced run reports. `busy_s` is averaged over
    the cores in the trace; names and gaps are of the busiest core's
    plane (data parallelism runs the same program on each)."""
    per_core = device_ops(planes)
    if not per_core:
        raise ValueError(
            f"no '{OPS_LINE}' line on a '{DEVICE_PLANE_PREFIX}*' plane: "
            f"planes {sorted(planes)}"
        )
    per_core = {k: leaves(ev) for k, ev in per_core.items()}
    if window is None:
        los, his = zip(*(window_of(ev) for ev in per_core.values()))
        window = (min(los), max(his))
    if not host_events:
        host_events = host_spans(planes)
    t0, t1 = window
    clipped = {k: clip(ev, t0, t1) for k, ev in per_core.items()}
    busy = {k: busy_ns(ev) for k, ev in clipped.items()}
    lead = max(busy, key=busy.get)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "cores": len(busy),
        "per_name_s": {k: v / 1e9 for k, v in per_name_ns(clipped[lead]).items()},
        "events": clipped[lead],
        "breakdown": {
            "device_ops": top(clipped[lead], key=group_key),
            "idle_gaps": idle_gaps(clipped[lead], t0, t1, host_events),
        },
    }
