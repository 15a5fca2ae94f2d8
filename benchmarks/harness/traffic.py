"""The one general traffic generator: a traffic mix is a data file of
parameters (`benchmarks/traffic/<name>.json`), this module turns it and
`--seed` into a schedule. Serving mixes (`"kind": "open_loop"`) are an
open loop, after `serve/trace.py::generate_trace` (a copy: the yardstick
may not change with the program): Poisson arrivals at `rate_rps`, burst
episodes at `burst_rate_x` times it, clamped log-normal prompt and output
lengths, optionally tenants that share a prompt prefix.

Every seed gets the SAME work. The arrivals' gaps and the (prompt, output)
sizes are drawn once from the mix's own `shape_seed` over the run's whole
length; `--seed` only deals them out in another order and writes other
prompt texts (and, in the driver, other weights). So two seeds differ as
two runs of one seed do, not by how many long requests they happened to
draw.

Pure and jax-free: same mix, seed and length give the same schedule.
"""

from __future__ import annotations

import dataclasses
import math
import random

#: printable ASCII without the quote and the backslash: one byte, and so
#: one token of the program's raw-byte tokenizer, per character
ALPHABET = "".join(
    chr(c) for c in range(32, 127) if chr(c) not in '"\\'
)


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # offset from the schedule's start
    prompt_len: int
    max_new_tokens: int
    prompt: str
    sample_seed: int
    burst: bool
    tenant: str | None = None
    prefix_len: int = 0


def _clamped_lognormal(rng, median, sigma, lo, hi) -> int:
    value = math.exp(rng.gauss(math.log(median), sigma))
    return max(lo, min(hi, int(round(value))))


def _in_burst(bursts, t: float) -> bool:
    return any(start <= t < start + dur for start, dur in bursts)


def shape(mix: dict, duration_s: float, rate_rps: float | None = None):
    """The work of one run, from the mix alone: arrival gaps (seconds, at
    the base or the burst rate of where they start) and (prompt, output)
    sizes, one per request that falls before `duration_s`."""
    rng = random.Random(mix["shape_seed"])
    rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
    bursts = [tuple(b) for b in mix.get("bursts", [])]
    burst_rate = rate * float(mix.get("burst_rate_x", 1.0))
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    gaps, sizes, t = [], [], 0.0
    while True:
        gap = rng.expovariate(burst_rate if _in_burst(bursts, t) else rate)
        if t + gap >= duration_s:
            break
        t += gap
        gaps.append(gap)
        sizes.append((
            _clamped_lognormal(rng, p["median"], p["sigma"], p["min"], p["max"]),
            _clamped_lognormal(rng, o["median"], o["sigma"], o["min"], o["max"]),
        ))
    return gaps, sizes


def schedule(mix: dict, seed: int, duration_s: float,
             rate_rps: float | None = None, start_s: float = 0.0,
             first_index: int = 0) -> list[Request]:
    """The run's requests in order of their due time."""
    gaps, sizes = shape(mix, duration_s, rate_rps)
    rng = random.Random(int(seed))
    if not mix.get("bursts"):
        # with bursts the gaps belong to where they fall; without, any
        # order of the same gaps is the same Poisson process
        rng.shuffle(gaps)
    rng.shuffle(sizes)
    tenants = int(mix.get("tenants", 0))
    prefix_len = int(mix.get("shared_prefix_len", 0))
    prefixes = [
        "".join(rng.choice(ALPHABET) for _ in range(prefix_len))
        for _ in range(tenants)
    ]
    bursts = [tuple(b) for b in mix.get("bursts", [])]
    out, t = [], 0.0
    for i, (gap, (plen, olen)) in enumerate(zip(gaps, sizes)):
        t += gap
        tenant, head = None, ""
        if tenants:
            k = rng.randrange(tenants)
            tenant, head = f"tenant{k}", prefixes[k]
            plen = max(plen, prefix_len + 1)
        tail = "".join(rng.choice(ALPHABET) for _ in range(plen - len(head)))
        out.append(Request(
            index=first_index + i, due_s=start_s + t, prompt_len=plen,
            max_new_tokens=olen, prompt=head + tail,
            sample_seed=rng.randrange(2**31), burst=_in_burst(bursts, t),
            tenant=tenant, prefix_len=len(head),
        ))
    return out


def stats(requests: list[Request]) -> dict:
    return {
        "requests": len(requests),
        "prompt_tokens": sum(r.prompt_len for r in requests),
        "output_tokens": sum(r.max_new_tokens for r in requests),
        "prompt_len_max": max((r.prompt_len for r in requests), default=0),
        "output_len_max": max((r.max_new_tokens for r in requests), default=0),
        "span_s": requests[-1].due_s if requests else 0.0,
    }
