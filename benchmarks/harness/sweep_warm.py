"""The knee of a mix whose requests share long prefixes: `sweep.py`'s
sweep with the shared contexts kept warm from step to step.

    python3 benchmarks/harness/sweep_warm.py --workload <cell> \
        --rates 0.6,0.7,0.8 [--seconds 90] [--seed 1]

`sweep.py` makes each step's schedule alone, and `traffic.schedule` draws
the tenants' prefixes after shuffling a number of gaps that depends on the
rate: every step then brings new contexts, whose cold prefills (four of
16k tokens take 35 s in `agent_long_decode`) fill much of the step, where
a cell pays them once, in its ramp. Here the steps' schedules are
`traffic.schedule`'s own, but every request's shared prefix is replaced
by the one its tenant had when first seen, and a first phase sends one
short request a tenant and waits for it, so that every step begins with
its contexts in the prefix cache. One process, one set-up, a drain
between the steps, as in `sweep.py`.

Beside `sweep.py`'s columns each row holds the slots that were busy (a
request holds one from its first token to its last): at the step's end,
on average over the step's last third, and at the most. The knee is the
highest rate at which no request waits for its first token at the step's
end; the table goes to `chiprun_out/sweep_warm_<cell>.json`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from harness import sweep, traffic  # noqa: E402

WARM_NEW_TOKENS = 4
WARM_TAIL = 16


def warm_schedules(mix: dict, seed: int, seconds: float, rates: list):
    """(the warming requests, [each rate's requests]): `traffic.schedule`'s
    steps with one set of prefixes, due times counted from the end of the
    warming phase (which lasts a second by the feed's clock and as long as
    its requests take besides)."""
    steps, prefixes, start, index = [], {}, 1.0, int(mix.get("tenants", 0))
    for rate in rates:
        reqs = traffic.schedule(mix, seed, seconds, rate_rps=rate,
                                start_s=start, first_index=index)
        for r in reqs:
            prefixes.setdefault(r.tenant, r.prompt[: r.prefix_len])
        steps.append([
            dataclasses.replace(
                r, prompt=prefixes[r.tenant] + r.prompt[r.prefix_len:])
            for r in reqs])
        index += len(reqs)
        start += seconds
    warm = [
        traffic.Request(
            index=i, due_s=0.0, prompt_len=len(head) + WARM_TAIL,
            max_new_tokens=WARM_NEW_TOKENS,
            prompt=head + traffic.ALPHABET[i % len(traffic.ALPHABET)] * WARM_TAIL,
            sample_seed=i, burst=False, tenant=tenant, prefix_len=len(head))
        for i, (tenant, head) in enumerate(sorted(
            (t, h) for t, h in prefixes.items() if t is not None))
    ]
    return warm, steps


def slots_busy(entries, t: float) -> int:
    """Requests that held a slot at `t`: first token by then, not done."""
    return sum(
        1 for e in entries
        if e["tokens"] and e["tokens"][0] <= t
        and (e["done"] is None or e["done"] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=90.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    ctx = bench_run.prepare(args.workload)
    ctx.update(seed=args.seed, seconds=args.seconds, trace=False)
    from harness import serve_driver
    from harness import window as win_math

    mix = ctx["traffic"]
    rates = [float(r) for r in args.rates.split(",")]
    warm, steps = warm_schedules(mix, args.seed, args.seconds, rates)
    phases = [(warm, 1.0)] + [
        (reqs, 1.0 + (n + 1) * args.seconds) for n, reqs in enumerate(steps)]
    served = serve_driver.serve(ctx, phases, drain_s=float(mix["drain_s"]))
    feed, log = served.feed, list(served.entries.values())
    rows = []
    for n, rate in enumerate(rates):
        t0 = feed.phase_t0[n + 1]
        t1 = t0 + args.seconds
        due = win_math.in_window(log, t0, t1)
        ttft = win_math.ttfts(log, t0, t1, miss_at=feed.t_end)
        gaps = win_math.token_gaps(log, t0, t1)
        third = [slots_busy(log, t1 - args.seconds / 3 * k / 20)
                 for k in range(21)]
        rows.append({
            "rate_rps": rate, "offered": len(due),
            "failed": sum(1 for e in due if e["failed"]),
            "tokens_per_s": win_math.tokens_per_s(log, t0, t1),
            "ttft_p50_ms": 1e3 * (win_math.percentile(ttft, 50) or 0),
            "ttft_p95_ms": 1e3 * (win_math.percentile(ttft, 95) or 0),
            "tpot_p50_ms": 1e3 * (win_math.percentile(gaps, 50) or 0),
            "tpot_p95_ms": 1e3 * (win_math.percentile(gaps, 95) or 0),
            "waiting_mid": sweep.waiting(due, t0 + args.seconds / 2),
            "waiting_end": sweep.waiting(due, t1),
            "slots_busy_end": slots_busy(log, t1),
            "slots_busy_last_third": sum(third) / len(third),
            "slots_busy_max": max(
                slots_busy(log, t0 + args.seconds * k / 90) for k in range(91)),
            "drain_s": max(0.0, max((e["done"] or t1) for e in due) - t1)
            if due else 0.0,
        })
        print("sweep_warm: " + json.dumps(rows[-1]), flush=True)
    stats = served.stats or {}
    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sweep_warm_{args.workload}.json"), "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seed": args.seed, "feed_max_lag_ms": 1e3 * feed.max_lag_s,
                   "warm_s": feed.phase_t0[1] - feed.phase_t0[0],
                   "num_slots": stats.get("num_slots"),
                   "prefix_cached_tokens": stats.get("prefix_cached_tokens"),
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
