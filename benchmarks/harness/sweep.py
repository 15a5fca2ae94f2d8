"""Find the knee of a serving cell once, by a sweep on the chip.

    python3 benchmarks/harness/sweep.py --workload <cell> --rates 2,4,6,8 \
        [--seconds 20] [--seed 1]

One process and one set-up: the cell's traffic mix is offered at each
rate in turn for `--seconds`, with a drain between, and a table is
printed (and written to `chiprun_out/sweep_<cell>.json`): offered and
finished requests, completed tokens per second, time to first token, and
how many requests were still waiting for their first token half-way and
at the end of the step. The knee is the highest rate at which that queue
does not grow over the step; the cell's file then holds 0.8 of it as a
number. The benchmark's own runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402


def waiting(entries, t: float) -> int:
    """Requests due by `t` whose first token had not arrived by then."""
    return sum(
        1 for e in entries
        if e["due"] <= t and not (e["tokens"] and e["tokens"][0] <= t)
        and not (e["failed"] and e["done"] is not None and e["done"] <= t)
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    ctx = bench_run.prepare(args.workload)
    ctx.update(seed=args.seed, seconds=args.seconds, trace=False)
    from harness import serve_driver, traffic
    from harness import window as win_math

    mix = ctx["traffic"]
    rates = [float(r) for r in args.rates.split(",")]
    phases, start, index = [], 0.0, 0
    for rate in rates:
        reqs = traffic.schedule(mix, args.seed, args.seconds, rate_rps=rate,
                                start_s=start, first_index=index)
        index += len(reqs)
        start += args.seconds
        phases.append((reqs, start))
    served = serve_driver.serve(ctx, phases, drain_s=float(mix["drain_s"]))
    feed, log = served.feed, list(served.entries.values())
    rows = []
    for n, rate in enumerate(rates):
        t0 = feed.phase_t0[n]
        t1 = t0 + args.seconds
        due = win_math.in_window(log, t0, t1)
        ttft = win_math.ttfts(log, t0, t1, miss_at=feed.t_end)
        gaps = win_math.token_gaps(log, t0, t1)
        rows.append({
            "rate_rps": rate, "offered": len(due),
            "failed": sum(1 for e in due if e["failed"]),
            "tokens_per_s": win_math.tokens_per_s(log, t0, t1),
            "ttft_p50_ms": 1e3 * (win_math.percentile(ttft, 50) or 0),
            "ttft_p95_ms": 1e3 * (win_math.percentile(ttft, 95) or 0),
            "tpot_p50_ms": 1e3 * (win_math.percentile(gaps, 50) or 0),
            "tpot_p95_ms": 1e3 * (win_math.percentile(gaps, 95) or 0),
            "waiting_mid": waiting(due, t0 + args.seconds / 2),
            "waiting_end": waiting(due, t1),
            "drain_s": max(0.0, max((e["done"] or t1) for e in due) - t1)
            if due else 0.0,
        })
        print("sweep: " + json.dumps(rows[-1]), flush=True)
    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sweep_{args.workload}.json"), "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seed": args.seed, "feed_max_lag_ms": 1e3 * feed.max_lag_s,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
