"""Read, on the chip, the numbers a cell's limits are set from.

    python3 benchmarks/harness/calibrate.py --workload <cell> \
        --seeds 201,202,... [--fault-seeds 211,212,213] \
        [--control-seeds 301,302,303] [--seconds 2]

One process, one look for the chip: for every seed the cell's driver runs
with a short window and the numbers compared against the reference are
printed (the lower readings: the largest a sound run gives). A fault seed
is a sound run after which the faults and the reference one precision
down are read by the same numbers, put in the program's place; a control
seed runs the program with the configuration's `control` switched on (its
own lower-precision path). Those are the upper readings: the smallest
each gives. The table goes
to standard output and to `chiprun_out/calibrate_<cell>.json`. The
benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    ctx0 = bench_run.prepare(args.workload)
    rows = []

    def one(seed: int, control: bool = False, faults: bool = False):
        ctx = dict(ctx0, seed=seed, seconds=args.seconds, trace=False,
                   control=control, read_faults=faults)
        result = bench_run.execute(ctx)
        row = {
            "seed": seed, "control": control, "correct": result["correct"],
            "checks": {k: v["value"] for k, v in result["checks"].items()},
            "faults": result["notes"].get("faults"),
            "notes": {k: v for k, v in result["notes"].items() if k != "faults"},
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "failed": result["failed"], "attempted": result["attempted"],
        }
        rows.append(row)
        print("calibrate: " + json.dumps(row), flush=True)

    for s in filter(None, args.seeds.split(",")):
        one(int(s))
    for s in filter(None, args.fault_seeds.split(",")):
        one(int(s), faults=True)
    for s in filter(None, args.control_seeds.split(",")):
        one(int(s), control=True)

    def fold(rows, pick):
        out = {}
        for r in rows:
            for k, v in r["checks"].items():
                out[k] = pick(out[k], v) if k in out else v
        return out

    sound = [r for r in rows if not r["control"]]
    ctrl = [r for r in rows if r["control"]]
    summary = {
        "workload": args.workload,
        "lower_max_over_seeds": fold(sound, max),
        "control_min_over_seeds": fold(ctrl, min),
        "faults_min_over_seeds": {},
        "rows": rows,
    }
    for r in rows:
        for fault, checks in (r["faults"] or {}).items():
            slot = summary["faults_min_over_seeds"].setdefault(fault, {})
            for k, v in checks.items():
                slot[k] = min(slot[k], v) if k in slot else v
    out_dir = os.path.join(bench_run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate_{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("calibrate: summary " + json.dumps(
        {k: v for k, v in summary.items() if k != "rows"}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
