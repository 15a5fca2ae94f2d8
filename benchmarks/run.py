"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name: the cell's entry in
`BENCHMARK.json` names its configuration (`benchmarks/configs/<config>.json`,
which names its driver module, its plain reference
`benchmarks/reference/<reference>.py` and its model family
`benchmarks/families/<adapter>.py`: the map between the program's
parameter tree and the reference's leaves, the operation and byte counts,
weight kinds of its own; `harness/family.py`) and its traffic mix
(`benchmarks/traffic/<traffic>.json`); a per-layer metric is read by
`benchmarks/metrics/<metric>.py::read(observations)`. No list of cells,
drivers, families or metrics lives in code: a later PR adds files and
entries, a new model family among them
(`tests/benchmarks/test_family_files.py` adds one).

The last line of standard output is the result, one JSON object; the
numbers `correct` was decided from are printed beside their limits as the
last lines of standard error and under the result's last key. Without a
TPU whose `device_kind` is in `harness/peaks.py`, or with fewer chips than
the cell asks for, it exits non-zero and prints no result. (A
configuration file flagged `"rehearsal": true` may run on the CPU: the
tests' tiny configurations are, a cell never is.)
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def fail(msg: str, code: int = 2):
    sys.stderr.write(f"benchmarks/run.py: {msg}\n")
    raise SystemExit(code)


def load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        fail(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    fail(f"{what} {name!r} is not in BENCHMARK.json "
         f"(have: {[e['name'] for e in entries]})")


def metrics_of(bench: dict, group: str, cell: dict) -> list[dict]:
    """The metrics of `group` this cell reports: those that list it, and
    those that list no cells at all (for a per-layer metric: every cell
    that reports the end-to-end metric it moves)."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def load_reader(metric: str):
    """`benchmarks/metrics/<metric>.py`, loaded by its file name (a metric's
    name may hold dots, a module's may not)."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    if not os.path.isfile(path):
        fail(f"per-layer metric {metric!r}: no reader {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location("metric_" + re.sub(r"\W", "_", metric), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def device_report(chips: int, rehearsal: bool) -> tuple[dict, dict | None]:
    import jax

    from harness import peaks

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"benchmark: platform={dev['platform']} device_kind={dev['kind']} "
          f"devices={dev['count']} jax={jax.__version__}", flush=True)
    if rehearsal and dev["platform"] != "tpu":
        return dev, None
    if dev["platform"] != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is {dev['platform']!r}; a "
             f"cell is measured on the chip and nowhere else")
    try:
        peak = peaks.peaks_for(dev["kind"])
    except peaks.UnknownDevice as e:
        fail(str(e))
    if dev["count"] != chips:
        fail(f"the cell asks for {chips} chip(s) and this machine has "
             f"{dev['count']}")
    return dev, peak


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) of a cell, by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"), "benchmark")
    cell = named(bench["workloads"], workload, "workload")
    cfg_entry = named(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]), "configuration")
    traffic = load_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"), "traffic mix")
    if not os.path.isdir(os.path.join(ROOT, "pytorch_distributed_training_tpu")):
        fail("the system under test (pytorch_distributed_training_tpu/) is "
             "not in this checkout")
    return bench, cell, config, traffic


def prepare(workload: str) -> dict:
    """Everything of a run but its seed, length and tracing: the cell's
    files, the import path, the compile cache and the look for the chip."""
    bench, cell, config, traffic = load_cell(workload)
    rehearsal = config.get("rehearsal") is True
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not rehearsal:
        # one fixed directory inside the checkout; the program takes it
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    device, peak = device_report(cell["chips"], rehearsal)
    return {
        "bench": bench, "cell": cell, "config": config, "traffic": traffic,
        "process_t0": PROCESS_T0, "root": ROOT, "peaks": peak,
        "device": device, "rehearsal": rehearsal,
        "work_dir": os.path.join(ROOT, ".bench_work"),
    }


def execute(ctx: dict) -> dict:
    """Drive the cell once and build the result line's object. `ctx` is
    `prepare()`'s with `seed`, `seconds` and `trace` added."""
    from harness import compare

    bench, cell = ctx["bench"], ctx["cell"]
    driver = importlib.import_module("harness." + ctx["config"]["driver"])
    run = driver.run(ctx)
    obs = run["observations"]
    obs.update(cell=cell, config=ctx["config"], traffic=ctx["traffic"],
               peaks=ctx["peaks"], device=ctx["device"],
               end_to_end=run["end_to_end"])
    if ctx["trace"]:
        wanted = metrics_of(bench, "per_layer", cell)
        values = {m["name"]: load_reader(m["name"]).read(obs) for m in wanted}
    else:
        wanted = metrics_of(bench, "end_to_end", cell)
        values = run["end_to_end"]
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values.get(m["name"]) is not None
    }
    dev_out = dict(ctx["device"], memory_peak_bytes=run["memory_peak_bytes"])
    result = {
        "correct": compare.all_ok(run["checks"]),
        "attempted": run["attempted"], "failed": run["failed"],
        "metrics": metrics, "device": dev_out,
    }
    if ctx["rehearsal"]:
        result["rehearsal"] = True
    if ctx["trace"] and obs.get("trace"):
        dev_out["busy_s"] = obs["trace"]["busy_s"]
        dev_out["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = obs["trace"]["breakdown"]
    result["notes"] = run.get("notes", {})
    # last: every number compared, beside its limit
    result["checks"] = compare.as_json(run["checks"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail(f"--seed must be a whole number >= 0, got {args.seed}")
    ctx = prepare(args.workload)
    ctx.update(seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    result = execute(ctx)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        ok = c["value"] == c["value"] and c["value"] <= c["limit"]
        sys.stderr.write(
            f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g}) "
            f"{'ok' if ok else 'FAILED'}\n")
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
