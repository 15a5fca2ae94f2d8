"""Drives a serving cell through `cli.serve_lm.main`, in this process.

`serve_lm.main(argv, in_stream=Feed, out_stream=Sink)`: the feed is the
server's standard input, an iterator over the seeded schedule that sleeps
until each request is due and then yields its JSON line (`serve_stdio`
submits and reads on without waiting for answers, so this is an open
loop); the sink is its standard output and stamps every event line as it
arrives. Times count from when a request was DUE, and how late the feed
ran is printed. The feed's first read happens once the engine is built
and warm; `ramp_s` of the same traffic then fills the slots before the
window opens, all of it set-up. After the window the feed sends nothing
more and waits at most `drain_s` for the requests that were due inside
it: one not finished by then, refused, or answered with an `error` event
has failed. The program's own weights are replaced by the benchmark's
seeded ones as they are loaded (`generate_lm.load_model_and_params`).

Once `main` has returned and the engine is gone, the plain reference runs
over a seeded sample of the window's finished requests, the longest among
them, and `correct` is whether every served token's logit lies within the
limit of the reference's best.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import random
import shutil
import threading
import time
import types

from harness import adapters, compare, family, traffic, trace_reduce
from harness import window as win_math


class Sink:
    """The server's standard output: every line stamped on arrival. The
    engine's own thread writes here, so it only notes; parsing waits."""

    def __init__(self):
        self.lines = []          # (arrival time, text)
        self.closed_ids = set()  # requests with a done or an error event
        self._lock = threading.Lock()

    def write(self, text: str) -> int:
        now = time.perf_counter()
        with self._lock:
            self.lines.append((now, text))
            if '"event": "done"' in text or '"event": "error"' in text:
                try:
                    self.closed_ids.add(json.loads(text).get("id"))
                except json.JSONDecodeError:
                    pass
        return len(text)

    def flush(self) -> None:
        pass


class Feed:
    """The server's standard input: the schedule, one line when due."""

    def __init__(self, phases: list, sink: Sink, drain_s: float,
                 on_ready=None):
        #: phases: [(requests, end_s)] in order, offsets from the first
        #: read: a run has one (ramp and window), a sweep one per rate.
        #: After each the feed waits, `drain_s` at the most, for everything
        #: sent so far, and pushes the later phases back by that wait.
        self.phases, self.sink, self.drain_s = phases, sink, drain_s
        self.on_ready = on_ready
        self.t_ready = None
        self.sent = {}           # id -> (due time, sent time), run clock
        self.phase_t0 = []       # run-clock start of each phase
        self.max_lag_s = 0.0
        self.t_end = None
        self._it = self._lines()

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def _wait_closed(self, ids, not_before: float, deadline: float):
        while True:
            now = time.perf_counter()
            if now >= deadline:
                return
            if now >= not_before and ids <= self.sink.closed_ids:
                return
            time.sleep(0.01)

    def _lines(self):
        self.t_ready = time.perf_counter()
        if self.on_ready:
            self.on_ready(self.t_ready)
        shift, start_s = 0.0, 0.0  # drains push the later phases back
        for requests, end_s in self.phases:
            self.phase_t0.append(self.t_ready + shift + start_s)
            for r in requests:
                target = self.t_ready + shift + r.due_s
                while True:
                    now = time.perf_counter()
                    if now >= target:
                        break
                    time.sleep(min(target - now, 0.02))
                self.max_lag_s = max(self.max_lag_s, now - target)
                rid = f"q{r.index}"
                self.sent[rid] = (target, now)
                yield json.dumps({
                    "id": rid, "prompt": r.prompt,
                    "max_new_tokens": r.max_new_tokens,
                    "temperature": 0.0, "seed": r.sample_seed,
                }) + "\n"
            phase_end = self.t_ready + shift + end_s
            self._wait_closed(set(self.sent), phase_end,
                              phase_end + self.drain_s)
            shift += max(0.0, time.perf_counter() - phase_end)
            start_s = end_s
        self.t_end = time.perf_counter()


def parse_log(sink: Sink, feed: Feed) -> dict:
    """{id: entry} with `due`, `sent`, `tokens` (arrival times),
    `token_ids`, `done`, `failed`, `error`."""
    entries = {
        rid: {"id": rid, "due": due, "sent": sent, "tokens": [],
              "token_ids": [], "done": None, "failed": False, "error": None}
        for rid, (due, sent) in feed.sent.items()
    }
    for t, text in sink.lines:
        try:
            ev = json.loads(text)
        except json.JSONDecodeError:
            continue
        e = entries.get(ev.get("id"))
        if e is None:
            continue
        if ev["event"] == "token":
            e["tokens"].append(t)
            e["token_ids"].append(ev["token_id"])
        elif ev["event"] == "done":
            e["done"] = t
            e["status"] = ev.get("status")
            e["finish_reason"] = ev.get("finish_reason")
            if ev.get("status") != "done":
                e["failed"] = True
        elif ev["event"] == "error":
            e["failed"], e["error"] = True, ev.get("error")
    for e in entries.values():
        if e["done"] is None:
            e["failed"] = True
    return entries


def _patch_weights(ctx, source):
    """`serve_lm` loads its model through `generate_lm`: hand it the
    benchmark's seeded weights in place of its own random ones."""
    from pytorch_distributed_training_tpu.cli import generate_lm

    original = generate_lm.load_model_and_params

    def load(args, tok):
        model, params, step = original(args, tok)
        params = adapters.install(params, source, family.of(ctx["config"]))
        return model, params, step

    generate_lm.load_model_and_params = load
    return lambda: setattr(generate_lm, "load_model_and_params", original)


def serve(ctx, phases, *, drain_s, trace_at=None, extra_argv=()):
    """One server life, fed `phases`: returns the feed, the log entries by
    request id, the engine's stats, the telemetry records, the tracer's
    notes and the source of the seeded weights, as attributes."""
    from pytorch_distributed_training_tpu.cli import serve_lm

    config = ctx["config"]
    reference = importlib.import_module("reference." + config["reference"])
    source = family.source(
        config, reference.weight_spec(config["model"]), ctx["seed"])
    sink = Sink()
    tracer = {}

    def on_ready(t_ready):
        if trace_at is None:
            return
        import jax

        def body():
            start_s, length_s = trace_at
            time.sleep(max(0.0, t_ready + start_s - time.perf_counter()))
            jax.profiler.start_trace(tracer["dir"])
            tracer["t0"] = time.perf_counter()
            time.sleep(length_s)
            tracer["t1"] = time.perf_counter()
            jax.profiler.stop_trace()

        tracer["thread"] = threading.Thread(
            target=body, name="bench-profiler", daemon=True)
        tracer["thread"].start()

    feed = Feed(phases, sink, drain_s, on_ready)
    argv = list(config["argv"]) + list(extra_argv) + ["--seed", str(ctx["seed"])]
    metrics_dir = None
    if trace_at is not None:
        tracer["dir"] = os.path.join(ctx["work_dir"], "trace")
        metrics_dir = os.path.join(ctx["work_dir"], "metrics")
        argv += ["--metrics-dir", metrics_dir]
    print(f"benchmark: cli.serve_lm.main({argv})", flush=True)
    restore = _patch_weights(ctx, source)
    if ctx.get("sabotage"):
        # tests only: the timed path broken underneath the harness
        ctx["sabotage"]()
    try:
        stats = serve_lm.main(argv, in_stream=feed, out_stream=sink)
    finally:
        restore()
        if tracer.get("thread"):
            tracer["thread"].join(timeout=120)
    records = []
    if metrics_dir and os.path.isfile(os.path.join(metrics_dir, "metrics.jsonl")):
        with open(os.path.join(metrics_dir, "metrics.jsonl")) as f:
            records = [json.loads(line) for line in f if line.strip()]
    return types.SimpleNamespace(
        feed=feed, entries=parse_log(sink, feed), stats=stats,
        records=records, tracer=tracer, source=source)


def pick_sample(entries: list, requests: dict, seed: int, check: dict):
    """A seeded sample of the window's finished requests with the longest
    in it, until it holds `check["tokens"]` served tokens."""
    done = [e for e in entries if not e["failed"] and e["token_ids"]]
    if not done:
        return []
    size = lambda e: requests[e["id"]].prompt_len + len(e["token_ids"])  # noqa: E731
    longest = max(done, key=size)
    rest = [e for e in done if e is not longest]
    random.Random(int(seed)).shuffle(rest)
    sample, n = [longest], len(longest["token_ids"])
    for e in rest:
        if n >= check["tokens"] or len(sample) >= check["max_requests"]:
            break
        sample.append(e)
        n += len(e["token_ids"])
    return sample


def run(ctx) -> dict:
    import jax

    config, mix = ctx["config"], ctx["traffic"]
    # the family's counts, looked for before the server is built: a
    # configuration whose family brings none exits here, naming it
    count_prefill = family.count(config, "prefill_flops")
    count_decode = family.count(config, "decode_flops")
    ramp_s, seconds = float(mix["ramp_s"]), float(ctx["seconds"])
    shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    os.makedirs(ctx["work_dir"], exist_ok=True)
    requests = traffic.schedule(mix, ctx["seed"], ramp_s + seconds)
    by_id = {f"q{r.index}": r for r in requests}
    print(f"benchmark: schedule {traffic.stats(requests)}", flush=True)
    trace_at = None
    if ctx["trace"]:
        trace_at = (ramp_s + min(mix["trace_after_s"], seconds / 2),
                    min(mix["trace_s"], seconds / 2))
    control = list(config["control"]["argv"]) if ctx.get("control") else []
    served = serve(
        ctx, [(requests, ramp_s + seconds)], drain_s=float(mix["drain_s"]),
        trace_at=trace_at, extra_argv=control)
    feed, tracer = served.feed, served.tracer
    entries, stats, records = served.entries, served.stats, served.records

    w0 = feed.t_ready + ramp_s
    w1 = w0 + seconds
    setup_s = w0 - ctx["process_t0"]
    log = list(entries.values())
    due = win_math.in_window(log, w0, w1)
    failed = sum(1 for e in due if e["failed"])
    ttft = win_math.ttfts(log, w0, w1, miss_at=feed.t_end)
    gaps = win_math.token_gaps(log, w0, w1)
    rate = win_math.tokens_per_s(log, w0, w1)
    ttft_p95 = win_math.percentile(ttft, 95)
    tpot_p95 = win_math.percentile(gaps, 95)
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices())
    from pytorch_distributed_training_tpu.ops import dispatch

    print(f"benchmark: window {seconds:.1f}s after {ramp_s:.0f}s ramp: "
          f"{len(due)} requests due, {failed} failed, {rate:.1f} tokens/s, "
          f"first token p95 {1e3 * (ttft_p95 or 0):.1f} ms, token gap p50 "
          f"{1e3 * (win_math.percentile(gaps, 50) or 0):.1f} p95 "
          f"{1e3 * (tpot_p95 or 0):.1f} ms; "
          f"feed ran at most {1e3 * feed.max_lag_s:.2f} ms late; setup "
          f"{setup_s:.2f}s; engine {json.dumps(stats, default=str)[:600]}; "
          f"dispatch paths {dict(dispatch.DISPATCH_PATHS)}", flush=True)
    for e in due:
        if e["failed"]:
            print(f"benchmark: failed {e['id']}: {e['error'] or 'not finished'}",
                  flush=True)

    # work of the window, for the shares of the peak: prompts whose first
    # token arrived in it, and every token decoded in it with its context,
    # counted by the configuration's family, which is handed what the run
    # observed of the request beside the sizes
    prefill_flops = decode_flops = 0.0
    window_contexts = []
    trace_contexts = []
    t_tr = (tracer.get("t0"), tracer.get("t1"))
    for e in log:
        r = by_id[e["id"]]
        observed = {"request": r, "entry": e, "engine": stats}
        for j, t in enumerate(e["tokens"]):
            context = r.prompt_len + j
            if w0 <= t < w1:
                if j == 0:
                    prefill_flops += count_prefill(config, r.prompt_len, observed)
                else:
                    decode_flops += count_decode(config, context, observed)
                    window_contexts.append(context)
            if j and t_tr[0] is not None and t_tr[0] <= t < t_tr[1]:
                trace_contexts.append(context)

    gc.collect()
    trace = None
    if ctx["trace"]:
        planes = trace_reduce.load(tracer["dir"])
        if trace_reduce.device_ops(planes) or not ctx["rehearsal"]:
            trace = trace_reduce.reduce(planes)
            trace["contexts"] = trace_contexts

    reference = importlib.import_module("reference." + config["reference"])
    # how many requests make the sample's hundreds of served tokens is the
    # mix's to say, where its outputs are shorter than the configuration
    # reckoned with
    sample = pick_sample(due, by_id, ctx["seed"],
                         {**config["check"], **mix.get("check", {})})
    pairs = [
        ([ord(c) for c in by_id[e["id"]].prompt], e["token_ids"])
        for e in sample
    ]
    t_ref = time.perf_counter()
    ref_control = (config["control"]["reference_precision"]
                   if ctx.get("read_faults") else None)
    ref = reference.served_token_gaps(
        config, served.source, pairs, control=ref_control)
    ref_s = time.perf_counter() - t_ref
    checks = [
        compare.Check("max_logit_gap",
                      ref["max_logit_gap"] if pairs else float("inf"),
                      config["limits"]["max_logit_gap"]),
    ]
    notes = {
        "reference_s": ref_s, "checked_tokens": ref["tokens"],
        "checked_requests": len(pairs), "feed_max_lag_ms": 1e3 * feed.max_lag_s,
        "requests_due": len(due),
    }
    if ref_control:
        notes["faults"] = {"reference_" + ref_control: {
            "max_logit_gap": ref["control_max_logit_gap"]}}
    return {
        "attempted": len(due),
        "failed": failed,
        "end_to_end": {
            "serve.tokens_per_s": rate,
            "serve.ttft_p95_ms": 1e3 * ttft_p95,
            "serve.tpot_p95_ms": 1e3 * tpot_p95,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": peak,
        "checks": checks,
        "notes": notes,
        "observations": {
            "window_s": seconds, "ttft_s": ttft, "token_gaps_s": gaps,
            "records": records, "trace": trace, "chips": ctx["cell"]["chips"],
            "prefill_flops": prefill_flops, "decode_flops": decode_flops,
            "window_contexts": window_contexts,
        },
    }
