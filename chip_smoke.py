"""Chip smoke: the two main paths, once each, on whatever TPU is attached.

    python chip_smoke.py

The quickest proof that the system still starts on the chip. One process
(a chip belongs to one process at a time) drives both halves of the repo
through the entry points a user would call, at the full width of the
models, with weights made from a seed:

1. trainer — ``cli.train_dp --model bert-large-cased --task synthetic``:
   the default recipe (bf16, seq 128, global batch 96 = 8 x 12) for four
   optimizer updates and one eval pass, AOT warm start on, the mesh
   spanning every chip the machine has (``data=-1``);
2. server — ``cli.serve_lm --model gpt2-medium`` (24 x h1024, vocab 50257):
   default paged KV / device sampling / strict guards, stdio mode, five
   requests over three prompt buckets, 16-24 new tokens each.

It checks what comes out (finite losses near ln 2 for the two-class task,
parameters that moved, every chip holding live state and a shard of the
batch, the fused kernels dispatched; every request answered in full with
in-vocabulary ids, no ``error`` event, equal prompts giving equal streams),
prints per-phase wall time with compile time apart, the compile-cache
directory with its entry count before and after, and which path each op
took — and fails if any of it is off. Without a TPU it refuses to run.
Last line of stdout on success:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import io
import json
import math
import sys
import tempfile
import time

TRAIN_ARGS = [
    "--model", "bert-large-cased", "--task", "synthetic",
    "--num-epochs", "1", "--train-size", "384", "--eval-size", "64",
    "--mesh-data", "-1", "--log-every", "1",
]
SERVE_ARGS = ["--model", "gpt2-medium", "--warmup"]
#: (prompt, max_new_tokens); the raw-byte tokenizer makes len(prompt) the
#: token count, so these land in buckets 16, 64, 16, 128 and 32
REQUESTS = [
    ("Hello, TPU.", 16),
    ("The quick brown fox jumps over the lazy dog.", 24),
    ("Hello, TPU.", 16),
    ("In a hole in the ground there lived a hobbit. Not a nasty, dirty, "
     "wet hole, filled with the ends of worms.", 16),
    ("Call me Ishmael. Some", 20),
]


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: nothing downstream may turn a bad
    phase into exit code 0."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"chip_smoke: ok: {what}", flush=True)


def read_jsonl(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def run_trainer(devices) -> dict:
    import jax

    from pytorch_distributed_training_tpu.cli import train_dp
    from pytorch_distributed_training_tpu.ops import dispatch
    from pytorch_distributed_training_tpu.train.loop import Trainer

    seen = {}

    class ObservedTrainer(Trainer):
        """The CLI's Trainer, unchanged, with a look at its state while it
        is alive: ``train_dp.main`` hands back only the metric history."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            epoch = self.train_loader.epoch

            def observed_epoch(*a, **k):
                for batch in epoch(*a, **k):
                    seen.setdefault("batch_devices", len(
                        jax.tree.leaves(batch)[0].sharding.device_set
                    ))
                    yield batch

            self.train_loader.epoch = observed_epoch

        @staticmethod
        def _sample(params):
            # first and last leaves (embedding norm ... classifier head)
            leaves = jax.tree.leaves(params)
            return leaves[:4] + leaves[-4:]

        def run(self):
            # copies: the step donates the state these leaves live in
            seen["before"] = [x.copy() for x in self._sample(self.state.params)]
            seen["mode"] = dispatch.mode()
            return super().run()

        def evaluate(self):
            out = super().evaluate()
            # inside run(): the trained state is still resident
            seen["moved"] = [
                float(abs(a - b).max())
                for a, b in zip(self._sample(self.state.params),
                                seen.pop("before"))
            ]
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                seen[key] = [
                    (d.memory_stats() or {}).get(key, 0) for d in devices
                ]
            seen["param_devices"] = len(
                jax.tree.leaves(self.state.params)[0].sharding.device_set
            )
            return out

    train_dp.Trainer = ObservedTrainer
    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as mdir:
        t0 = time.perf_counter()
        history = train_dp.main(TRAIN_ARGS + ["--metrics-dir", mdir])
        wall_s = time.perf_counter() - t0
        records = read_jsonl(f"{mdir}/metrics.jsonl")
    (compile_rec,) = [r for r in records if r["record"] == "compile"]
    steps = [r for r in records if r["record"] == "step"]
    # the train step's compiled collectives, where the guards audited them
    collectives = {
        r["name"]: {k: [v["count"], v["bytes"]] for k, v in r["by_kind"].items()}
        for r in records if r["record"] == "comm_audit" and "by_kind" in r
    }
    losses = [s["loss"] for s in steps]
    print(f"chip_smoke: trainer losses {losses}", flush=True)

    check(len(steps) == 4 and len(history) == 1,
          f"trainer took 4 optimizer updates and 1 eval pass "
          f"({len(steps)} steps, {len(history)} epoch records)")
    check(all(math.isfinite(x) for x in losses)
          and math.isfinite(history[0]["train_loss"]),
          "every training loss is finite")
    check(all(abs(x - math.log(2)) < 0.35 for x in losses),
          "losses sit near ln 2, as a fresh two-class head must")
    check(0.0 <= history[0]["accuracy"] <= 1.0,
          f"eval pass produced an accuracy ({history[0]['accuracy']:.3f})")
    check(all(math.isfinite(d) and d > 0.0 for d in seen["moved"]),
          f"parameters moved and stayed finite (max |delta| per sampled "
          f"leaf {seen['moved']})")
    check(not any(s["compile_inclusive"] for s in steps),
          "AOT warm start: no step paid a compile")
    n = len(devices)
    check(seen["batch_devices"] == n and seen["param_devices"] == n,
          f"batch and params span all {n} device(s)")
    check(all(b > 0 for b in seen["bytes_in_use"]),
          f"every device holds live buffers {seen['bytes_in_use']}")
    want_mode = "direct" if n == 1 else "shard_map"
    check(seen["mode"] == want_mode,
          f"kernel dispatch mode is {seen['mode']!r} (want {want_mode!r})")
    paths = dict(dispatch.DISPATCH_PATHS)
    kernel = {k: v for k, v in paths.items() if not k.endswith(":xla")}
    check(any(k.startswith("layer_norm:") for k in kernel)
          and any(k.startswith("dal:") for k in kernel),
          f"fused LayerNorm kernels dispatched at bert-large shapes {paths}")
    if n > 1:
        sharded = {k: v for k, v in paths.items() if k.endswith(":shard_map")}
        check(sum(sharded.values()) > 0,
              f"kernels routed through shard_map {sharded}")
    return {
        "wall_s": round(wall_s, 2),
        "compile_s": round(compile_rec["compile_s"], 2),
        "cache_hit": compile_rec["cache_hit"],
        # dispatch to dispatch: the first records' intervals are the loop
        # running ahead of the device, the last is its step
        "steady_step_s": round(steps[-1]["step_s"], 4),
        "dispatch_mode": seen["mode"],
        "dispatch_paths": paths,
        "peak_bytes_in_use": seen["peak_bytes_in_use"],
        "collectives": collectives,
    }


class TimedLines:
    """The server's stdin: the first read happens once the engine is built
    and warm, which splits set-up (compile) time from serving time."""

    def __init__(self, lines):
        self._lines = iter(lines)
        self.first_read_t = None

    def __iter__(self):
        return self

    def __next__(self):
        if self.first_read_t is None:
            self.first_read_t = time.perf_counter()
        return next(self._lines)


def run_server() -> dict:
    from pytorch_distributed_training_tpu.cli import serve_lm
    from pytorch_distributed_training_tpu.ops import dispatch
    from pytorch_distributed_training_tpu.utils.config import model_preset

    vocab = model_preset("gpt2-medium").vocab_size
    paths_before = dict(dispatch.DISPATCH_PATHS)
    stdin = TimedLines(
        json.dumps({"id": f"q{i}", "prompt": p, "max_new_tokens": n}) + "\n"
        for i, (p, n) in enumerate(REQUESTS)
    )
    stdout = io.StringIO()
    t0 = time.perf_counter()
    # raises SystemExit when the serve loop died
    stats = serve_lm.main(SERVE_ARGS, in_stream=stdin, out_stream=stdout)
    t1 = time.perf_counter()
    events = [json.loads(line) for line in stdout.getvalue().splitlines()]

    errors = [e for e in events if e["event"] == "error"]
    check(not errors, f"no error event ({errors[:2]})")
    tokens = {f"q{i}": [] for i in range(len(REQUESTS))}
    for e in events:
        if e["event"] == "token":
            tokens[e["id"]].append(e["token_id"])
    done = {e["id"]: e for e in events if e["event"] == "done"}
    for i, (_, want) in enumerate(REQUESTS):
        d = done.get(f"q{i}")
        check(d is not None and d["status"] == "done"
              and d["finish_reason"] == "length" and d["new_tokens"] == want
              and len(tokens[f"q{i}"]) == want,
              f"request q{i} finished with its {want} tokens")
    check(all(0 <= t < vocab for ts in tokens.values() for t in ts),
          f"every token id is inside the vocabulary of {vocab}")
    check(tokens["q0"] == tokens["q2"],
          "equal greedy prompts got equal streams, slot by slot")
    check(stats["finished"] == len(REQUESTS),
          f"engine finished {stats['finished']} requests")
    ttft = [done[f"q{i}"]["ttft_s"] for i in range(len(REQUESTS))]
    paths = {
        k: v - paths_before.get(k, 0)
        for k, v in dispatch.DISPATCH_PATHS.items()
        if v != paths_before.get(k, 0)
    }
    return {
        "wall_s": round(t1 - t0, 2),
        "setup_and_compile_s": round(stdin.first_read_t - t0, 2),
        "serve_s": round(t1 - stdin.first_read_t, 2),
        "ttft_s": [round(x, 3) for x in ttft],
        "new_tokens": sum(n for _, n in REQUESTS),
        "dispatch_paths": paths,
    }


def main() -> None:
    import jax

    from pytorch_distributed_training_tpu import native
    from pytorch_distributed_training_tpu.train.compile import (
        cache_entry_count,
        enable_compile_cache,
    )

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']} "
          f"jax={jax.__version__}", flush=True)
    if device["platform"] != "tpu":
        sys.stderr.write(
            "chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{device['platform']!r}; this script proves the system on "
            "the chip and runs nowhere else\n"
        )
        raise SystemExit(2)

    cache_dir = enable_compile_cache()
    entries = [cache_entry_count(cache_dir)]
    print(f"chip_smoke: compile cache {cache_dir} holds {entries[0]} "
          f"entries", flush=True)
    print("chip_smoke: data loader: "
          + ("native C++ batcher" if native.native_available()
             else "Python (native build unavailable)"), flush=True)

    report = {}
    for name, phase in (("trainer", lambda: run_trainer(devices)),
                        ("server", run_server)):
        print(f"chip_smoke: ---- {name}", flush=True)
        report[name] = phase()
        entries.append(cache_entry_count(cache_dir))
        print(f"chip_smoke: {name}: {json.dumps(report[name])}", flush=True)
    print(f"chip_smoke: compile cache {cache_dir}: entries {entries[0]} -> "
          f"{entries[1]} (trainer) -> {entries[2]} (server)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
