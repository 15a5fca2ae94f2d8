"""A temporary copy of the benchmark with tiny configurations added as
files and entries only: how the tests rehearse `benchmarks/run.py` on the
CPU, and the proof that a configuration, a model family, a cell and a
per-layer metric need no edit of a file that is there. Never a cell: the configurations are
flagged `"rehearsal": true`, which is what lets `run.py` leave the chip.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_BERT = {
    "name": "tiny_dp", "rehearsal": True, "source": "tests only",
    "driver": "train_driver", "reference": "bert_large_dp",
    "adapter": "bert",
    "model": {
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 128, "vocab_size": 1024,
        "max_position_embeddings": 128, "type_vocab_size": 2,
        "layer_norm_eps": 1e-12, "num_labels": 2,
    },
    "program_model": {"hidden_dropout": 0.0, "attention_dropout": 0.0},
    "recipe": {
        "max_seq_length": 32, "global_batch_per_chip": 16,
        "micro_batch_per_chip": 4, "learning_rate": 2e-5, "warmup_steps": 100,
        "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8,
    },
    "argv": ["--model", "tiny", "--task", "synthetic", "--num-epochs", "1",
             "--eval-size", "32", "--mesh-data", "-1", "--log-every", "0",
             "--max-seq-length", "32", "--no-bf16",
             # on the CPU backend device_put aliases the native loader's
             # slots, which the prefetcher overwrites under the step
             "--native-loader", "off"],
    "control": {"argv": ["--bf16"], "reference_precision": "bfloat16",
                "why": "this configuration states float32: one step down"},
    "check": {"updates": 3},
    # sound float32 runs read 1e-7, 3e-6 and 3e-4; the bfloat16 control
    # 4e-5, 0.08 and 9e-3 (CPU, seeds 11-13)
    "limits": {"loss_gap": 1e-5, "grad1_norm_gap": 1e-3, "delta_norm_gap": 3e-3},
}
TINY_STEADY = {"kind": "train_steady", "warm_steps": 4,
               "max_steps_per_second": 400, "traced_rate_share": 0.6,
               "trace_steps": 2}


TINY_GPT2 = {
    "name": "tiny_paged", "rehearsal": True, "source": "tests only",
    "driver": "serve_driver", "reference": "gpt2_medium_paged",
    "adapter": "gpt2", "weights": {"std": 0.2},
    "model": {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
              "n_positions": 128, "vocab_size": 1024,
              "layer_norm_epsilon": 1e-5},
    "argv": ["--model", "gpt2-tiny", "--warmup", "--num-slots", "4",
             "--prompt-buckets", "16,32", "--max-new-tokens-cap", "32",
             "--queue-depth", "256", "--stall-timeout-s", "120"],
    "control": {"argv": ["--weights-dtype", "int8", "--kv-dtype", "int8"],
                "reference_precision": "int8"},
    "check": {"tokens": 60, "max_requests": 4},
    # sound runs read 0 to 0.022, the int8 control 0.076 to 0.11 (CPU)
    "limits": {"max_logit_gap": 0.045},
}
TINY_CHAT = {
    "kind": "open_loop", "shape_seed": 3, "rate_rps": 6.0, "ramp_s": 1,
    "drain_s": 30,
    "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
    "trace_after_s": 0.5, "trace_s": 0.5,
}
# long prompts in, a few tokens out, as `summarize_prefill`: the sample
# needs more requests than the configuration's four to hold 60 tokens
TINY_SUMMARIZE = {
    "kind": "open_loop", "shape_seed": 4, "rate_rps": 10.0, "ramp_s": 1,
    "drain_s": 30,
    "prompt_tokens": {"median": 24, "sigma": 0.3, "min": 8, "max": 32},
    "output_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "trace_after_s": 0.5, "trace_s": 0.5,
    "check": {"tokens": 60, "max_requests": 24},
}


def make_checkout(tmp: str, *, configs=(), traffic=(), cells=(),
                  per_layer=(), readers=(), end_to_end_cells=(),
                  listed=(), files=()) -> str:
    """Copy `BENCHMARK.json` and `benchmarks/` into `tmp`, link the system
    under test beside them, and ADD the given files and entries (`files`:
    `(path under benchmarks/, text)`, a family's or a reference's;
    `listed`: `(per-layer metric, cell)`, the cell's name added to the
    list of a metric that is there, as a PR that adds a cell does)."""
    root = os.path.join(tmp, "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("pytorch_distributed_training_tpu", "native"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in configs:
        rel = f"benchmarks/configs/{cfg['name']}.json"
        _write_new(os.path.join(root, rel), json.dumps(cfg, indent=1))
        bench["configs"].append({
            "name": cfg["name"], "source": cfg["source"], "file": rel,
            "reduced": [], "why": "tests only"})
    for name, mix in traffic:
        _write_new(os.path.join(root, f"benchmarks/traffic/{name}.json"),
                   json.dumps(mix, indent=1))
    bench["workloads"].extend(cells)
    have = {m["name"]: m for m in bench["end_to_end"]}
    for metric, cell in end_to_end_cells:
        if metric not in have:  # a metric no real cell reports (yet)
            have[metric] = {
                "name": metric, "unit": "ms" if metric.endswith("_ms") else "1/s",
                "better": "lower" if metric.endswith("_ms") else "higher",
                "bound": 0.1, "source": "host_clock", "workloads": []}
            bench["end_to_end"].append(have[metric])
        have[metric]["workloads"].append(cell)
    for metric, cell in listed:
        named, = [m for m in bench["per_layer"] if m["name"] == metric]
        named["workloads"].append(cell)
    bench["per_layer"].extend(per_layer)
    for name, source in readers:
        _write_new(os.path.join(root, f"benchmarks/metrics/{name}.py"), source)
    for rel, text in files:
        _write_new(os.path.join(root, "benchmarks", rel), text)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


def _write_new(path: str, text: str) -> None:
    if os.path.exists(path):
        raise FileExistsError(f"{path}: adding must not edit a file that is there")
    with open(path, "w") as f:
        f.write(text)


def run_cell(root: str, cell: str, *, seed=7, seconds=0.5, trace=0,
             timeout=600, env=None):
    """`run.py` in a child process on the CPU; returns (rc, result|None,
    stdout, stderr)."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("JAX_COMPILATION_CACHE_DIR", None)
    full_env.pop("XLA_FLAGS", None)  # one CPU device, not conftest's eight
    full_env.update(env or {})
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, env=full_env, capture_output=True, text=True, timeout=timeout)
    result = None
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    if p.returncode == 0 and lines:
        result = json.loads(lines[-1])
    return p.returncode, result, p.stdout, p.stderr


TINY_CELLS = [
    {"name": "tiny_dp1", "config": "tiny_dp", "traffic": "tiny_steady",
     "chips": 1, "why": "tests only"},
    {"name": "tiny_chat1", "config": "tiny_paged", "traffic": "tiny_chat",
     "chips": 1, "why": "tests only"},
    {"name": "tiny_prefill1", "config": "tiny_paged",
     "traffic": "tiny_summarize", "chips": 1, "why": "tests only"},
]
#: the readers that take the harness's own observations, which a
#: rehearsal has too (shares of a peak read nothing without a chip)
TINY_LISTED = [
    ("train.data_wait_ms", "tiny_dp1"), ("train.mfu", "tiny_dp1"),
    ("train.device_idle", "tiny_dp1"),
    *[(m, c) for m in ("serve.tpot_p50_ms", "serve.mfu", "serve.device_idle")
      for c in ("tiny_chat1", "tiny_prefill1")],
]
TINY_END_TO_END = [
    ("train.samples_per_s_per_chip", "tiny_dp1"),
    ("serve.ttft_p95_ms", "tiny_chat1"),
    ("serve.tpot_p95_ms", "tiny_chat1"),
    ("serve.tpot_p95_ms", "tiny_prefill1"),
]


def make_tiny_checkout(tmp: str, **more) -> str:
    """Both tiny configurations and their cells, added as files and
    entries."""
    more.setdefault("cells", [])
    return make_checkout(
        tmp, configs=[TINY_BERT, TINY_GPT2],
        traffic=[("tiny_steady", TINY_STEADY), ("tiny_chat", TINY_CHAT),
                 ("tiny_summarize", TINY_SUMMARIZE)],
        cells=TINY_CELLS + list(more.pop("cells")),
        end_to_end_cells=TINY_END_TO_END, listed=TINY_LISTED, **more)


def run_script(root: str, script: str, *args, timeout=600):
    """A test-owned script in the checkout, on one CPU device; returns
    (rc, last JSON line or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, script, *map(str, args)], cwd=root,
                       env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    result = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return p.returncode, result, p.stderr
