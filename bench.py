"""Headline benchmark: BERT-large MRPC-recipe fine-tune throughput.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N}

Task shape is the reference's DP recipe — bert-large-cased classifier,
seq 128, global batch 96, bf16 (replacing fp16 AMP), AdamW — from reference
test_data_parallelism.py:49-50,112,174. Data is the in-repo synthetic
MRPC-shaped task (zero-egress image; same tensor contract as GLUE/MRPC).

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
denominator is the driver's north-star target: 2× an A100's BERT-large
fine-tune throughput. A100 fp16 BERT-large at seq 128 sustains ≈330
samples/sec (NVIDIA DGX A100 reference results: ~2.6-2.8k seq/s phase-1
pretraining across 8 GPUs), so baseline = 660 samples/sec/chip and
vs_baseline ≥ 1.0 means the north star is met.

The grad-accum split differs from the reference's micro=8×accum=12 on
purpose: MAX_GPU_BATCH_SIZE=8 was a GPU memory cap (reference
test_data_parallelism.py:49); one TPU chip fits far larger microbatches, and
a sweep (12×8 … 96×1) lands on micro 24 × accum 4 (unrolled) as the v5e
sweet spot —
same global batch semantics, best MXU occupancy. Override with
--micro-batch-size/--global-batch-size for other splits.

Matmul precision: the dense matmuls run on the MXU's 2x-rate int8 tier
(ops/quant.py; per-channel weight scales, per-tensor activation/gradient
scales, STE backward) with DELAYED activation scaling — each site
quantizes with the previous microbatch's amax carried in the train state,
removing the absmax-before-quantize serialization (~9 ms/step; 726 → 766
samples/s/chip). Everything else (attention math, softmax/LN stats,
residual stream, optimizer) keeps the bf16/fp32 policy. bf16 plateaus at
~615 samples/s/chip on this chip with the dots at ~90% of peak (NOTES.md
r3 ledger) — the int8 tier is the hardware's remaining throughput lever,
and it is convergence-gated across THREE seeds on BOTH schedules: the
3-epoch recipe A/B vs bf16 lands inside the bf16 ensemble's band every
time (HISTORY_bert_large_recipe_seed{42,43,44}_int8full_delayed*.json vs
the bf16/_int8full artifacts; NOTES.md int8 section). ``--matmul-impl
native`` reverts to pure bf16; ``--no-quant-delayed`` keeps dynamic
scales.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# Per-model baselines. The reference publishes NO numbers (BASELINE.md);
# the only driver-set target is the bert-large north star: 2x an A100's
# fp16 BERT-large fine-tune throughput (~330 samples/s at seq 128). Other
# models have no sanctioned denominator — their vs_baseline is null rather
# than a misleading ratio against the bert-large constant (VERDICT r3
# weak-#3: BENCH_gpt2_medium.json carried vs_baseline 0.0676 against 660).
MODEL_BASELINES = {
    "bert-large-cased": {
        "value": 660.0,
        "note": "2x A100 fp16 BERT-large MRPC fine-tune (north star)",
        "precision": "fp16 AMP (A100)",
    },
}


def chip_device() -> dict:
    """The benchmark's one backend touch, in this process: what JAX runs
    on, as every result reports it. A chip belongs to one process — a
    probe child would have to give it up again before the benchmark could
    start — so there is none."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def run_bench(
    model_name: str = "bert-large-cased",
    global_batch: int = 96,
    micro_batch: int = 24,
    seq_len: int = 128,
    warmup_steps: int = 5,
    timed_steps: int = 30,
    repeats: int = 3,
    chain_steps: int = 1,
    matmul_impl: str = "default",
    quant_delayed: bool | None = None,
    quant_delayed_grads: bool = False,
) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.comms.mesh import build_mesh
    from pytorch_distributed_training_tpu.data.pipeline import ShardedLoader
    from pytorch_distributed_training_tpu.data.synthetic import (
        synthetic_pair_task,
    )
    from pytorch_distributed_training_tpu.models import (
        BertForSequenceClassification,
    )
    from pytorch_distributed_training_tpu.parallel import (
        ShardingPolicy,
        state_shardings,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_state
    from pytorch_distributed_training_tpu.train.optim import adamw_with_schedule
    from pytorch_distributed_training_tpu.train.state import create_train_state
    from pytorch_distributed_training_tpu.train.step import make_train_step
    from pytorch_distributed_training_tpu.utils.config import (
        TrainConfig,
        model_preset,
    )

    n_chips = jax.device_count()
    mesh = build_mesh()
    from pytorch_distributed_training_tpu.ops.dispatch import set_kernel_mesh

    # register the kernel-dispatch mesh (as Trainer.__init__ does): on a
    # multi-chip run the fused Pallas ops otherwise silently fall back to
    # XLA math and the benchmark measures the wrong path
    set_kernel_mesh(mesh)
    # int8 MXU matmuls are convergence-gated PER RECIPE (module docstring);
    # only the recipe that actually ran the gate (bert-large on the MRPC
    # recipe, NOTES.md int8 section) defaults to it — every other model
    # stays on its preset's native path unless the caller opts in
    # explicitly (the flag's help says what that implies).
    mcfg = model_preset(model_name)
    if matmul_impl == "default":
        matmul_impl = (
            "int8_full" if model_name == "bert-large-cased" else "native"
        )
    mcfg.matmul_impl = matmul_impl
    if quant_delayed is None:
        # default ON for the int8 tiers: multi-seed convergence-gated
        # (module docstring) and +40 samples/s/chip over dynamic scales
        quant_delayed = matmul_impl in ("int8", "int8_full")
    if quant_delayed:
        if matmul_impl not in ("int8", "int8_full"):
            raise SystemExit(
                "--quant-delayed requires an int8 matmul impl "
                f"(got {matmul_impl!r})"
            )
        # delayed activation scaling (ops/quant.py): amaxes carried in the
        # train state, calibrated below on the first batch
        mcfg.quant_delayed = True
    if quant_delayed_grads:
        # opt-in A/B knob (NOT the gated default): delayed dy scaling in
        # the backward — requires its own convergence gate before it may
        # ever become a default (module docstring contract)
        if not (mcfg.quant_delayed and matmul_impl == "int8_full"):
            raise SystemExit(
                "--quant-delayed-grads requires delayed int8_full"
            )
        mcfg.quant_delayed_grads = True
    need_pos = (
        seq_len + mcfg.pad_token_id + 1 if mcfg.roberta_style else seq_len
    )
    if need_pos > mcfg.max_position_embeddings:
        # long-context benches train from random init, so growing the
        # position table is legitimate (a pretrained run would need
        # interpolation instead)
        mcfg.max_position_embeddings = need_pos
    if mcfg.causal:
        from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel

        model = GPT2LMModel(mcfg)
        objective = "causal_lm"
    else:
        model = BertForSequenceClassification(mcfg)
        objective = "classification"
    tcfg = TrainConfig(
        global_batch_size=global_batch,
        micro_batch_size=micro_batch,
        max_seq_length=seq_len,
        # bf16 accumulation carry + bf16 adam first moment: each ~1% step
        # time; both convergence-checked against fp32 on the MRPC recipe
        # (loss within 4e-5, identical eval metrics)
        grad_accum_dtype="bfloat16",
        adam_mu_dtype="bfloat16",
        adam_nu_dtype="bfloat16",
    )
    tx, _ = adamw_with_schedule(tcfg, total_steps=1000)

    example = {
        "input_ids": jnp.ones((2, seq_len), jnp.int32),
        "attention_mask": jnp.ones((2, seq_len), jnp.int32),
        "token_type_ids": jnp.zeros((2, seq_len), jnp.int32),
    }
    state = create_train_state(
        model, tx, jax.random.key(42, impl=tcfg.prng_impl), example
    )
    shardings = state_shardings(state, ShardingPolicy(), mesh)
    state = shard_state(state, shardings)
    train_step = make_train_step(
        grad_accum_steps=tcfg.grad_accum_steps,
        mesh=mesh,
        state_shardings=shardings,
        objective=objective,
        accum_dtype=tcfg.grad_accum_dtype,
        chain_steps=chain_steps,
        # the per-step grad-norm metric costs one extra read of every
        # gradient leaf (~0.7 GB -> ~1 ms on bert-large, measured +3.6
        # samples/s off). The Trainer keeps it (it feeds --log-every
        # diagnostics); the bench matches the reference's hot loop, which
        # logs nothing per step (reference test_data_parallelism.py:140-150).
        log_grad_norm=False,
    )

    # A few distinct batches, cycled, with per-step device placement included
    # in the timing (as a real input pipeline would pay it).
    n_examples = global_batch * 4
    if mcfg.causal:
        from pytorch_distributed_training_tpu.data.synthetic import (
            synthetic_lm_task,
        )

        data = synthetic_lm_task(
            n_examples, max_length=seq_len, vocab_size=mcfg.vocab_size, seed=42
        )
    else:
        data = synthetic_pair_task(
            n_examples, max_length=seq_len, vocab_size=mcfg.vocab_size, seed=42
        )
    loader = ShardedLoader(
        data, mesh,
        global_batch_size=global_batch,
        grad_accum_steps=tcfg.grad_accum_steps,
        train=True, seed=42,
    )
    batches_np = []  # keep host-side; re-place each timed step
    for b in loader.epoch(0):
        batches_np.append(jax.tree.map(lambda x: jax.device_get(x), b))

    from pytorch_distributed_training_tpu.comms.ingest import make_global_batch
    from pytorch_distributed_training_tpu.comms.mesh import TRAIN_BATCH_PSPEC

    def place(i):
        return make_global_batch(
            mesh, batches_np[i % len(batches_np)], pspec=TRAIN_BATCH_PSPEC
        )

    if chain_steps > 1:
        # Chained driver (train/step.py): ONE dispatch per chain_steps
        # optimizer steps over pre-placed batches. Builder-measured equal
        # to per-step dispatch in r3 (jax's async dispatch already
        # pipelines the dispatch latency away); not measured on today's
        # machine.
        import numpy as _np
        from jax.sharding import PartitionSpec as P

        if chain_steps > timed_steps:
            raise SystemExit(
                f"--chain-steps {chain_steps} must be <= --timed-steps "
                f"{timed_steps}"
            )
        timed_steps = (timed_steps // chain_steps) * chain_steps

        def place_chain(i):
            stack = {
                k: _np.stack(
                    [batches_np[(i + j) % len(batches_np)][k]
                     for j in range(chain_steps)]
                )
                for k in batches_np[0]
            }
            return make_global_batch(
                mesh, stack, pspec=P(None, *TRAIN_BATCH_PSPEC)
            )

        # placement stays in-loop, matching the per-step path (a real
        # input pipeline pays H2D either way, so the --chain-steps
        # comparison isolates dispatch amortization only)
        feed = place_chain
        calls_per_pass = timed_steps // chain_steps
        warmup_calls = max(warmup_steps // chain_steps, 1)
    else:
        feed = place
        calls_per_pass = timed_steps
        warmup_calls = warmup_steps

    if state.quant is not None:
        from pytorch_distributed_training_tpu.train.step import calibrate_quant

        state = calibrate_quant(
            state, jax.tree.map(lambda x: x[0], place(0)),
            objective=objective,
            loss_scale=1.0 / tcfg.grad_accum_steps,
        )

    for i in range(warmup_calls):
        state, metrics = train_step(state, feed(i))
    jax.block_until_ready(state.params)

    # best-of-N passes: the minimum is the steady-state number (placement
    # still in-loop). Each pass ends with a device_get of a scalar produced
    # by the last step, so the clock stops only when the device has.
    elapsed = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for i in range(calls_per_pass):
            state, metrics = train_step(state, feed(i))
        float(jax.device_get(metrics["loss"]))
        elapsed = min(elapsed, time.perf_counter() - t0)

    sps = global_batch * timed_steps / elapsed
    sps_chip = sps / n_chips
    recipe = "causal-LM" if mcfg.causal else "MRPC-recipe"
    precision = (
        "bf16" if mcfg.matmul_impl == "native"
        else "int8-MXU matmuls + bf16 elsewhere, convergence-gated"
    )
    extra = {
        "samples_per_sec_total": round(sps, 2),
        "n_chips": n_chips,
        "device": chip_device(),
        "grad_accum_steps": tcfg.grad_accum_steps,
        "final_loss": float(jax.device_get(metrics["loss"])),
        "matmul_impl": mcfg.matmul_impl,
        "quant_delayed": mcfg.quant_delayed,
        "quant_delayed_grads": mcfg.quant_delayed_grads,
    }
    if chain_steps > 1:
        extra["chain_steps"] = chain_steps
    baseline = MODEL_BASELINES.get(model_name)
    if baseline:
        extra["baseline"] = baseline["note"]
        # the denominator's precision differs from an int8-tier headline;
        # record it so downstream comparisons can't silently conflate tiers
        extra["baseline_precision"] = baseline["precision"]
        vs = round(sps_chip / baseline["value"], 4)
    else:
        extra["baseline"] = (
            "none: reference publishes no numbers and the driver's "
            "north-star ratio is defined for bert-large-cased only"
        )
        vs = None
    return {
        "metric": f"{model_name} {recipe} fine-tune throughput (seq {seq_len}, global batch {global_batch}, {precision})",
        "value": round(sps_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": vs,
        "extra": extra,
    }


# --------------------------------------------------------------- serve mode
# Closed-loop serving load generator on CPU: N client threads drive the
# continuous-batching engine (serve/) over a configurable prompt-length mix,
# against a sequential one-shot generate() baseline on the SAME workload.
# Writes BENCH_serve.json with throughput + latency percentiles. Runs in a
# JAX_PLATFORMS=cpu subprocess (the --quick pattern) so the parent never
# initializes a backend; driven by the `perf`+`serve`-marked pytest
# (tests/test_serve_bench.py), kept out of tier-1 timing noise.


def _serve_stats_mod():
    """scripts/summarize_metrics.py as a module (scripts/ isn't a package)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts",
        "summarize_metrics.py",
    )
    spec = importlib.util.spec_from_file_location("summarize_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _ListSink:
    """In-memory telemetry sink: the bench reads percentiles straight from
    the records instead of round-tripping a JSONL file."""

    def __init__(self):
        self.records = []

    def emit(self, record):
        rec = dict(record)
        rec.setdefault("ts", time.time())
        self.records.append(rec)

    def flush(self, **kw):
        pass


_BENCH_WAIT_S = 300.0


def _await_done(event, what: str) -> None:
    # bounded: a dead engine loop must FAIL the bench child, not hang it
    if not event.wait(_BENCH_WAIT_S):
        raise RuntimeError(f"bench child timed out waiting for {what}")


def _join_clients(threads) -> None:
    for t in threads:
        t.join(_BENCH_WAIT_S)
        if t.is_alive():
            raise RuntimeError("bench client thread failed to finish")


def _serve_child(cfg_json: str) -> None:
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.models.generate import generate
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.serve import (
        BackpressureError,
        EngineConfig,
        InferenceServer,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = json.loads(cfg_json)
    mix = cfg["prompt_mix"]
    max_new = cfg["max_new"]
    n_requests = cfg["requests"]

    mcfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(mcfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))[
        "params"
    ]
    rng = np.random.default_rng(42)
    prompts = [
        rng.integers(1, mcfg.vocab_size, mix[i % len(mix)]).astype(np.int32)
        for i in range(n_requests)
    ]

    # ---- sequential one-shot baseline (generate() per request, batch=1);
    # warm each distinct prompt length first so compile stays out of both
    # timed sections
    warm = {
        n: rng.integers(1, mcfg.vocab_size, n).astype(np.int32)
        for n in sorted({len(p) for p in prompts})
    }
    for p in warm.values():
        np.asarray(generate(model, params, p[None], max_new_tokens=max_new))
    t0 = time.perf_counter()
    seq_tokens = 0
    for p in prompts:
        out = np.asarray(generate(model, params, p[None],
                                  max_new_tokens=max_new))
        seq_tokens += out.shape[1] - len(p)
    seq_wall = time.perf_counter() - t0

    # ---- continuous-batching engine over the same workload
    registry = MetricsRegistry()
    sink = _ListSink()
    registry.attach_sink(sink)
    buckets = tuple(sorted({len(p) for p in prompts}))
    server = InferenceServer(
        model, params,
        EngineConfig(num_slots=cfg["slots"], prompt_buckets=buckets,
                     max_new_tokens=max_new),
        queue_depth=cfg["queue_depth"], registry=registry,
    ).start()
    # warm every prefill bucket + the decode step before timing
    for n in buckets:
        _await_done(server.submit(warm[n], max_new_tokens=2).done,
                    f"warmup bucket {n}")
    sink.records.clear()

    work = list(prompts)
    lock = threading.Lock()
    rejected = [0]
    accepted_ids = []

    def client():
        while True:
            with lock:
                if not work:
                    return
                p = work.pop()
            while True:
                try:
                    req = server.submit(p, max_new_tokens=max_new)
                    break
                except BackpressureError:
                    with lock:
                        rejected[0] += 1
                    time.sleep(0.002)
            with lock:
                accepted_ids.append(req.id)
            _await_done(req.done, "request completion")

    threads = [
        threading.Thread(target=client, daemon=True)
        for _ in range(cfg["concurrency"])
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    _join_clients(threads)
    eng_wall = time.perf_counter() - t0
    server.close(drain=True)

    serve_summary = _serve_stats_mod().summarize_serve(sink.records)
    eng_tokens = serve_summary["tokens"]
    # span-coverage gate: every accepted request must yield a complete,
    # root-closed span tree with zero orphans and phase sums reconciling
    # against the serve span (telemetry/spans.py tiles the phases, so
    # anything else is an instrumentation regression)
    from pytorch_distributed_training_tpu.telemetry.spans import (
        trace_coverage,
    )

    coverage = trace_coverage(sink.records, accepted_ids=accepted_ids)
    result = {
        "metric": (
            f"serving quick bench (tiny LM, CPU, {n_requests} requests x "
            f"{max_new} new tokens, prompt mix {mix}, "
            f"{cfg['slots']} slots, {cfg['concurrency']} clients)"
        ),
        "engine": {
            "tokens_per_s": round(eng_tokens / eng_wall, 2),
            "wall_s": round(eng_wall, 3),
            "tokens": eng_tokens,
            "requests": serve_summary["done"],
            "rejected_submits": rejected[0],
            "slots": cfg["slots"],
            "queue_depth": cfg["queue_depth"],
            "ttft_s": serve_summary["ttft_s"],
            "tpot_s": serve_summary["tpot_s"],
            "queue_wait_s": serve_summary["queue_wait_s"],
            "stats": server.stats(),
        },
        "spans": {
            "traces": coverage["traces"],
            "coverage": coverage["coverage"],
            "orphan_spans": coverage["orphan_spans"],
            "incomplete": coverage["incomplete"],
            "phase_sum_bad": coverage["phase_sum_bad"],
            "span_coverage_ok": (
                coverage["coverage"] == 1.0
                and coverage["orphan_spans"] == 0
                and not coverage["phase_sum_bad"]
            ),
        },
        "sequential": {
            "tokens_per_s": round(seq_tokens / seq_wall, 2),
            "wall_s": round(seq_wall, 3),
            "tokens": seq_tokens,
        },
        "speedup": round((eng_tokens / eng_wall) / (seq_tokens / seq_wall), 3),
    }
    print(json.dumps(result))


def run_serve(
    requests: int = 16,
    concurrency: int = 6,
    slots: int = 4,
    max_new: int = 16,
    prompt_mix=(6, 10, 14),
    queue_depth: int = 4,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")
    cfg = dict(
        requests=requests, concurrency=concurrency, slots=slots,
        max_new=max_new, prompt_mix=list(prompt_mix),
        queue_depth=queue_depth,
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--serve-child", json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve bench failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# --------------------------------------------------------------- paged mode
# Paged-KV + on-device-sampling A/B on CPU: the same closed-loop load as
# --serve, run through three engine configurations — dense cache + host
# sampling (the pre-paged engine), paged cache + device sampling (the new
# default), both on a UNIFORM prompt-length workload, and paged+device on a
# MIXED workload (prompt lengths spanning 1x-8x) whose page pool is sized
# BELOW num_slots x longest-context — a shape the dense layout cannot admit
# at equal memory, since dense charges every slot the longest context.
# Writes BENCH_paged.json; driven by the `perf`+`serve`-marked pytest,
# kept out of tier-1 timing noise.


def _paged_child(cfg_json: str) -> None:
    """One engine configuration over one closed-loop workload. Also the
    child for --spec: optional ``spec_k``/``prefill_chunk`` cfg keys turn
    speculation/chunked prefill on, and the result carries a digest of the
    token streams (request-order) so the parent can assert the A/B
    variants emitted IDENTICAL tokens."""
    import hashlib
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.serve import (
        BackpressureError,
        EngineConfig,
        InferenceServer,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = json.loads(cfg_json)
    mix = cfg["prompt_mix"]
    max_new = cfg["max_new"]
    n_requests = cfg["requests"]

    mcfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(mcfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))[
        "params"
    ]
    rng = np.random.default_rng(42)
    tenants = cfg.get("tenants", 0)
    if tenants:
        # multi-tenant shared-system-prompt workload (--prefix): request i
        # belongs to tenant ``i % tenants`` and its prompt is that tenant's
        # fixed shared prefix plus a private tail of prompt_mix length —
        # identical across the cold/cached variants (same rng draws)
        prefixes = [
            rng.integers(
                1, mcfg.vocab_size, cfg["shared_prefix_len"]
            ).astype(np.int32)
            for _ in range(tenants)
        ]
        prompts = [
            np.concatenate([
                prefixes[i % tenants],
                rng.integers(
                    1, mcfg.vocab_size, mix[i % len(mix)]
                ).astype(np.int32),
            ])
            for i in range(n_requests)
        ]
    else:
        prompts = [
            rng.integers(
                1, mcfg.vocab_size, mix[i % len(mix)]
            ).astype(np.int32)
            for i in range(n_requests)
        ]

    from pytorch_distributed_training_tpu.ops.quant import (
        dequantize_serve_params,
        quantize_serve_params,
    )

    # quality probe BEFORE any grid snapping: max |logit| drift between the
    # pristine fp32 weights and their int8 round-trip on one prompt — the
    # bench's quantization-error headline (engines below see snapped or
    # quantized weights, where the drift is zero by construction)
    max_logit_drift = None
    if cfg.get("logit_probe"):
        probe = jnp.asarray(prompts[0])[None, :]
        base_logits = model.apply({"params": params}, probe)
        rt = dequantize_serve_params(quantize_serve_params(params))
        max_logit_drift = float(jnp.max(jnp.abs(
            model.apply({"params": rt}, probe) - base_logits
        )))
    # snap fp32 weights onto the int8 grid so a FP32 engine and an int8
    # engine run numerically identical matmul weights — the token-identity
    # A/B for weight-only quantization (idempotent: snapping an already
    # snapped tree is a no-op)
    if cfg.get("snap"):
        params = dequantize_serve_params(quantize_serve_params(params))

    registry = MetricsRegistry()
    sink = _ListSink()
    registry.attach_sink(sink)
    buckets = tuple(sorted({len(p) for p in prompts}))
    ecfg = EngineConfig(
        num_slots=cfg["slots"], prompt_buckets=buckets,
        max_new_tokens=max_new,
        kv_layout=cfg["kv_layout"], sampling=cfg["sampling"],
        page_size=cfg["page_size"], num_pages=cfg["num_pages"],
        spec_k=cfg.get("spec_k", 0),
        prefill_chunk=cfg.get("prefill_chunk", 0),
        tp=cfg.get("tp", 1),
        warmup=cfg.get("warmup", False),
        weights_dtype=cfg.get("weights_dtype", "float32"),
        kv_dtype=cfg.get("kv_dtype", "float32"),
        prefix_cache=cfg.get("prefix_cache", False),
        tenant_page_quota=cfg.get("tenant_page_quota", 0.0),
    )
    server = InferenceServer(
        model, params, ecfg,
        queue_depth=cfg["queue_depth"], registry=registry,
    ).start()
    # warm every prefill bucket + the decode step before timing (same
    # sampling params as the load: operands are traced either way, so one
    # program serves both, but the warm request must not skew percentiles)
    for n in buckets:
        _await_done(
            server.submit(
                rng.integers(1, mcfg.vocab_size, n).astype(np.int32),
                max_new_tokens=2, temperature=cfg["temperature"],
                top_k=cfg["top_k"],
            ).done,
            f"warmup bucket {n}",
        )
    # the comm audit fires at warmup-compile time (engine-level warmup,
    # tp mode); grab it before the timing window resets the sink
    comm_audits = [
        dict(r) for r in sink.records if r.get("record") == "comm_audit"
    ]
    sink.records.clear()

    work = list(enumerate(prompts))
    lock = threading.Lock()
    rejected = [0]
    streams: dict[int, list] = {}

    def client():
        while True:
            with lock:
                if not work:
                    return
                i, p = work.pop()
            while True:
                try:
                    req = server.submit(
                        p, max_new_tokens=max_new,
                        temperature=cfg["temperature"], top_k=cfg["top_k"],
                        seed=i,
                        tenant=f"tenant{i % tenants}" if tenants else None,
                    )
                    break
                except BackpressureError:
                    with lock:
                        rejected[0] += 1
                    time.sleep(0.002)
            _await_done(req.done, "request completion")
            with lock:
                streams[i] = [int(t) for t in req.tokens]

    threads = [
        threading.Thread(target=client, daemon=True)
        for _ in range(cfg["concurrency"])
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    _join_clients(threads)
    wall = time.perf_counter() - t0
    server.close(drain=True)

    serve_summary = _serve_stats_mod().summarize_serve(sink.records)
    stats = server.stats()

    # resident bytes of the attention/MLP projection weights in the dtype
    # the ENGINE holds them — the weight-only-int8 memory headline (the
    # embedding/LN leaves stay fp32 in every variant and are excluded so
    # the tiny model's vocab table doesn't mask the matmul-weight ratio)
    from pytorch_distributed_training_tpu.ops.quant import (
        _SERVE_QUANT_MODULES,
    )
    resident = (
        quantize_serve_params(params)
        if cfg.get("weights_dtype", "float32") == "int8" else params
    )
    matmul_weight_bytes = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(resident):
        names = {getattr(k, "key", None) for k in path}
        if names & set(_SERVE_QUANT_MODULES):
            matmul_weight_bytes += int(leaf.size) * leaf.dtype.itemsize

    result = {
        "kv_layout": cfg["kv_layout"],
        "sampling": cfg["sampling"],
        "weights_dtype": stats.get("weights_dtype", "float32"),
        "kv_dtype": stats.get("kv_dtype", "float32"),
        "variant": stats.get("variant", "fp32"),
        "kv_bytes_per_token": stats.get("kv_bytes_per_token"),
        "matmul_weight_bytes": matmul_weight_bytes,
        "max_logit_drift": max_logit_drift,
        "prompt_mix": mix,
        "tokens_per_s": round(serve_summary["tokens"] / wall, 2),
        "wall_s": round(wall, 3),
        "tokens": serve_summary["tokens"],
        "requests": serve_summary["done"],
        "rejected_submits": rejected[0],
        "ttft_s": serve_summary["ttft_s"],
        "tpot_s": serve_summary["tpot_s"],
        "kv_pages_total": stats.get("kv_pages_total"),
        "kv_pages_peak": stats.get("kv_pages_peak"),
        "page_exhausted": stats.get("page_exhausted"),
        "buckets": serve_summary["buckets"],
        # token-identity key: same digest across variants <=> bit-identical
        # streams for every request (request order, not completion order)
        "stream_digest": hashlib.sha256(
            json.dumps([streams[i] for i in sorted(streams)]).encode()
        ).hexdigest(),
        "spec_k": stats.get("spec_k", 0),
        "spec_dispatches": stats.get("spec_dispatches"),
        "spec_drafted": stats.get("spec_drafted"),
        "spec_accepted": stats.get("spec_accepted"),
        "spec_accept_rate": stats.get("spec_accept_rate"),
        "tokens_per_dispatch": stats.get("tokens_per_dispatch"),
        "prefill_chunk": stats.get("prefill_chunk", 0),
        "prefill_chunks": stats.get("prefill_chunks"),
        # prefix-cache surface (--prefix): real tokens pushed through the
        # prefill programs (the cache's savings show up here), the engine's
        # prefix_cache stats block (None when the cache is off), and the
        # end-state shared-page count
        "prefill_tokens": stats.get("prefill_tokens"),
        "prefix": stats.get("prefix_cache"),
        "kv_pages_shared": stats.get("kv_pages_shared"),
        "tp": stats.get("tp", 1),
        # per-tick collective footprint of the hot program, straight from
        # the compile-time comm audit (tp>1 + warmup only; else empty)
        "comm_audits": [
            {k: a.get(k) for k in ("name", "manifest", "ok", "deviations",
                                   "by_kind", "total_bytes",
                                   "total_moved_bytes")}
            for a in comm_audits
        ],
    }
    print(json.dumps(result))


def run_paged(
    requests: int = 16,
    concurrency: int = 6,
    slots: int = 4,
    max_new: int = 16,
    page_size: int = 8,
    queue_depth: int = 4,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")

    def one(name: str, **over) -> dict:
        base = dict(
            requests=requests, concurrency=concurrency, slots=slots,
            max_new=max_new, queue_depth=queue_depth, page_size=page_size,
            num_pages=0, temperature=0.8, top_k=20,
        )
        base.update(over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--paged-child", json.dumps(base)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"paged bench variant {name!r} failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    # uniform A/B: one prompt length, so the only difference between the
    # variants is cache layout + where sampling runs
    uniform_mix = [10]
    dense = one("dense_host", prompt_mix=uniform_mix,
                kv_layout="dense", sampling="host")
    paged = one("paged_device", prompt_mix=uniform_mix,
                kv_layout="paged", sampling="device")

    # mixed workload: prompt lengths spanning 1x-8x, with the page pool
    # sized BELOW num_slots x longest-context — dense at equal memory
    # cannot even configure this engine (it charges every slot the
    # longest context); paged admits the whole mix and backpressures on
    # pages when the mix momentarily doesn't fit
    mixed_mix = [6, 12, 24, 48]
    longest = max(mixed_mix) + max_new
    pages_per_slot = -(-longest // page_size)
    dense_equiv_pages = slots * pages_per_slot        # what dense would need
    mixed_pages = max(pages_per_slot + 1, (3 * dense_equiv_pages) // 4 + 1)
    mixed = one("paged_mixed", prompt_mix=mixed_mix,
                kv_layout="paged", sampling="device",
                num_pages=mixed_pages)

    result = {
        "metric": (
            f"paged-KV + device-sampling quick bench (tiny LM, CPU, "
            f"{requests} requests x {max_new} new tokens, {slots} slots, "
            f"page {page_size} tok)"
        ),
        "uniform": {
            "prompt_mix": uniform_mix,
            "dense_host": dense,
            "paged_device": paged,
            "speedup": round(
                paged["tokens_per_s"] / dense["tokens_per_s"], 3
            ),
        },
        "mixed": {
            "prompt_mix": mixed_mix,
            "pages_total": mixed["kv_pages_total"],
            "dense_equivalent_pages": dense_equiv_pages,
            "pool_below_dense_equiv": (
                mixed["kv_pages_total"] < dense_equiv_pages
            ),
            "paged_device": mixed,
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# ---------------------------------------------------------------- spec mode
# Speculative-decoding + chunked-prefill A/B on CPU: the same closed-loop
# load through four paged+device engine configurations — baseline, spec
# only, chunked prefill only, and both — all greedy so the token-identity
# contract is checkable from the digests (every variant MUST emit the same
# streams; speculation/chunking are latency knobs, not sampling changes).
# Reports per-bucket TTFT/TPOT, acceptance stats, and the TPOT speedup the
# perf gate asserts (>= 2x on the dispatch-overhead-dominated CPU bench).
# Writes BENCH_spec.json; driven by the `perf`+`serve`-marked pytest in
# tests/test_spec.py, kept out of tier-1 timing noise.


def run_spec(
    requests: int = 16,
    concurrency: int = 6,
    slots: int = 4,
    max_new: int = 32,
    spec_k: int = 7,
    prefill_chunk: int = 8,
    page_size: int = 8,
    queue_depth: int = 4,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")

    # mixed prompt lengths so chunked prefill has real work (the longest
    # prompt streams in over several chunks) and per-bucket latency rows
    # are populated; greedy so the n-gram drafter's acceptance — and the
    # cross-variant stream digests — are deterministic
    prompt_mix = [8, 16, 32, 48]

    def one(name: str, **over) -> dict:
        base = dict(
            requests=requests, concurrency=concurrency, slots=slots,
            max_new=max_new, queue_depth=queue_depth, page_size=page_size,
            num_pages=0, temperature=0.0, top_k=0, prompt_mix=prompt_mix,
            kv_layout="paged", sampling="device",
        )
        base.update(over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--paged-child", json.dumps(base)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"spec bench variant {name!r} failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    baseline = one("baseline")
    spec = one("spec", spec_k=spec_k)
    chunked = one("chunked", prefill_chunk=prefill_chunk)
    both = one("spec_chunked", spec_k=spec_k, prefill_chunk=prefill_chunk)

    variants = {
        "baseline": baseline, "spec": spec,
        "chunked": chunked, "spec_chunked": both,
    }
    digests = {n: v["stream_digest"] for n, v in variants.items()}
    result = {
        "metric": (
            f"speculative-decoding + chunked-prefill quick bench (tiny LM, "
            f"CPU, {requests} requests x {max_new} new tokens, {slots} "
            f"slots, k={spec_k}, chunk={prefill_chunk})"
        ),
        "prompt_mix": prompt_mix,
        **variants,
        # the two acceptance-criteria numbers, precomputed for the gate
        "tpot_speedup": round(
            baseline["tpot_s"]["p50"] / spec["tpot_s"]["p50"], 3
        ),
        "streams_identical": len(set(digests.values())) == 1,
        "stream_digests": digests,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def run_prefix(
    requests: int = 32,
    concurrency: int = 6,
    slots: int = 4,
    max_new: int = 16,
    tenants: int = 4,
    shared_prefix_len: int = 96,
    page_size: int = 8,
    queue_depth: int = 6,
    tenant_page_quota: float = 0.0,
    out_path: str | None = None,
) -> dict:
    """A/B of the shared-KV prefix cache on the multi-tenant
    shared-system-prompt workload: identical requests through a cold
    engine (prefix_cache off, every prompt prefilled from scratch) and a
    cached engine (prefix_cache on). Token identity is asserted via the
    stream digests; the wins are prefill tokens actually computed and
    TTFT."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")

    # short private tails on a long shared prefix: the regime where
    # serving the prefix once dominates (prompt ~100-104 tokens, 96
    # shared, near the tiny model's 128-position ceiling). The long prefix
    # is the point — it makes the cold monolithic prefill structurally
    # expensive, so the cached TTFT win measures skipped compute, not
    # dispatch-overhead noise.
    prompt_mix = [4, 6, 8]
    # pool sized for: 4 tenants x 20 cached prefix pages + 4 slots x 23
    # worst-case pages + warm-bucket trie inserts (evictable under LRU)
    num_pages = max(128, 2 * (tenants + slots + 1)
                    * ((shared_prefix_len + max(prompt_mix) + max_new)
                       // page_size + 1))

    def one(name: str, **over) -> dict:
        base = dict(
            requests=requests, concurrency=concurrency, slots=slots,
            max_new=max_new, queue_depth=queue_depth, page_size=page_size,
            num_pages=num_pages, temperature=0.0, top_k=0,
            prompt_mix=prompt_mix,
            kv_layout="paged", sampling="device",
            tenants=tenants, shared_prefix_len=shared_prefix_len,
            # engine-level warmup: the cached variant's chunk + COW-copy
            # programs must be compiled before the timed window, exactly
            # like the cold variant's bucket prefills — else the first hit
            # pays a mid-flight compile and the TTFT A/B measures XLA
            warmup=True,
        )
        base.update(over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--paged-child", json.dumps(base)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"prefix bench variant {name!r} failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = one("cold")
    cached = one(
        "cached", prefix_cache=True, tenant_page_quota=tenant_page_quota,
    )

    reduction = (
        1.0 - cached["prefill_tokens"] / cold["prefill_tokens"]
        if cold["prefill_tokens"] else 0.0
    )
    result = {
        "metric": (
            f"shared-KV prefix cache quick bench (tiny LM, CPU, "
            f"{requests} requests x {max_new} new tokens, {tenants} "
            f"tenants x {shared_prefix_len}-token shared prefix, "
            f"{slots} slots)"
        ),
        "prompt_mix": prompt_mix,
        "tenants": tenants,
        "shared_prefix_len": shared_prefix_len,
        "cold": cold,
        "cached": cached,
        # the acceptance-criteria numbers, precomputed for the gate
        "streams_identical": (
            cold["stream_digest"] == cached["stream_digest"]
        ),
        "prefill_token_reduction": round(reduction, 4),
        "ttft_p50_speedup": round(
            cold["ttft_s"]["p50"] / cached["ttft_s"]["p50"], 3
        ) if cached["ttft_s"]["p50"] else None,
        "prefix_hit_rate": cached["prefix"]["prefix_hit_rate"],
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# ------------------------------------------------------------------ tp mode
# Tensor-parallel serving A/B on CPU: the same closed-loop greedy load
# through tp=1 and tp=N engines (plus both again with speculation on), all
# on a forced-multi-device host mesh so sharding is real. The contract is
# the serve engine's acceptance bar: tp=N must emit BIT-IDENTICAL streams
# to tp=1 (tensor parallelism is a partitioning knob, not a sampling
# change), and the tp=N hot program's compile-time comm audit must conform
# to serve_tp_manifest (exactly 2 all-reduces per layer, bounded bytes, no
# weight all-gather). Writes BENCH_tp.json; driven by the `perf`+`tp`-
# marked pytest in tests/test_tp_serve.py, kept out of tier-1.


def run_tp(
    requests: int = 16,
    concurrency: int = 6,
    slots: int = 4,
    max_new: int = 32,
    tp: int = 2,
    spec_k: int = 7,
    page_size: int = 8,
    queue_depth: int = 4,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # unlike the other CPU benches (which pop XLA_FLAGS), tp mode NEEDS
    # virtual devices: every variant — tp=1 included — runs on the same
    # N-device host so the A/B isolates partitioning, not device count
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={max(tp, 2)}"
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")

    # same mixed prompt lengths as --spec so digests are comparable across
    # bench modes; greedy so the identity contract is checkable
    prompt_mix = [8, 16, 32, 48]

    def one(name: str, **over) -> dict:
        base = dict(
            requests=requests, concurrency=concurrency, slots=slots,
            max_new=max_new, queue_depth=queue_depth, page_size=page_size,
            num_pages=0, temperature=0.0, top_k=0, prompt_mix=prompt_mix,
            kv_layout="paged", sampling="device", warmup=True,
        )
        base.update(over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--paged-child", json.dumps(base)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"tp bench variant {name!r} failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    tp1 = one("tp1", tp=1)
    tpn = one("tpN", tp=tp)
    tp1_spec = one("tp1_spec", tp=1, spec_k=spec_k)
    tpn_spec = one("tpN_spec", tp=tp, spec_k=spec_k)

    variants = {
        "tp1": tp1, f"tp{tp}": tpn,
        "tp1_spec": tp1_spec, f"tp{tp}_spec": tpn_spec,
    }
    digests = {n: v["stream_digest"] for n, v in variants.items()}
    audits = {
        n: v["comm_audits"] for n, v in variants.items() if v["comm_audits"]
    }
    result = {
        "metric": (
            f"tensor-parallel serving quick bench (tiny LM, CPU host mesh, "
            f"tp={tp}, {requests} requests x {max_new} new tokens, "
            f"{slots} slots, k={spec_k})"
        ),
        "tp": tp,
        "prompt_mix": prompt_mix,
        **variants,
        "tokens_per_s_ratio": round(
            tpn["tokens_per_s"] / tp1["tokens_per_s"], 3
        ) if tp1["tokens_per_s"] else None,
        "streams_identical": len(set(digests.values())) == 1,
        "stream_digests": digests,
        # every sharded variant's audit must have come back clean
        "comm_audit_ok": all(
            a["ok"] for per in audits.values() for a in per
        ) and bool(audits),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# ---------------------------------------------------------------- int8 mode
# Quantized-serving quality/throughput matrix on CPU: the same closed-loop
# greedy load through fp32 / weight-only-int8 / weight+KV-int8 engines
# (and the full-int8 engine again with speculation on), all paged+device.
# Weights are pre-snapped onto the int8 grid so weight-only quantization
# is provably LOSSLESS — the fp32 and weight-int8 engines must emit
# bit-identical streams (same sha256 digest) while the int8 engine holds
# its projection weights at ~0.27x the bytes. Int8 KV is lossy by design;
# its contract is capacity, priced by a pool-bytes-matched A/B: at the
# SAME pool byte budget the int8 layout holds >= 1.9x the pages (so
# >= 1.9x concurrent contexts), demonstrated by serving 2x the slots out
# of the matched-bytes int8 pool with zero page-exhausted rejections.
# Writes BENCH_int8.json; driven by the `perf`-marked pytest in
# tests/test_quant_serve.py, kept out of tier-1.


def run_int8(
    requests: int = 16,
    concurrency: int = 6,
    slots: int = 4,
    max_new: int = 32,
    spec_k: int = 7,
    page_size: int = 8,
    queue_depth: int = 4,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")

    # same mixed prompt lengths as --spec/--tp so digests are comparable
    # across bench modes; greedy so the identity contract is checkable
    prompt_mix = [8, 16, 32, 48]

    def one(name: str, **over) -> dict:
        base = dict(
            requests=requests, concurrency=concurrency, slots=slots,
            max_new=max_new, queue_depth=queue_depth, page_size=page_size,
            num_pages=0, temperature=0.0, top_k=0, prompt_mix=prompt_mix,
            kv_layout="paged", sampling="device", snap=True,
        )
        base.update(over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--paged-child", json.dumps(base)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"int8 bench variant {name!r} failed "
                f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    fp32 = one("fp32", logit_probe=True)
    w8 = one("weight_int8", weights_dtype="int8")
    w8kv8 = one("weight_kv_int8", weights_dtype="int8", kv_dtype="int8")
    w8kv8_spec = one("weight_kv_int8_spec", weights_dtype="int8",
                     kv_dtype="int8", spec_k=spec_k)

    # pool-bytes-matched capacity A/B: price the fp32 pool that exactly
    # covers the closed-loop worst case, then give the int8 engine the
    # SAME byte budget in int8 pages and make it serve 2x the slots
    longest = max(prompt_mix) + max_new
    pages_per_ctx = -(-longest // page_size)
    fp32_pages = slots * pages_per_ctx
    pool_bytes = fp32_pages * page_size * fp32["kv_bytes_per_token"]
    int8_pages = pool_bytes // (page_size * w8kv8["kv_bytes_per_token"])
    contexts_ratio = int8_pages / fp32_pages
    cap_slots = 2 * slots
    fp32_cap = one("fp32_kv_capacity", num_pages=fp32_pages)
    int8_cap = one("int8_kv_capacity", weights_dtype="int8",
                   kv_dtype="int8", num_pages=int(int8_pages),
                   slots=cap_slots, concurrency=cap_slots)

    variants = {
        "fp32": fp32, "weight_int8": w8, "weight_kv_int8": w8kv8,
        "weight_kv_int8_spec": w8kv8_spec,
        "fp32_kv_capacity": fp32_cap, "int8_kv_capacity": int8_cap,
    }
    result = {
        "metric": (
            f"int8 serving quality/throughput matrix (tiny LM, CPU, "
            f"{requests} requests x {max_new} new tokens, {slots} slots, "
            f"k={spec_k}, page {page_size} tok)"
        ),
        "prompt_mix": prompt_mix,
        **variants,
        # weight-only int8 is lossless on the snapped grid: identical
        # streams at a fraction of the resident projection-weight bytes
        "weight_only_streams_identical": (
            fp32["stream_digest"] == w8["stream_digest"]
        ),
        "tokens_per_s_ratio_weight_only": round(
            w8["tokens_per_s"] / fp32["tokens_per_s"], 3
        ) if fp32["tokens_per_s"] else None,
        "weight_bytes_ratio": round(
            w8["matmul_weight_bytes"] / fp32["matmul_weight_bytes"], 3
        ),
        "max_logit_drift": fp32["max_logit_drift"],
        # int8-KV capacity at matched pool bytes
        "kv_pool_bytes": int(pool_bytes),
        "kv_contexts_ratio": round(contexts_ratio, 3),
        "kv_capacity_slots": {"fp32": slots, "int8": cap_slots},
        "kv_capacity_page_exhausted": {
            "fp32": fp32_cap["page_exhausted"],
            "int8": int8_cap["page_exhausted"],
        },
        "stream_digests": {
            n: v["stream_digest"] for n, v in variants.items()
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# --------------------------------------------------------------- fleet mode
# Availability-under-failure drill on CPU: a 2-replica supervised fleet
# behind the router (serve/fleet.py + serve/router.py), closed-loop load in
# two phases — baseline (both replicas healthy) and chaos (one replica
# SIGKILLed mid-load) — reporting availability (every request must end in a
# stream-to-completion OR an explicit retryable answer) and the p99 latency
# delta the failover costs. Runs in a JAX_PLATFORMS=cpu subprocess (the
# replicas are subprocesses of THAT child); driven by the `perf`+`chaos`-
# marked pytest (tests/test_serve_bench.py), kept out of tier-1.


def _fleet_child(cfg_json: str) -> None:
    import http.client
    import threading

    from pytorch_distributed_training_tpu.serve.fleet import (
        FleetConfig,
        ServeFleet,
    )
    from pytorch_distributed_training_tpu.serve.router import (
        RouterConfig,
        make_router_http_server,
    )

    cfg = json.loads(cfg_json)
    n_requests = cfg["requests"]
    max_new = cfg["max_new"]

    fleet = ServeFleet(
        FleetConfig(
            num_replicas=2,
            replica_args=(
                "--model", "gpt2-tiny", "--num-slots", "2",
                "--prompt-buckets", "16,32", "--max-new-tokens-cap", "64",
                "--queue-depth", "16",
            ),
            max_restarts=1,
            backoff_s=0.2,
            drain_timeout_s=15.0,
        ),
        RouterConfig(
            health_interval_s=0.05, breaker_threshold=3,
            breaker_cooldown_s=0.5, retry_backoff_s=0.02,
            retry_backoff_max_s=0.1, ttfb_timeout_s=120.0,
        ),
    ).start()
    assert fleet.wait_ready(timeout=180), fleet.stats()
    httpd = make_router_http_server(fleet.router)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def one_request(i: int, phase: str) -> dict:
        t0 = time.perf_counter()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
            conn.request(
                "POST", "/generate",
                body=json.dumps({
                    "prompt": f"{phase} request {i}",
                    "max_new_tokens": max_new,
                }),
                headers={"X-Request-Id": f"{phase}-{i}"},
            )
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                conn.close()
                return {"outcome": "rejected",
                        "latency_s": time.perf_counter() - t0}
            lines = resp.read().decode().splitlines()
            conn.close()
            last = json.loads(lines[-1]) if lines else {}
            if last.get("event") == "done":
                outcome = "done"
            elif last.get("event") == "error" and last.get("retryable"):
                outcome = "retryable_error"
            else:
                outcome = "bad"
            return {"outcome": outcome,
                    "latency_s": time.perf_counter() - t0}
        except Exception as e:
            return {"outcome": "exception", "error": repr(e),
                    "latency_s": time.perf_counter() - t0}

    def run_phase(phase: str, kill_at: int | None) -> dict:
        results: list = [None] * n_requests
        started = threading.Semaphore(0)
        work = list(range(n_requests))
        lock = threading.Lock()

        def client():
            while True:
                with lock:
                    if not work:
                        return
                    i = work.pop(0)
                started.release()
                results[i] = one_request(i, phase)

        killer = None
        if kill_at is not None:
            def kill_mid_load():
                for _ in range(kill_at):
                    started.acquire()
                fleet.replica(0).kill()     # hard mid-load kill

            killer = threading.Thread(target=kill_mid_load, daemon=True)
            killer.start()
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, daemon=True)
            for _ in range(cfg["concurrency"])
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
        lat = sorted(r["latency_s"] for r in results if r is not None)

        def pct(p):
            import math

            return (
                lat[min(len(lat) - 1, math.ceil(p / 100 * len(lat)) - 1)]
                if lat else None
            )

        outcomes = [r["outcome"] if r else "hang" for r in results]
        return {
            "requests": n_requests,
            "done": outcomes.count("done"),
            "retryable_errors": outcomes.count("retryable_error"),
            "rejected": outcomes.count("rejected"),
            "hung_or_bad": sum(
                1 for o in outcomes
                if o in ("bad", "exception", "hang")
            ),
            "availability": outcomes.count("done") / n_requests,
            "explicit_answer_rate": sum(
                1 for o in outcomes
                if o in ("done", "retryable_error", "rejected")
            ) / n_requests,
            "p50_s": pct(50),
            "p99_s": pct(99),
            "wall_s": round(wall, 3),
        }

    # warm both replicas' compile caches out of the timed phases
    for i in range(2):
        one_request(i, "warm")

    baseline = run_phase("base", kill_at=None)
    # replica 0 dies after a quarter of the chaos-phase requests have
    # started — early enough that most of the load runs against a
    # one-replica pool, late enough that requests are provably in flight
    chaos = run_phase("chaos", kill_at=max(1, n_requests // 4))

    # let the supervisor bring the pool back, then prove it recovered
    recovered = fleet.wait_ready(timeout=180, min_replicas=2)
    post = one_request(0, "post")

    stats = fleet.stats()
    httpd.shutdown()
    fleet.stop(drain=False)

    result = {
        "metric": (
            f"fleet quick bench (tiny LM, CPU, 2 replicas, "
            f"{n_requests} requests x {max_new} new tokens per phase, "
            f"replica 0 SIGKILLed mid-chaos-load)"
        ),
        "baseline": baseline,
        "chaos": chaos,
        "p99_delta": (
            round(chaos["p99_s"] / baseline["p99_s"], 3)
            if baseline["p99_s"] and chaos["p99_s"] else None
        ),
        "availability": chaos["availability"],
        "router": {
            "failovers": stats["router"]["failovers"],
            "rejected": stats["router"]["rejected"],
            "hedges": stats["router"]["hedges"],
        },
        "recovery": {
            "pool_recovered": recovered,
            "post_recovery_request": post["outcome"],
            "replica0_restarts_used": stats["replicas"][0]["restarts_used"],
        },
    }
    print(json.dumps(result))


def run_fleet(
    requests: int = 16,
    concurrency: int = 4,
    max_new: int = 24,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("PDT_TPU_FAULT", None)      # the bench kills by pid, not spec
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")
    cfg = dict(requests=requests, concurrency=concurrency, max_new=max_new)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--fleet-child", json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet bench failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# --------------------------------------------------------------- storm mode
# Overload-survival drill on CPU: a seeded OPEN-LOOP trace (Poisson base +
# one burst episode, heavy-tailed sizes, SLO tiers — serve/trace.py) is
# replayed against a 2-replica autoscaled fleet while replica 0 is
# SIGKILLed mid-burst. Unlike the closed-loop benches the offered load
# does not self-throttle, so the burst genuinely queues and the brownout
# ladder + autoscaler actually fire. Gates: interactive availability
# >= 0.99 (honest retries allowed — clients honor the Retry-After the
# server computes), zero hung waiters, >= 1 scale-up AND >= 1 drain-based
# scale-down with measured latencies, every shed explicit (429/503 +
# Retry-After), every accepted stream token-identical to an unloaded
# greedy reference pass, and every accepted request's spans merging into
# a complete trace tree across the coordinator + replica streams (zero
# orphans, phase sums reconciling). Runs in a JAX_PLATFORMS=cpu
# subprocess.


def _storm_prompt(prompt_len: int) -> str:
    """Deterministic prompt of exactly prompt_len tokens under the serve
    CLI's raw-byte fallback tokenizer (one token per byte), identical
    across the reference and storm passes so greedy streams are
    comparable."""
    return "".join(str((prompt_len + j) % 10) for j in range(prompt_len))


def _storm_child(cfg_json: str) -> None:
    import http.client
    import math
    import threading

    from pytorch_distributed_training_tpu.serve.autoscale import (
        AutoscaleConfig,
        Autoscaler,
    )
    from pytorch_distributed_training_tpu.serve.fleet import (
        FleetConfig,
        ServeFleet,
    )
    from pytorch_distributed_training_tpu.serve.router import (
        RouterConfig,
        make_router_http_server,
    )
    from pytorch_distributed_training_tpu.serve.trace import (
        TraceConfig,
        generate_trace,
        replay,
        trace_stats,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    cfg = json.loads(cfg_json)
    burst_start = cfg["burst_start_s"]
    burst_dur = cfg["burst_dur_s"]
    trace_cfg = TraceConfig(
        seed=cfg["seed"],
        duration_s=cfg["duration_s"],
        base_rate_rps=cfg["base_rps"],
        burst_rate_rps=cfg["burst_rps"],
        bursts=((burst_start, burst_dur),),
        interactive_fraction=0.7,
        # sizes chosen to fit the replicas' 16/32 prompt buckets and keep
        # the CPU run inside the bench budget while still heavy-tailed
        prompt_len_median=8.0, prompt_len_sigma=0.5,
        prompt_len_min=2, prompt_len_max=24,
        output_tokens_median=10.0, output_tokens_sigma=0.8,
        output_tokens_min=2, output_tokens_max=32,
        interactive_deadline_s=60.0, batch_deadline_s=120.0,
    )
    events = generate_trace(trace_cfg)

    registry = MetricsRegistry()
    sink = _ListSink()
    registry.attach_sink(sink)

    # per-replica JSONL streams: the span-coverage gate merges these with
    # the coordinator's records fleet-side (the sink flushes per emit, so
    # even the SIGKILLed replica's completed spans survive on disk)
    import tempfile

    metrics_dir = tempfile.mkdtemp(prefix="storm-metrics-")

    fleet = ServeFleet(
        FleetConfig(
            num_replicas=2,
            replica_args=(
                "--model", "gpt2-tiny", "--num-slots", "4",
                "--prompt-buckets", "16,32", "--max-new-tokens-cap", "64",
                "--queue-depth", "24",
                "--interactive-deadline-s", "60",
                "--batch-deadline-s", "120",
                "--brownout-high", "0.75", "--brownout-low", "0.25",
                "--brownout-clamp", "8",
            ),
            replica_extra_args={
                i: ("--metrics-dir", f"{metrics_dir}/replica-{i}",
                    "--replica-name", f"replica-{i}")
                for i in range(3)       # up to the autoscaler's ceiling
            },
            max_restarts=2,
            backoff_s=0.2,
            drain_timeout_s=20.0,
        ),
        RouterConfig(
            health_interval_s=0.05, breaker_threshold=3,
            breaker_cooldown_s=0.5, retry_backoff_s=0.02,
            retry_backoff_max_s=0.1, ttfb_timeout_s=120.0,
        ),
        registry=registry,
    ).start()
    assert fleet.wait_ready(timeout=180), fleet.stats()
    httpd = make_router_http_server(fleet.router)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    autoscaler = Autoscaler(
        fleet,
        AutoscaleConfig(
            min_replicas=1, max_replicas=3,
            scale_up_queue_depth=3.0, scale_down_queue_depth=0.5,
            page_occupancy_high=0.85,
            up_hold_s=0.4, down_hold_s=1.5,
            up_cooldown_s=3.0, down_cooldown_s=3.0,
            poll_interval_s=0.2,
        ),
        registry=registry,
    )

    def one_request(rid: str, prompt_len: int, max_new: int,
                    tier: str) -> dict:
        """One POST /generate through the router. Outcomes: ``done``
        (stream completed; ``tokens`` carries the greedy ids), ``shed``
        (explicit 4xx/5xx answer; records whether it was HONEST — allowed
        status + Retry-After header), ``retryable_error`` (stream started
        then died retryably, e.g. the SIGKILLed replica) or
        ``exception``."""
        t0 = time.perf_counter()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
            conn.request(
                "POST", "/generate",
                body=json.dumps({
                    "prompt": _storm_prompt(prompt_len),
                    "max_new_tokens": max_new,
                    "tier": tier,
                }),
                headers={"X-Request-Id": rid},
            )
            resp = conn.getresponse()
            if resp.status != 200:
                retry_after = resp.getheader("Retry-After")
                resp.read()
                conn.close()
                return {
                    "outcome": "shed",
                    "status": resp.status,
                    "honest": (
                        resp.status in (429, 503)
                        and retry_after is not None
                    ),
                    "retry_after_s": float(retry_after or 1.0),
                    "latency_s": time.perf_counter() - t0,
                }
            lines = resp.read().decode().splitlines()
            conn.close()
            parsed = [json.loads(ln) for ln in lines if ln.strip()]
            last = parsed[-1] if parsed else {}
            if last.get("event") == "done":
                return {
                    "outcome": "done",
                    "tokens": [
                        ev["token_id"] for ev in parsed
                        if ev.get("event") == "token"
                    ],
                    "latency_s": time.perf_counter() - t0,
                }
            if last.get("event") == "error" and last.get("retryable"):
                return {"outcome": "retryable_error",
                        "latency_s": time.perf_counter() - t0}
            return {"outcome": "bad", "last": last,
                    "latency_s": time.perf_counter() - t0}
        except Exception as e:
            return {"outcome": "exception", "error": repr(e),
                    "latency_s": time.perf_counter() - t0}

    # ---- unloaded reference pass: one greedy stream per distinct prompt
    # length at the full output cap; the storm's accepted streams must be
    # exact prefixes of these (greedy + identical weights across replicas).
    # Doubles as the compile-cache warmup for both prompt buckets.
    ref_max_new = {}
    for ev in events:
        ref_max_new[ev.prompt_len] = max(
            ref_max_new.get(ev.prompt_len, 0), ev.max_new_tokens
        )
    reference = {}
    for plen, max_new in sorted(ref_max_new.items()):
        out = one_request(f"ref-{plen}", plen, max_new, "interactive")
        if out["outcome"] != "done":
            raise RuntimeError(f"reference pass failed for len={plen}: {out}")
        reference[plen] = out["tokens"]

    # ---- the storm: open-loop replay + mid-burst SIGKILL + autoscaler
    autoscaler.start()
    results: list = [None] * len(events)
    threads: list = []
    kill_at_s = burst_start + cfg["kill_offset_s"]
    kill_info = {"fired_t_s": None}

    def client(ev) -> None:
        t0 = time.perf_counter()
        attempts = []
        # interactive clients retry honest retryable answers (honoring the
        # server's Retry-After, capped so the bench terminates); batch
        # traffic takes its shed and leaves — exactly the SLO contract
        budget = 8 if ev.tier == "interactive" else 1
        for attempt in range(budget):
            out = one_request(
                f"storm-{ev.index}-{attempt}", ev.prompt_len,
                ev.max_new_tokens, ev.tier,
            )
            attempts.append(out)
            if out["outcome"] == "done" or (
                out["outcome"] == "shed" and not out["honest"]
            ):
                break
            if attempt + 1 < budget:
                time.sleep(min(out.get("retry_after_s", 0.5), 4.0))
        results[ev.index] = {
            "tier": ev.tier,
            "prompt_len": ev.prompt_len,
            "burst": ev.burst,
            "attempts": attempts,
            "final": attempts[-1]["outcome"],
            "tokens": attempts[-1].get("tokens"),
            "total_s": time.perf_counter() - t0,
        }

    def killer() -> None:
        time.sleep(kill_at_s)
        kill_info["fired_t_s"] = kill_at_s
        fleet.replica(0).kill()     # hard SIGKILL mid-burst

    threading.Thread(target=killer, daemon=True).start()

    def fire(ev) -> None:
        t = threading.Thread(target=client, args=(ev,), daemon=True)
        t.start()
        threads.append(t)

    replayed = replay(events, fire)

    hung = 0
    for t in threads:
        t.join(_BENCH_WAIT_S)
        if t.is_alive():
            hung += 1
    hung += sum(1 for r in results if r is None)

    # ---- quiet tail: the pool drains, the autoscaler's idle signal holds
    # and retires the storm capacity through the graceful exit-75 path
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        st = autoscaler.stats()
        down_done = any(
            r.get("record") == "fleet_scale" and r.get("action") == "down"
            and r.get("drain_s") is not None
            for r in sink.records
        )
        up_ready = st["scale_ups"] == 0 or any(
            r.get("record") == "autoscale_ready" for r in sink.records
        )
        if st["scale_downs"] >= 1 and down_done and up_ready:
            break
        time.sleep(0.25)

    # recovery: brownout must fall back to level 0 on every live replica
    brownout_zero = False
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        views = [r for r in fleet.router.replicas if r.available()]
        if views and all(
            int(v.health.get("brownout_level", 0)) == 0 for v in views
        ):
            brownout_zero = True
            break
        time.sleep(0.25)
    post = one_request("post-recovery", 8, 16, "interactive")

    auto_stats = autoscaler.stats()
    fleet_stats = fleet.stats()
    autoscaler.close()
    httpd.shutdown()
    fleet.stop(drain=False)

    # ---- fleet-side span merge: coordinator records (router spans) +
    # every replica's on-disk stream; every ACCEPTED request (final
    # attempt ended "done") must merge into a complete trace tree
    import glob as _glob

    merged_records = list(sink.records)
    for path in sorted(_glob.glob(
        os.path.join(metrics_dir, "replica-*", "metrics.jsonl")
    )):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    merged_records.append(json.loads(line))
                except json.JSONDecodeError:
                    pass        # torn final line from the SIGKILL
    accepted_rids = [
        f"storm-{i}-{len(r['attempts']) - 1}"
        for i, r in enumerate(results)
        if r is not None and r["final"] == "done"
    ]
    from pytorch_distributed_training_tpu.telemetry.spans import (
        trace_coverage,
    )

    span_coverage = trace_coverage(
        merged_records, accepted_ids=accepted_rids
    )

    # ---- gates
    def pct(lat: list, p: float):
        lat = sorted(lat)
        return (
            round(lat[min(len(lat) - 1, math.ceil(p / 100 * len(lat)) - 1)],
                  4)
            if lat else None
        )

    def tier_summary(tier: str) -> dict:
        rows = [r for r in results if r is not None and r["tier"] == tier]
        done = [r for r in rows if r["final"] == "done"]
        shed = [r for r in rows if r["final"] == "shed"]
        lat = [r["total_s"] for r in done]
        return {
            "requests": len(rows),
            "done": len(done),
            "shed": len(shed),
            "other": len(rows) - len(done) - len(shed),
            "availability": (
                round(len(done) / len(rows), 4) if rows else None
            ),
            "p50_s": pct(lat, 50),
            "p95_s": pct(lat, 95),
            "p99_s": pct(lat, 99),
        }

    sheds = [
        a for r in results if r is not None
        for a in r["attempts"] if a["outcome"] == "shed"
    ]
    dishonest_sheds = sum(1 for s in sheds if not s["honest"])

    mismatches = []
    checked = 0
    for r in results:
        if r is None or r["final"] != "done":
            continue
        checked += 1
        ref = reference[r["prompt_len"]]
        got = r["tokens"]
        if len(got) > len(ref) or got != ref[:len(got)]:
            mismatches.append({
                "prompt_len": r["prompt_len"],
                "got": got[:8], "ref": ref[:8],
            })

    ready_s = [
        r["ready_s"] for r in sink.records
        if r.get("record") == "autoscale_ready"
    ]
    drain_s = [
        r["drain_s"] for r in sink.records
        if r.get("record") == "fleet_scale" and r.get("action") == "down"
        and r.get("drain_s") is not None
    ]

    interactive = tier_summary("interactive")
    batch = tier_summary("batch")
    gates = {
        "interactive_availability_ok": (
            interactive["availability"] is not None
            and interactive["availability"] >= 0.99
        ),
        "zero_hung_waiters": hung == 0,
        "scale_up_recorded": auto_stats["scale_ups"] >= 1 and bool(ready_s),
        "scale_down_recorded": (
            auto_stats["scale_downs"] >= 1 and bool(drain_s)
        ),
        "sheds_all_explicit": dishonest_sheds == 0,
        "token_identity_ok": not mismatches,
        "recovered": brownout_zero and post["outcome"] == "done",
        "span_coverage_ok": (
            span_coverage["coverage"] == 1.0
            and span_coverage["orphan_spans"] == 0
            and not span_coverage["phase_sum_bad"]
        ),
    }
    result = {
        "metric": (
            f"storm bench (tiny LM, CPU, seeded open-loop replay: "
            f"{len(events)} requests over {trace_cfg.duration_s:.0f}s, "
            f"burst {cfg['burst_rps']}rps@{burst_start:.0f}s, replica 0 "
            f"SIGKILLed mid-burst, autoscaled 2->3->drain)"
        ),
        "trace": {"seed": trace_cfg.seed, **trace_stats(events)},
        "replay": replayed,
        "interactive": interactive,
        "batch": batch,
        "sheds": {
            "total": len(sheds),
            "dishonest": dishonest_sheds,
            "by_status": {
                str(s): sum(1 for x in sheds if x["status"] == s)
                for s in sorted({x["status"] for x in sheds})
            },
        },
        "hung_waiters": hung,
        "token_identity": {
            "streams_checked": checked,
            "mismatches": mismatches[:5],
        },
        "autoscale": {
            "scale_ups": auto_stats["scale_ups"],
            "scale_downs": auto_stats["scale_downs"],
            "scale_up_ready_s": [round(s, 3) for s in ready_s],
            "scale_down_drain_s": [round(s, 3) for s in drain_s],
        },
        "kill": {
            "replica": "r0",
            "at_s": kill_info["fired_t_s"],
            "restarts_used": next(
                (r["restarts_used"] for r in fleet_stats["replicas"]
                 if r["replica"] == "r0"), None,
            ),
        },
        "recovery": {
            "brownout_returned_to_zero": brownout_zero,
            "post_storm_request": post["outcome"],
        },
        "spans": {
            "accepted": len(accepted_rids),
            "traces": span_coverage["traces"],
            "coverage": span_coverage["coverage"],
            "orphan_spans": span_coverage["orphan_spans"],
            "incomplete": span_coverage["incomplete"][:5],
            "phase_sum_bad": span_coverage["phase_sum_bad"][:5],
        },
        "pool": fleet_stats["pool"],
        "gates": gates,
        "ok": all(gates.values()),
    }
    print(json.dumps(result))


def run_storm(
    seed: int = 0,
    duration_s: float = 14.0,
    base_rps: float = 2.0,
    burst_rps: float = 10.0,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("PDT_TPU_FAULT", None)      # the bench kills by pid, not spec
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")
    cfg = dict(
        seed=seed, duration_s=duration_s, base_rps=base_rps,
        burst_rps=burst_rps, burst_start_s=4.0,
        burst_dur_s=max(2.0, duration_s / 4), kill_offset_s=1.0,
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--storm-child", json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"storm bench failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# ---------------------------------------------------------------- swap mode
# Latency-under-rollout drill on CPU: a 2-replica fleet serves a closed
# loop while a NEW checkpoint step is published mid-load and rolled across
# the pool one replica at a time (serve/hotswap.py). Reports the p99 delta
# the rollout window costs vs the healthy baseline, the publish->converged
# time (both replicas and the router's skew view on the new step), and
# that zero requests failed. Runs in a JAX_PLATFORMS=cpu subprocess;
# driven by the `perf`+`swap`-marked pytest, kept out of tier-1 timing.


def _swap_child(cfg_json: str) -> None:
    import http.client
    import threading

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.serve.fleet import (
        FleetConfig,
        ServeFleet,
    )
    from pytorch_distributed_training_tpu.serve.hotswap import (
        publish_params_checkpoint,
    )
    from pytorch_distributed_training_tpu.serve.router import (
        RouterConfig,
        make_router_http_server,
    )
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = json.loads(cfg_json)
    n_requests = cfg["requests"]
    max_new = cfg["max_new"]

    mcfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(mcfg)

    def params_for(seed: int):
        return model.init(
            jax.random.key(seed), jnp.ones((1, 8), jnp.int32)
        )["params"]

    import tempfile

    ckpt_dir = tempfile.mkdtemp(prefix="bench_swap_ckpt_")
    publish_params_checkpoint(ckpt_dir, 1, params_for(0))
    # the step-2 weights are built BEFORE any timed phase: the publisher
    # thread must only write bytes mid-load, not trace/compile a model
    # init while the client threads fight it for the GIL
    params_v2 = params_for(7)

    fleet = ServeFleet(
        FleetConfig(
            num_replicas=2,
            replica_args=(
                "--model", "gpt2-tiny", "--num-slots", "2",
                "--prompt-buckets", "16,32", "--max-new-tokens-cap", "64",
                "--queue-depth", "16", "--checkpoint-dir", ckpt_dir,
            ),
            max_restarts=1,
            backoff_s=0.2,
            drain_timeout_s=15.0,
        ),
        RouterConfig(
            health_interval_s=0.05, breaker_threshold=3,
            breaker_cooldown_s=0.5, retry_backoff_s=0.02,
            retry_backoff_max_s=0.1, ttfb_timeout_s=120.0,
        ),
    ).start()
    assert fleet.wait_ready(timeout=180), fleet.stats()
    fleet.enable_hotswap(ckpt_dir, poll_interval_s=0.1)
    httpd = make_router_http_server(fleet.router)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def one_request(i: int, phase: str) -> dict:
        t0 = time.perf_counter()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
            conn.request(
                "POST", "/generate",
                body=json.dumps({
                    "prompt": f"{phase} request {i}",
                    "max_new_tokens": max_new,
                }),
                headers={"X-Request-Id": f"{phase}-{i}"},
            )
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                conn.close()
                return {"outcome": "rejected",
                        "latency_s": time.perf_counter() - t0}
            lines = resp.read().decode().splitlines()
            conn.close()
            last = json.loads(lines[-1]) if lines else {}
            outcome = "done" if last.get("event") == "done" else "bad"
            return {"outcome": outcome,
                    "latency_s": time.perf_counter() - t0}
        except Exception as e:
            return {"outcome": "exception", "error": repr(e),
                    "latency_s": time.perf_counter() - t0}

    def run_phase(phase: str, publish_at: int | None) -> dict:
        results: list = [None] * n_requests
        started = threading.Semaphore(0)
        work = list(range(n_requests))
        lock = threading.Lock()
        publish_t = [None]

        def client():
            while True:
                with lock:
                    if not work:
                        return
                    i = work.pop(0)
                started.release()
                results[i] = one_request(i, phase)

        publisher = None
        if publish_at is not None:
            def publish_mid_load():
                for _ in range(publish_at):
                    started.acquire()
                publish_params_checkpoint(ckpt_dir, 2, params_v2)
                publish_t[0] = time.perf_counter()

            publisher = threading.Thread(target=publish_mid_load,
                                         daemon=True)
            publisher.start()
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, daemon=True)
            for _ in range(cfg["concurrency"])
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        wall = time.perf_counter() - t0
        if publisher is not None:
            # the closed loop can finish before the publish lands (CPU
            # requests are fast); the convergence clock still needs the
            # real publish timestamp
            publisher.join(120)
        lat = sorted(r["latency_s"] for r in results if r is not None)

        def pct(p):
            import math

            return (
                lat[min(len(lat) - 1, math.ceil(p / 100 * len(lat)) - 1)]
                if lat else None
            )

        outcomes = [r["outcome"] if r else "hang" for r in results]
        return {
            "requests": n_requests,
            "done": outcomes.count("done"),
            "failed": sum(
                1 for o in outcomes if o not in ("done", "rejected")
            ),
            "rejected": outcomes.count("rejected"),
            "p50_s": pct(50),
            "p99_s": pct(99),
            "wall_s": round(wall, 3),
            "publish_t": publish_t[0],
        }

    # warm both replicas' compile caches out of the timed phases (two
    # rounds: the second lands on warm programs on BOTH replicas, so the
    # baseline phase measures steady state, not residual compiles)
    for i in range(4):
        one_request(i, "warm")

    # baseline runs twice and the p99 denominator averages the passes:
    # p99 over 16 requests IS the worst sample, so a single pass is one
    # host hiccup away from either masking or inventing rollout cost
    base_passes = [run_phase(f"base{i}", publish_at=None) for i in range(2)]
    baseline = dict(base_passes[0])
    baseline["p99_s"] = sum(p["p99_s"] for p in base_passes) / 2
    baseline["p50_s"] = sum(p["p50_s"] for p in base_passes) / 2
    baseline["done"] = min(p["done"] for p in base_passes)
    baseline["failed"] = sum(p["failed"] for p in base_passes)
    # step 2 publishes after a quarter of the swap-phase requests started:
    # the rollout window overlaps the measured load
    swap = run_phase("swap", publish_at=max(1, n_requests // 4))

    # convergence: both replicas serving step 2 AND the router's skew is 0
    def converged() -> bool:
        stats = fleet.router.stats()
        return (
            all(v == 2 for v in stats["weights"].values())
            and stats["version_skew"] == 0
        )

    deadline = time.perf_counter() + 120
    while time.perf_counter() < deadline and not converged():
        time.sleep(0.05)
    convergence_s = (
        time.perf_counter() - swap["publish_t"]
        if swap["publish_t"] is not None and converged() else None
    )
    post = one_request(0, "post")

    stats = fleet.stats()
    httpd.shutdown()
    fleet.stop(drain=False)

    result = {
        "metric": (
            f"hot-swap quick bench (tiny LM, CPU, 2 replicas, "
            f"{n_requests} requests x {max_new} new tokens per phase, "
            f"checkpoint step 2 published + rolled out mid-swap-load)"
        ),
        "baseline": {k: v for k, v in baseline.items() if k != "publish_t"},
        "swap": {k: v for k, v in swap.items() if k != "publish_t"},
        "p99_delta": (
            round(swap["p99_s"] / baseline["p99_s"], 3)
            if baseline["p99_s"] and swap["p99_s"] else None
        ),
        "failed_requests": baseline["failed"] + swap["failed"],
        "convergence_s": (
            round(convergence_s, 3) if convergence_s is not None else None
        ),
        "converged": converged(),
        "post_rollout_request": post["outcome"],
        "weights": stats["router"]["weights"],
        "version_skew": stats["router"]["version_skew"],
        "hotswap": stats.get("hotswap"),
        "replica_restarts": [
            r["restarts_used"] for r in stats["replicas"]
        ],
    }
    print(json.dumps(result))


def run_swap(
    requests: int = 16,
    concurrency: int = 4,
    max_new: int = 48,
    out_path: str | None = None,
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("PDT_TPU_FAULT", None)      # the bench publishes real steps
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")
    cfg = dict(requests=requests, concurrency=concurrency, max_new=max_new)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--swap-child", json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"swap bench failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


# --------------------------------------------------------------- quick mode
# Input-pipeline A/B on CPU: prefetch-off vs prefetch-on through the REAL
# Trainer (tiny synthetic task), plus a cold->warm compile-cache pair,
# producing one comparison JSON. Each variant runs in its own subprocess
# under JAX_PLATFORMS=cpu so the parent never initializes a backend and the
# warm run exercises a true fresh-process cache load (the actual warm-start
# story). Driven by the `perf`-marked pytest (tests/test_perf_pipeline.py),
# kept out of tier-1 timing noise.


def _quick_child(cfg_json: str) -> None:
    """One quick-mode variant: tiny synthetic Trainer run, telemetry on."""
    cfg = json.loads(cfg_json)
    from pytorch_distributed_training_tpu.parallel import ShardingPolicy
    from pytorch_distributed_training_tpu.train.loop import Trainer
    from pytorch_distributed_training_tpu.utils.config import (
        MeshConfig,
        TrainConfig,
        model_preset,
    )

    gb = cfg["global_batch"]
    mcfg = model_preset("tiny", compute_dtype="float32")
    tcfg = TrainConfig(
        num_epochs=1,
        global_batch_size=gb,
        micro_batch_size=gb // 2,
        eval_batch_size=gb,
        train_size=gb * cfg["steps"],
        eval_size=gb,
        warmup_steps=4,
        log_every=0,
        bf16=False,
        prefetch_depth=cfg["prefetch_depth"],
        metrics_dir=cfg["metrics_dir"],
    )
    Trainer(
        mcfg, tcfg, MeshConfig(), ShardingPolicy(), task="synthetic"
    ).run()


def _quick_stats(metrics_dir: str) -> dict:
    """Fold one variant's stream: steady-state data wait + compile record."""
    records = []
    with open(os.path.join(metrics_dir, "metrics.jsonl")) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    steps = [r for r in records if r.get("record") == "step"]
    # steady state: drop the pipeline-fill first step
    steady = steps[1:] if len(steps) > 1 else steps
    waits = [s["data_wait_s"] for s in steady]
    occs = [s["prefetch_occupancy"] for s in steady
            if "prefetch_occupancy" in s]
    compile_rec = next(
        (r for r in records if r.get("record") == "compile"), None
    )
    comm = [r for r in records if r.get("record") == "comm_audit"]
    return {
        "steps": len(steps),
        "steady_steps": len(steady),
        "data_wait_mean_s": sum(waits) / len(waits) if waits else None,
        "data_wait_total_s": sum(waits),
        "prefetch_occupancy_mean": sum(occs) / len(occs) if occs else None,
        "compile_s": compile_rec.get("compile_s") if compile_rec else None,
        "cache_hit": compile_rec.get("cache_hit") if compile_rec else None,
        "compile_inclusive_steps": sum(
            1 for s in steps if s.get("compile_inclusive")
        ),
        "comm_audit": {
            "audits": len(comm),
            "ok": all(r.get("ok") is not False for r in comm),
            "collectives": sum(r.get("count", 0) for r in comm),
            "total_bytes": sum(r.get("total_bytes", 0) for r in comm),
        },
    }


def run_quick(steps: int = 24, global_batch: int = 64,
              out_path: str | None = None) -> dict:
    import tempfile

    work = tempfile.mkdtemp(prefix="bench_quick_")
    variants = {
        "prefetch_off": dict(prefetch_depth=0),
        "prefetch_on": dict(prefetch_depth=2),
    }
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single CPU device, no forced SPMD mesh
    # the cold->warm pair needs a cache that starts empty: place it from
    # outside, the way a deployment would (train/compile.py sets no path
    # of its own when the environment names one)
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "compile_cache")
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("HF_DATASETS_OFFLINE", "1")
    stats = {}
    for name, extra in variants.items():
        mdir = os.path.join(work, name)
        cfg = dict(
            steps=steps, global_batch=global_batch, metrics_dir=mdir,
            **extra,
        )
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--quick-child", json.dumps(cfg)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"quick variant {name!r} failed (rc={proc.returncode}):\n"
                f"{proc.stderr[-2000:]}"
            )
        stats[name] = _quick_stats(mdir)
    off, on = stats["prefetch_off"], stats["prefetch_on"]
    result = {
        "metric": (
            f"input-pipeline quick bench (tiny synthetic, CPU, "
            f"{steps} steps x batch {global_batch})"
        ),
        "prefetch_off": off,
        "prefetch_on": on,
        "data_wait_reduction_s": (
            off["data_wait_mean_s"] - on["data_wait_mean_s"]
            if off["data_wait_mean_s"] is not None
            and on["data_wait_mean_s"] is not None
            else None
        ),
        "warm_start": {
            # run 1 compiled cold, run 2 (same jit keys, new process) warm
            "cold_compile_s": off["compile_s"],
            "warm_compile_s": on["compile_s"],
            "cache_hit_second_run": on["cache_hit"],
        },
        "comm_audit": {
            # warm-start manifest audit per variant: a single-CPU-device
            # quick run must stay collective-free end to end
            "audits": off["comm_audit"]["audits"] + on["comm_audit"]["audits"],
            "ok": off["comm_audit"]["ok"] and on["comm_audit"]["ok"],
            "collectives": (
                off["comm_audit"]["collectives"]
                + on["comm_audit"]["collectives"]
            ),
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--model", default="bert-large-cased")
    p.add_argument("--global-batch-size", type=int, default=96)
    p.add_argument("--micro-batch-size", type=int, default=24)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--warmup-steps", type=int, default=5)
    p.add_argument("--timed-steps", type=int, default=30)
    p.add_argument("--chain-steps", type=int, default=1,
                   help="optimizer steps fused per dispatch (1 = per-step)")
    p.add_argument("--matmul-impl", default="default",
                   choices=("default", "native", "int8", "int8_full"),
                   help="dense-matmul path (ops/quant.py). default = "
                        "int8_full for the convergence-gated bert-large "
                        "recipe, native elsewhere; picking int8 explicitly "
                        "for an ungated recipe is on the caller")
    p.add_argument("--quant-delayed", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="delayed (previous-microbatch) int8 activation "
                        "scaling — removes the per-site absmax "
                        "serialization (ops/quant.py). Default: on for "
                        "int8 impls (multi-seed convergence-gated), "
                        "meaningless otherwise")
    p.add_argument("--quant-delayed-grads",
                   action=argparse.BooleanOptionalAction, default=False,
                   help="A/B knob, NOT a gated default: delayed dy scaling "
                        "in the backward (ops/quant.py sink-gradient "
                        "channel); requires delayed int8_full")
    p.add_argument("--quick", action="store_true",
                   help="input-pipeline A/B on CPU: prefetch off vs on "
                        "through the real Trainer + cold->warm compile-"
                        "cache pair; writes a comparison JSON (no TPU, "
                        "no probe)")
    p.add_argument("--quick-steps", type=int, default=24)
    p.add_argument("--quick-batch", type=int, default=64)
    p.add_argument("--quick-out", default=None,
                   help="where --quick writes its comparison JSON "
                        "(default: print only)")
    p.add_argument("--quick-child", default=None, help=argparse.SUPPRESS)
    p.add_argument("--serve", action="store_true",
                   help="closed-loop serving bench on CPU: the continuous-"
                        "batching engine (serve/) vs sequential one-shot "
                        "generate() over the same prompt mix; writes a "
                        "throughput+latency-percentile JSON (no TPU, no "
                        "probe)")
    p.add_argument("--serve-requests", type=int, default=16)
    p.add_argument("--serve-concurrency", type=int, default=6,
                   help="closed-loop client threads")
    p.add_argument("--serve-slots", type=int, default=4,
                   help="engine decode slots")
    p.add_argument("--serve-max-new", type=int, default=16)
    p.add_argument("--serve-prompt-mix", default="6,10,14",
                   help="comma-separated prompt lengths, cycled across "
                        "requests")
    p.add_argument("--serve-queue-depth", type=int, default=4,
                   help="admission-queue depth (below concurrency so the "
                        "backpressure path is exercised)")
    p.add_argument("--serve-out", default="BENCH_serve.json",
                   help="where --serve writes its JSON")
    p.add_argument("--serve-child", default=None, help=argparse.SUPPRESS)
    p.add_argument("--paged", action="store_true",
                   help="paged-KV + device-sampling A/B on CPU: dense+host "
                        "vs paged+device on a uniform workload, plus "
                        "paged+device on a mixed 1x-8x prompt-length "
                        "workload whose page pool is smaller than the "
                        "dense layout could even configure; writes "
                        "BENCH_paged.json (no TPU, no probe)")
    p.add_argument("--paged-requests", type=int, default=16)
    p.add_argument("--paged-concurrency", type=int, default=6,
                   help="closed-loop client threads")
    p.add_argument("--paged-slots", type=int, default=4,
                   help="engine decode slots")
    p.add_argument("--paged-max-new", type=int, default=16)
    p.add_argument("--paged-page-size", type=int, default=8,
                   help="tokens per KV page")
    p.add_argument("--paged-queue-depth", type=int, default=4)
    p.add_argument("--paged-out", default="BENCH_paged.json",
                   help="where --paged writes its JSON")
    p.add_argument("--paged-child", default=None, help=argparse.SUPPRESS)
    p.add_argument("--spec", action="store_true",
                   help="speculative-decoding + chunked-prefill A/B on "
                        "CPU: baseline vs spec vs chunked vs both, all "
                        "paged+device+greedy on a mixed prompt mix; "
                        "asserts token-identical streams and reports the "
                        "TPOT speedup; writes BENCH_spec.json (no TPU, "
                        "no probe)")
    p.add_argument("--spec-requests", type=int, default=16)
    p.add_argument("--spec-concurrency", type=int, default=6,
                   help="closed-loop client threads")
    p.add_argument("--spec-slots", type=int, default=4,
                   help="engine decode slots")
    p.add_argument("--spec-max-new", type=int, default=32,
                   help="tokens per request; long enough that decode "
                        "dispatches (what speculation amortises) dominate "
                        "each request's TPOT window over its one-off "
                        "prefill share")
    p.add_argument("--spec-k", type=int, default=7,
                   help="draft tokens per slot per verify dispatch")
    p.add_argument("--spec-prefill-chunk", type=int, default=8,
                   help="prompt tokens streamed per chunked-prefill tick")
    p.add_argument("--spec-page-size", type=int, default=8,
                   help="tokens per KV page")
    p.add_argument("--spec-queue-depth", type=int, default=4)
    p.add_argument("--spec-out", default="BENCH_spec.json",
                   help="where --spec writes its JSON")
    p.add_argument("--prefix", action="store_true",
                   help="shared-KV prefix cache A/B on CPU: the identical "
                        "multi-tenant shared-system-prompt workload "
                        "through a cold engine (prefix_cache off) and a "
                        "cached engine; asserts bit-identical streams via "
                        "digests and reports the prefill-token reduction, "
                        "TTFT speedup and hit rate; writes "
                        "BENCH_prefix.json (no TPU, no probe)")
    p.add_argument("--prefix-requests", type=int, default=32)
    p.add_argument("--prefix-concurrency", type=int, default=6,
                   help="closed-loop client threads")
    p.add_argument("--prefix-slots", type=int, default=4,
                   help="engine decode slots")
    p.add_argument("--prefix-max-new", type=int, default=16)
    p.add_argument("--prefix-tenants", type=int, default=4,
                   help="tenants, each with its own shared system prefix")
    p.add_argument("--prefix-shared-len", type=int, default=96,
                   help="tokens in each tenant's shared prefix")
    p.add_argument("--prefix-page-size", type=int, default=8,
                   help="tokens per KV page")
    p.add_argument("--prefix-queue-depth", type=int, default=6)
    p.add_argument("--prefix-tenant-quota", type=float, default=0.0,
                   help="tenant_page_quota for the cached variant "
                        "(0 = off)")
    p.add_argument("--prefix-out", default="BENCH_prefix.json",
                   help="where --prefix writes its JSON")
    p.add_argument("--tp", action="store_true",
                   help="tensor-parallel serving A/B on CPU: tp=1 vs tp=N "
                        "engines (and both again with speculation) on a "
                        "forced-multi-device host mesh, same greedy prompt "
                        "mix; asserts token-identical streams + a clean "
                        "per-tick comm audit against serve_tp_manifest; "
                        "writes BENCH_tp.json (no TPU, no probe)")
    p.add_argument("--tp-n", type=int, default=2,
                   help="tensor-parallel width for the sharded variants")
    p.add_argument("--tp-requests", type=int, default=16)
    p.add_argument("--tp-concurrency", type=int, default=6,
                   help="closed-loop client threads")
    p.add_argument("--tp-slots", type=int, default=4,
                   help="engine decode slots")
    p.add_argument("--tp-max-new", type=int, default=32)
    p.add_argument("--tp-spec-k", type=int, default=7,
                   help="draft tokens per slot in the speculative variants")
    p.add_argument("--tp-page-size", type=int, default=8,
                   help="tokens per KV page")
    p.add_argument("--tp-queue-depth", type=int, default=4)
    p.add_argument("--tp-out", default="BENCH_tp.json",
                   help="where --tp writes its JSON")
    p.add_argument("--int8", action="store_true",
                   help="quantized-serving matrix on CPU: fp32 vs weight-"
                        "only-int8 vs weight+KV-int8 engines (and full "
                        "int8 with speculation) under the same greedy "
                        "load; asserts weight-only token identity on the "
                        "snapped grid, ~0.27x resident projection-weight "
                        "bytes, and >=1.9x concurrent contexts from a "
                        "pool-bytes-matched int8 KV pool; writes "
                        "BENCH_int8.json (no TPU, no probe)")
    p.add_argument("--int8-requests", type=int, default=16)
    p.add_argument("--int8-concurrency", type=int, default=6,
                   help="closed-loop client threads")
    p.add_argument("--int8-slots", type=int, default=4,
                   help="engine decode slots (capacity variant serves 2x)")
    p.add_argument("--int8-max-new", type=int, default=32)
    p.add_argument("--int8-spec-k", type=int, default=7,
                   help="draft tokens per slot in the speculative variant")
    p.add_argument("--int8-page-size", type=int, default=8,
                   help="tokens per KV page")
    p.add_argument("--int8-queue-depth", type=int, default=4)
    p.add_argument("--int8-out", default="BENCH_int8.json",
                   help="where --int8 writes its JSON")
    p.add_argument("--fleet", action="store_true",
                   help="fleet resilience bench on CPU: 2 supervised "
                        "replicas behind the router, one SIGKILLed "
                        "mid-load; reports availability + the p99 latency "
                        "delta vs the healthy baseline (no TPU, no probe)")
    p.add_argument("--fleet-requests", type=int, default=16,
                   help="closed-loop requests per phase")
    p.add_argument("--fleet-concurrency", type=int, default=4,
                   help="closed-loop client threads")
    p.add_argument("--fleet-max-new", type=int, default=24)
    p.add_argument("--fleet-out", default="BENCH_fleet.json",
                   help="where --fleet writes its JSON")
    p.add_argument("--fleet-child", default=None, help=argparse.SUPPRESS)
    p.add_argument("--storm", action="store_true",
                   help="overload-survival bench on CPU: a seeded open-"
                        "loop trace (Poisson base + burst, SLO tiers) "
                        "replayed against an autoscaled fleet with one "
                        "replica SIGKILLed mid-burst; gates interactive "
                        "availability, explicit sheds, scale-up/down "
                        "latencies and token identity vs an unloaded run "
                        "(no TPU, no probe)")
    p.add_argument("--storm-seed", type=int, default=0,
                   help="trace seed (same seed -> identical storm)")
    p.add_argument("--storm-duration-s", type=float, default=14.0)
    p.add_argument("--storm-base-rps", type=float, default=2.0)
    p.add_argument("--storm-burst-rps", type=float, default=10.0,
                   help="arrival rate inside the burst episode")
    p.add_argument("--storm-out", default="BENCH_storm.json",
                   help="where --storm writes its JSON")
    p.add_argument("--storm-child", default=None, help=argparse.SUPPRESS)
    p.add_argument("--swap", action="store_true",
                   help="hot-swap rollout bench on CPU: 2 replicas behind "
                        "the router, a new checkpoint step published and "
                        "rolled across the pool mid-load; reports the p99 "
                        "delta during the rollout window, publish-to-"
                        "convergence time and zero failed requests (no "
                        "TPU, no probe)")
    p.add_argument("--swap-requests", type=int, default=16,
                   help="closed-loop requests per phase")
    p.add_argument("--swap-concurrency", type=int, default=4,
                   help="closed-loop client threads")
    p.add_argument("--swap-max-new", type=int, default=48,
                   help="tokens per request; long enough that a request "
                        "is not dwarfed by the (constant, ~tens of ms on "
                        "the tiny model) per-replica restore window, "
                        "matching real serving where requests are long "
                        "relative to a swap")
    p.add_argument("--swap-out", default="BENCH_swap.json",
                   help="where --swap writes its JSON")
    p.add_argument("--swap-child", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.quick_child:
        _quick_child(args.quick_child)
        return {"quick_child": True}
    if args.serve_child:
        _serve_child(args.serve_child)
        return {"serve_child": True}
    if args.paged_child:
        _paged_child(args.paged_child)
        return {"paged_child": True}
    if args.paged:
        result = run_paged(
            requests=args.paged_requests,
            concurrency=args.paged_concurrency,
            slots=args.paged_slots,
            max_new=args.paged_max_new,
            page_size=args.paged_page_size,
            queue_depth=args.paged_queue_depth,
            out_path=args.paged_out,
        )
        print(json.dumps(result))
        return result
    if args.spec:
        result = run_spec(
            requests=args.spec_requests,
            concurrency=args.spec_concurrency,
            slots=args.spec_slots,
            max_new=args.spec_max_new,
            spec_k=args.spec_k,
            prefill_chunk=args.spec_prefill_chunk,
            page_size=args.spec_page_size,
            queue_depth=args.spec_queue_depth,
            out_path=args.spec_out,
        )
        print(json.dumps(result))
        return result
    if args.prefix:
        result = run_prefix(
            requests=args.prefix_requests,
            concurrency=args.prefix_concurrency,
            slots=args.prefix_slots,
            max_new=args.prefix_max_new,
            tenants=args.prefix_tenants,
            shared_prefix_len=args.prefix_shared_len,
            page_size=args.prefix_page_size,
            queue_depth=args.prefix_queue_depth,
            tenant_page_quota=args.prefix_tenant_quota,
            out_path=args.prefix_out,
        )
        print(json.dumps(result))
        return result
    if args.tp:
        result = run_tp(
            requests=args.tp_requests,
            concurrency=args.tp_concurrency,
            slots=args.tp_slots,
            max_new=args.tp_max_new,
            tp=args.tp_n,
            spec_k=args.tp_spec_k,
            page_size=args.tp_page_size,
            queue_depth=args.tp_queue_depth,
            out_path=args.tp_out,
        )
        print(json.dumps(result))
        return result
    if args.int8:
        result = run_int8(
            requests=args.int8_requests,
            concurrency=args.int8_concurrency,
            slots=args.int8_slots,
            max_new=args.int8_max_new,
            spec_k=args.int8_spec_k,
            page_size=args.int8_page_size,
            queue_depth=args.int8_queue_depth,
            out_path=args.int8_out,
        )
        print(json.dumps(result))
        return result
    if args.fleet_child:
        _fleet_child(args.fleet_child)
        return {"fleet_child": True}
    if args.swap_child:
        _swap_child(args.swap_child)
        return {"swap_child": True}
    if args.storm_child:
        _storm_child(args.storm_child)
        return {"storm_child": True}
    if args.storm:
        result = run_storm(
            seed=args.storm_seed,
            duration_s=args.storm_duration_s,
            base_rps=args.storm_base_rps,
            burst_rps=args.storm_burst_rps,
            out_path=args.storm_out,
        )
        print(json.dumps(result))
        return result
    if args.swap:
        result = run_swap(
            requests=args.swap_requests,
            concurrency=args.swap_concurrency,
            max_new=args.swap_max_new,
            out_path=args.swap_out,
        )
        print(json.dumps(result))
        return result
    if args.fleet:
        result = run_fleet(
            requests=args.fleet_requests,
            concurrency=args.fleet_concurrency,
            max_new=args.fleet_max_new,
            out_path=args.fleet_out,
        )
        print(json.dumps(result))
        return result
    if args.serve:
        result = run_serve(
            requests=args.serve_requests,
            concurrency=args.serve_concurrency,
            slots=args.serve_slots,
            max_new=args.serve_max_new,
            prompt_mix=tuple(
                int(n) for n in args.serve_prompt_mix.split(",") if n.strip()
            ),
            queue_depth=args.serve_queue_depth,
            out_path=args.serve_out,
        )
        print(json.dumps(result))
        return result
    if args.quick:
        result = run_quick(
            steps=args.quick_steps, global_batch=args.quick_batch,
            out_path=args.quick_out,
        )
        print(json.dumps(result))
        return result

    def failure_artifact(metric: str, error: dict) -> None:
        # Structured failure: one JSON line naming the cause, so a missing
        # chip or a mid-run crash yields a diagnosable artifact instead of
        # a bare rc=1.
        print(json.dumps({
            "metric": metric,
            "value": None,
            "unit": "samples/sec/chip",
            "vs_baseline": None,
            "error": error,
        }))

    # A timing of XLA's CPU backend is not a samples/sec/chip number: no
    # chip, no benchmark — and no fallback.
    try:
        device = chip_device()
    except RuntimeError as e:  # backend failed to initialize
        failure_artifact(
            "benchmark not run: JAX backend unavailable",
            {"type": type(e).__name__, "message": str(e)[-1000:]},
        )
        return None
    if device["platform"] != "tpu":
        failure_artifact(
            "benchmark not run: no TPU", {"device": device},
        )
        return None
    from pytorch_distributed_training_tpu.train.compile import (
        enable_compile_cache,
    )

    enable_compile_cache()
    try:
        result = run_bench(
            model_name=args.model,
            global_batch=args.global_batch_size,
            micro_batch=args.micro_batch_size,
            seq_len=args.seq_len,
            warmup_steps=args.warmup_steps,
            timed_steps=args.timed_steps,
            chain_steps=args.chain_steps,
            matmul_impl=args.matmul_impl,
            quant_delayed=args.quant_delayed,
            quant_delayed_grads=args.quant_delayed_grads,
        )
    except SystemExit:
        raise  # argument errors keep their own message/exit code
    except Exception as e:  # noqa: BLE001 — the artifact must name the cause
        import traceback

        failure_artifact("benchmark failed mid-run", {
            "type": type(e).__name__,
            "message": str(e)[-1000:],
            "traceback_tail": traceback.format_exc()[-2000:],
        })
        return None
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
