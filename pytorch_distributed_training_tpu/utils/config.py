"""Typed configuration + real CLI parsing.

Replaces the reference's hardcoded config dict
``{"lr": 2e-5, "num_epochs": 3, "correct_bias": True, "seed": 42,
"batch_size": 96}`` (reference test_data_parallelism.py:174) and its magic
constants ``MAX_GPU_BATCH_SIZE = 8`` / ``EVAL_BATCH_SIZE = 32``
(test_data_parallelism.py:49-50). Defaults here match the reference exactly
so convergence/throughput comparisons are apples-to-apples.

Also fixes the reference's ``argparse type=bool`` bug (any non-empty string,
including ``--fp16=False``, parsed truthy; test_data_parallelism.py:171-172)
by using ``argparse.BooleanOptionalAction``.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any


@dataclasses.dataclass
class MeshConfig:
    """Logical device-mesh shape.

    Canonical axis order is ``(data, fsdp, stage, model)``:

    - ``data``  — pure data parallelism (per-replica batch shard; gradients
      psum over this axis, the XLA/ICI equivalent of DDP's NCCL allreduce,
      reference test_data_parallelism.py:146).
    - ``fsdp``  — data parallelism with parameters/optimizer state sharded on
      their leading dim (ZeRO-3 style, as a sharding rule, not a new engine).
    - ``stage`` — pipeline stages (the ConcatBert 2-stage layer split,
      reference test_model_parallelism.py:40-89, generalized).
    - ``model`` — tensor/branch model parallelism (the TriBert branch axis,
      reference test_model_parallelism.py:92-163, and sharded matmuls).
    - ``seq``   — sequence/context parallelism: activations sharded on the
      sequence dim, attention computed by ring attention
      (``ops.ring_attention``) with K/V blocks ppermuted around this axis.
      Innermost so ring hops ride adjacent-chip ICI links.

    Any axis set to ``-1`` absorbs all remaining devices (at most one).
    """

    data: int = -1
    fsdp: int = 1
    stage: int = 1
    model: int = 1
    seq: int = 1

    AXIS_NAMES = ("data", "fsdp", "stage", "model", "seq")

    def resolved_shape(self, n_devices: int) -> tuple[int, int, int, int, int]:
        sizes = [self.data, self.fsdp, self.stage, self.model, self.seq]
        n_fill = sum(1 for s in sizes if s == -1)
        if n_fill > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {sizes}")
        fixed = 1
        for s in sizes:
            if s != -1:
                if s < 1:
                    raise ValueError(f"invalid mesh axis size {s}")
                fixed *= s
        if n_fill:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes = [n_devices // fixed if s == -1 else s for s in sizes]
        elif fixed != n_devices:
            raise ValueError(
                f"mesh shape {sizes} (={fixed} devices) != available devices {n_devices}"
            )
        return tuple(sizes)  # type: ignore[return-value]


@dataclasses.dataclass
class ModelConfig:
    """Transformer encoder/decoder hyperparameters.

    Presets cover the reference's models: ``bert-base-cased`` (hidden 768, 12
    layers; reference test_model_parallelism.py:230-238), ``bert-large-cased``
    (hidden 1024, 24 layers; test_data_parallelism.py:112), plus
    ``roberta-large`` and ``gpt2-medium`` for the driver's extra configs
    (BASELINE.json configs[3-4]).
    """

    vocab_size: int = 28996  # bert-*-cased vocab
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    num_labels: int = 2
    # "reference" (XLA einsum) | "flash" (Pallas kernel, ops/flash_attention)
    # | "ring" (sequence-parallel, ops/ring_attention)
    attention_impl: str = "reference"
    # Dense-matmul execution path (ops/quant.py): "native" = XLA matmuls in
    # compute_dtype; "int8" = dynamic-quantized int8 forward on the MXU's
    # 2x-rate int8 path with a bf16 straight-through backward; "int8_full" =
    # int8 dgrad/wgrad too. OPT-IN — convergence must be demonstrated
    # per-recipe before a benchmark reports it (NOTES.md int8 section).
    matmul_impl: str = "native"
    # Delayed (previous-microbatch) activation scaling for the int8 path:
    # removes the per-site absmax serialization (~9 ms/step on bert-large,
    # NOTES.md) by carrying amaxes in the flax "quant" collection through
    # the train state. Requires calibration before step 0 (the Trainer and
    # bench do it on the first real batch). Only read when matmul_impl is
    # int8/int8_full; unsupported under the GPipe pipeline trainer.
    quant_delayed: bool = False
    # Extends quant_delayed to the BACKWARD's dy quantization (full mode):
    # dy amaxes carried one microbatch late, removing the backward's two
    # per-site absmax serializations. The observations leave the backward
    # through a cotangent sink (ops/quant.py int8_dense_delayed_grads);
    # supported by the standard train step only (not the pipeline
    # schedules). Requires quant_delayed and dy calibration before step 0.
    quant_delayed_grads: bool = False
    # Dropout mask generator (ops/dropout.py): "kernel" draws the keep mask
    # from the per-core TPU PRNG inside a Pallas op (only the x-dtype
    # mask-scale tensor touches HBM; falls back to bits32 off-TPU);
    # "bits32" compares raw jax PRNG words (no int->float conversion; same
    # 1/2^32 granularity — fp32 uniforms only carry 24 random bits);
    # "exact" is bit-exact with flax nn.Dropout under the same key.
    dropout_impl: str = "kernel"
    # dtype policy: params fp32, compute bf16 (TPU-native replacement for the
    # reference's fp16 AMP, test_data_parallelism.py:55; SURVEY.md §2b).
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # causal decoder flag (GPT-2 family)
    causal: bool = False
    # Autoregressive-decode mode (generation): attention modules maintain a
    # KV cache in the flax "cache" variable collection and attend over it;
    # position ids advance from the cached index (models/generate.py). Only
    # meaningful with causal=True; training paths leave this False.
    decode: bool = False
    # KV-cache layout for decode=True models: "dense" keeps one contiguous
    # [batch, max_len] buffer per attention layer (the classic flax cache);
    # "paged" stores K/V in fixed-size pages gathered through a per-sequence
    # block table (vLLM PagedAttention layout — serve/paged_cache.py owns
    # the allocator, ops/paged_attention.py the gather kernel). Only read
    # when decode=True; training paths ignore it.
    kv_layout: str = "dense"
    # Tokens per KV page (paged layout only). Real-TPU deployments want the
    # lane width (128); CPU/tests use small pages to exercise page turnover.
    kv_page_size: int = 16
    # Total pages in each layer's pool, INCLUDING the reserved null page 0
    # (never allocated; idle sequences point at it so their writes are
    # harmless). Must be set > 0 before building a paged decode model —
    # the serving engine computes it from its slot/budget config.
    kv_num_pages: int = 0
    # Storage dtype of the paged K/V pools (paged layout only): "auto"
    # stores pages in the compute dtype (the classic layout); "int8" stores
    # symmetric per-entry-per-head quantized pages plus fp32 ``k_scales``/
    # ``v_scales`` pools of shape [num_pages, page_size, heads] beside the
    # block tables — quantize-on-write at the scatter site, dequantize
    # in-kernel on read (ops/paged_attention.py). Allocator arithmetic and
    # block tables are dtype-invariant; only the pool bytes change.
    kv_cache_dtype: str = "auto"
    # Multi-token-query paged decode (speculative verify / chunked prefill):
    # a chunk of new tokens is scattered into the pages and then attends
    # causally over the WHOLE context (prior pages + itself) through the
    # 4-D-query paged_attention path, instead of the fresh-sequence
    # intra-chunk einsum. Only read when decode=True and kv_layout="paged";
    # the serving engine builds a second model view with this set rather
    # than flipping it on the decode model (chunk==1 decode keeps the
    # single-query program and its bitwise pins).
    paged_multiquery: bool = False
    # RoBERTa-style embeddings (pad-offset position ids, no token types)
    roberta_style: bool = False
    pad_token_id: int = 0
    # tanh-approximate gelu keeps the MXU pipeline fed (erf's transcendental
    # epilogue throttled the fused mlp_up matmul to ~103 TF/s vs ~187 on
    # v5e); set False for bit-level parity with BERT's erf gelu (HF
    # ``hidden_act="gelu"``) — activation diff is ~1e-3, fine-tune metrics
    # match either way.
    gelu_approximate: bool = True
    remat: bool = False  # jax.checkpoint each layer (trade FLOPs for HBM)
    # What the per-layer remat SAVES (only read when remat=True):
    #   "nothing"  — classic full remat: recompute the whole layer in the
    #                backward (max memory savings, ~2x layer FLOPs);
    #   "dots"     — selective remat: save every matmul/einsum output,
    #                recompute only the cheap elementwise tail (LN, gelu,
    #                dropout masks regenerate from their counter streams).
    #                Matmul FLOPs stay 1x — this is what unlocks larger
    #                microbatches on the LM recipes without paying full
    #                recompute (VERDICT r2 #5);
    #   "weight_dots" — save only the UNBATCHED dots (xW projections/MLP),
    #                recompute the batched attention-score einsums too —
    #                between the other two in both memory and FLOPs.
    remat_policy: str = "nothing"
    # Rematerialize ONLY the MLP tail (mlp_up → gelu → mlp_down) of each
    # GPT-2 block, structurally (plain jax.checkpoint around the
    # sub-function, NO saveable policies — those crashed the TPU compiler
    # at gpt2-medium scale in r3, NOTES.md). Drops the [B,S,4·hidden]
    # gelu residuals (the largest per-layer activations) for one extra
    # mlp_up matmul in the backward — the middle ground between no remat
    # (OOM at micro 8) and full-layer remat (recomputes attention too).
    remat_mlp: bool = False
    # Rematerialize the attention core (scores/softmax/probs) in the
    # backward pass instead of saving probs residuals — a strict win on the
    # seq-128 encoder recipe (see models/bert.py); applies to the
    # "reference" attention impl only.
    attention_remat: bool = True
    # LayerNorm implementation (ops/layer_norm.py): "fused" = the Pallas
    # row-block kernel on TPU (fp32 stats, one HBM read/write per tensor —
    # XLA's kLoop reduce fusions cost ~37 ms/step of the bert-large recipe,
    # the kernel ~5 ms); "reference" = jnp math. Identical formula either
    # way; off-TPU both run the jnp path.
    layernorm_impl: str = "fused"
    # Stack layers on a leading [num_layers] param dim walked by lax.scan:
    # near-constant compile time in depth, and the layer dim shards over the
    # mesh "stage" axis (ShardingPolicy(stage=True)) — the 2-stage layer
    # split capability (reference ConcatBert, test_model_parallelism.py:40-89)
    scan_layers: bool = False

    def __post_init__(self):
        # Validate remat_policy EAGERLY (not only when remat=True in
        # models.bert.remat_policy): a typo'd --remat-policy, or one set
        # without --remat, should fail loudly instead of being silently
        # ignored (ADVICE r3).
        if self.remat_policy not in ("nothing", "dots", "weight_dots"):
            raise ValueError(
                f"remat_policy must be nothing/dots/weight_dots, got "
                f"{self.remat_policy!r}"
            )
        if self.remat_policy != "nothing" and not self.remat:
            import warnings

            warnings.warn(
                f"remat_policy={self.remat_policy!r} has no effect without "
                f"remat=True",
                stacklevel=2,
            )
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be dense/paged, got {self.kv_layout!r}"
            )
        if self.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be auto/int8, got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.kv_cache_dtype == "int8" and self.kv_layout != "paged":
            raise ValueError(
                "kv_cache_dtype='int8' requires kv_layout='paged' (the "
                "dense cache has no scale-pool layout); got "
                f"kv_layout={self.kv_layout!r}"
            )
        if self.kv_layout == "paged" and self.kv_page_size < 1:
            raise ValueError(
                f"kv_page_size must be >= 1, got {self.kv_page_size}"
            )
        if self.remat_mlp and self.remat:
            import warnings

            # full-layer remat already recomputes the MLP; nesting a second
            # checkpoint inside it recomputes the MLP TWICE in the backward
            # for zero extra memory savings
            warnings.warn(
                "remat_mlp=True is redundant under remat=True (the layer "
                "checkpoint already recomputes the MLP); the nested "
                "checkpoint only adds recompute",
                stacklevel=2,
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


_MODEL_PRESETS: dict[str, dict[str, Any]] = {
    # reference test_data_parallelism.py:69 uses bert-large-cased tokenizer
    # (vocab 28996) and :112 the bert-large-cased model.
    "bert-base-cased": dict(
        vocab_size=28996, hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072,
    ),
    "bert-large-cased": dict(
        vocab_size=28996, hidden_size=1024, num_layers=24, num_heads=16,
        intermediate_size=4096,
    ),
    "roberta-large": dict(
        vocab_size=50265, hidden_size=1024, num_layers=24, num_heads=16,
        intermediate_size=4096, max_position_embeddings=514,
        type_vocab_size=1, roberta_style=True, pad_token_id=1,
        layer_norm_eps=1e-5,
    ),
    "gpt2-medium": dict(
        vocab_size=50257, hidden_size=1024, num_layers=24, num_heads=16,
        intermediate_size=4096, max_position_embeddings=1024,
        type_vocab_size=0, causal=True, layer_norm_eps=1e-5,
        # Pallas flash attention: at seq 1024 the causal block-skipping +
        # unmaterialized scores beat the XLA einsum path (~23% on v5e);
        # encoders at seq 128 keep "reference" (smaller matmuls lose there).
        attention_impl="flash",
    ),
    # tiny configs for tests/smoke runs (no reference counterpart; SURVEY.md
    # §4 parity tests)
    "tiny": dict(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_position_embeddings=128,
    ),
    "gpt2-tiny": dict(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_position_embeddings=128,
        type_vocab_size=0, causal=True, layer_norm_eps=1e-5,
    ),
}


def model_preset(name: str, **overrides: Any):
    """The preset's configuration: a ``ModelConfig``, or a family's own
    (``models/latent_moe.py::LatentMoEConfig``, ``models/sambay.py::
    SambaYConfig``, which are no sets of ``ModelConfig`` fields: other
    blocks, other sizes)."""
    if name not in _MODEL_PRESETS:
        from pytorch_distributed_training_tpu.models import latent_moe, sambay

        families = (latent_moe, sambay)
        for family in families:
            if name in family.PRESETS:
                return family.preset(name, **overrides)
        have = sorted({*_MODEL_PRESETS, *(p for f in families for p in f.PRESETS)})
        raise KeyError(f"unknown model preset {name!r}; have {have}")
    kwargs = dict(_MODEL_PRESETS[name])
    kwargs.update(overrides)
    return ModelConfig(**kwargs)


@dataclasses.dataclass
class TrainConfig:
    """Training hyperparameters; defaults mirror the reference.

    - lr 2e-5, 3 epochs, seed 42, global batch 96 → micro batch 8 ×
      accumulation 12 (reference test_data_parallelism.py:49-50,89-93,174)
    - eval batch 32 (test_data_parallelism.py:50)
    - AdamW **with** bias correction (``correct_bias=True``,
      test_data_parallelism.py:120,174)
    - linear schedule with 100 warmup steps (test_data_parallelism.py:131-135)
    - bf16 replaces the fp16 AMP flag (test_data_parallelism.py:55)

    The accumulation boundary here is the *correct* one — update after every
    ``grad_accum_steps`` microbatches — not the reference's off-by-one
    ``step % accum == 0`` that steps on the very first microbatch
    (SURVEY.md §2c-1).
    """

    learning_rate: float = 2e-5
    num_epochs: int = 3
    seed: int = 42
    global_batch_size: int = 96
    micro_batch_size: int = 8  # reference MAX_GPU_BATCH_SIZE
    eval_batch_size: int = 32
    warmup_steps: int = 100
    weight_decay: float = 0.0
    # The reference never clips gradients (neither script calls
    # clip_grad_norm_), so clipping is off by default; set > 0 to enable.
    max_grad_norm: float = 0.0
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    # AdamW moment storage dtypes; "bfloat16" halves that moment's
    # optimizer-state traffic in the fused update (math stays fp32 —
    # train/fused_adamw.py). Both convergence-checked on the MRPC recipe
    # before becoming bench defaults; fp32 is the conservative default.
    adam_mu_dtype: str = "float32"
    adam_nu_dtype: str = "float32"
    bf16: bool = True
    # Gradient-accumulation carry dtype: "float32" (default) or "bfloat16"
    # (halves the scan-carry HBM traffic; microbatch gradients round to bf16
    # before summing — AdamW's sqrt(v) normalization makes fine-tuning
    # insensitive to this, but fp32 is the conservative default).
    grad_accum_dtype: str = "float32"
    max_seq_length: int = 128  # the reference's own TPU pad branch (:96-98)
    # 0 = use the full dataset; >0 truncates (fast smoke/integration runs)
    train_size: int = 0
    eval_size: int = 0
    log_every: int = 50
    checkpoint_dir: str | None = None
    checkpoint_every_steps: int = 0  # 0 = per-epoch only
    resume: bool = False
    # Path to a WordPiece vocab.txt (e.g. from a local HF bert-*-cased
    # cache): real GLUE text is then encoded with the REAL vocabulary
    # (C++ bulk encoder when built, data/glue.py) instead of the offline
    # HashTokenizer stand-in. None = hash tokenizer / synthetic fallback.
    vocab_path: str | None = None
    # Fault injection (testing the failure->restart->resume loop, SURVEY.md
    # §5 "failure detection / fault injection" — absent in the reference,
    # whose only story is crash propagation): process ``crash_rank``
    # hard-exits (os._exit, no cleanup/checkpoint flush) right after
    # completing update number ``crash_at_step``. 0 = disabled.
    crash_at_step: int = 0
    crash_rank: int = 0
    # ---------------------------------------------------- fault tolerance
    # Preemption-safe shutdown (faults/preemption.py): SIGTERM/SIGINT set a
    # flag; the Trainer stops at the next step boundary, writes an emergency
    # checkpoint (if checkpoint_dir is set) inside preempt_grace_s, emits a
    # `preemption` telemetry record and exits RESUMABLE (code 75) so an
    # external supervisor restarts without burning a failure-budget slot.
    handle_preemption: bool = True
    preempt_grace_s: float = 30.0
    # Hung-step watchdog (faults/watchdog.py): armed around device-blocking
    # sections (step dispatch/block, checkpoint joins, host collectives).
    # After max(watchdog_min_stall_s, watchdog_stall_factor x rolling-median
    # section time) it records a `watchdog_stall` with all-thread stacks;
    # past watchdog_hard_timeout_s it aborts the process (exit 84) so the
    # supervisor restarts instead of hanging forever. hard_timeout 0 = never
    # abort (stall records only).
    watchdog: bool = True
    watchdog_stall_factor: float = 10.0
    watchdog_min_stall_s: float = 60.0
    watchdog_hard_timeout_s: float = 1800.0
    # Checkpoint integrity verification level on restore (train/manifest.py):
    # "size" checks the per-save manifest's file inventory by byte size
    # (catches truncation/partial commits); "digest" re-hashes every file
    # (catches same-size corruption, costs a full read); "off" trusts orbax.
    # A latest step that fails verification is skipped in favor of the
    # newest VERIFIED step (Checkpointer.verified_latest_step).
    checkpoint_verify: str = "size"
    profile_dir: str | None = None  # enable jax.profiler traces when set
    debug_nans: bool = False
    # Structured telemetry (telemetry/): when set, process 0 appends a JSONL
    # stream under this directory — run-metadata header, per-step timing
    # breakdown (data wait / dispatch / device block), per-epoch records
    # with cross-host straggler stats, checkpoint/restart events. Fold it
    # into a table with scripts/summarize_metrics.py. Per-step records
    # synchronize on each step's loss (honest device-time attribution costs
    # the async-dispatch overlap); leave unset for maximum throughput.
    metrics_dir: str | None = None
    # "text" | "json": json switches the framework loggers to one-JSON-
    # object-per-line records (machine-scrapable multi-host logs).
    log_format: str = "text"
    # Train-batch assembly engine: "auto" uses the native C++ prefetching
    # batcher (native/src/batcher.cpp) when a toolchain is available, else
    # the Python loader; "on" requires it; "off" forces the Python loader.
    native_loader: str = "auto"
    # Latency-hiding input pipeline (data/prefetch.py): a background thread
    # runs host assembly + device placement for the NEXT prefetch_depth
    # train batches while the current step computes, so H2D transfers
    # overlap device time instead of serializing in front of each dispatch.
    # Batch order is bitwise-identical to the unwrapped loader. 0 = today's
    # synchronous assemble->place->dispatch path.
    prefetch_depth: int = 2
    # AOT warm start: .lower().compile() the train/eval steps before epoch
    # 0, so the first step is a normal steady-state step (no
    # compile_inclusive flag) and compile wall time is attributed to its
    # own `compile` telemetry record. Skipped automatically for custom
    # train_step_factory schedules, chain_steps > 1 and seq-sharded meshes
    # (their batch layouts are owned elsewhere).
    aot_warmup: bool = True
    # Optimizer steps fused per dispatch (train/step.py): ONE compiled call
    # executes chain_steps updates back-to-back on device over a pre-stacked
    # [chain_steps, accum, micro, ...] batch. Amortizes host dispatch
    # latency on high-latency control planes (builder-measured ~equal in
    # r3 — jax's async dispatch already pipelines it; kept for
    # remote/colab-style runtimes where it matters). Per-step numerics are
    # identical; loss/grad-norm metrics come back for the LAST step of each
    # chain only, and logging/checkpoint cadences round to chain boundaries.
    chain_steps: int = 1
    # Accumulation-scan unrolling: "auto" unrolls when grad_accum_steps <= 4
    # (XLA folds the zeros init into microbatch 1 and schedules across
    # iterations, ~3 ms/step on bert-large); "off" forces the rolled loop —
    # unrolling lets XLA overlap microbatch LIFETIMES, which raises peak
    # activation memory (gpt2-medium at micro 8 OOMs unrolled, fits rolled
    # — NOTES.md round-4); "on" forces unrolling regardless of count.
    unroll_accum: str = "auto"
    # Runtime correctness guards (analysis/guards.py): "record" (default)
    # wraps the train/eval steps with a recompile counter (a retrace after
    # the warm-up compile emits a `recompile` telemetry record) and runs
    # post-lower donation + sharding audits; "strict" additionally arms
    # jax.transfer_guard("disallow") around warm step calls and raises on
    # any violation (what the tier-1 guard tests run under); "off" disables
    # the layer. PDT_TPU_GUARDS overrides the default.
    guards: str = "record"
    # Dropout-key PRNG: "rbg" rides the TPU hardware generator (profiled
    # ~1.5x step speedup over threefry on bert-large — threefry's bit
    # arithmetic competes with the matmuls for VPU cycles); "threefry2x32"
    # gives jax's default stream for bit-exact cross-run/cross-backend repro.
    prng_impl: str = "rbg"

    @property
    def grad_accum_steps(self) -> int:
        """Derived exactly as the reference derives it (:89-93): if the
        requested global batch exceeds the micro batch, split."""
        if self.global_batch_size % self.micro_batch_size:
            raise ValueError(
                f"global_batch_size {self.global_batch_size} must be divisible "
                f"by micro_batch_size {self.micro_batch_size}"
            )
        return self.global_batch_size // self.micro_batch_size


def add_dataclass_args(parser: argparse.ArgumentParser, cls, prefix: str = "") -> None:
    """Register every field of a dataclass as a typed CLI flag.

    Booleans become ``--flag/--no-flag`` pairs (BooleanOptionalAction),
    fixing the reference's ``type=bool`` bug (SURVEY.md §2c-4).
    """
    for f in dataclasses.fields(cls):
        if f.name.isupper():
            continue
        name = f"--{prefix}{f.name.replace('_', '-')}"
        default = f.default if f.default is not dataclasses.MISSING else None
        ftype = f.type if isinstance(f.type, type) else str(f.type)
        if ftype in (bool, "bool"):
            parser.add_argument(
                name, action=argparse.BooleanOptionalAction, default=default
            )
        elif ftype in (int, "int"):
            parser.add_argument(name, type=int, default=default)
        elif ftype in (float, "float"):
            parser.add_argument(name, type=float, default=default)
        else:
            parser.add_argument(name, type=str, default=default)


def dataclass_from_args(cls, args: argparse.Namespace, prefix: str = ""):
    # argparse converts dashes in flag names to underscores in dests; mirror
    # that here so e.g. prefix="mesh-" finds dest "mesh_data".
    dest_prefix = prefix.replace("-", "_")
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name.isupper():
            continue
        key = f"{dest_prefix}{f.name}"
        if hasattr(args, key):
            kwargs[f.name] = getattr(args, key)
    return cls(**kwargs)
