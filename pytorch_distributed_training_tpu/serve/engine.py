"""Continuous-batching decode engine: paged KV cache + on-device sampling.

The one-shot ``models/generate.py`` path compiles a whole
prefill+scan program per (batch, prompt_len, max_new_tokens) triple and
holds every request in lockstep — fine for offline batch generation,
wrong for a server where requests arrive at different times with
different lengths. This engine is the serving counterpart (continuous
batching a la Orca, block-structured KV a la vLLM's PagedAttention):

- **KV pages**: K/V lives in fixed-size pages — ``[num_pages, page_size,
  heads * head_dim]`` pools per attention layer (lane-dense: a token's
  heads folded into the minor axis, so a pool keeps ONE device layout
  from parameter to donated result and no program relays it out around a
  write) — addressed through a per-slot block table that
  ``serve/paged_cache.py`` allocates on admit and frees on evict
  (defrag-free; page 0 is the reserved null page idle slots park on).
  The decode step runs the model at batch ``num_slots`` directly with
  per-slot ``position_ids``/``context_len`` operands; no vmap, no
  per-slot freeze select — page structure isolates slots. Admission is
  a PAGE budget, not a slot-shape budget: one engine serves wildly
  mixed context lengths, and the pool can be sized well under
  ``num_slots * cache_len`` tokens because short requests only hold the
  pages they need.

- **Prefill into a slot**: one jitted program per prompt-length *bucket*
  (compilation stays bounded by the bucket list). Prefill scatters the
  prompt's K/V straight into the slot's pages and attends intra-chunk
  (no staging buffer); pad positions beyond the real length are
  overwritten by generated tokens exactly one step before the causal
  mask would first expose them.

- **Sampling**: temperature/top-k/seed/step ride into the jitted
  programs as traced per-slot operands and the next token is selected
  in-trace (``serve/sampling.device_sample``; greedy is a ``jnp.where``
  select, per the traced-branch rule). Each tick's D2H is ONE explicit
  ``jax.device_get`` of ``[slots]`` int32 ids — which is why the whole
  tick can run under a strict ``GuardSet.transfer_scope`` once every
  program is warm. (``serve/sampling.host_sample`` is the numpy mirror
  the tests hold the device sampler to; the engine never calls it.)

- **One step in flight**: the ids a decode step samples stay on the
  device and the next step reads them there (``where(fresh, host tokens,
  previous ids)`` in the same program), so the plain decode tick
  dispatches step k while step k-1 still runs and only then fetches,
  emits and retires step k-1: in the steady state the host's whole part
  of a tick runs under the device's step (``DecodeEngine.tick``).

Integration: prefill/decode dispatch+block run under
``faults.watchdog_guard``; each tick routes through
``FaultPlan.slow_host_delay``; per-request TTFT/TPOT/queue-wait,
tick-level queue-depth/slot-occupancy and per-tick
``kv_pages_used``/``kv_pages_free`` go through ``telemetry/``.

**Speculative decoding** (``EngineConfig.spec_k > 0``): a cheap draft
lane proposes k tokens per slot per tick — either host-side n-gram
self-drafting (``spec_draft="ngram"``, zero extra dispatches:
prompt-lookup over the slot's own history) or a small draft model
resident beside the base model (``spec_draft="model"``, greedy
single-token draft dispatches sharing the allocator's block table into
separate draft pools). ONE jitted verify dispatch then scores all k+1
positions (pending token + k drafts) through the multi-token-query paged
attention path and runs exact-match acceptance sampling on device
(``serve/sampling.spec_accept``): every emitted token is literally the
``fold_in(key(seed), step)`` stream's sample for its position, so the
accepted stream is BIT-IDENTICAL to the non-speculative stream for greedy
and fixed-seed sampling — the draft only controls how many positions one
dispatch commits. Rejected drafts roll back by the host simply NOT
advancing the slot's context cursor past the accepted prefix: the dead
K/V lanes stay in the slot's over-reserved pages (see
``PageAllocator.pages_reserved``), masked by ``context_len`` and
overwritten on reuse — zero allocator churn.

**Chunked prefill** (``EngineConfig.prefill_chunk > 0``): prompts stream
into their pages ``prefill_chunk`` tokens per tick through the same
multi-token-query program (ONE compiled chunk program replaces the
one-jitted-prefill-per-bucket scheme), interleaving with decode ticks so
a long prompt's prefill no longer stalls short requests' decode;
``prefill_concurrency`` caps mid-prefill residency via the queue's
``defer`` hold (a hold is not page exhaustion).

Live weight hot-swap (serve/hotswap.py): ``request_swap(params, version)``
queues a validated replacement params tree from any thread; the serve
loop applies it at the START of the next tick, once the step in flight
(old weights) is retired (``swap_params`` — never between two dispatches
of one tick, so a tick is never torn between two weight versions) and the
OLD params stay alive until the first post-swap tick completes cleanly
(trial/commit; a trial-tick failure rolls back to them). The resident
page pools are untouched by a swap — in-flight slots simply continue
decoding on the new weights — and because the
replacement tree is validated to the same treedef/shapes/dtypes and
pre-placed on device, the swap hits the existing compiled programs (no
retrace, no implicit transfer: clean under ``PDT_TPU_GUARDS=strict``).
Only the pools are donated, so holding the previous params through the
trial window is free of copies.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.analysis import concurrency
from pytorch_distributed_training_tpu.analysis.guards import (
    GuardSet,
    guard_mode_from_env,
)
from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
    serve_manifest,
    serve_tp_manifest,
)
from pytorch_distributed_training_tpu.faults.watchdog import watchdog_guard
from pytorch_distributed_training_tpu.ops.moe import routing_totals
from pytorch_distributed_training_tpu.ops.quant import (
    dequantize_serve_params,
    quantize_serve_params,
    serve_params_variant,
)
from pytorch_distributed_training_tpu.serve.paged_cache import (
    PageAllocator,
    strip_tables,
    with_tables,
)
from pytorch_distributed_training_tpu.serve.queue import (
    GenRequest,
    RequestQueue,
    emit_expiry,
)
from pytorch_distributed_training_tpu.serve.sampling import (
    device_sample,
    spec_accept,
)
from pytorch_distributed_training_tpu.telemetry.spans import (
    Phase,
    Tracer,
    setup_phase,
)
from pytorch_distributed_training_tpu.utils.logging import get_logger

logger = get_logger(__name__)

#: prefix of the tick's phase names; records and flight entries drop it
_TICK = "serve_tick."


@dataclasses.dataclass
class EngineConfig:
    """Decode-engine shape knobs (everything that fixes compiled programs).

    ``cache_len`` (largest bucket + ``max_new_tokens``) bounds every
    request: a request needs ``bucket(prompt) + max_new_tokens <=
    cache_len``, which holds by construction since per-request
    ``max_new_tokens`` is capped at the config value.

    Pool sizing: a request admitted at bucket ``b`` holds
    ``ceil((b + max_new_tokens) / page_size)`` pages for its whole life
    (worst case reserved up front, so decode can never starve mid-answer).
    ``num_pages=0`` auto-sizes the pool so every slot can hold a
    worst-case request (plus the reserved null page); set it LOWER to
    trade admission concurrency for KV memory (page-exhaustion
    backpressure kicks in).
    """

    num_slots: int = 4
    prompt_buckets: tuple = (16, 32, 64)
    max_new_tokens: int = 64
    # Fixed names, one value each: the engine has one KV layout and one
    # sampler. Kept (with ``stats()["kv_layout"]``/``["sampling"]`` and
    # serve_lm's two flags) because benchmarks/configs/*.json and
    # tests/benchmarks/ still pass them; see ROADMAP C11.
    kv_layout: str = "paged"
    sampling: str = "device"
    page_size: int = 16
    num_pages: int = 0          # total pages incl. null page; 0 = auto
    # Compile every program (all buckets + decode) at engine build so the
    # first request never pays compilation and strict tick-wide transfer
    # scoping arms from the first real tick.
    warmup: bool = False
    # Speculative decoding: draft tokens proposed per slot per tick; 0
    # disables (the one-token decode program runs).
    spec_k: int = 0
    # Draft lane: "ngram" = host-side prompt-lookup self-drafting (no
    # draft checkpoint, zero extra dispatches); "model" = a small draft
    # model passed to the engine (greedy draft dispatches per tick).
    spec_draft: str = "ngram"
    # Chunked prefill: prompt tokens scattered per tick per slot; 0 keeps
    # the monolithic per-bucket prefill programs.
    prefill_chunk: int = 0
    # Max slots simultaneously mid-chunked-prefill; further admissions are
    # DEFERRED (transient queue hold, not page exhaustion) until a
    # streaming prompt finishes.
    prefill_concurrency: int = 1
    # Tensor parallelism: the engine's jitted programs run under pjit over
    # a `model`-axis mesh of this many devices, attention heads + MLP
    # hidden sharded (parallel/sharding.py serve rules), paged pools split
    # by heads. 1 = today's single-device engine, bit-identical
    # streams either way.
    tp: int = 1
    # Serving precision variants. weights_dtype="int8" quantizes every
    # attention/MLP matmul weight ONCE at engine build (per-output-channel
    # scales, ops/quant.quantize_serve_params); the jitted programs
    # dequantize in-trace, so activations/logits/sampling stay fp32 while
    # resident weight bytes roughly halve. kv_dtype="int8" stores the
    # paged K/V pools as int8 with fp32 per-page-per-head scale pools
    # riding beside the block tables (allocator arithmetic and admission
    # are dtype-invariant). Both compose with tp and speculation;
    # "float32" keeps today's exact baseline. weights_dtype="bfloat16" keeps
    # every floating leaf resident in bfloat16 (cast once at build; a tree
    # that arrives in bfloat16, as a model sized to the chip is loaded,
    # stays as it is): half the weight bytes a step reads, nothing
    # dequantized in-trace.
    weights_dtype: str = "float32"
    kv_dtype: str = "float32"
    # Shared-KV prefix cache (serve/prefix_cache.py): finished prompts'
    # fully-written pages are indexed in a token-keyed trie, and a later
    # request with a matching prompt prefix maps those pages into its block
    # table (refcount bumped) and prefills only the tail — the tail streams
    # through the chunked-prefill program starting at the cached boundary.
    # Streams stay bit-identical to cold prefill (pinned by tests).
    prefix_cache: bool = False
    # Per-tenant page quota as a fraction of the pool (0 = unlimited): a
    # tenant whose PRIVATE (non-shared) page footprint would exceed
    # quota * (num_pages - 1) is held at admission — shared prefix pages
    # are free, so one tenant cannot monopolize the pool with private
    # state while everyone shares the cached prefixes. Requires
    # prefix_cache=True (quota accounting rides its admission path).
    tenant_page_quota: float = 0.0
    # Flight-recorder ring capacity (telemetry/flight.py): last N tick
    # summaries kept for post-mortem dumps. Must be >= 1.
    flight_capacity: int = 256

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {self.num_slots}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}"
            )
        self.prompt_buckets = tuple(sorted(set(int(b) for b in self.prompt_buckets)))
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise ValueError(
                f"prompt_buckets must be positive lengths, got "
                f"{self.prompt_buckets!r}"
            )
        for flag, given, only in (
            ("--kv-layout", self.kv_layout, "paged"),
            ("--sampling", self.sampling, "device"),
        ):
            if given != only:
                raise ValueError(
                    f"{flag} {given}: that path was removed in PR 31; the "
                    f"engine serves {only!r} only"
                )
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_draft not in ("ngram", "model"):
            raise ValueError(
                f"spec_draft must be ngram/model, got {self.spec_draft!r}"
            )
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}"
            )
        if self.prefill_concurrency < 1:
            raise ValueError(
                f"prefill_concurrency must be >= 1, got "
                f"{self.prefill_concurrency}"
            )
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.weights_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"weights_dtype must be float32/bfloat16/int8, got "
                f"{self.weights_dtype!r}"
            )
        if self.kv_dtype not in ("float32", "int8"):
            raise ValueError(
                f"kv_dtype must be float32/int8, got {self.kv_dtype!r}"
            )
        if not 0.0 <= self.tenant_page_quota <= 1.0:
            raise ValueError(
                f"tenant_page_quota must be in [0, 1], got "
                f"{self.tenant_page_quota}"
            )
        if self.tenant_page_quota > 0.0 and not self.prefix_cache:
            raise ValueError(
                "tenant_page_quota requires prefix_cache=True (quota "
                "accounting rides the prefix-cache admission path)"
            )
        if self.flight_capacity < 1:
            raise ValueError(
                f"flight_capacity must be >= 1, got {self.flight_capacity}"
            )
        if 0 < self.num_pages < self.pages_per_slot + 1:
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold even one "
                f"worst-case request ({self.pages_per_slot} pages + the "
                f"reserved null page) — a lone request would wait on "
                f"pages forever"
            )

    @property
    def cache_len(self) -> int:
        return self.prompt_buckets[-1] + self.max_new_tokens

    @property
    def pages_per_slot(self) -> int:
        """Block-table row width: pages covering one worst-case request
        INCLUDING the speculative overshoot (a verify tick scatters up to
        ``spec_k`` draft tokens past the committed context before
        acceptance is known — see ``PageAllocator.pages_reserved``)."""
        return -(-(self.cache_len + self.spec_k) // self.page_size)

    @property
    def total_pages(self) -> int:
        """Pool size including the reserved null page 0."""
        if self.num_pages > 0:
            return self.num_pages
        return self.num_slots * self.pages_per_slot + 1


def _check_tp_divisible(cfg, tp: int, role: str) -> None:
    """Head-sharding feasibility: the model axis splits attention heads
    and the MLP hidden dim into equal slices, so both must divide."""
    for axis, size in (
        ("num_heads", cfg.num_heads),
        ("intermediate_size", cfg.intermediate_size),
    ):
        if size % tp:
            raise ValueError(
                f"tp={tp} does not divide {role} model's {axis}={size} — "
                f"attention heads and the MLP hidden dim shard over the "
                f"model axis, so each shard needs an equal slice"
            )


@dataclasses.dataclass
class _Slot:
    """Engine-private per-slot state between ticks."""

    request: GenRequest
    # sampled, not yet fed through decode: the host's copy, current while
    # no step of the slot is in flight (``steps_done == steps_retired``)
    pending_token: int
    # two counters a slot. Decode steps DISPATCHED (generated tokens fed
    # into the KV): context, the sampler's step index and the stop by
    # length are built from it. Decode steps RETIRED (their ids fetched and
    # emitted): one behind while a step is in flight.
    steps_done: int = 0
    steps_retired: int = 0
    # chunked prefill: "prefill" while the prompt is still streaming into
    # the slot's pages (prefill_pos tokens scattered so far), "decode" once
    # the first token is sampled
    phase: str = "decode"
    prefill_pos: int = 0
    # speculative lane membership (request opt-in/out resolved against the
    # engine default at admission; fixed for the slot's lifetime)
    spec: bool = False


@dataclasses.dataclass
class _InFlight:
    """One dispatched decode step whose ids are not fetched yet."""

    out: object                 # device: [slots] ids, or (ids, routing)
    # the (slot index, _Slot) pairs of the dispatch: retirement walks
    # THESE, and skips a pair whose slot has been given up since
    pairs: list


@dataclasses.dataclass
class SwapTicket:
    """Outcome handle for one requested weight swap: ``done`` fires when
    the engine committed (``ok=True``) or rolled back (``ok=False``) the
    swap — the requesting thread blocks on it, never on the serve loop."""

    version: Optional[int]
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )
    ok: Optional[bool] = None
    error: Optional[str] = None
    stage: Optional[str] = None

    def resolve(self, ok: bool, *, error: str = None, stage: str = None):
        self.ok = ok
        self.error = error
        self.stage = stage
        self.done.set()


class DecodeEngine:
    """Slotted continuous-batching decode over a causal LM.

    Single-threaded by contract: ``tick``/``cancel_all`` run on the serve
    loop thread (serve/server.py); construction may happen anywhere.
    """

    def __init__(self, model, params, config: EngineConfig,
                 queue: RequestQueue, *, registry=None, **parts):
        """``parts``: the keyword arguments of ``_build``."""
        with setup_phase("serve_setup.engine", registry=registry):
            self._build(model, params, config, queue, registry=registry,
                        **parts)
        if config.warmup:
            with setup_phase("serve_setup.warmup", registry=registry):
                self._warmup()

    def _build(
        self,
        model,
        params,
        config: EngineConfig,
        queue: RequestQueue,
        *,
        registry=None,
        guards: Optional[GuardSet] = None,
        weights_step: Optional[int] = None,
        draft_model=None,
        draft_params=None,
        brownout=None,
        tracer=None,
        flight=None,
        slo=None,
        replica_name: Optional[str] = None,
    ):
        """Pools, placement, guards and the observability plane: all of
        construction but the warm-up compiles."""
        cfg = model.config
        if not cfg.causal:
            raise ValueError("DecodeEngine needs a causal model")
        if cfg.scan_layers:
            # serve loops are exactly the "hot serving" case the generate()
            # docstring defers: unstack ONCE at engine build, not per call
            from pytorch_distributed_training_tpu.models.relayout import (
                unstack_scanned_params,
            )

            cfg = dataclasses.replace(cfg, scan_layers=False)
            model = type(model)(cfg)
            params = unstack_scanned_params(params)
        self.config = config
        # a model family that lacks some serving paths says so itself, by
        # the flag's name, before anything is placed or compiled
        check = getattr(cfg, "check_serving", None)
        if check is not None:
            check(config)
        if config.cache_len + config.spec_k > cfg.max_position_embeddings:
            raise ValueError(
                f"cache_len {config.cache_len} (= largest bucket "
                f"{config.prompt_buckets[-1]} + max_new_tokens "
                f"{config.max_new_tokens}) + spec_k {config.spec_k} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings} "
                f"(speculative drafts occupy positions past the committed "
                f"context before acceptance is known)"
            )
        # Resident precision variant: fixed for the engine's lifetime by
        # weights_dtype (the compiled programs' input dtypes never change,
        # which is what keeps variant hot-swaps retrace-free). Weight-only
        # int8 quantizes the matmul kernels ONCE here — per-output-channel
        # fp32 scales ride the tree as kernel_scale leaves — and every
        # jitted program below dequantizes in-trace.
        self.variant = "int8" if config.weights_dtype == "int8" else "fp32"
        if config.weights_dtype == "int8":
            params = quantize_serve_params(params)
        params = self._resident_dtype(params)
        # Placement: EVERY program input — params, KV state, host-built
        # operands — is committed to one explicit sharding from the first
        # call on. jit keys its executables on committed-ness: a resident
        # tree left uncommitted (``jnp.zeros``, a bare ``device_put``)
        # comes back committed from the first program that also took a
        # committed input (a restored checkpoint), and the next call of
        # every warm program then builds a second executable.
        #
        # tp == 1: the engine's one device. Tensor-parallel mesh (tp > 1):
        # every jitted program below runs under pjit over a `model`-axis
        # mesh — params shard by the serve rules (heads / MLP hidden),
        # pools shard by heads, and all host-built operands are
        # placed REPLICATED through self._put (a device-0-committed operand
        # mixed with mesh-sharded params is a placement error, not a
        # resharding).
        self._mesh = None
        self._repl = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        if config.tp > 1:
            from pytorch_distributed_training_tpu.comms.mesh import (
                MeshConfig,
                build_mesh,
            )

            _check_tp_divisible(cfg, config.tp, "model")
            devices = jax.devices()
            if len(devices) < config.tp:
                raise ValueError(
                    f"tp={config.tp} needs {config.tp} devices, have "
                    f"{len(devices)}"
                )
            self._mesh = build_mesh(
                MeshConfig(data=1, fsdp=1, stage=1, model=config.tp, seq=1),
                devices=devices[: config.tp],
            )
            self._repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec()
            )
        dcfg = dataclasses.replace(
            cfg, decode=True, kv_layout="paged",
            kv_page_size=config.page_size,
            kv_num_pages=config.total_pages,
            kv_cache_dtype="int8" if config.kv_dtype == "int8" else "auto",
        )
        # What the model keeps per sequence (paged_cache.SlotMemory), where
        # the family declares it: pages for the allocator, rings and states
        # sized here by num_slots, once. A family that declares nothing
        # (the empty tuple) keeps page pools only, which ``with_tables``
        # then finds by their leaves' names.
        self._memory = ()
        if hasattr(dcfg, "slot_memory"):
            dcfg = dataclasses.replace(dcfg, kv_num_slots=config.num_slots)
            self._memory = dcfg.slot_memory()
        self._decode_model = type(model)(dcfg)
        # routed experts (a share of them held here): the decode step also
        # returns its routing counts, fetched with the sampled ids
        self._routed = bool(getattr(cfg, "n_routed_experts", 0))
        # Multi-token-query view of the SAME decode model (shared params,
        # shared pools): the verify and chunk programs append a block of
        # tokens at context_len and attend over prior pages plus the block.
        # A separate view — not a flag flip on _decode_model — so the
        # chunk==1 decode program and its bitwise pins are untouched.
        self._mq_model = None
        if (config.spec_k > 0 or config.prefill_chunk > 0
                or config.prefix_cache):
            self._mq_model = type(model)(
                dataclasses.replace(dcfg, paged_multiquery=True)
            )
        # Draft lane (spec_draft="model"): a small model resident beside
        # the base one, with its OWN page pools at the SAME page geometry
        # so the allocator's block tables address both. "ngram" drafting
        # needs no device state at all.
        self._draft_model = None
        self._draft_mq_model = None
        self._draft_params = None
        self._draft_cache = None
        if config.spec_k > 0 and config.spec_draft == "model":
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "spec_draft='model' needs draft_model/draft_params "
                    "(pass spec_draft='ngram' for checkpoint-free "
                    "self-drafting)"
                )
            dmc = draft_model.config
            if dmc.scan_layers:
                from pytorch_distributed_training_tpu.models.relayout import (
                    unstack_scanned_params,
                )

                dmc = dataclasses.replace(dmc, scan_layers=False)
                draft_params = unstack_scanned_params(draft_params)
            if dmc.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dmc.vocab_size} != base vocab "
                    f"{cfg.vocab_size} — draft tokens must be base tokens"
                )
            if config.cache_len + config.spec_k > dmc.max_position_embeddings:
                raise ValueError(
                    f"draft max_position_embeddings "
                    f"{dmc.max_position_embeddings} cannot cover cache_len "
                    f"{config.cache_len} + spec_k {config.spec_k}"
                )
            ddcfg = dataclasses.replace(
                dmc, decode=True, kv_layout="paged",
                kv_page_size=config.page_size,
                kv_num_pages=config.total_pages,
                scan_layers=False,
                kv_cache_dtype=(
                    "int8" if config.kv_dtype == "int8" else "auto"
                ),
            )
            self._draft_model = type(draft_model)(ddcfg)
            if config.prefill_chunk > 0 or config.prefix_cache:
                self._draft_mq_model = type(draft_model)(
                    dataclasses.replace(ddcfg, paged_multiquery=True)
                )
            if config.weights_dtype == "int8":
                # the draft lane serves at the same precision variant as
                # the base model (same dequant-in-trace scheme)
                draft_params = quantize_serve_params(draft_params)
            if self._mesh is not None:
                _check_tp_divisible(dmc, config.tp, "draft")
            self._draft_params = jax.device_put(
                draft_params, self._shardings_for(draft_params)
            )
        # explicit placement: restored checkpoints arrive as host arrays,
        # and a host tree reaching the warm compiled calls would be an
        # implicit per-tick H2D (a strict-mode transfer violation). Under
        # tp the placement IS the sharding: weights shard at load, and
        # every later swap re-places onto the same shardings so the warm
        # programs never see a new input layout (no recompile).
        self._param_shardings = self._shardings_for(params)
        self._params = jax.device_put(params, self._param_shardings)
        self._queue = queue
        # live weight-swap state: version served, one pending (validated,
        # device-placed) replacement, and the trial window's keep-alive of
        # the previous params until the first post-swap tick commits
        self.weights_step = weights_step
        self.swaps = 0              # committed swaps
        self.swap_rollbacks = 0     # trial-tick failures rolled back
        self._swap_lock = concurrency.lock("serve.engine.swap")
        self._pending_swap = None   # (params, version, ticket, variant)
        self._trial = None          # (prev_params, prev_version, ticket)
        self._last_swap_variant = None  # incoming variant of newest swap
        if registry is None:
            from pytorch_distributed_training_tpu.telemetry.registry import (
                get_registry,
            )

            registry = get_registry()
        self._registry = registry
        # Runtime guards (analysis/guards.py): each compiled entry point is
        # wrapped so a retrace after its warm-up compile — one prefill per
        # bucket, one decode step — is a recorded violation, and warm calls
        # run under the implicit-transfer guard. The WHOLE tick
        # additionally runs under ``transfer_scope`` once every program is
        # warm (strict mode: the single token-id device_get is the only
        # D2H a tick is allowed).
        self._guards = guards or GuardSet(
            mode=guard_mode_from_env(), registry=registry
        )

        # Page pools are shaped by config, not by the init input; the
        # abstract init only discovers the cache tree structure. The
        # block_table/context_len placeholder leaves are per-call
        # operands, not resident state — strip them.
        shapes = jax.eval_shape(
            lambda: self._decode_model.init(
                jax.random.key(0),
                jnp.ones((1, 1), jnp.int32),
                position_ids=jnp.zeros((1, 1), jnp.int32),
            )
        )["cache"]
        self._cache = self._place_pools(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), strip_tables(shapes)
        ))
        self._pages = PageAllocator(
            config.total_pages, config.page_size,
            config.pages_per_slot, config.num_slots,
        )
        # Shared-KV prefix cache (config.prefix_cache): trie over finished
        # prompts' fully-written page runs, beside the allocator.
        self._prefix = None
        if config.prefix_cache:
            from pytorch_distributed_training_tpu.serve.prefix_cache import (
                PrefixCache,
            )

            self._prefix = PrefixCache(self._pages)
        if self._draft_model is not None:
            dshapes = jax.eval_shape(
                lambda: self._draft_model.init(
                    jax.random.key(0),
                    jnp.ones((1, 1), jnp.int32),
                    position_ids=jnp.zeros((1, 1), jnp.int32),
                )
            )["cache"]
            self._draft_cache = self._place_pools(jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                strip_tables(dshapes),
            ))
        self._slots: list[Optional[_Slot]] = [None] * config.num_slots
        # the plain decode path keeps ONE step in flight across the tick
        # boundary (``_run_tick``): the step not yet retired, and the ids
        # of the newest dispatch, which the next step reads on the device
        self._inflight: Optional[_InFlight] = None
        self._prev_ids = self._put(np.zeros((config.num_slots,), np.int32))
        self.overlapped_ticks = 0       # ticks that dispatched, THEN retired
        self.discarded_slot_steps = 0   # dispatched, never emitted
        self._prefill_fns: dict[int, object] = {}   # bucket -> jitted fn
        self._decode_fn = None
        self._verify_fn_ = None         # spec_k > 0: the k+1-position program
        self._chunk_fn_ = None          # prefill_chunk > 0: the chunk program
        self._draft_decode_fn_ = None   # spec_draft="model" programs
        self._draft_prefill_fns: dict[int, object] = {}
        self._draft_chunk_fn_ = None
        self._copy_fn_ = None           # prefix_cache: COW page-copy program
        self._draft_copy_fn_ = None
        # Chunk-program width: prefill_chunk when chunked prefill is on;
        # a prefix-cache engine without it still needs the chunk program
        # for cache-hit TAIL prefills (which start at a nonzero context the
        # monolithic per-bucket programs cannot express) and uses one page
        # of tokens per tick.
        self._chunk_size = (
            config.prefill_chunk if config.prefill_chunk > 0
            else config.page_size
        )
        # speculation / chunked-prefill accounting (stats() + telemetry)
        self.spec_dispatches = 0        # verify dispatches executed
        self.spec_drafted = 0           # draft tokens proposed
        self.spec_accepted = 0          # draft tokens accepted
        self.decode_dispatches = 0      # decode-phase dispatches (any kind)
        self.decode_tokens = 0          # tokens emitted by decode-phase work
        self.prefill_chunks = 0         # chunk dispatches executed
        # prefix-cache accounting. prefill_tokens counts REAL prompt tokens
        # actually pushed through a prefill program (monolithic or chunk),
        # cache on or off: what the cache saves is read against it.
        self.prefill_tokens = 0
        # prompt tokens admissions took from cached pages instead
        self.prefix_cached_tokens = 0
        # routed-expert accounting over the decode steps (``_routed``):
        # steps counted, tokens routed to held experts (all layers), the
        # sum of each step's busiest expert, pairs routed to absent ones
        self.moe_steps = 0
        self.moe_held_tokens = 0
        self.moe_held_max_sum = 0
        self.moe_absent_pairs = 0
        self.cow_copies = 0             # COW page copies dispatched
        self.tenant_blocked = 0         # admissions held by tenant quota
        self._tenant_pages: dict[str, int] = {}  # tenant -> private pages
        self._slot_charge: dict[int, tuple] = {}  # slot -> (tenant, pages)
        self._match_scratch = None      # (req_id, PrefixMatch) from accept
        self.ticks = 0
        self.busy_ticks = 0         # ticks that admitted/decoded work — the
        # clock serve-scoped fault injection counts in
        self.admitted = 0
        self.finished = 0
        self.page_exhausted = 0     # ticks the FIFO head waited on pages
        self._page_blocked = False  # scratch flag for the admission pass
        # Overload ladder (serve/queue.py BrownoutController): the tick loop
        # feeds it queue pressure; the HTTP front-end reads its level at
        # admission. Optional — a None brownout means "never degrade".
        self.brownout = brownout
        # Observed drain rate (finished requests/sec, EWMA over ~1s windows):
        # the live half of the honest Retry-After estimate. Written only by
        # the engine thread; read as one float from HTTP threads.
        self.drain_rate = 0.0
        self._drain_window_t = time.monotonic()
        self._drain_window_finished = 0
        # liveness heartbeat: stamped at the end of every tick (including
        # idle ones — the serve loop re-ticks every idle-wait interval), so
        # /healthz can tell "loop wedged mid-tick" from "loop idle"
        self.last_tick_t = time.monotonic()
        # ---- observability plane (PR-16)
        # Request spans are emitted RETROACTIVELY at finish from the
        # request's monotonic stamps (engine thread only), so the hot path
        # adds counters, not emits.
        self.replica_name = replica_name
        if tracer is None:
            tracer = Tracer(registry=registry, component=replica_name or "engine")
        self.tracer = tracer
        if flight is None:
            from pytorch_distributed_training_tpu.telemetry.flight import (
                FlightRecorder,
            )

            flight = FlightRecorder(
                config.flight_capacity,
                component=replica_name or "engine",
                registry=registry,
            )
        self.flight = flight
        from pytorch_distributed_training_tpu.telemetry import flight as _flight_mod

        _flight_mod.register(self.flight)
        # Optional burn-rate monitor: the finish path feeds it outcomes.
        self.slo = slo
        # Swap windows the engine has applied: [t0, t1, version, variant,
        # outcome]. Engine-thread-only; requests whose lifetime intersects
        # a window get a swap_overlap span.
        self._swap_windows: deque = deque(maxlen=32)
        # scratch: events collected during the current tick for the flight
        # recorder entry (swap applied/committed/rollback, brownout moves)
        self._tick_events: list = []
        self._prev_brownout_level = 0
        # the current tick's closed phases, in closing order (engine thread)
        self._tick_phases: list = []

    def _resident_dtype(self, params):
        """``weights_dtype="bfloat16"``: every floating leaf resident in
        bfloat16 (leaves that already are stay untouched; any other
        setting leaves the tree as it is)."""
        if self.config.weights_dtype != "bfloat16":
            return params
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating)
            and x.dtype != jnp.bfloat16 else x, params)

    def _phase(self, name: str, rid: Optional[str] = None) -> Phase:
        """One phase of the current tick, ``serve_tick.<name>``: closed, it
        joins ``_tick_phases``. ``rid`` names the request it served."""
        return Phase(_TICK + name, self._tick_phases.append, ident=rid)

    # -------------------------------------------------------------- compiled

    def _put(self, tree):
        """ONE explicit H2D for host-built operands, committed to the
        engine's device — or, tensor-parallel, REPLICATED onto the mesh:
        every program input must live on all the mesh's devices
        (params/pools sharded, operands replicated), or dispatch would
        mix device-0-committed arrays with mesh-committed ones."""
        return jax.device_put(tree, self._repl)

    def _tables(self, pools, block_table, context_len, slot_ops=()):
        """``with_tables`` over what the model declared: each memory's
        node is handed its operands by its path, with a prefill's
        ``slot_ops`` (``_slot_ops``) where rings and states are kept by
        slot. A family that declared nothing has its page pools found by
        their leaves' names."""
        slot, chunk_len = slot_ops or (None, None)
        return with_tables(
            pools, block_table, context_len, memory=self._memory,
            slot=slot, chunk_len=chunk_len)

    def _slot_ops(self, slot: int, real: int) -> tuple:
        """The operands a batch-1 prefill needs beside its block-table row
        where rings and states are kept by slot: which slot it fills and
        how many of the step's tokens are real. None for a family whose
        memory is pages alone."""
        if all(m.kind == "pages" for m in self._memory):
            return ()
        return (np.asarray([slot], np.int32), np.asarray([real], np.int32))

    def _row_logits(self, model, params, cache, ids, positions, index):
        """Float32 logits [vocab] of row ``index()`` of a batch-1 prefill
        step, and the step's variables. A model that says it takes
        ``logit_index`` is told the row, and runs what keeps nothing for
        that row alone. (``index`` is a function so that the older families'
        programs compute it where they always did, after the model.)"""
        variables = {"params": params, "cache": cache}
        if getattr(model, "takes_logit_index", False):
            logits, vars_ = model.apply(
                variables, ids, position_ids=positions, mutable=["cache"],
                logit_index=index()[None])
            return logits[0, 0, :].astype(jnp.float32), vars_
        logits, vars_ = model.apply(
            variables, ids, position_ids=positions, mutable=["cache"])
        last = jnp.take_along_axis(
            logits, index()[None, None, None], axis=1
        )[0, 0, :].astype(jnp.float32)
        return last, vars_

    def _shardings_for(self, params):
        """What ``device_put`` places a serving params tree onto: the
        engine's one device, or per-leaf tp shardings over the mesh."""
        if self._mesh is None:
            return self._repl
        from pytorch_distributed_training_tpu.parallel.sharding import (
            serve_param_shardings,
        )

        return serve_param_shardings(params, self._mesh)

    def _place_pools(self, pools):
        """Commit a K/V pool tree to the engine's device — or shard it over
        the tp mesh: pools split by heads (each shard owns its own
        1/N-width page pool) while the page axis stays whole, so the
        allocator's block-table arithmetic is untouched. Value pools
        ``[pages, page_size, heads*head_dim]`` and (int8 cache) scale
        pools ``[pages, page_size, heads]`` both carry their heads on the
        last axis — one spec, one placement."""
        if self._mesh is None:
            return self._put(pools)
        from pytorch_distributed_training_tpu.parallel.sharding import (
            serve_pool_shardings,
        )

        return jax.device_put(
            pools, serve_pool_shardings(pools, self._mesh)
        )

    @property
    def param_shardings(self):
        """Where the serving params live (one device's sharding, or
        per-leaf NamedShardings under tp): hot-swap loaders ``device_put``
        replacement trees onto exactly this so a live swap keeps the
        compiled programs' input layouts (no recompile, no implicit
        reshard)."""
        return self._param_shardings

    def _serve_manifest(self, name: str):
        """Expected-collective manifest for one serve program. The
        single-device engine (tp=1, no mesh) pins ZERO collectives; the
        tensor-parallel engine pins exactly the head-sharding contract —
        all-reduce only, all-reduce REQUIRED, payload ceiling of 2
        activation-sized reductions per layer from the ring cost model
        (``serve_tp_manifest``), so a silently replicated weight (no
        collectives) and a weight all-gather (wrong kind + ceiling blown)
        both fail the audit. The audit costs one extra compile per
        program, so only the steady-state hot program of a warmed engine
        is audited — the single-token decode step, or the verify program
        when speculation replaces it — and the per-bucket/chunk prefills
        share its partitioning story (and already carry donation audits).
        Tests that skip warmup skip the manifest too."""
        if not self.config.warmup or name != self._hot_program():
            return None
        if self.config.tp > 1:
            mcfg = self._decode_model.config
            q = 1 + (self.config.spec_k if name == "serve_verify" else 0)
            # dtype-aware ceiling: the smallest sharded projection (the
            # hidden x hidden attention-out kernel) at the RESIDENT weight
            # byte width — 1 byte/element for weight-only int8 — so an
            # int8 replica's contract is pinned at the smaller count and a
            # program that moved even one weight matrix on top of its
            # activations fails the audit at compile time.
            wbytes = (
                1 if self.config.weights_dtype == "int8"
                else jnp.dtype(mcfg.param_dtype).itemsize
            )
            manifest = serve_tp_manifest(
                self.config.tp,
                layers=mcfg.num_layers,
                hidden=mcfg.hidden_size,
                max_q_tokens=self.config.num_slots * q,
                dtype_bytes=jnp.dtype(mcfg.compute_dtype).itemsize,
                name=name,
                weight_bytes_floor=mcfg.hidden_size * mcfg.hidden_size
                * wbytes,
            )
        else:
            manifest = serve_manifest(1, name=name)
        # the same compiled text also answers whether a resident pool is
        # rewritten whole (kv_pool_relayout_ops): hand the audit the
        # per-device element counts of the pools (a tp shard holds 1/N)
        return dataclasses.replace(
            manifest,
            kv_pool_elements=tuple(sorted({
                math.prod(leaf.sharding.shard_shape(leaf.shape))
                for leaf in jax.tree.leaves(self._cache)
            })),
            # the model's own named scopes (``program_scopes`` record) and
            # a latent model's row widths (``latent_row_gathers``,
            # ``window_row_gathers`` and the kernel's ``*_row_fetches``)
            trace_scopes=getattr(self._decode_model, "trace_scopes", ()),
            latent_row=getattr(self._decode_model.config, "latent_row", 0),
            window_row=getattr(self._decode_model.config, "window_row", 0),
        )

    def _hot_program(self) -> str:
        """The steady-state program of a tick, the one whose compiled text
        is audited: the verify program when speculation replaces the
        single-token decode step."""
        return "serve_verify" if self.config.spec_k > 0 else "serve_decode"

    def _prefill_fn(self, bucket: int):
        """Jitted prefill-into-slot for one prompt bucket. Compiles once per
        bucket (the queue only produces configured buckets).

        ``(params, pools, ids, real_len, bt_row, seed, temp, top_k,
        *slot_ops)``; returns ``(token id, new pools)``: the first token, a scalar int32
        sampled in-trace from the last real position's logits.
        """
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn

        def prefill(params, pools, ids, real_len, bt_row, seed, temp,
                    top_k, *slot_ops):
            # weight-only int8: dequantize in-trace (identity on fp32
            # trees) — XLA folds the broadcast multiply into the
            # matmuls, so only int8 kernels + scales stay resident
            params = dequantize_serve_params(params)
            # fresh sequence: context_len 0, K/V scattered straight
            # into the slot's pages through its block-table row
            cache = self._tables(
                pools, bt_row, jnp.zeros((1,), jnp.int32), slot_ops
            )
            last, vars_ = self._row_logits(
                self._decode_model, params, cache, ids,
                jnp.arange(bucket, dtype=jnp.int32)[None],
                lambda: real_len - 1,
            )
            new_pools = strip_tables(vars_["cache"])
            token = device_sample(
                last[None], seed[None], jnp.zeros((1,), jnp.int32),
                temp[None], top_k[None],
            )[0]
            return token, new_pools

        # the resident pools are rewritten every prefill: donate them so
        # XLA updates pages in place instead of holding a second full
        # copy alive across the call; audit_donation verifies
        # post-first-compile that XLA actually kept the aliasing
        fn = self._guards.wrap_jit(
            f"serve_prefill_b{bucket}",
            jax.jit(prefill, donate_argnums=(1,)),
            audit_donation=True,
            comm_manifest=self._serve_manifest(f"serve_prefill_b{bucket}"),
        )
        self._prefill_fns[bucket] = fn
        return fn

    def _decode_step_fn(self):
        """ONE jitted program advancing every slot a single token.

        ``(params, pools, prev_ids, fresh, tokens, bt, ctx, seeds, steps,
        temps, top_ks)``: batch-``num_slots`` apply with per-slot
        ``position_ids``/``context_len``; idle slots' block-table rows
        point at the null page, so their writes land there and their
        outputs are discarded by the host (no freeze select needed).
        A slot's input token never waits for the host: ``prev_ids`` is the
        ids array the step before returned, still on the device (not
        donated: the host fetches it AFTER this step is dispatched), and
        the host sends ``tokens`` only for the ``fresh`` slots, those with
        no step in flight (a prefill or last chunk has just handed them
        their first token, or their last step is retired already).
        Returns ``([slots] int32 token ids, new pools)``; a model with
        routed experts returns ``(ids, routing counts)`` in the ids' place.
        """
        if self._decode_fn is not None:
            return self._decode_fn
        routed = self._routed

        def decode(params, pools, prev_ids, fresh, tokens, bt, ctx, seeds,
                   steps, temps, top_ks):
            params = dequantize_serve_params(params)
            tokens = jnp.where(fresh, tokens, prev_ids)
            cache = self._tables(pools, bt, ctx)
            # a model with routed experts also hands back what the
            # step routed where (counted over the live slots: an idle
            # slot sits at context 0, which no live one does)
            extra = (
                {"token_mask": (ctx > 0)[:, None]} if routed else {}
            )
            logits, vars_ = self._decode_model.apply(
                {"params": params, "cache": cache},
                tokens[:, None],
                position_ids=ctx[:, None],
                mutable=["cache", "routing"] if routed else ["cache"],
                **extra,
            )
            new_pools = strip_tables(vars_["cache"])
            last = logits[:, 0, :].astype(jnp.float32)
            out = device_sample(last, seeds, steps, temps, top_ks)
            if routed:
                out = (out, routing_totals(vars_["routing"]))
            return out, new_pools

        # pools donated for the same reason as prefill: the decode tick
        # consumes the whole resident pools and returns the replacement
        # (audited post-first-compile, like prefill)
        self._decode_fn = self._guards.wrap_jit(
            "serve_decode",
            jax.jit(decode, donate_argnums=(1,)),
            audit_donation=True,
            comm_manifest=self._serve_manifest("serve_decode"),
        )
        return self._decode_fn

    def _verify_fn(self):
        """ONE jitted program scoring all ``spec_k + 1`` positions per slot
        and running exact-match acceptance on device.

        ``(params, pools, tokens, bt, ctx, seeds, steps0, temps, top_ks)``
        with ``tokens`` [slots, k+1] int32 — row = [pending, d1..dk] — and
        ``ctx`` [slots] the committed context length. The block is
        scattered at positions ctx..ctx+k and attends through the
        multi-token-query paged path; ``spec_accept`` samples every
        position with its own fold-in stream. Returns ``((target
        [slots, k+1], accept [slots]) int32, new pools)`` — the tick's
        whole D2H. Rejected drafts are "rolled back" by the HOST simply
        not advancing ctx past the accepted prefix; their K/V lanes are
        dead (masked by context_len) until overwritten.
        """
        if self._verify_fn_ is not None:
            return self._verify_fn_
        q_len = self.config.spec_k + 1

        def verify(params, pools, tokens, bt, ctx, seeds, steps0, temps,
                   top_ks):
            params = dequantize_serve_params(params)
            cache = with_tables(pools, bt, ctx)
            logits, vars_ = self._mq_model.apply(
                {"params": params, "cache": cache},
                tokens,
                position_ids=ctx[:, None]
                + jnp.arange(q_len, dtype=jnp.int32)[None, :],
                mutable=["cache"],
            )
            new_pools = strip_tables(vars_["cache"])
            target, accept = spec_accept(
                logits.astype(jnp.float32), tokens[:, 1:],
                seeds, steps0, temps, top_ks,
            )
            return (target, accept), new_pools

        self._verify_fn_ = self._guards.wrap_jit(
            "serve_verify",
            jax.jit(verify, donate_argnums=(1,)),
            audit_donation=True,
            comm_manifest=self._serve_manifest("serve_verify"),
        )
        return self._verify_fn_

    def _chunk_fn(self):
        """ONE jitted chunked-prefill program shared by every bucket and
        every chunk index (first, middle, ragged-last — the host pads the
        last chunk; pad lanes are invisible to real rows by the causal
        horizon and to later ticks by context_len, the same argument as
        monolithic-prefill padding).

        ``(params, pools, ids, ctx0, sample_idx, bt_row, seed, temp,
        top_k, *slot_ops)`` — ids [1, C] int32, ctx0 [1] int32 (tokens already
        scattered), sample_idx scalar int32 (chunk-local row of the
        prompt's LAST real token; only the final chunk's sample is used by
        the host). Returns ``(token_id, new pools)``.
        """
        if self._chunk_fn_ is not None:
            return self._chunk_fn_
        C = self._chunk_size

        def chunk(params, pools, ids, ctx0, sample_idx, bt_row, seed, temp,
                  top_k, *slot_ops):
            params = dequantize_serve_params(params)
            cache = self._tables(pools, bt_row, ctx0, slot_ops)
            last, vars_ = self._row_logits(
                self._mq_model, params, cache, ids,
                ctx0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :],
                lambda: sample_idx,
            )
            new_pools = strip_tables(vars_["cache"])
            token = device_sample(
                last[None], seed[None], jnp.zeros((1,), jnp.int32),
                temp[None], top_k[None],
            )[0]
            return token, new_pools

        self._chunk_fn_ = self._guards.wrap_jit(
            "serve_chunk",
            jax.jit(chunk, donate_argnums=(1,)),
            audit_donation=True,
            comm_manifest=self._serve_manifest("serve_chunk"),
        )
        return self._chunk_fn_

    def _draft_decode_fn(self):
        """Greedy single-token decode on the DRAFT model (spec_draft=
        "model"): same batched shape as the base decode step, writing into
        the draft pools through the shared block tables. Run ``spec_k + 1``
        times per tick (re-feeding the last committed token first, so the
        draft cache self-heals whatever the previous tick's acceptance
        was), collecting the k draft proposals."""
        if self._draft_decode_fn_ is not None:
            return self._draft_decode_fn_

        def draft_decode(params, pools, tokens, bt, ctx):
            params = dequantize_serve_params(params)
            cache = with_tables(pools, bt, ctx)
            logits, vars_ = self._draft_model.apply(
                {"params": params, "cache": cache},
                tokens[:, None],
                position_ids=ctx[:, None],
                mutable=["cache"],
            )
            new_pools = strip_tables(vars_["cache"])
            token = jnp.argmax(
                logits[:, 0, :].astype(jnp.float32), axis=-1
            ).astype(jnp.int32)
            return token, new_pools

        self._draft_decode_fn_ = self._guards.wrap_jit(
            "serve_draft_decode",
            jax.jit(draft_decode, donate_argnums=(1,)),
            audit_donation=True,
        )
        return self._draft_decode_fn_

    def _draft_prefill_fn(self, bucket: int):
        """Prompt prefill into the DRAFT pools (monolithic flavor): the
        draft lane needs the same committed context as the base model
        before it can propose continuations. The sampled head is never
        used — only the scattered K/V matters."""
        fn = self._draft_prefill_fns.get(bucket)
        if fn is not None:
            return fn

        def draft_prefill(params, pools, ids, bt_row):
            params = dequantize_serve_params(params)
            cache = with_tables(pools, bt_row, jnp.zeros((1,), jnp.int32))
            _, vars_ = self._draft_model.apply(
                {"params": params, "cache": cache},
                ids,
                position_ids=jnp.arange(bucket, dtype=jnp.int32)[None],
                mutable=["cache"],
            )
            return strip_tables(vars_["cache"])

        fn = self._guards.wrap_jit(
            f"serve_draft_prefill_b{bucket}",
            jax.jit(draft_prefill, donate_argnums=(1,)),
            audit_donation=True,
        )
        self._draft_prefill_fns[bucket] = fn
        return fn

    def _draft_chunk_fn(self):
        """Chunked-prefill mirror into the DRAFT pools (no sampling)."""
        if self._draft_chunk_fn_ is not None:
            return self._draft_chunk_fn_
        C = self._chunk_size

        def draft_chunk(params, pools, ids, ctx0, bt_row):
            params = dequantize_serve_params(params)
            cache = with_tables(pools, bt_row, ctx0)
            _, vars_ = self._draft_mq_model.apply(
                {"params": params, "cache": cache},
                ids,
                position_ids=ctx0[:, None]
                + jnp.arange(C, dtype=jnp.int32)[None, :],
                mutable=["cache"],
            )
            return strip_tables(vars_["cache"])

        self._draft_chunk_fn_ = self._guards.wrap_jit(
            "serve_draft_chunk",
            jax.jit(draft_chunk, donate_argnums=(1,)),
            audit_donation=True,
        )
        return self._draft_chunk_fn_

    @staticmethod
    def _page_copy(pools, src, dst):
        """Copy page ``src`` onto page ``dst`` in every pool leaf. The page
        axis leads every paged leaf — K/V pools and (int8 cache) scale
        pools alike — and is never sharded under tp (pools split on their
        last, heads-bearing axis only), so one shard-local gather/scatter
        covers every dtype and tp variant."""
        return jax.tree.map(lambda leaf: leaf.at[dst].set(leaf[src]), pools)

    def _copy_fn(self):
        """Jitted copy-on-write page copy over the BASE pools: a cache hit
        whose divergence point falls mid-page clones the partially-matching
        shared page into the slot's fresh private page before the tail
        prefill's first write (a slot never writes a page with
        refcount > 1). The stale lanes past the cached boundary are masked
        by ``context_len`` and overwritten by the tail prefill — the same
        dead-lane argument as prefill padding."""
        if self._copy_fn_ is not None:
            return self._copy_fn_
        self._copy_fn_ = self._guards.wrap_jit(
            "serve_cow_copy",
            jax.jit(self._page_copy, donate_argnums=(0,)),
            audit_donation=True,
        )
        return self._copy_fn_

    def _draft_copy_fn(self):
        """COW page copy over the DRAFT pools (spec_draft="model"): the
        shared block-table row addresses both pool sets, so a repointed
        entry needs the draft-side K/V cloned too."""
        if self._draft_copy_fn_ is not None:
            return self._draft_copy_fn_
        self._draft_copy_fn_ = self._guards.wrap_jit(
            "serve_draft_cow_copy",
            jax.jit(self._page_copy, donate_argnums=(0,)),
            audit_donation=True,
        )
        return self._draft_copy_fn_

    def _warm_chunk(self, draft: bool):
        """Compile + null-run the chunk program (and its draft mirror)."""
        cfg = self.config
        W = cfg.pages_per_slot
        ops = self._put((
            np.zeros((1, self._chunk_size), np.int32),
            np.zeros((1,), np.int32),
            np.int32(0),
            np.zeros((1, W), np.int32),
            np.int32(0), np.float32(0.0), np.int32(0),
            # slot 0, no real token: neither its state nor its rings move
            *self._slot_ops(0, 0),
        ))
        out, self._cache = self._chunk_fn()(
            self._params, self._cache, *ops
        )
        if draft:
            dops = self._put((
                np.zeros((1, self._chunk_size), np.int32),
                np.zeros((1,), np.int32),
                np.zeros((1, W), np.int32),
            ))
            self._draft_cache = self._draft_chunk_fn()(
                self._draft_params, self._draft_cache, *dops
            )
        return out

    def _warmup(self) -> None:
        """Compile every serving program (one prefill per bucket + the
        decode step) with null operands before the engine goes live.
        Warm-up calls run against the reserved null page (all-zero block
        tables), so they leave no state a real admit would see.
        Also the precondition for strict tick-wide transfer scoping: after
        warm-up, ``_scope_ready()`` holds from the first real tick.
        One ``serve_setup.warmup.<program>`` phase per compiled program
        (compiles are synchronous at dispatch) and ``.drain`` for the null
        executions."""
        cfg = self.config
        W = cfg.pages_per_slot
        draft = self._draft_model is not None
        outs = []

        def warm(program: str):
            return setup_phase(
                "serve_setup.warmup." + program, registry=self._registry)

        if cfg.prefill_chunk > 0:
            # ONE chunk program replaces the whole per-bucket prefill set
            with warm("chunk"):
                outs.append(self._warm_chunk(draft))
        else:
            for bucket in cfg.prompt_buckets:
                with warm(f"prefill_{bucket}"):
                    ops = self._put((
                        np.zeros((1, bucket), np.int32),
                        np.int32(1),
                        np.zeros((1, W), np.int32),
                        np.int32(0), np.float32(0.0), np.int32(0),
                        *self._slot_ops(0, 0),
                    ))
                    out, self._cache = self._prefill_fn(bucket)(
                        self._params, self._cache, *ops
                    )
                    outs.append(out)
                    if draft:
                        dops = self._put((
                            np.zeros((1, bucket), np.int32),
                            np.zeros((1, W), np.int32),
                        ))
                        self._draft_cache = self._draft_prefill_fn(bucket)(
                            self._draft_params, self._draft_cache, *dops
                        )
        if cfg.prefix_cache:
            if cfg.prefill_chunk == 0:
                # cold prefills stay monolithic, but cache-hit TAILS stream
                # through the chunk program — warm it too
                with warm("chunk"):
                    outs.append(self._warm_chunk(draft))
            # COW copy program: a null-page self-copy leaves no state
            with warm("copy"):
                pg = self._put((np.int32(0), np.int32(0)))
                self._cache = self._copy_fn()(self._cache, *pg)
                if draft:
                    self._draft_cache = self._draft_copy_fn()(
                        self._draft_cache, *pg
                    )
        S = cfg.num_slots
        # (block table, context, seeds, steps, temperatures, top-ks) of a
        # step; before them the decode step takes the fresh mask and the
        # tokens, the verify program k+1 tokens a slot
        step_ops = (
            np.zeros((S, W), np.int32),
            np.zeros((S,), np.int32),
            np.zeros((S,), np.int32), np.zeros((S,), np.int32),
            np.zeros((S,), np.float32), np.zeros((S,), np.int32),
        )
        if cfg.spec_k > 0:
            # verify replaces the single-token decode step entirely
            with warm("verify"):
                ops = self._put(
                    (np.zeros((S, cfg.spec_k + 1), np.int32),) + step_ops)
                out, self._cache = self._verify_fn()(
                    self._params, self._cache, *ops
                )
                outs.append(out)
                if draft:
                    dops = self._put((
                        np.zeros((S,), np.int32),
                        np.zeros((S, W), np.int32),
                        np.zeros((S,), np.int32),
                    ))
                    dout, self._draft_cache = self._draft_decode_fn()(
                        self._draft_params, self._draft_cache, *dops
                    )
                    outs.append(dout)
        else:
            with warm("decode"):
                # every slot fresh: the previous ids are read by none
                ops = self._put((
                    np.ones((S,), np.bool_), np.zeros((S,), np.int32),
                ) + step_ops)
                out, self._cache = self._decode_step_fn()(
                    self._params, self._cache, self._prev_ids, *ops
                )
                outs.append(out)
        # ONE sync for the whole warm-up batch (compiles are synchronous at
        # dispatch; this only drains the null executions)
        with warm("drain"):
            jax.block_until_ready(outs)

    def _scope_ready(self) -> bool:
        """True when the whole tick can run under the strict transfer
        scope: every program compiled+warm (a cold compile inside the
        scope would transfer its baked constants — that's what
        ``warmup=True`` is for)."""
        required = []
        if self.config.spec_k > 0:
            required.append(self._verify_fn_)
            if self._draft_model is not None:
                required.append(self._draft_decode_fn_)
        else:
            required.append(self._decode_fn)
        if self.config.prefill_chunk > 0 or self.config.prefix_cache:
            # cache-hit tails stream through the chunk program even when
            # cold prefills are monolithic
            required.append(self._chunk_fn_)
            if self._draft_model is not None:
                required.append(self._draft_chunk_fn_)
        if self.config.prefill_chunk == 0:
            for bucket in self.config.prompt_buckets:
                required.append(self._prefill_fns.get(bucket))
                if self._draft_model is not None:
                    required.append(self._draft_prefill_fns.get(bucket))
        if self.config.prefix_cache:
            required.append(self._copy_fn_)
            if self._draft_model is not None:
                required.append(self._draft_copy_fn_)
        return all(fn is not None and fn.warm for fn in required)

    # ------------------------------------------------------------- hot swap

    @property
    def params(self):
        """The currently-serving params tree (hot-swap loaders build their
        restore spec from it; reading the reference is thread-safe)."""
        return self._params

    @staticmethod
    def _params_spec(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef, [
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves
        ]

    def _validate_swap(self, params) -> None:
        """A replacement tree must match the running model exactly —
        anything else would retrace (new shapes/dtypes) or crash mid-tick
        (new structure). Checked BEFORE any engine state changes."""
        cur_def, cur_spec = self._params_spec(self._params)
        new_def, new_spec = self._params_spec(params)
        if cur_def != new_def:
            raise ValueError(
                "swap rejected: params tree structure does not match the "
                "running model"
            )
        for i, (cur, new) in enumerate(zip(cur_spec, new_spec)):
            if cur != new:
                raise ValueError(
                    f"swap rejected: leaf {i} is {new[0]}/{new[1]}, running "
                    f"model has {cur[0]}/{cur[1]} (shape/dtype mismatch — "
                    f"checkpoint from an incompatible model config)"
                )

    def _coerce_variant(self, params):
        """Convert an incoming swap tree to the engine's RESIDENT
        precision variant; returns ``(converted tree, incoming variant
        name)``. An fp32 publish swapping into an int8 engine is
        re-quantized (per-channel scales recomputed); an int8 publish
        swapping into an fp32 engine is dequantized. Matching variants
        pass through untouched. Because the resident representation never
        changes, a variant transition is an ordinary zero-retrace swap —
        the warm programs' input shapes/dtypes are invariant."""
        incoming = serve_params_variant(params)
        if incoming == self.variant:
            return self._resident_dtype(params), incoming
        if self.variant == "int8":
            return quantize_serve_params(params), incoming
        return self._resident_dtype(dequantize_serve_params(params)), incoming

    def request_swap(self, params, version: Optional[int]) -> SwapTicket:
        """Queue a validated weight swap from ANY thread; the serve loop
        applies it between ticks. Returns a ticket whose ``done`` event
        fires at commit or rollback. Raises ``ValueError`` on a tree that
        can't serve under the running model (nothing is queued) and
        ``RuntimeError`` while another swap is still in flight.
        Precision-variant aware: the incoming tree's variant (fp32 vs
        weight-only int8) is detected and coerced to the resident variant
        BEFORE validation, so a variant swap is an explicit admitted
        transition, recorded by name — not a shape/dtype rejection."""
        params, variant = self._coerce_variant(params)
        self._validate_swap(params)
        # re-place onto the SAME shardings the warm programs were compiled
        # against — a tree committed anywhere else (under tp: replicated,
        # or on device 0) would change the compiled input layouts and
        # build new programs
        placed = jax.device_put(params, self._param_shardings)
        with self._swap_lock:
            if self._pending_swap is not None:
                raise RuntimeError(
                    "a weight swap is already pending; one at a time"
                )
            ticket = SwapTicket(version)
            self._pending_swap = (placed, version, ticket, variant)
        return ticket

    def swap_params(self, params, version: Optional[int],
                    ticket: Optional[SwapTicket] = None, *,
                    variant: Optional[str] = None) -> None:
        """Atomically install ``params`` as the serving weights. MUST run
        between ticks (the serve loop calls it at tick start via
        ``request_swap``; direct calls are for single-threaded use). The
        resident KV state and the compiled programs are untouched — slots
        in flight continue on the new weights — and the previous params are
        kept alive until ``_commit_swap`` (first clean post-swap tick)."""
        if variant is None:
            # direct (single-threaded) callers get the same variant
            # coercion request_swap applies before queueing
            params, variant = self._coerce_variant(params)
        self._validate_swap(params)
        prev_params, prev_version = self._params, self.weights_step
        self._params = jax.device_put(params, self._param_shardings)
        self.weights_step = version
        self._trial = (prev_params, prev_version, ticket)
        self._last_swap_variant = variant
        if self._prefix is not None:
            # cached KV is a function of the weights that wrote it — every
            # entry is now wrong, not just stale. Flushed on APPLY (before
            # the trial tick, and kept flushed on rollback: conservative,
            # a rolled-back swap only costs re-prefills). In-flight slots
            # keep their already-mapped pages — their streams started
            # under the old weights and finish consistently; the flush
            # guarantees no POST-swap admission maps a pre-swap page.
            dropped = self._prefix.invalidate_all()
            if dropped:
                self._tick_events.append(f"prefix_invalidate:{dropped}")
            self._registry.inc("serve/prefix_invalidations")
        # open swap window: closed by commit/rollback; requests whose
        # lifetime intersects it get a swap_overlap span at finish
        self._swap_windows.append({
            "t0": time.monotonic(), "t1": None,
            "version": version, "variant": variant, "outcome": "open",
        })
        self._tick_events.append(f"swap_applied:{version}")
        self._registry.inc("serve/swaps_applied")
        self._registry.emit({
            "record": "swap_applied",
            "version": version,
            "from_version": prev_version,
            # which precision variant was PUBLISHED (the resident variant
            # it was coerced to is fixed per engine: stats()["variant"])
            "variant": variant,
        })

    def _close_swap_window(self, outcome: str) -> None:
        if self._swap_windows and self._swap_windows[-1]["t1"] is None:
            self._swap_windows[-1]["t1"] = time.monotonic()
            self._swap_windows[-1]["outcome"] = outcome

    def _commit_swap(self) -> None:
        _prev, _prev_version, ticket = self._trial
        self._trial = None
        self.swaps += 1
        self._close_swap_window("committed")
        self._tick_events.append(f"swap_committed:{self.weights_step}")
        self._registry.inc("serve/swaps")
        self._registry.gauge("serve/weights_step", self.weights_step)
        self._registry.emit({
            "record": "swap_committed",
            "version": self.weights_step,
            "variant": self._last_swap_variant,
        })
        if ticket is not None:
            ticket.resolve(True)

    def _rollback_swap(self, error: str) -> None:
        """The first post-swap tick failed: restore the previous params
        (never donated, still alive) and record the failure. The KV cache
        may hold a torn tick's state only if the failure happened INSIDE a
        compiled call — the deterministic drills fire before dispatch, and
        a genuinely torn cache is the serve loop failure path's problem."""
        prev_params, prev_version, ticket = self._trial
        self._trial = None
        failed_version = self.weights_step
        self._params = prev_params
        self.weights_step = prev_version
        self.swap_rollbacks += 1
        self._close_swap_window("rollback")
        self._tick_events.append(f"swap_rollback:{failed_version}")
        self._registry.inc("serve/swap_rollbacks")
        self._registry.emit({
            "record": "swap_failed",
            "version": failed_version,
            "stage": "tick",
            "error": error,
        })
        self._registry.emit({
            "record": "swap_rollback",
            "from_version": failed_version,
            "to_version": prev_version,
            "stage": "tick",
        })
        logger.error(
            "post-swap tick failed (%s); rolled back to weights step %s",
            error, prev_version,
        )
        if ticket is not None:
            ticket.resolve(False, error=error, stage="tick")

    # ------------------------------------------------------------ accounting

    def _emit_request_record(self, req: GenRequest) -> None:
        reg = self._registry
        n = len(req.tokens)
        queue_wait = (
            req.admit_t - req.submit_t if req.admit_t is not None else None
        )
        ttft = (
            req.first_token_t - req.submit_t
            if req.first_token_t is not None
            else None
        )
        decode_s = (
            req.finish_t - req.first_token_t
            if req.finish_t is not None and req.first_token_t is not None
            else None
        )
        tpot = decode_s / (n - 1) if decode_s is not None and n > 1 else None
        reg.emit({
            "record": "serve_request",
            "id": req.id,
            "tier": req.tier,
            "status": req.status,
            "finish_reason": req.finish_reason,
            "prompt_len": req.prompt_len,
            "cached_tokens": req.cached_tokens,
            "chunks": req.chunks,
            "bucket": req.bucket,
            "new_tokens": n,
            "queue_wait_s": queue_wait,
            "ttft_s": ttft,
            "tpot_s": tpot,
            "total_s": (
                req.finish_t - req.submit_t
                if req.finish_t is not None
                else None
            ),
            # which weights version produced this answer — the join key a
            # rollout post-mortem needs (mid-rollout, different replicas
            # legitimately answer from different steps)
            "weights_step": self.weights_step,
        })

    def _emit_spans(self, req: GenRequest) -> None:
        """Retroactively emit the request's span tree from its monotonic
        stamps (engine thread, at finish). The replica phases TILE the
        request exactly — queue is submit→admit, prefill is admit→first
        token, decode is first token→finish — so per-phase durations sum
        to the serve span's total by construction (tests/test_obs.py).
        A request that never left the queue gets a queue span covering its
        whole life; ``admission`` (page reservation) nests under prefill;
        ``swap_overlap``/``brownout_clamp`` annotate what touched it."""
        tr = self.tracer
        trace = req.id
        base_attrs = {
            "tier": req.tier,
            "status": req.status,
            "finish_reason": req.finish_reason,
            "weights_step": self.weights_step,
            "variant": self.variant,
        }
        if self.replica_name:
            base_attrs["replica"] = self.replica_name
        serve = tr.begin(
            trace, "serve", parent=req.trace_parent, t0=req.submit_t,
            attrs={**base_attrs, "bucket": req.bucket,
                   "new_tokens": len(req.tokens)},
        )
        admit = req.admit_t
        queue_end = admit if admit is not None else req.finish_t
        q = tr.begin(trace, "queue", parent=serve.span, t0=req.submit_t,
                     attrs={"tier": req.tier})
        tr.end(q, t1=queue_end)
        if admit is not None:
            first = req.first_token_t
            prefill_end = first if first is not None else req.finish_t
            p = tr.begin(
                trace, "prefill", parent=serve.span, t0=admit,
                attrs={"bucket": req.bucket, "chunks": req.chunks,
                       "tick": req.admit_tick},
            )
            if req.reserve_t is not None:
                attrs = {"pages": self._pages_for(req)}
                if self._prefix is not None:
                    attrs["prefix_hit"] = req.prefix_hit
                    attrs["cached_tokens"] = req.cached_tokens
                a = tr.begin(trace, "admission", parent=p.span, t0=admit,
                             attrs=attrs)
                tr.end(a, t1=req.reserve_t)
            tr.end(p, t1=prefill_end)
            if first is not None:
                d = tr.begin(
                    trace, "decode", parent=serve.span, t0=first,
                    attrs={
                        "ticks": req.decode_ticks,
                        "tokens": len(req.tokens),
                        "drafted": req.drafted,
                        "accepted": req.accepted,
                    },
                )
                tr.end(d, t1=req.finish_t)
        if req.clamped_from is not None:
            tr.event(
                trace, "brownout_clamp", parent=serve.span, t=req.submit_t,
                attrs={"from_max_new": req.clamped_from,
                       "to_max_new": req.max_new_tokens},
            )
        for w in self._swap_windows:
            hi = w["t1"] if w["t1"] is not None else req.finish_t
            lo = max(w["t0"], req.submit_t)
            hi = min(hi, req.finish_t)
            if hi > lo:
                s = tr.begin(
                    trace, "swap_overlap", parent=serve.span, t0=lo,
                    attrs={"version": w["version"], "variant": w["variant"],
                           "outcome": w["outcome"]},
                )
                tr.end(s, t1=hi)
        tr.end(serve, t1=req.finish_t)

    def _finish(self, req: GenRequest, status: str, reason: str) -> None:
        req.status = status
        req.finish_reason = reason
        req.finish_t = time.monotonic()
        self.finished += 1
        self._registry.inc(f"serve/finished_{status}")
        self._emit_request_record(req)
        self._emit_spans(req)
        if self.slo is not None and status != "cancelled":
            # expired requests WERE served capacity-wise but missed their
            # deadline; only hard errors count against availability here
            # (sheds/rejections are fed by the front-end and router)
            self.slo.observe(
                req.tier,
                available=status != "error",
                deadline_met=(
                    None if req.deadline_s is None else status == "done"
                ),
            )
        cb = req.on_finish
        if cb is not None:
            try:
                cb(req)
            except Exception:  # pragma: no cover - user callback
                logger.exception("on_finish callback failed for %s", req.id)
        req.done.set()

    def _emit_token(self, req: GenRequest, token: int) -> None:
        now = time.monotonic()
        if req.first_token_t is None:
            req.first_token_t = now
        req.tokens.append(int(token))
        self._registry.inc("serve/tokens")
        cb = req.stream
        if cb is not None:
            try:
                cb(req, int(token))
            except Exception:  # pragma: no cover - user callback
                logger.exception("stream callback failed for %s", req.id)

    # ----------------------------------------------------------------- slots

    def slot_occupancy(self) -> float:
        n = sum(1 for s in self._slots if s is not None)
        return n / len(self._slots)

    def page_occupancy(self) -> float:
        """Fraction of the KV page pool in use — an autoscaler pressure
        signal alongside queue depth."""
        total = self._pages.num_pages - 1
        return self._pages.pages_used / total if total > 0 else 0.0

    def page_split(self) -> tuple[int, int]:
        """(shared, free) page counts for /healthz — how much of the pool
        is multi-referenced (prefix cache + in-flight sharers) vs
        immediately allocatable."""
        return (self._pages.pages_shared, self._pages.pages_free)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _evict(self, slot: int) -> None:
        """Free ``slot`` for reuse and return its pages."""
        self._slots[slot] = None
        self._release_pages(slot)

    def _release_pages(self, slot: int) -> None:
        """Drop ``slot``'s page references (shared pages survive in other
        rows / the prefix cache) and return its quota charge to the
        tenant. Every release path funnels through here so the per-tenant
        private-page ledger can never drift from the allocator."""
        self._pages.release(slot)
        charge = self._slot_charge.pop(slot, None)
        if charge is not None:
            tenant, n = charge
            left = self._tenant_pages.get(tenant, 0) - n
            if left > 0:
                self._tenant_pages[tenant] = left
            else:
                self._tenant_pages.pop(tenant, None)

    def _charge_tenant(self, slot: int, tenant: Optional[str],
                       n: int) -> None:
        """Ledger ``n`` freshly-allocated (private) pages against
        ``tenant``'s quota for the lifetime of ``slot``'s reservation.
        Shared prefix pages are free by design."""
        if self.config.tenant_page_quota <= 0.0 or tenant is None:
            return
        self._tenant_pages[tenant] = self._tenant_pages.get(tenant, 0) + n
        self._slot_charge[slot] = (tenant, n)

    def _tenant_quota_pages(self) -> int:
        """Private-page ceiling per tenant (fraction of the usable pool)."""
        return max(
            1, int(self.config.tenant_page_quota * (self._pages.num_pages - 1))
        )

    def _pages_for(self, req: GenRequest) -> int:
        """Up-front page reservation for one request: the worst case —
        bucket + the request's max_new_tokens — plus the speculative
        overshoot (``spec_k`` draft positions scattered past the committed
        context before acceptance is known; reserved for EVERY request
        when speculation is on, since non-spec slots ride the same verify
        dispatch and its scatter). This is the documented budget formula:
        with it, ``page_exhausted`` can never fire for an admitted slot."""
        return self._pages.pages_reserved(
            req.bucket + req.max_new_tokens, self.config.spec_k
        )

    def _admission_fits(self, req: GenRequest) -> bool:
        """Page-budget admission predicate (``RequestQueue.pop_ready``):
        the whole worst case must be allocatable up front, so an admitted
        request can never starve mid-decode.

        With the prefix cache on, the trie match happens HERE (and is
        stashed for the admit that immediately follows a True return):
        only the TAIL pages — reservation minus fully-matched shared pages
        — must come from the free list, a tenant over its private-page
        quota is held without counting as page exhaustion, and page
        pressure first tries LRU-evicting cache-only runs before declaring
        the head blocked."""
        need = self._pages_for(req)
        match = None
        if self._prefix is not None:
            # only prompt[:-1] is matchable: the tail prefill must cover at
            # least the last prompt token (it samples the first output),
            # which also keeps every later decode/verify write strictly
            # past the shared full-page region
            match = self._prefix.match(
                [int(t) for t in req.prompt_ids[: req.prompt_len - 1]]
            )
            self._match_scratch = (req.id, match)
            # free-list draw: fresh tail pages + the COW private copy
            # (the partially-matched page itself is mapped, not drawn)
            need -= len(match.pages)
        if (
            self.config.tenant_page_quota > 0.0
            and req.tenant is not None
            and self._tenant_pages.get(req.tenant, 0) + need
            > self._tenant_quota_pages()
        ):
            self.tenant_blocked += 1
            self._registry.inc("serve/tenant_blocked")
            return False
        if self._pages.can_alloc(need):
            return True
        if self._prefix is not None:
            # page pressure: drop idle cached runs (LRU, refcount-1 only)
            # before giving up — but never the pages this very match is
            # about to map
            protect = set(match.pages)
            if match.cow_src is not None:
                protect.add(match.cow_src)
            if self._prefix.evict_until(
                need - self._pages.pages_free, protect=protect
            ) and self._pages.can_alloc(need):
                return True
        self._page_blocked = True
        return False

    def _take_match(self, req: GenRequest):
        """Consume the trie match stashed by ``_admission_fits`` for the
        request that was just popped (None when the cache is off). The
        accept that returns True is always the LAST one before the pop,
        so a single scratch slot suffices; the id check is a guard against
        that invariant ever breaking."""
        if self._prefix is None:
            return None
        stashed, self._match_scratch = self._match_scratch, None
        if stashed is not None and stashed[0] == req.id:
            return stashed[1]
        # accept was skipped or stale (shouldn't happen): re-match
        return self._prefix.match(
            [int(t) for t in req.prompt_ids[: req.prompt_len - 1]]
        )

    def _prefill_resident(self) -> int:
        return sum(
            1 for s in self._slots if s is not None and s.phase == "prefill"
        )

    def _admission_defer(self, req: GenRequest) -> bool:
        """Transient chunked-prefill residency hold (``pop_ready(defer=)``):
        while ``prefill_concurrency`` slots are still streaming prompts in,
        new admissions wait a tick. Checked BEFORE the page predicate so a
        hold never inflates ``page_exhausted`` — the mid-prefill slot keeps
        getting chunk ticks instead of being starved by admission work."""
        return self._prefill_resident() >= self.config.prefill_concurrency

    def _slot_spec(self, req: GenRequest) -> bool:
        """Resolve the request's speculative opt-in/out against the engine
        default (on whenever spec_k > 0)."""
        if self.config.spec_k <= 0:
            return False
        return req.spec if req.spec is not None else True

    def _admit_chunked(self, req: GenRequest, slot: int) -> None:
        """Chunked admission: reserve the slot + pages and let the tick
        loop stream the prompt in ``prefill_chunk`` tokens at a time (the
        first dispatch happens on the SAME tick via ``_advance_prefills``
        order — admission itself is pure bookkeeping)."""
        self._reserve(req, slot)
        self._slots[slot] = _Slot(
            request=req, pending_token=-1, phase="prefill",
            prefill_pos=0, spec=self._slot_spec(req),
        )

    def _admit_hit(self, req: GenRequest, slot: int, match) -> None:
        """Prefix-cache-hit admission: map the shared full pages into the
        slot's block-table row (read-only — refcounts bumped), COW-copy
        the partially-matched page when the divergence point falls
        mid-page, and leave the slot in prefill phase at the cached
        boundary — the tick loop streams only the TAIL through the chunk
        program. Reservation draws only ``reserved - full`` pages from the
        free list; the request's worst case is still fully covered, so
        ``page_exhausted`` can never fire mid-flight."""
        self._mark_admitted(req)
        reserved = self._pages_for(req)
        shared = list(match.pages)
        cow = match.cow_src is not None
        if cow:
            shared.append(match.cow_src)
        self._pages.admit_shared(slot, shared, reserved - len(shared))
        self._charge_tenant(slot, req.tenant, reserved - len(match.pages))
        req.reserve_t = time.monotonic()
        try:
            if cow:
                # private copy BEFORE the tail prefill's first write: the
                # slot must never write a page with refcount > 1. Stale
                # lanes past cached_len in the copy are masked by
                # context_len and overwritten by the tail prefill.
                old, new = self._pages.cow(slot, len(match.pages))
                ops = self._put((np.int32(old), np.int32(new)))
                with watchdog_guard("serve_prefill"), \
                        self._phase("dispatch", req.id):
                    self._cache = self._copy_fn()(self._cache, *ops)
                    if self._draft_model is not None:
                        self._draft_cache = self._draft_copy_fn()(
                            self._draft_cache, *ops
                        )
                self.cow_copies += 1
                self._registry.inc("serve/cow_copies_total")
        except BaseException:
            self._release_pages(slot)
            raise
        req.prefix_hit = True
        req.cached_tokens = match.cached_len
        self.prefix_cached_tokens += match.cached_len
        self._slots[slot] = _Slot(
            request=req, pending_token=-1, phase="prefill",
            prefill_pos=match.cached_len, spec=self._slot_spec(req),
        )

    def _insert_prefix(self, slot: int, req: GenRequest) -> None:
        """Index the just-prefilled prompt's FULL pages in the trie (the
        cache takes its own reference on each newly-indexed page, so they
        survive the slot's release). Called after the prefill dispatch
        that wrote the last prompt position — bucket/chunk padding never
        lands in the first ``prompt_len // page_size`` pages, so every
        indexed lane holds real K/V."""
        if self._prefix is None:
            return
        ps = self.config.page_size
        full = req.prompt_len // ps
        if full <= 0:
            return
        self._prefix.insert(
            [int(t) for t in req.prompt_ids[: full * ps]],
            self._pages.slot_pages(slot)[:full],
        )

    def _mark_admitted(self, req: GenRequest) -> None:
        req.status = "running"
        req.admit_t = time.monotonic()
        # the tick that admitted it: the request's ``prefill`` span names
        # it, as that tick's ``prefill`` phase names the request
        req.admit_tick = self.ticks + 1
        self.admitted += 1
        self._registry.inc("serve/admitted")

    def _reserve(self, req: GenRequest, slot: int) -> None:
        """The bookkeeping of an admission: the request is running, its
        worst-case pages are the slot's and charged to its tenant."""
        self._mark_admitted(req)
        pages = self._pages_for(req)
        self._pages.admit(slot, pages)
        self._charge_tenant(slot, req.tenant, pages)
        req.reserve_t = time.monotonic()

    def _admit(self, req: GenRequest, slot: int) -> None:
        """Prefill ``req`` into ``slot`` (reserved) and take its first
        token: one ``prefill`` phase, the wait for the token inside it."""
        with self._phase("prefill", req.id) as phase:
            bucket = req.bucket
            phase.attrs = {"bucket": bucket, "prompt_len": req.prompt_len}
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : req.prompt_len] = req.prompt_ids
            try:
                # ONE explicit H2D for all host-built operands (np →
                # device); under the strict tick-wide transfer scope,
                # explicit device_put/device_get are the only transfers a
                # tick makes
                ops = self._put((
                    padded,
                    np.int32(req.prompt_len),
                    self._pages.block_table[slot : slot + 1],
                    np.int32(req.seed),
                    np.float32(req.temperature),
                    np.int32(min(req.top_k, np.iinfo(np.int32).max)),
                    *self._slot_ops(slot, req.prompt_len),
                ))
                with watchdog_guard("serve_prefill"):
                    out, self._cache = self._prefill_fn(bucket)(
                        self._params, self._cache, *ops
                    )
                    if self._draft_model is not None:
                        # mirror the prompt into the draft pools (same
                        # block-table row, draft-side K/V) so the draft
                        # lane shares the slot's committed context from its
                        # first tick
                        dops = self._put((
                            padded,
                            self._pages.block_table[slot : slot + 1],
                        ))
                        self._draft_cache = self._draft_prefill_fn(bucket)(
                            self._draft_params, self._draft_cache, *dops
                        )
                    # explicit d2h (np.asarray would be an implicit
                    # transfer — the exact pattern the transfer guard
                    # disallows on chips)
                    with self._phase("prefill_wait", req.id):
                        token = int(jax.device_get(out))
            except BaseException:
                # failed admissions must not leak the pages just reserved
                self._release_pages(slot)
                raise
            self.prefill_tokens += req.prompt_len
            # index the prompt's full pages BEFORE any release below:
            # the cache's own reference keeps them alive past the slot
            self._insert_prefix(slot, req)
            self._emit_token(req, token)
            if self._is_terminal(req, token):
                self._release_pages(slot)
                return
            self._slots[slot] = _Slot(
                request=req, pending_token=token, spec=self._slot_spec(req)
            )

    def _is_terminal(self, req: GenRequest, token: int) -> bool:
        """Finish ``req`` if ``token`` completed it; True when finished."""
        if req.eot_id is not None and token == req.eot_id:
            self._finish(req, "done", "eot")
            return True
        if len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "done", "length")
            return True
        return False

    # ------------------------------------------------------- chunked prefill

    def _advance_prefills(self, attrs: dict) -> bool:
        """Stream one ``prefill_chunk``-token chunk into every mid-prefill
        slot (one batch-1 dispatch each through the shared chunk program).
        A prompt's last chunk blocks the tick for its first token, so the
        decode step in flight is retired just before it (``_retire``, which
        writes to the tick record's ``attrs``); a chunk before the last
        fetches nothing and is only queued behind that step.
        The final chunk is ragged: ids are zero-padded, the prompt's last
        real token's row is sampled, and the pad lanes are dead by the
        causal horizon now and by ``context_len`` forever after — the same
        argument that makes monolithic-prefill padding safe. On the final
        chunk the slot flips to decode phase with its first token emitted;
        decode ticks for OTHER slots keep running between chunks, which is
        the whole point (a long prompt no longer stalls short requests)."""
        C = self._chunk_size
        chunks = 0
        for i, s in enumerate(self._slots):
            if s is None or s.phase != "prefill":
                continue
            req = s.request
            start = s.prefill_pos
            end = min(start + C, req.prompt_len)
            ids = np.zeros((1, C), np.int32)
            ids[0, : end - start] = req.prompt_ids[start:end]
            is_last = end >= req.prompt_len
            if is_last:
                self._retire(attrs)
            sample_idx = (
                np.int32(req.prompt_len - 1 - start) if is_last
                else np.int32(0)
            )
            with watchdog_guard("serve_prefill"), \
                    self._phase("prefill", req.id):
                ops = self._put((
                    ids,
                    np.asarray([start], np.int32),
                    sample_idx,
                    self._pages.block_table[i : i + 1],
                    np.int32(req.seed),
                    np.float32(req.temperature),
                    np.int32(min(req.top_k, np.iinfo(np.int32).max)),
                    *self._slot_ops(i, end - start),
                ))
                out, self._cache = self._chunk_fn()(
                    self._params, self._cache, *ops
                )
                if self._draft_model is not None:
                    dops = self._put((
                        ids,
                        np.asarray([start], np.int32),
                        self._pages.block_table[i : i + 1],
                    ))
                    self._draft_cache = self._draft_chunk_fn()(
                        self._draft_params, self._draft_cache, *dops
                    )
                fetched = None
                if is_last:
                    with self._phase("prefill_wait", req.id):
                        fetched = jax.device_get(out)
            self.prefill_chunks += 1
            req.chunks += 1
            chunks += 1
            self.prefill_tokens += end - start
            s.prefill_pos = end
            if is_last:
                # index the now fully-written prompt pages before any
                # terminal release (the cache ref keeps them alive)
                self._insert_prefix(i, req)
                token = int(fetched)
                self._emit_token(req, token)
                if self._is_terminal(req, token):
                    self._evict(i)
                else:
                    s.phase = "decode"
                    s.pending_token = token
                    s.steps_done = 0
        if chunks:
            self._registry.gauge("serve/prefill_chunks", chunks)
        return chunks > 0

    # ------------------------------------------------------------- drafting

    @staticmethod
    def _ngram_draft(hist: list, k: int) -> list:
        """Prompt-lookup self-drafting (zero dispatches): find the most
        recent EARLIER occurrence of the trailing bigram (unigram
        fallback) in the slot's own prompt+output history and propose its
        historical continuation, padded by repeating the last proposal.
        Wrong guesses only cost acceptance — verification makes the
        emitted stream independent of draft quality."""
        out = []
        for n in (2, 1):
            if len(hist) <= n:
                continue
            pat = hist[-n:]
            for i in range(len(hist) - n - 1, -1, -1):
                if hist[i : i + n] == pat:
                    out = list(hist[i + n : i + n + k])
                    break
            if out:
                break
        while len(out) < k:
            out.append(out[-1] if out else hist[-1])
        return out[:k]

    def _last_committed_token(self, s: _Slot) -> int:
        """The token whose K/V sits at position ctx-1 (last FED token):
        the newest generated-and-fed token, or the prompt's last real
        token right after prefill."""
        r = s.request
        if s.steps_done >= 1:
            return int(r.tokens[s.steps_done - 1])
        return int(r.prompt_ids[r.prompt_len - 1])

    def _model_drafts(self, spec_slots) -> np.ndarray:
        """Draft-model lane: k+1 batched greedy single-token dispatches on
        the draft model. The FIRST feed re-writes the last committed
        token at ctx-1 — idempotent K/V resync that heals the one position
        a fully-accepted previous tick never fed the draft — then the
        pending token and each proposal feed forward. Only spec slots get
        real block-table rows; everyone else parks on the null page."""
        with self._phase("operands"):
            cfg = self.config
            S, k = cfg.num_slots, cfg.spec_k
            drafts = np.zeros((S, k), np.int32)
            toks = np.zeros((S,), np.int32)
            ctx = np.zeros((S,), np.int32)
            bt = np.zeros_like(self._pages.block_table)
            for i in spec_slots:
                s = self._slots[i]
                toks[i] = self._last_committed_token(s)
                ctx[i] = s.request.prompt_len + s.steps_done - 1
                bt[i] = self._pages.block_table[i]
            pending = np.zeros((S,), np.int32)
            inc = np.zeros((S,), np.int32)
            for i in spec_slots:
                pending[i] = self._slots[i].pending_token
                inc[i] = 1
        fn = self._draft_decode_fn()
        outs = []
        with watchdog_guard("serve_decode"):
            # the autoregressive chain stays ON DEVICE: dispatch j >= 2
            # feeds dispatch j-1's output array directly (no host sync in
            # the loop), and the k proposals come back in ONE device_get.
            # Dispatch 0's output is discarded — it only resyncs the
            # draft cache at ctx-1; dispatch 1 feeds the pending token.
            with self._phase("dispatch"):
                bt_d = self._put(bt)
                feed = self._put(toks)
                for j in range(k + 1):
                    out, self._draft_cache = fn(
                        self._draft_params, self._draft_cache, feed,
                        bt_d, self._put(ctx),
                    )
                    outs.append(out)
                    feed = self._put(pending) if j == 0 else out
                    ctx = ctx + inc
            with self._phase("decode_wait"):
                proposals = np.stack(jax.device_get(outs[1:]), axis=1)
        for i in spec_slots:
            drafts[i] = proposals[i]
        return drafts

    # ---------------------------------------------------------- verify tick

    def _verify_tick(self, active) -> None:
        """ONE verify dispatch advancing every decode-phase slot 1..k+1
        tokens: draft (host n-gram or draft model), score all k+1
        positions, accept the leading exact-match run on device, emit the
        accepted tokens plus the first divergence's stream sample.
        Non-spec slots ride the same dispatch with their acceptance forced
        to 0 — they emit exactly the one token the decode step would.
        Rollback is implicit: the slot's context cursor only
        advances past what was accepted; rejected drafts' K/V lanes die by
        masking and are overwritten when their positions are legitimately
        reached (zero allocator churn, pinned by tests)."""
        cfg = self.config
        S, k = cfg.num_slots, cfg.spec_k
        Q = k + 1
        spec_slots = [i for i in active if self._slots[i].spec]
        if self._draft_model is not None and spec_slots:
            # the draft lane has a dispatch and a wait of its own
            drafts = self._model_drafts(spec_slots)
        else:
            with self._phase("operands"):
                drafts = np.zeros((S, k), np.int32)
                for i in spec_slots:
                    s = self._slots[i]
                    r = s.request
                    hist = [int(t) for t in r.prompt_ids[: r.prompt_len]]
                    hist.extend(int(t) for t in r.tokens)
                    drafts[i] = self._ngram_draft(hist, k)
        with self._phase("operands"):
            tokens = np.zeros((S, Q), np.int32)
            ctx = np.zeros((S,), np.int32)
            seeds = np.zeros((S,), np.int32)
            steps0 = np.zeros((S,), np.int32)
            temps = np.zeros((S,), np.float32)
            top_ks = np.zeros((S,), np.int32)
            # sanitized block table: mid-prefill slots hold REAL pages but
            # are not in this dispatch — their rows must read as the null
            # page or the verify scatter would stomp their streamed prompt
            # K/V
            bt = np.zeros_like(self._pages.block_table)
            for i in active:
                s = self._slots[i]
                r = s.request
                tokens[i, 0] = s.pending_token
                tokens[i, 1:] = drafts[i] if s.spec else s.pending_token
                ctx[i] = r.prompt_len + s.steps_done
                seeds[i] = np.int32(r.seed)
                steps0[i] = s.steps_done + 1   # == len(r.tokens) at sample
                temps[i] = r.temperature
                top_ks[i] = min(r.top_k, np.iinfo(np.int32).max)
                bt[i] = self._pages.block_table[i]
            ops = self._put(
                (tokens, bt, ctx, seeds, steps0, temps, top_ks)
            )
        with watchdog_guard("serve_decode"):
            with self._phase("dispatch"):
                out, self._cache = self._verify_fn()(
                    self._params, self._cache, *ops
                )
            # the tick's D2H: per-position stream samples + accept counts
            with self._phase("decode_wait"):
                target, accept = jax.device_get(out)
        with self._phase("emit"):
            self.spec_dispatches += 1
            self.decode_dispatches += 1
            emitted = 0
            accepted = 0
            for i in active:
                s = self._slots[i]
                r = s.request
                a = int(accept[i]) if s.spec else 0
                r.decode_ticks += 1
                if s.spec:
                    self.spec_drafted += k
                    self.spec_accepted += a
                    r.drafted += k
                    r.accepted += a
                    accepted += a
                finished = False
                for j in range(a + 1):
                    token = int(target[i, j])
                    s.steps_done += 1
                    self._emit_token(r, token)
                    emitted += 1
                    if self._is_terminal(r, token):
                        self._evict(i)
                        finished = True
                        break
                if not finished:
                    s.pending_token = int(target[i, a])
            self.decode_tokens += emitted
            if spec_slots:
                self._registry.gauge(
                    "serve/spec_accept_rate", accepted / (k * len(spec_slots))
                )
            self._registry.gauge(
                "serve/tokens_per_dispatch", emitted / len(active)
            )

    # ------------------------------------------------------------------ tick

    def tick(self) -> bool:
        """One engine iteration: apply a pending weight swap, then expire,
        admit, decode one token for every active slot. Returns True when
        any work happened (the serve loop idles on the queue condition
        otherwise).

        The order of a plain decode tick (``spec_k == 0``): ONE decode step
        stays in flight across the tick boundary. Tick k does its host work
        (expire, admit, operands) and dispatches step k while step k-1
        still runs, and only then fetches, emits and retires step k-1: in
        the steady state the gap between two tokens is the device's step,
        not step + host. A slot therefore has two counters, steps
        DISPATCHED (``_Slot.steps_done``: context, the sampler's index, the
        stop by length) and steps RETIRED (``steps_retired``; what
        ``req.tokens`` and ``_is_terminal`` have seen), and retirement walks
        the (slot, request) pairs of ITS dispatch: a pair whose request
        finished since (end-of-text found one step late, a deadline, a
        cancel) is skipped and counted (``discarded_slot_steps``), its id
        never emitted. The engine retires FIRST and then goes on where it
        can see that it must — no option says so: a weight swap is pending
        (below); a prefill is about to block the tick (an admission, a
        prompt's last chunk); no slot is active, so the tick only retires
        (``has_work()`` stays true until the last ids are out);
        ``cancel_all`` drops the step whole. A fetch that fails gives every
        dispatched, unretired slot-step back (``_give_back_dispatched``):
        the slots are dispatched again from their last emitted token.
        ``spec_k > 0`` keeps its own synchronous path (``_verify_tick``
        drafts from emitted ids) and never leaves a step in flight.

        Swap protocol: a queued ``request_swap`` is installed at the top
        of the tick body, after the step in flight — which ran on the OLD
        weights — is retired, so a failure of that step is not blamed on
        the new version; everything the tick dispatches then runs on the
        new weights (never torn across versions). The swap stays in its
        trial window until the body completes: a clean tick commits it
        (previous params released), a failing tick rolls back to the old
        params and the loop keeps serving — a bad swap must degrade the
        weights version, not availability.

        Transfer discipline: once every program is warm, the WHOLE tick
        body executes under ``GuardSet.transfer_scope`` — in strict mode
        any implicit host<->device copy raises; the tick's only transfers
        are the explicit operand ``device_put`` and the token-id
        ``device_get``.
        """
        try:
            # tick-wide watchdog guard (nests over the inner prefill/decode
            # guards): a hang ANYWHERE in the tick body — including the
            # injected-fault hooks that fire outside dispatch sections —
            # stalls a named section, which dumps the flight recorder
            with watchdog_guard("serve_tick"):
                if self._scope_ready():
                    with self._guards.transfer_scope("serve_tick"):
                        worked = self._tick_body()
                else:
                    worked = self._tick_body()
        except Exception as e:
            if self._trial is not None:
                self._rollback_swap(f"{type(e).__name__}: {e}")
                self.last_tick_t = time.monotonic()
                return True
            raise
        if self._trial is not None:
            self._commit_swap()
        return worked

    def _install_pending_swap(self) -> None:
        """Take the queued swap, if any, and open its trial window."""
        with self._swap_lock:
            pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        params, version, ticket, variant = pending
        try:
            self.swap_params(params, version, ticket, variant=variant)
        except Exception as e:  # pragma: no cover - validated at request
            if ticket is not None:
                ticket.resolve(
                    False, error=f"{type(e).__name__}: {e}",
                    stage="apply",
                )

    def _tick_body(self) -> bool:
        """One tick as a ``serve_tick`` phase whose children (``expire``,
        ``admit`` and ``prefill`` per admission, ``chunks``, ``operands``,
        ``dispatch``, ``decode_wait``, ``emit``, ``publish``) tile it.
        A ``prefill`` holds its program to the end of its ``prefill_wait``.
        A ``dispatch`` only queues step k; the ``decode_wait`` after it is
        the wait for what is LEFT of step k-1 (the step in flight since the
        tick before), and ``emit`` hands out step k-1's ids: so dispatch
        start to that wait's end is "dispatch plus the rest of step k-1",
        and the host's other phases ran under step k-1. Where the tick
        retired first (``tick``), ``decode_wait`` and ``emit`` come before
        the ``dispatch`` (inside ``chunks`` where a prompt's last chunk
        asked for them) or stand alone. The record's ``overlapped`` is 1
        where the tick dispatched before it retired, ``discarded`` counts
        the slot-steps it skipped at retirement. A busy tick is written
        out once, after its last phase, as ONE ``serve_tick`` record —
        where a sink is attached."""
        self._tick_phases = []
        with Phase("serve_tick", ident=self.ticks + 1) as tick:
            worked = self._run_tick(tick)
        if worked and self._registry.sink is not None:
            phases = sorted(self._tick_phases, key=lambda p: p.t0)
            self._registry.emit({
                "record": "serve_tick",
                "component": self.replica_name or "engine",
                "tick": tick.ident,
                "busy_tick": self.busy_ticks,
                "t0_s": tick.t0,
                "t1_s": tick.t1,
                **tick.attrs,
                # (name, start, end, request id or null[, attributes])
                "phases": [
                    [p.name[len(_TICK):], p.t0, p.t1, p.ident]
                    + ([p.attrs] if p.attrs else [])
                    for p in phases
                ],
            })
        return worked

    def _run_tick(self, tick: Phase) -> bool:
        t0 = tick.t0
        worked = False
        admitted0, prefill_tokens0 = self.admitted, self.prefill_tokens
        chunks0, cached0 = self.prefill_chunks, self.prefix_cached_tokens
        live_tokens = 0     # the active slots' contexts at dispatch, summed
        # what ``_retire`` adds to the tick's record: the slot-steps it
        # skipped, a routed model's counts; 1 where the tick dispatched
        # BEFORE it retired the step in flight
        attrs = {"overlapped": 0, "discarded": 0}

        if self._pending_swap is not None:
            # the step in flight ran on the OLD weights: retired before
            # the trial window opens, so its failure is not the new
            # version's
            worked = self._retire(attrs)
            self._install_pending_swap()

        with self._phase("expire"):
            for req in self._queue.expire_overdue():
                emit_expiry(self._registry, req, "queued")
                self._finish(req, "expired", "deadline")
                worked = True

            # running-slot deadlines: stop spending decode on an abandoned
            # answer
            now = time.monotonic()
            for i, s in enumerate(self._slots):
                if s is not None and s.request.overdue(now):
                    self._evict(i)
                    emit_expiry(self._registry, s.request, "running")
                    self._finish(s.request, "expired", "deadline")
                    worked = True

        # admissions: fill free slots in scheduler order; the FIFO head
        # must also fit the page budget (a blocked head blocks the queue —
        # no-bypass backpressure, requests behind it wait for pages to
        # free rather than starving it)
        self._page_blocked = False
        # "streaming" engines park admitted prompts in prefill phase and
        # advance them chunk-by-chunk: chunked prefill always, and any
        # prefix-cache engine (cache-hit tails stream from the cached
        # boundary even when cold prefills stay monolithic)
        chunked = self.config.prefill_chunk > 0
        streaming = chunked or self._prefix is not None
        speculative = self.config.spec_k > 0
        while True:
            req = None
            try:
                # one ``admit`` phase per pass, up to the prefill (the last
                # pass finds no slot or no request)
                with self._phase("admit"):
                    slot = self._free_slot()
                    if slot is not None:
                        # the residency hold only guards CHUNKED engines
                        # (long prompts streaming in over many ticks); a
                        # prefix-only engine's hit tails span at most two
                        # chunks, so holding admissions behind them would
                        # just serialize the queue
                        req = self._queue.pop_ready(
                            accept=self._admission_fits,
                            defer=self._admission_defer if chunked else None,
                        )
                    if req is None:
                        break
                    match = self._take_match(req)
                    if match is not None:
                        self._prefix.note(match.hit)
                    monolithic = False
                    if match is not None and match.hit:
                        self._admit_hit(req, slot, match)
                    elif chunked:
                        self._admit_chunked(req, slot)
                    else:
                        self._reserve(req, slot)
                        monolithic = True
                if monolithic:
                    # the prefill blocks this tick: hand out the step in
                    # flight first
                    self._retire(attrs)
                    self._admit(req, slot)
            except Exception:
                if req is not None:
                    # the request is already popped and not yet slotted: an
                    # admission failure (guard violation, wedged prefill,
                    # OOM) must not orphan it — its waiter would hang
                    # forever while the loop's failure path cancels only
                    # queued+slotted work
                    self._registry.inc("serve/admit_failures")
                    self._finish(req, "error", "admit_failure")
                raise
            worked = True
        if self._page_blocked:
            self.page_exhausted += 1
            self._registry.inc("serve/page_exhausted")

        # streaming prompts advance one chunk each, AFTER admissions (a
        # just-admitted slot gets its first chunk this very tick) and
        # BEFORE decode (its pages must be committed before the verify
        # scatter could reach them)
        with self._phase("chunks"):
            if streaming:
                worked = self._advance_prefills(attrs) or worked
            active = [
                i for i, s in enumerate(self._slots)
                if s is not None and s.phase == "decode"
            ]
            if not speculative:
                # a slot whose LAST step is dispatched (the prefill gave
                # the first of its ``max_new_tokens``) waits for its
                # retirement and is in no further dispatch: the stop by
                # length wastes nothing
                active = [
                    i for i in active
                    if 1 + self._slots[i].steps_done
                    < self._slots[i].request.max_new_tokens
                ]
        if active and speculative:
            self._verify_tick(active)
            worked = True
        elif active:
            with self._phase("operands"):
                S = self.config.num_slots
                fresh = np.zeros((S,), np.bool_)
                tokens = np.zeros((S,), np.int32)
                ctx = np.zeros((S,), np.int32)
                seeds = np.zeros((S,), np.int32)
                steps = np.zeros((S,), np.int32)
                temps = np.zeros((S,), np.float32)
                top_ks = np.zeros((S,), np.int32)
                for i in active:
                    s = self._slots[i]
                    r = s.request
                    if s.steps_done == s.steps_retired:
                        # no step of its own in flight: the host has its
                        # token (the others' is on the device)
                        fresh[i] = True
                        tokens[i] = s.pending_token
                    ctx[i] = r.prompt_len + s.steps_done
                    seeds[i] = np.int32(r.seed)
                    steps[i] = s.steps_done + 1   # == len(r.tokens) at sample
                    temps[i] = r.temperature
                    top_ks[i] = min(r.top_k, np.iinfo(np.int32).max)
                live_tokens = int(ctx.sum())
                # a COPY: the allocator's table changes under the step in
                # flight (a retirement frees rows). Slots that hold pages
                # and are not in this dispatch (mid-prefill; waiting for
                # their last retirement: context 0 here, which no live
                # slot has) get null rows, so the decode scatter can't
                # stomp their K/V
                bt = self._pages.block_table.copy()
                bt[ctx == 0] = 0
                ops = self._put(
                    (fresh, tokens, bt, ctx, seeds, steps, temps, top_ks))
            with watchdog_guard("serve_decode"), self._phase("dispatch"):
                out, self._cache = self._decode_step_fn()(
                    self._params, self._cache, self._prev_ids, *ops
                )
                # the operands are the device's now: freed here, inside
                # a phase, not at the tick's return
                del ops
                self._prev_ids = out[0] if self._routed else out
                pairs = [(i, self._slots[i]) for i in active]
                for _, s in pairs:
                    s.steps_done += 1
                self.decode_dispatches += 1
            attrs["overlapped"] = int(self._inflight is not None)
            self.overlapped_ticks += attrs["overlapped"]
            # step k is queued behind step k-1: only now fetch, emit and
            # retire step k-1, under step k
            self._retire(attrs, then=_InFlight(out, pairs))
            if self._trial is not None:
                # a trial tick sees its own ids before the swap commits
                self._retire(attrs)
            worked = True
        else:
            # no slot to dispatch: a tick that only retires has worked
            worked = self._retire(attrs) or worked

        with self._phase("publish") as publish:
            self.ticks += 1
            tick.attrs = {
                "decode_active": len(active),
                "admitted": self.admitted - admitted0,
                "prefill_tokens": self.prefill_tokens - prefill_tokens0,
                "cached_tokens": self.prefix_cached_tokens - cached0,
                "chunks": self.prefill_chunks - chunks0,
                "live_tokens": live_tokens,
                **attrs,
            }
            depth = self._queue.depth()
            self._registry.gauge("serve/queue_depth", depth)
            self._registry.gauge(
                "serve/slot_occupancy", self.slot_occupancy())
            self._registry.gauge(
                "serve/kv_pages_used", self._pages.pages_used)
            self._registry.gauge(
                "serve/kv_pages_free", self._pages.pages_free)
            if self._prefix is not None:
                lookups = self._prefix.hits + self._prefix.misses
                self._registry.gauge(
                    "serve/prefix_hit_rate",
                    self._prefix.hits / lookups if lookups else 0.0,
                )
                self._registry.gauge(
                    "serve/pages_shared", self._pages.pages_shared
                )
                self._registry.gauge("serve/cow_copies", self.cow_copies)
            if self.brownout is not None:
                level = self.brownout.observe(depth / self._queue.max_depth)
                self._registry.gauge("serve/brownout_level", level)
                if level != self._prev_brownout_level:
                    self._tick_events.append(
                        f"brownout:{self._prev_brownout_level}->{level}"
                    )
                    self._prev_brownout_level = level
                if level >= 1 and self._prefix is not None:
                    # brownout pressure: idle cached runs are the cheapest
                    # capacity to give back — drop every cache-only page
                    # (they rebuild from traffic once the ladder steps
                    # down)
                    dropped = self._prefix.evict_idle()
                    if dropped:
                        self._tick_events.append(
                            f"prefix_evict_idle:{dropped}")
            now = time.monotonic()
            window = now - self._drain_window_t
            if window >= 1.0:
                rate = (self.finished - self._drain_window_finished) / window
                # EWMA so one quiet window doesn't zero the estimate
                # mid-storm
                self.drain_rate = (
                    rate if self.drain_rate == 0.0
                    else 0.5 * self.drain_rate + 0.5 * rate
                )
                self._drain_window_t = now
                self._drain_window_finished = self.finished
                self._registry.gauge("serve/drain_rate_rps", self.drain_rate)
            if worked:
                self.busy_ticks += 1
                self._registry.observe("serve/tick", time.monotonic() - t0)
            # flight-recorder entry for every busy or eventful tick —
            # appended BEFORE the chaos hooks below, so a hang injected at
            # this tick dumps a ring whose LAST entry is the stalled tick
            # itself (its ``publish`` therefore ends where ``dur_ms`` does)
            events, self._tick_events = self._tick_events, []
            if worked or events:
                now = time.monotonic()
                phases = {"publish": now - publish.t0}
                for p in self._tick_phases:
                    name = p.name[len(_TICK):]
                    phases[name] = phases.get(name, 0.0) + p.t1 - p.t0
                self.flight.record(
                    tick=self.ticks,
                    busy_tick=self.busy_ticks,
                    dur_ms=round((now - t0) * 1e3, 3),
                    # milliseconds by phase name; a wait lies inside its
                    # ``prefill`` and is counted under both names
                    phases={k: round(v * 1e3, 3) for k, v in phases.items()},
                    queue_depth=depth,
                    slots_active=sum(1 for s in self._slots if s is not None),
                    prefill_resident=self._prefill_resident(),
                    decode_active=len(active),
                    pages_used=self._pages.pages_used,
                    brownout=(
                        self.brownout.level
                        if self.brownout is not None else 0
                    ),
                    weights_step=self.weights_step,
                    finished=self.finished,
                    events=events,
                )
            if worked:
                # deterministic chaos hooks: slow_host:Nx stretches serving
                # time (deadline/backpressure drills); the replica_* kinds
                # crash, hang or slow THIS replica at an exact busy tick
                # (router failover / breaker / drain drills). Both fire
                # before the heartbeat stamp below, so an injected hang
                # reads as a stale heartbeat — exactly like a wedged device
                # would.
                from pytorch_distributed_training_tpu.faults.inject import (
                    get_plan,
                )

                plan = get_plan()
                plan.slow_host_delay(time.monotonic() - t0)
                plan.fire_serve_tick(self.busy_ticks, time.monotonic() - t0)
            self.last_tick_t = time.monotonic()
        return worked

    def _retire(self, attrs: dict, then: Optional[_InFlight] = None) -> bool:
        """Fetch, emit and retire the decode step in flight, leaving
        ``then`` (the step just dispatched, or none) in flight; True where
        there was one. Walks the pairs of ITS dispatch: a pair whose slot
        was given up since (end-of-text one step late, a deadline) is
        skipped and counted, its id never emitted. ``attrs``, the tick
        record's, gains the count and a routed model's counts."""
        step, self._inflight = self._inflight, then
        if step is None:
            return False
        try:
            # the tick's single D2H: [slots] int32 ids
            with watchdog_guard("serve_decode"), self._phase("decode_wait"):
                sampled = jax.device_get(step.out)
        except Exception:
            self._give_back_dispatched()
            raise
        with self._phase("emit"):
            if self._routed:
                # (ids, (tokens a held expert, pairs to absent ones))
                sampled, (held, absent) = sampled
                attrs.update(self._count_routing(held, int(absent)))
            discarded = 0
            for i, s in step.pairs:
                if self._slots[i] is not s:
                    discarded += 1
                    continue
                token = int(sampled[i])
                s.steps_retired += 1
                s.request.decode_ticks += 1
                self._emit_token(s.request, token)
                if self._is_terminal(s.request, token):
                    self._evict(i)      # slot + pages free for reuse
                else:
                    s.pending_token = token
            attrs["discarded"] += discarded
            self.discarded_slot_steps += discarded
            self.decode_tokens += len(step.pairs) - discarded
            self._registry.gauge("serve/tokens_per_dispatch", 1.0)
            # the step's device output is done with: freed here, inside a
            # phase, not at the return
            del step
        return True

    def _give_back_dispatched(self) -> None:
        """A step's fetch failed: nothing stays in flight (the step after
        it read its ids) and every slot is as its last retirement left it,
        ``fresh``, so the next tick dispatches it again from
        ``pending_token`` into the same positions — after a failed trial
        tick on the weights rolled back to, as an engine that dispatched
        and fetched within one tick would. The slot-steps given up are
        counted as discarded. No slot reads the failed ids, but they must
        not stay an operand of the next step."""
        self._inflight = None
        self._prev_ids = self._put(
            np.zeros((self.config.num_slots,), np.int32))
        for s in self._slots:
            if s is not None:
                self.discarded_slot_steps += s.steps_done - s.steps_retired
                s.steps_done = s.steps_retired

    # -------------------------------------------------------------- shutdown

    def has_work(self) -> bool:
        """Slots, queued requests, or a decode step whose ids are not out
        yet: a drain ticks until the last retirement."""
        return (
            any(s is not None for s in self._slots)
            or bool(self._queue.depth())
            or self._inflight is not None
        )

    def cancel_all(self) -> None:
        """Terminate every in-flight and queued request (non-drain shutdown);
        partial outputs stay on the request. The decode step in flight is
        dropped whole, unfetched: every one of its slot-steps is counted
        as discarded."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self.discarded_slot_steps += len(step.pairs)
        for i, s in enumerate(self._slots):
            if s is not None:
                self._evict(i)
                self._registry.inc("serve/cancelled")
                self._finish(s.request, "cancelled", "cancelled")
        for req in self._queue.drain_pending():
            self._registry.inc("serve/cancelled")
            self._finish(req, "cancelled", "cancelled")

    def _count_routing(self, held, absent: int) -> dict:
        """Fold one decode step's routing counts (tokens routed to each
        held expert, summed over the expert layers; pairs routed to absent
        experts) into the stats; returns the tick record's attributes."""
        total, top = int(held.sum()), int(held.max())
        self.moe_steps += 1
        self.moe_held_tokens += total
        self.moe_held_max_sum += top
        self.moe_absent_pairs += absent
        return {
            "expert_tokens_max": top,
            "expert_tokens_mean": total / len(held),
            "absent_pairs": absent,
        }

    def _kv_bytes_per_token(self) -> int:
        """Resident pool bytes one committed token occupies across every
        layer (K and V): ``head_dim`` values per head at the pool dtype,
        plus one fp32 scale per entry per head when the pools are int8 —
        the capacity arithmetic behind the int8 cache's concurrency win
        (at head_dim 64 and fp32 compute, int8 pools cost (64+4)/256 of
        the fp32 bytes per token)."""
        if self._memory:
            # the model declared its memories: pages are what a token costs
            return sum(m.bytes_per_token for m in self._memory)
        mcfg = self._decode_model.config
        values = getattr(mcfg, "cache_values_per_token", None)
        if values is not None:
            # pools of another kind (a latent row a layer, an indexer key
            # in some): the model counts its own
            return values() * jnp.dtype(mcfg.compute_dtype).itemsize
        if self.config.kv_dtype == "int8":
            per_head = mcfg.head_dim + 4
        else:
            per_head = (
                mcfg.head_dim * jnp.dtype(mcfg.compute_dtype).itemsize
            )
        return 2 * mcfg.num_layers * mcfg.num_heads * per_head

    def _slot_memory_stats(self) -> dict:
        """What a slot keeps beside its pages (a family that declares no
        memories keeps nothing), and how many layers read the page pool
        that has the most readers."""
        memory = self._memory

        def per_slot(kind):
            return sum(m.bytes_per_slot for m in memory if m.kind == kind)

        return {
            "state_bytes_per_slot": per_slot("state"),
            "ring_bytes_per_slot": per_slot("ring"),
            "kv_pool_readers": max(
                (m.readers for m in memory if m.kind == "pages"), default=1),
        }

    def _kv_pool_relayout_ops(self) -> Optional[int]:
        """Whole-pool copy/transpose/convert instructions in the compiled
        hot program, from its comm audit's record (None before warm-up or
        without one; 0 when every pool keeps one device layout from
        parameter to donated result)."""
        return self._hot_audit().get("kv_pool_relayout_ops")

    def _hot_audit(self) -> dict:
        hot = self._guards.wrapped.get(self._hot_program())
        return getattr(hot, "comm_record", None) or {}

    def stats(self) -> dict:
        return {
            "ticks": self.ticks,
            "busy_ticks": self.busy_ticks,
            "admitted": self.admitted,
            "finished": self.finished,
            # plain decode ticks that dispatched step k BEFORE they retired
            # step k-1, over all of them (None under speculation, whose
            # ticks are synchronous); slot-steps dispatched and never
            # emitted (end-of-text found a step late, a deadline, a cancel)
            "decode_overlap_share": (
                self.overlapped_ticks / self.decode_dispatches
                if self.decode_dispatches and self.config.spec_k == 0
                else None
            ),
            "discarded_slot_steps": self.discarded_slot_steps,
            "kv_pool_relayout_ops": self._kv_pool_relayout_ops(),
            # how many of those only stage a pool through on-chip memory
            # and back, in one layout (analysis/spmd/hlo.count_space_moves)
            "kv_pool_space_moves": self._hot_audit().get(
                "kv_pool_space_moves"),
            # XLA gathers of cached latent rows in the hot program and the
            # row_fetch kernel's calls (None for a model without a latent
            # pool): one of the two a selection group
            "latent_row_gathers": self._hot_audit().get(
                "latent_row_gathers"),
            "latent_row_fetches": self._hot_audit().get(
                "latent_row_fetches"),
            # the same of a window group's rows (one a group), and the rows
            # a decode step reads a slot for it (None without windows)
            "window_row_gathers": self._hot_audit().get(
                "window_row_gathers"),
            "window_row_fetches": self._hot_audit().get(
                "window_row_fetches"),
            "window_rows_per_slot": getattr(
                self._decode_model.config, "window_rows_per_slot", None),
            # what a slot keeps beside its pages, and the pool's readers
            **self._slot_memory_stats(),
            # share of the busy ticks that held a prefill chunk (a token gap
            # over such a tick is a lump, not a plain decode step)
            "chunk_tick_share": (
                self.prefill_chunks / self.busy_ticks
                if self.busy_ticks else None
            ),
            "queue_depth": self._queue.depth(),
            "queue_depth_by_tier": self._queue.depth_by_tier(),
            "slot_occupancy": self.slot_occupancy(),
            "page_occupancy": self.page_occupancy(),
            "drain_rate_rps": self.drain_rate,
            "brownout": (
                self.brownout.stats() if self.brownout is not None else None
            ),
            "spans_emitted": self.tracer.emitted,
            **self.flight.stats(),
            **(self.slo.stats() if self.slo is not None else {}),
            "num_slots": self.config.num_slots,
            "prompt_buckets": list(self.config.prompt_buckets),
            "compiled_prefill_buckets": sorted(self._prefill_fns),
            "kv_layout": self.config.kv_layout,
            "sampling": self.config.sampling,
            "tp": self.config.tp,
            "weights_dtype": self.config.weights_dtype,
            "kv_dtype": self.config.kv_dtype,
            "variant": self.variant,
            "kv_bytes_per_token": self._kv_bytes_per_token(),
            "kv_page_size": self.config.page_size,
            "kv_pages_total": self._pages.num_pages - 1,
            "kv_pages_used": self._pages.pages_used,
            "kv_pages_free": self._pages.pages_free,
            "kv_pages_shared": self._pages.pages_shared,
            "kv_pages_peak": self._pages.peak_used,
            "page_exhausted": self.page_exhausted,
            "prefill_tokens": self.prefill_tokens,
            "prefix_cached_tokens": self.prefix_cached_tokens,
            "moe": (
                {
                    "steps": self.moe_steps,
                    "held_tokens": self.moe_held_tokens,
                    "held_max_sum": self.moe_held_max_sum,
                    "absent_pairs": self.moe_absent_pairs,
                }
                if self._routed else None
            ),
            "prefix_cache": (
                {
                    **self._prefix.stats(),
                    "cow_copies": self.cow_copies,
                    "pages_shared": self._pages.pages_shared,
                    "tenant_blocked": self.tenant_blocked,
                    "tenant_page_quota": self.config.tenant_page_quota,
                }
                if self._prefix is not None else None
            ),
            "spec_k": self.config.spec_k,
            "spec_draft": (
                self.config.spec_draft if self.config.spec_k > 0 else None
            ),
            "spec_dispatches": self.spec_dispatches,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": (
                self.spec_accepted / self.spec_drafted
                if self.spec_drafted else None
            ),
            "tokens_per_dispatch": (
                self.decode_tokens / self.decode_dispatches
                if self.decode_dispatches else None
            ),
            "prefill_chunk": self.config.prefill_chunk,
            "prefill_chunks": self.prefill_chunks,
            "weights_step": self.weights_step,
            "swaps": self.swaps,
            "swap_rollbacks": self.swap_rollbacks,
            "swap_pending": self._pending_swap is not None,
            "guard_mode": self._guards.mode,
            "guard_recompiles": self._guards.recompile_violations,
            "guard_implicit_transfers": self._guards.transfer_violations,
        }
