"""Expected-collective manifests + the ``comm_audit`` runtime guard.

A :class:`CommManifest` is a program's pinned communication contract:
which collective kinds it is allowed to contain, which it MUST contain,
and (optionally) a payload-bytes ceiling. ``comm_audit`` checks a warmed
program's compiled HLO against its manifest the same way
``analysis/guards.donation_audit`` checks donation: parse ``as_text()``,
emit one ``comm_audit`` telemetry record, count deviations, and raise
:class:`~pytorch_distributed_training_tpu.analysis.guards.GuardViolation`
in strict mode. Record mode logs deviations without failing — the
rollout path new manifests go through before being pinned strict.

Canonical manifests live here too: ``train_manifest(mesh)`` derives the
kinds a train step may legitimately emit from which mesh axes are
non-trivial (an fsdp mesh earns all-gather/reduce-scatter; a pipeline
mesh earns collective-permute; a 1-device mesh earns NOTHING), and
``serve_manifest(num_devices)`` pins today's single-device serve
programs to zero collectives — the contract the sharded-replica work
will consciously relax, kind by kind, instead of silently breaking.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Optional

from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
    COLLECTIVE_KINDS,
    CostModel,
    count_kernel_calls,
    count_relayouts,
    count_row_gathers,
    count_space_moves,
    extract_collectives,
    scope_instructions,
    summarize_collectives,
)

#: every ``program_scopes`` record the audits wrote, oldest first: what a
#: reader in the same process has where no sink is attached (the
#: benchmark's training harness attaches none). Bounded: a process warms a
#: handful of programs, a test suite many.
PROGRAM_SCOPES: collections.deque = collections.deque(maxlen=16)


@dataclasses.dataclass(frozen=True)
class CommManifest:
    """One program's expected-collective contract.

    ``allowed`` — kinds the program may contain (empty = zero
    collectives); ``required`` — kinds that must appear (catches the
    opposite regression: a "sharded" program that stopped communicating
    because everything got replicated); ``max_bytes`` — ceiling on total
    payload bytes across all collectives (e.g. a small multiple of param
    bytes for an fsdp step).
    """

    name: str
    allowed: tuple = ()
    required: tuple = ()
    max_bytes: Optional[int] = None
    # ceiling on ring-model bytes moved per device (CostModel.moved_bytes
    # summed over all collectives) — the wire-traffic twin of max_bytes
    max_moved_bytes: Optional[int] = None
    # per-device element counts of the serve engine's resident KV page
    # pools: the audit counts the program's whole-pool copy / transpose /
    # convert instructions (``kv_pool_relayout_ops``; a pool that keeps one
    # device layout from parameter to donated result reads 0)
    kv_pool_elements: tuple = ()
    # ``jax.named_scope`` names of the program's own: the audit also emits
    # a ``program_scopes`` record listing the compiled instructions under
    # each, so that a device trace (events named by instruction, no
    # metadata) can be read scope by scope
    trace_scopes: tuple = ()
    # values of one cached latent row (0: the program has none): the audit
    # counts the program's gathers of such rows under ``sparse_attn.gather``
    # (``latent_row_gathers``) and the ``row_fetch`` kernel's calls there
    # (``latent_row_fetches``): one of the two a selection group in a
    # decode step
    latent_row: int = 0
    # values of one cached row of a window group (0: the program has none):
    # the same under ``window_attn`` (``window_row_gathers``,
    # ``window_row_fetches``: one a window group in a decode step)
    window_row: int = 0

    def __post_init__(self):
        for kind in tuple(self.allowed) + tuple(self.required):
            if kind not in COLLECTIVE_KINDS:
                raise ValueError(
                    f"manifest {self.name!r}: unknown collective kind "
                    f"{kind!r} (must be one of {COLLECTIVE_KINDS})"
                )

    def check(self, summary: dict) -> list:
        """Deviations of an extracted-collective summary from this
        manifest (empty list = conforming)."""
        deviations = []
        kinds = set(summary.get("by_kind", {}))
        allowed = set(self.allowed) | set(self.required)
        for kind in sorted(kinds - allowed):
            slot = summary["by_kind"][kind]
            deviations.append(
                f"unexpected {kind} x{slot['count']} "
                f"({slot['bytes']} payload bytes)"
            )
        for kind in self.required:
            if kind not in kinds:
                deviations.append(f"required {kind} absent")
        if (
            self.max_bytes is not None
            and summary.get("total_bytes", 0) > self.max_bytes
        ):
            deviations.append(
                f"total payload {summary['total_bytes']}B exceeds "
                f"manifest ceiling {self.max_bytes}B"
            )
        if (
            self.max_moved_bytes is not None
            and summary.get("total_moved_bytes", 0) > self.max_moved_bytes
        ):
            deviations.append(
                f"total moved {summary['total_moved_bytes']}B exceeds "
                f"manifest moved-bytes ceiling {self.max_moved_bytes}B"
            )
        return deviations

    def to_record(self) -> dict:
        return {
            "name": self.name,
            "allowed": list(self.allowed),
            "required": list(self.required),
            "max_bytes": self.max_bytes,
            "max_moved_bytes": self.max_moved_bytes,
            "kv_pool_elements": list(self.kv_pool_elements),
        }


def train_manifest(mesh, *, max_bytes: Optional[int] = None,
                   name: str = "train_step",
                   fsdp_sharded: bool = False) -> CommManifest:
    """The kinds a train step may emit on this mesh. 1-device meshes pin
    zero collectives; a data axis earns gradient all-reduce; fsdp/model
    axes earn param all-gather + grad reduce-scatter (and all-to-all for
    tensor-parallel layouts); a stage axis earns pipeline permutes. An
    fsdp axis earns permutes too: XLA:TPU's fused reduce-scatter pads each
    shard to its ring granularity (1024 rows become 1032) and shifts the
    few surplus rows to the neighbour afterwards — 25 permutes of 48 KiB
    beside 167 MB of reduce-scatter in the bert-large fsdp=4 step compiled
    for a v5e 2x2. XLA:CPU emits none.

    ``fsdp_sharded=True`` (the mesh has an fsdp axis AND the sharding
    policy actually shards params over it) additionally REQUIRES an
    all-gather: sharded params must be gathered somewhere, so a step
    with none means everything silently ended up replicated — the
    de-sharding regression this manifest exists to catch.

    Every train step's manifest lists the step's own named scopes
    (``train/step.py::TRAIN_SCOPES``) as its ``trace_scopes``: the audit
    of the compiled step then also writes its ``program_scopes`` record."""
    from pytorch_distributed_training_tpu.train.step import TRAIN_SCOPES

    shape = dict(mesh.shape)
    if max(shape.values(), default=1) <= 1:
        return CommManifest(name, allowed=(), max_bytes=max_bytes,
                            trace_scopes=TRAIN_SCOPES)
    allowed = ["all-reduce"]
    required = []
    if shape.get("fsdp", 1) > 1 or shape.get("model", 1) > 1:
        allowed += ["all-gather", "reduce-scatter"]
        if fsdp_sharded and shape.get("fsdp", 1) > 1:
            required += ["all-gather"]
    if shape.get("model", 1) > 1:
        allowed += ["all-to-all"]
    if shape.get("stage", 1) > 1 or shape.get("fsdp", 1) > 1:
        allowed += ["collective-permute"]
    return CommManifest(
        name, allowed=tuple(allowed), required=tuple(required),
        max_bytes=max_bytes, trace_scopes=TRAIN_SCOPES,
    )


def serve_manifest(num_devices: int = 1,
                   name: str = "serve") -> CommManifest:
    """Serve programs on one device move nothing between chips — pinned.
    Multi-device serving (the sharded-replica roadmap item) starts from
    the full allowance and narrows per program as manifests get pinned."""
    if num_devices <= 1:
        return CommManifest(name, allowed=())
    return CommManifest(name, allowed=COLLECTIVE_KINDS)


def serve_tp_manifest(
    num_devices: int,
    *,
    layers: int,
    hidden: int,
    max_q_tokens: int,
    dtype_bytes: int = 4,
    name: str = "serve_tp",
    slack: float = 4.0,
    cost_model: Optional[CostModel] = None,
    weight_bytes_floor: Optional[int] = None,
) -> CommManifest:
    """The head-sharded serve engine's pinned contract: each layer's
    row-parallel attention-out and mlp_down matmuls combine their partial
    sums with exactly one all-reduce over the replicated ``[tokens,
    hidden]`` activation — so a program may contain ONLY all-reduces, MUST
    contain at least one (a "sharded" engine with none silently
    replicated its weights), and its total payload is bounded by ``2 *
    layers`` activation-sized reductions (slack absorbs dtype/fusion
    noise). An all-gather of weights is caught twice over: the kind is
    not allowed, and gathering even one projection would blow the
    activation-sized ceiling by orders of magnitude. ``max_q_tokens`` is
    the widest token block a dispatch scores — ``slots * (spec_k + 1)``
    for the verify program, ``slots`` for plain decode. The moved-bytes
    ceiling prices the same budget through the ring
    :class:`~pytorch_distributed_training_tpu.analysis.spmd.hlo.CostModel`
    (2·B·(g−1)/g per all-reduce)."""
    # ``weight_bytes_floor`` makes the ceiling dtype-aware end to end: an
    # int8-weight replica passes the bytes of its SMALLEST sharded
    # projection, and the ceiling is clamped strictly below payload +
    # floor, so a program that all-reduced (or gathered) even one weight
    # matrix on top of its activations breaks the contract at compile
    # time — slack can no longer mask a quantized engine silently
    # communicating fp32-sized (or any weight-sized) tensors.
    if num_devices <= 1:
        return CommManifest(name, allowed=())
    from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
        Collective,
    )

    payload = 2 * layers * max_q_tokens * hidden * dtype_bytes
    max_bytes = int(slack * payload)
    if weight_bytes_floor is not None:
        max_bytes = min(max_bytes, payload + int(weight_bytes_floor) - 1)
    cm = cost_model if cost_model is not None else CostModel()
    moved = cm.moved_bytes(Collective(
        name=name, kind="all-reduce", dtype="f32", bytes=payload,
        group_size=num_devices, line=0, asynchronous=False,
    ))
    return CommManifest(
        name,
        allowed=("all-reduce",),
        required=("all-reduce",),
        max_bytes=max_bytes,
        max_moved_bytes=int(slack * moved),
    )


def comm_audit(
    name: str,
    stage,
    manifest: CommManifest,
    *,
    registry=None,
    mode: str = "record",
    cost_model: Optional[CostModel] = None,
    world_size: Optional[int] = None,
) -> dict:
    """Audit a warmed program's collective footprint against ``manifest``.

    ``stage`` is a ``Lowered`` or ``Compiled`` (anything with
    ``as_text()``) — pass the COMPILED object: SPMD-partitioner
    collectives only exist post-compile. Emits one ``comm_audit``
    record; deviations bump ``guards/comm_deviations`` and raise
    ``GuardViolation`` in strict mode. Where the manifest names the
    engine's KV pools (``kv_pool_elements``) the record also carries
    ``kv_pool_relayout_ops``, counted from the same text: a count, not a
    deviation.
    """
    from pytorch_distributed_training_tpu.analysis.guards import (
        GuardViolation,
        _registry_or_default,
    )

    registry = _registry_or_default(registry)
    try:
        text = stage.as_text()
    except Exception as e:  # pragma: no cover - backend without text dump
        record = {
            "record": "comm_audit", "name": name,
            "manifest": manifest.name, "ok": None,
            "error": str(e)[:200],
        }
        registry.emit(record)
        return record
    if world_size is None:
        try:
            import jax

            world_size = jax.device_count()
        except Exception:  # pragma: no cover - jax-free caller
            world_size = None
    summary = summarize_collectives(
        extract_collectives(text, world_size=world_size),
        cost_model=cost_model,
    )
    deviations = manifest.check(summary)
    record = {
        "record": "comm_audit",
        "name": name,
        "manifest": manifest.name,
        "ok": not deviations,
        "deviations": deviations,
        **summary,
    }
    if manifest.kv_pool_elements:
        record["kv_pool_elements"] = list(manifest.kv_pool_elements)
        record["kv_pool_relayout_ops"] = count_relayouts(
            text, manifest.kv_pool_elements
        )
        record["kv_pool_space_moves"] = count_space_moves(
            text, manifest.kv_pool_elements
        )
    if manifest.latent_row:
        record["latent_row_gathers"] = count_row_gathers(
            text, "sparse_attn.gather", manifest.latent_row)
        record["latent_row_fetches"] = count_kernel_calls(
            text, "sparse_attn.gather", "row_fetch")
    if manifest.window_row:
        record["window_row_gathers"] = count_row_gathers(
            text, "window_attn", manifest.window_row)
        record["window_row_fetches"] = count_kernel_calls(
            text, "window_attn", "row_fetch")
    registry.emit(record)
    if manifest.trace_scopes:
        scopes = {
            "record": "program_scopes", "name": name,
            "scopes": scope_instructions(text, manifest.trace_scopes),
        }
        PROGRAM_SCOPES.append(scopes)
        registry.emit(scopes)
    if deviations:
        registry.inc("guards/comm_deviations", len(deviations))
        if mode == "strict":
            raise GuardViolation(
                f"comm audit {name!r}: compiled program deviates from "
                f"manifest {manifest.name!r}: {'; '.join(deviations)}"
            )
    return record
