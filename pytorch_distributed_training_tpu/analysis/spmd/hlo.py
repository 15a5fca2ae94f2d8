"""Compiled-HLO collective extraction + a simple ICI/DCN cost model.

XLA's SPMD partitioner inserts collectives (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute) at *compile* time — they are
invisible in the lowered StableHLO and only appear in the compiled
program's ``as_text()``. That is exactly where sharding regressions hide:
a "tensor-parallel" matmul that silently all-gathers full weights onto
every chip compiles, runs, and passes every numeric test, and only the
bench gets slower.

This module makes that footprint inspectable: ``extract_collectives``
parses a compiled HLO dump into structured :class:`Collective` entries
(kind, payload bytes, replica-group size), and :class:`CostModel` turns
them into bytes-moved-per-device estimates under ring algorithms, split
by link class (ICI within a host, DCN across hosts). Consumers:
``analysis/spmd/manifest.py`` (the ``comm_audit`` runtime guard),
``scripts/audit_hlo.py`` (CLI), and the collective-footprint pin tests.

Deliberately jax-free: it works on text, so it can audit dumps captured
on a real TPU from a dev box with no accelerator.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional

#: canonical collective kinds, matching XLA's HLO opcode spellings
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f16": 2, "bf16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "c128": 16,
}

_DTYPES_ALT = "|".join(sorted(_DTYPE_BYTES, key=len, reverse=True))

# `%name = <shape> <kind>(` — the shape is a single `f32[8,2]{1,0}` token
# or a tuple `(f32[...], f32[...])` for async starts / multi-operand ops.
# XLA:TPU writes tiled layouts with parentheses of their own
# (`bf16[16,64]{1,0:T(8,128)(2,1)}`), also inside the tuples of its
# combined all-reduces, so the tuple form runs to the `)` that the kind
# follows, not to the first one.
# `-done`/`-update` halves of async pairs never match (no `(` after kind).
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.-]+)\s*=\s*"
    r"(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")"
    r"(?P<suffix>-start)?\("
)

# `%all-reduce-scatter.6.clone (input: bf16[4096,1024]) -> bf16[1032,1024] {`
# XLA:TPU has no reduce-scatter opcode in its final HLO: it emits a fused
# computation named all-reduce-scatter* (an all-reduce and a dynamic-slice
# at the partition's offset) that its AllReduceScatterFusion emitter runs as
# ONE ring reduce-scatter — the full-size sum never exists in HBM.
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.-]+)\s+\(.*\{\s*$")
_TPU_REDUCE_SCATTER_PREFIX = "all-reduce-scatter"

# `%copy.217 = bf16[3073,16,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%pools...)`:
# the instructions that rewrite a whole buffer without computing anything
# new — a relayout, a transposition, a widening. `copy-start` is the async
# form (its `-done` half has the same shape and is not counted twice).
_RELAYOUT_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(?P<shape>\(.*?\)|\S+)\s+"
    r"(?:copy|copy-start|transpose|convert)\("
)

_SHAPE_TOKEN_RE = re.compile(r"(" + _DTYPES_ALT + r")\[([0-9,]*)\]")

# `%copy-start.10 = (bf16[11777,16,128]{2,1,0:T(8,128)(2,1)},
# bf16[11777,16,128]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(..)`:
# the two halves of an async copy with their layouts; `S(n)` names the
# memory space (1: on-chip). XLA:TPU's memory-space assignment stages a
# buffer that fits on-chip through it and writes it back: the same layout
# on both sides (`count_space_moves` says how many of the relayouts are
# of this kind).
_COPY_START_HALVES_RE = re.compile(
    r"=\s*\(\s*\w+\[[0-9,]*\](\{[^{}]*\})?,\s*\w+\[[0-9,]*\](\{[^{}]*\})?,"
    r".*\)\s+copy-start\(")
_MEMORY_SPACE_RE = re.compile(r"S\(\d+\)")


def _is_space_move(line: str) -> bool:
    """An async copy whose two halves differ by memory space alone."""
    m = _COPY_START_HALVES_RE.search(line)
    if not m:
        return False
    dst, src = (_MEMORY_SPACE_RE.sub("", g or "") for g in m.groups())
    return dst == src and (m.group(1) or "") != (m.group(2) or "")

_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective instruction lifted out of a compiled HLO dump."""

    name: str            # instruction name, e.g. "all-gather.5"
    kind: str            # canonical kind (no -start suffix)
    dtype: str           # element type of the (first) result buffer
    bytes: int           # payload: result buffer size in bytes
    group_size: int      # devices per replica group (0 = unknown)
    line: int            # 1-based line number in the dump
    asynchronous: bool   # the -start half of an async pair


def _shape_tokens(shape: str) -> list:
    return [
        (dt, math.prod(int(d) for d in dims.split(",")) if dims else 1)
        for dt, dims in _SHAPE_TOKEN_RE.findall(shape)
    ]


def _group_size(line: str, world_size: Optional[int]) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:  # explicit groups: {{0,1,2,3},{4,5,6,7}} — size of the first
        return len([t for t in m.group(1).split(",") if t.strip()])
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form: [num_groups,group_size]<=[world]
        return int(m.group(2))
    m = _PAIRS_RE.search(line)
    if m:  # permute: distinct devices touched by the pair list
        ids = set(re.findall(r"\d+", m.group(1)))
        return len(ids)
    # replica_groups={} (or absent) means "all devices"
    return world_size or 0


def extract_collectives(
    hlo_text: str, *, world_size: Optional[int] = None
) -> list:
    """Parse a compiled program's ``as_text()`` into :class:`Collective`s.

    ``world_size`` resolves ``replica_groups={}`` ("all devices");
    unresolvable group sizes stay 0 and cost as group-of-1 (zero moved).
    """
    out = []
    computation = ""
    for lineno, line in enumerate(hlo_text.splitlines(), start=1):
        m = _INSTR_RE.match(line)
        if m is None:
            header = _COMPUTATION_RE.match(line)
            if header is not None:
                computation = header.group("name")
            continue
        tokens = _shape_tokens(m.group("shape"))
        if not tokens:
            continue
        asynchronous = m.group("suffix") is not None
        if asynchronous and len(tokens) > 1:
            # async starts return (alias, result, ...) tuples; take the
            # largest buffer rather than double-counting the alias
            dtype, elems = max(tokens, key=lambda t: t[1] * _DTYPE_BYTES[t[0]])
            nbytes = elems * _DTYPE_BYTES[dtype]
        else:
            dtype = tokens[0][0]
            nbytes = sum(e * _DTYPE_BYTES[dt] for dt, e in tokens)
        kind = m.group("kind")
        group_size = _group_size(line, world_size)
        if kind == "all-reduce" and computation.startswith(
            _TPU_REDUCE_SCATTER_PREFIX
        ):
            # the fused form above: report what runs, with the scattered
            # shard as the payload (Collective.bytes is the RESULT buffer)
            kind = "reduce-scatter"
            nbytes //= max(group_size, 1)
        out.append(Collective(
            name=m.group("name"),
            kind=kind,
            dtype=dtype,
            bytes=int(nbytes),
            group_size=group_size,
            line=lineno,
            asynchronous=asynchronous,
        ))
    return out


_INSTRUCTION_SCOPE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.-]+)\s*=.*"
    r"metadata=\{[^}]*op_name=\"(?P<op>[^\"]*)\"")


def scope_instructions(hlo_text: str, scopes) -> dict:
    """``{scope: [instruction names]}`` of a compiled program: the
    instructions (outside fusion bodies, which run as their fusion) whose
    ``op_name`` metadata passes through ``jax.named_scope(scope)`` or a
    scope ``scope.<more>`` under it. A device trace names its events by
    instruction and carries no metadata, so this map is what ties a traced
    operation back to the program's own scopes. An instruction is listed
    under the first scope of ``scopes`` it matches."""
    out = {scope: [] for scope in scopes}
    in_fusion_body = False
    for line in hlo_text.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header:
            in_fusion_body = header.group("name").startswith("fused_computation")
            continue
        if in_fusion_body:
            continue
        m = _INSTRUCTION_SCOPE_RE.match(line)
        if not m:
            continue
        parts = m.group("op").split("/")
        for scope in scopes:
            if any(p == scope or p.startswith(scope + ".") for p in parts):
                out[scope].append(m.group("name"))
                break
    return out


# `%gather.3 = bf16[48,1,2048,2560]{...} gather(%pool, %rows), ...,
# slice_sizes={1,2560}, metadata={op_name=".../sparse_attn.gather/gather"}`
_GATHER_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*\S+\s+gather\(.*"
    r"slice_sizes=\{(?P<sizes>[0-9,]*)\}.*"
    r"metadata=\{[^}]*op_name=\"(?P<op>[^\"]*)\"")


def count_row_gathers(hlo_text: str, scope: str, row_width: int) -> int:
    """How many ``gather`` instructions of a compiled program, fused or
    not, run under ``jax.named_scope(scope)`` and fetch slices whose last
    extent is a whole multiple of ``row_width`` values: the gathers of
    cached rows, one or several layers' side by side, and not the scalar
    look-ups beside them. Part of what XLA:TPU's gather costs is per
    slice fetched, so this is how many times the program walks its chosen
    rows."""
    count = 0
    for line in hlo_text.splitlines():
        m = _GATHER_RE.match(line)
        if not m or scope not in m.group("op").split("/"):
            continue
        last = int(m.group("sizes").rsplit(",", 1)[-1] or 0)
        count += bool(last) and last % row_width == 0
    return count


# `%row_fetch.1 = bf16[48,2048,2560]{...} custom-call(...),
# custom_call_target="tpu_custom_call", ..., metadata={op_name=
# ".../sparse_attn.gather/jit(_row_fetch)/row_fetch/pallas_call" ...}`
_KERNEL_CALL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w-]+)(?:\.\d+)*\s*=\s*.*\scustom-call\(.*"
    r"metadata=\{[^}]*op_name=\"(?P<op>[^\"]*)\"")


def count_kernel_calls(hlo_text: str, scope: str, kernel: str) -> int:
    """How many Mosaic calls of a compiled program are named ``kernel``
    (the ``name`` of its ``pallas_call``) and run under
    ``jax.named_scope(scope)``: whether a kernel took the place of the XLA
    operations ``count_row_gathers`` counts."""
    count = 0
    for line in hlo_text.splitlines():
        m = _KERNEL_CALL_RE.match(line)
        count += bool(m) and m.group("name") == kernel and (
            scope in m.group("op").split("/"))
    return count


def count_relayouts(hlo_text: str, element_counts) -> int:
    """How many ``copy``/``transpose``/``convert`` instructions of a
    compiled program produce a buffer of one of ``element_counts``
    elements, fused or not. Handed the element counts of a program's
    resident buffers (the serve engine's KV page pools) this is the
    number of times the program rewrites one of them whole: XLA:TPU
    inserts such copies when a parameter's device layout is not the one
    its consumer runs in, and they cost a pass over the buffer each."""
    return _count_copies(hlo_text, element_counts, lambda line: True)


def count_space_moves(hlo_text: str, element_counts) -> int:
    """How many of ``count_relayouts``' instructions are async copies that
    move the buffer between memory spaces in ONE layout: the compiler
    staging a buffer that fits on-chip through it. Still a pass over the
    buffer each, and counted by ``count_relayouts`` like every other; this
    part of the count is at the compiler's discretion and no fault of the
    buffer's layout."""
    return _count_copies(hlo_text, element_counts, _is_space_move)


def _count_copies(hlo_text: str, element_counts, wanted_line) -> int:
    wanted = set(element_counts)
    if not wanted:
        return 0
    count = 0
    for line in hlo_text.splitlines():
        m = _RELAYOUT_RE.match(line)
        if m and any(
            elems in wanted for _, elems in _shape_tokens(m.group("shape"))
        ) and wanted_line(line):
            count += 1
    return count


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Ring-algorithm bytes-moved + wall-clock estimates per collective.

    Link classification is coarse on purpose: a replica group that fits
    inside one host rides ICI; anything wider pays DCN bandwidth. The
    point is relative footprint (does this program move param-sized or
    activation-sized traffic, and over which fabric), not a perf model.
    """

    ici_gbps: float = 90.0       # per-device intra-host bandwidth, GB/s
    dcn_gbps: float = 12.5       # per-device cross-host bandwidth, GB/s
    devices_per_host: int = 8

    def link(self, group_size: int) -> str:
        return "dcn" if group_size > self.devices_per_host else "ici"

    def moved_bytes(self, c: Collective) -> int:
        """Per-device bytes on the wire under ring algorithms.

        ``c.bytes`` is the RESULT buffer: the gathered size for
        all-gather, the scattered shard for reduce-scatter, the full
        buffer for all-reduce/all-to-all/permute.
        """
        g = max(c.group_size, 1)
        if g == 1:
            return 0
        if c.kind == "all-gather":
            return int(c.bytes * (g - 1) / g)
        if c.kind == "reduce-scatter":
            return int(c.bytes * (g - 1))          # input = result * g
        if c.kind == "all-reduce":
            return int(2 * c.bytes * (g - 1) / g)  # RS + AG
        if c.kind == "all-to-all":
            return int(c.bytes * (g - 1) / g)
        return int(c.bytes)                        # collective-permute

    def est_time_s(self, c: Collective) -> float:
        gbps = self.ici_gbps if self.link(c.group_size) == "ici" \
            else self.dcn_gbps
        return self.moved_bytes(c) / (gbps * 1e9)


def summarize_collectives(
    collectives, cost_model: Optional[CostModel] = None
) -> dict:
    """Fold extracted collectives into the ``comm_audit`` record shape."""
    cm = cost_model if cost_model is not None else CostModel()
    by_kind: dict = {}
    link_bytes = {"ici": 0, "dcn": 0}
    est_time_s = 0.0
    for c in collectives:
        slot = by_kind.setdefault(
            c.kind, {"count": 0, "bytes": 0, "moved_bytes": 0}
        )
        moved = cm.moved_bytes(c)
        slot["count"] += 1
        slot["bytes"] += c.bytes
        slot["moved_bytes"] += moved
        link_bytes[cm.link(c.group_size)] += moved
        est_time_s += cm.est_time_s(c)
    return {
        "count": len(collectives),
        "by_kind": by_kind,
        "total_bytes": sum(s["bytes"] for s in by_kind.values()),
        "total_moved_bytes": sum(
            s["moved_bytes"] for s in by_kind.values()
        ),
        "ici_moved_bytes": link_bytes["ici"],
        "dcn_moved_bytes": link_bytes["dcn"],
        "est_time_s": est_time_s,
    }
