"""Pallas kernel dispatch under sharded meshes (shard_map routing).

Problem (NOTES.md round-2; VERDICT r2 #3): a Pallas custom call inside a
GSPMD-partitioned program is treated as REPLICATED by the SPMD partitioner
— XLA all-gathers the sharded operands before every call, silently turning
the kernels' wins into catastrophic collective traffic. Round 2 therefore
gated every kernel to ``jax.device_count() == 1`` and sharded meshes fell
back to identical-math XLA ops (correct, but the fused-kernel throughput
evaporated exactly on the multi-chip configs that need it most).

The fix is the standard one: run the kernel INSIDE ``shard_map`` over the
axes its math is embarrassingly parallel in (batch/seq rows for LayerNorm
and dropout-add-LN tails, batch x heads for attention-probs mask-scale and
flash attention). Each device then invokes the kernel on its LOCAL shard
and no collective is emitted — GSPMD sees a manually-partitioned region.

The ops can't guess the mesh from inside a traced function, so the Trainer
(or any harness) registers the mesh + axis convention here before tracing:

    set_kernel_mesh(mesh)            # Trainer.__init__ / bench setup
    with use_kernel_mesh(mesh): ...  # tests

Dispatch contract per op (see each op's wrapper):
- ``mode() == "direct"``   — single-device TPU or the interpret context:
  call the kernel directly (round-2 behavior, unchanged).
- ``mode() == "shard_map"``— TPU backend, >1 device, mesh registered:
  wrap the kernel in shard_map with the op's specs; the per-device seed is
  offset by the linearized device index so dropout streams stay distinct.
- ``mode() == "off"``      — anything else: the op falls back to its
  XLA/jnp reference math (identical numerics), as before.

The reference delegates all of this to torch/NCCL (its kernels arrive
pre-sharded per GPU, reference test_data_parallelism.py:125-127); owning
the kernels means owning their partitioning story too.
"""

from __future__ import annotations

import contextlib
import threading
from collections import Counter
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

_CTX = threading.local()

# every dispatch decision, counted at trace time and keyed "op:path" — op is
# "layer_norm", "dal", "mask_scale" or "flash"; path is "direct" or
# "shard_map" (the Pallas kernel) or "xla" (the reference math: off-TPU, an
# impl that is not the fused one, a shape that does not tile, a mesh that
# does not divide). The fallbacks are correct and silent by design, and the
# compiled HLO hides the kernel under interpret mode, so this counter is
# the observable: Trainer / serve_lm log it, chip_smoke.py and the tests
# assert on it (``DISPATCH_PATHS["dal:shard_map"]`` etc.).
DISPATCH_PATHS: Counter = Counter()


def note_path(op: str, path: str) -> None:
    DISPATCH_PATHS[f"{op}:{path}"] += 1


def summary() -> str:
    """One log line: the mode this thread dispatches under and every path
    traced so far in the process."""
    return (
        f"kernel dispatch: mode={mode()!r}, traced paths "
        f"{dict(DISPATCH_PATHS)}"
    )


def set_kernel_mesh(
    mesh: Optional[Mesh],
    *,
    batch_axes: Sequence[str] = ("data", "fsdp"),
    seq_axis: str = "seq",
    head_axis: str = "model",
) -> None:
    """Register (or clear, with None) the mesh the kernels shard over."""
    _CTX.mesh = mesh
    _CTX.batch_axes = tuple(batch_axes)
    _CTX.seq_axis = seq_axis
    _CTX.head_axis = head_axis


@contextlib.contextmanager
def use_kernel_mesh(mesh: Mesh, **kwargs):
    prev = kernel_ctx()
    set_kernel_mesh(mesh, **kwargs)
    try:
        yield
    finally:
        if prev is None:
            set_kernel_mesh(None)
        else:
            set_kernel_mesh(
                prev[0], batch_axes=prev[1], seq_axis=prev[2],
                head_axis=prev[3],
            )


def kernel_ctx():
    """(mesh, batch_axes, seq_axis, head_axis) or None."""
    mesh = getattr(_CTX, "mesh", None)
    if mesh is None:
        return None
    return (mesh, _CTX.batch_axes, _CTX.seq_axis, _CTX.head_axis)


def interpret_active() -> bool:
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        _INTERPRET,
    )

    return getattr(_INTERPRET, "depth", 0) > 0


@contextlib.contextmanager
def manual_region():
    """Mark 'we are inside a shard_map body' (trace-time flag).

    Inside a manual region every mesh axis is already manually partitioned,
    so a kernel must be called DIRECTLY on the local shard — opening a
    second shard_map over the same mesh is a trace error ("context mesh
    Manual should match mesh passed to shard_map"), hit e.g. when
    GPipeClassifier's pipelined BertLayers (already inside gpipe_apply's
    shard_map) reach dropout_add_layer_norm with a registered kernel mesh.
    Every shard_map body this framework creates enters this context."""
    _CTX.manual_depth = getattr(_CTX, "manual_depth", 0) + 1
    try:
        yield
    finally:
        _CTX.manual_depth -= 1


@contextlib.contextmanager
def force_shard_map():
    """Test hook: make ``mode()`` report "shard_map" regardless of device
    count (requires a registered mesh). Lets the on-TPU tier execute the
    real Mosaic kernels through the shard_map routing on the single
    available chip — the 1-device mesh is trivial, the code path is not."""
    _CTX.force = "shard_map"
    try:
        yield
    finally:
        _CTX.force = None


def mode() -> str:
    """Kernel dispatch mode for the calling op (see module docstring)."""
    if getattr(_CTX, "manual_depth", 0) > 0:
        # already inside a shard_map body: operands are local shards,
        # call the kernel directly (nesting another shard_map would crash)
        if interpret_active() or jax.default_backend() == "tpu":
            return "direct"
        return "off"
    forced = getattr(_CTX, "force", None)
    if forced is not None and kernel_ctx() is not None:
        return forced
    if interpret_active():
        # the interpret context emulates kernels anywhere; with a mesh
        # registered it exercises the exact shard_map routing real chips use
        return "shard_map" if kernel_ctx() is not None else "direct"
    if jax.default_backend() != "tpu":
        return "off"
    if jax.device_count() == 1:
        return "direct"
    return "shard_map" if kernel_ctx() is not None else "off"


def linear_device_index(axes: Sequence[str], mesh: Mesh):
    """Linearized index over ``axes`` inside a shard_map body — offsets the
    per-device kernel PRNG seed so no two shards reuse a mask stream."""
    idx = None
    for a in axes:
        comp = jax.lax.axis_index(a)
        idx = comp if idx is None else idx * mesh.shape[a] + comp
    if idx is None:
        import jax.numpy as jnp

        return jnp.int32(0)
    return idx


def axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    """Product of the mesh axes' sizes — the shard count a dim divides by."""
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)
    return n


def plan_shards(shape: Sequence[int], extra_axes: dict):
    """Common shard-plan core for every kernel's shard_map wrapper.

    Dim 0 always shards over the registered batch axes; each
    ``{dim: mesh_axis}`` in ``extra_axes`` additionally shards that dim
    when the axis is >1 in the mesh. Returns
    ``(mesh, PartitionSpec, axes_used, local_shape)`` — ``axes_used`` is
    the ordered axis list for :func:`linear_device_index` seed offsets,
    ``local_shape`` the per-shard shape for the caller's own tileability
    checks — or None when no mesh is registered or a sharded dim doesn't
    divide (caller falls back to its XLA math). ONE implementation so the
    axis convention and divisibility rule can't drift between the ops
    (layer_norm row kernels, mask-scale, flash)."""
    from jax.sharding import PartitionSpec as P

    ctx = kernel_ctx()
    if ctx is None:
        return None
    mesh, batch_axes, _, _ = ctx
    entries: list = [None] * len(shape)
    entries[0] = tuple(batch_axes)
    axes_used = list(batch_axes)
    local = list(shape)
    f0 = axes_size(mesh, batch_axes)
    if shape[0] % f0:
        return None
    local[0] //= f0
    for dim, axis_name in extra_axes.items():
        f = mesh.shape.get(axis_name, 1)
        if f > 1:
            if shape[dim] % f:
                return None
            entries[dim] = axis_name
            axes_used.append(axis_name)
            local[dim] //= f
    return mesh, P(*entries), axes_used, tuple(local)
