"""Parameter-sharding policies: DP / FSDP / tensor-parallel as PartitionSpecs.

Every parallelism strategy in this framework is a *sharding policy* — a map
from parameter-tree paths to PartitionSpecs over the canonical mesh axes —
not a separate engine. This is the design stance SURVEY.md §2d prescribes:
the reference's strategies (DDP replication; hand-placed model parallelism,
test_model_parallelism.py:98-103; hybrid DDP-over-multi-device-module,
:248-253) plus the driver's FSDP config all collapse into:

- **dp**: params replicated; batch sharded over ``data`` (pure DDP twin).
- **fsdp**: params/optimizer state additionally sharded over the ``fsdp``
  axis on one eligible dimension (ZeRO-3 as a spec, XLA does the
  all-gather/reduce-scatter).
- **tp**: Megatron-style tensor parallelism over ``model`` for the
  transformer blocks — QKV projections column-parallel on the heads dim,
  attention out row-parallel, MLP up column- / down row-parallel. XLA
  inserts the psum where a row-parallel matmul needs it.

Optimizer state (Adam moments) shards exactly like its parameter —
``state_shardings`` maps the policy over the whole TrainState.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_training_tpu.train.state import TrainState


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    tp: bool = False  # shard transformer blocks over the "model" axis
    fsdp: bool = False  # shard remaining/bigger dims over the "fsdp" axis
    # branch-ensemble parallelism (the TriBert twin, models/branch.py): the
    # leading [n_branches] param dim shards over "model", so each model-axis
    # slice holds and runs exactly one branch.
    branch: bool = False
    # stage/layer-split parallelism (the ConcatBert twin): the leading
    # [num_layers] dim of scan-stacked layers (ModelConfig.scan_layers)
    # shards over "stage" — contiguous layer blocks per stage slice.
    stage: bool = False
    # minimum leaf size (elements) before fsdp sharding kicks in; tiny
    # params (norms, biases) stay replicated — sharding them costs more in
    # collective latency than it saves in HBM.
    fsdp_min_size: int = 2**16


def _tp_spec(path: tuple[str, ...], shape: tuple[int, ...]) -> P | None:
    """Megatron TP specs keyed on this framework's BERT parameter layout
    (models/bert.py). Returns None when TP doesn't apply to the leaf."""
    names = set(path)
    leaf = path[-1]
    ndim = len(shape)
    if leaf == "kernel_scale":
        # weight-only int8 scale (ops/quant.py quantize_kernel): same rank
        # as its kernel with the contracted axes kept as size-1 dims —
        # shard exactly like the kernel wherever the kernel's sharded axis
        # survives in the scale, and replicate the size-1 dims (a mesh
        # axis cannot split a singleton).
        spec = _tp_spec(path[:-1] + ("kernel",), shape)
        if spec is None:
            return None
        return P(*(
            axis if shape[i] != 1 else None for i, axis in enumerate(spec)
        ))
    if "attention" in names:
        # query/key/value: kernel [hidden, heads, head_dim], bias [heads, hd]
        if any(n in names for n in ("query", "key", "value")):
            if leaf == "kernel" and ndim == 3:
                return P(None, "model", None)
            if leaf == "bias" and ndim == 2:
                return P("model", None)
        if "out" in names:
            # out: kernel [heads, head_dim, hidden] — row-parallel (psum after)
            if leaf == "kernel" and ndim == 3:
                return P("model", None, None)
            if leaf == "bias":
                return P(None)
    if "mlp_up" in names:
        if leaf == "kernel" and ndim == 2:
            return P(None, "model")
        if leaf == "bias" and ndim == 1:
            return P("model")
    if "mlp_down" in names:
        if leaf == "kernel" and ndim == 2:
            return P("model", None)
        if leaf == "bias":
            return P(None)
    return None


def _add_fsdp(spec: P | None, shape: tuple[int, ...], fsdp_size: int,
              min_size: int) -> P | None:
    """Shard the largest still-unsharded divisible dim over ``fsdp``."""
    import numpy as np

    if fsdp_size <= 1 or int(np.prod(shape)) < min_size:
        return spec
    entries = list(spec) if spec is not None else [None] * len(shape)
    while len(entries) < len(shape):
        entries.append(None)
    candidates = [
        (shape[i], i)
        for i in range(len(shape))
        if entries[i] is None and shape[i] % fsdp_size == 0 and shape[i] > 1
    ]
    if not candidates:
        return spec
    _, dim = max(candidates)
    entries[dim] = "fsdp"
    return P(*entries)


def _leaf_spec(path, leaf, policy: ShardingPolicy, mesh: Mesh) -> P:
    """The single source of truth mapping one array (by path + shape) to its
    PartitionSpec. Used for params AND optimizer moments (whose paths carry
    the param path as a suffix), so both always shard identically."""
    if getattr(leaf, "ndim", 0) == 0:
        return P()
    names = tuple(
        p.key if hasattr(p, "key") else getattr(p, "name", str(p)) for p in path
    )
    spec = None
    # Stacked-param axes first: "branches" (vmapped ensemble, models/branch)
    # and "layers_scan" (scan-stacked layers) carry an extra leading dim that
    # shards over model/stage respectively; the per-layer rules (tp) then
    # apply to the trailing dims.
    lead = None
    if policy.branch and "branches" in names and mesh.shape["model"] > 1:
        lead = "model"
    elif policy.stage and "layers_scan" in names and mesh.shape["stage"] > 1:
        lead = "stage"
    if lead and leaf.shape[0] % mesh.shape[lead]:
        # stacked dim (n_branches / num_layers) not divisible by the axis —
        # replicate rather than crash; the caller picked an odd mesh.
        lead = None
    inner_shape = tuple(leaf.shape[1:] if lead else leaf.shape)
    inner_ndim = len(inner_shape)
    if policy.tp and mesh.shape["model"] > 1 and lead != "model":
        spec = _tp_spec(names, inner_shape)
    if lead:
        inner = list(spec) if spec is not None else []
        inner += [None] * (inner_ndim - len(inner))
        spec = P(lead, *inner)
    if policy.fsdp:
        spec = _add_fsdp(spec, leaf.shape, mesh.shape["fsdp"], policy.fsdp_min_size)
    return spec if spec is not None else P()


def param_pspecs(params, policy: ShardingPolicy, mesh: Mesh):
    """PartitionSpec pytree for a parameter pytree under the given policy."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, leaf, policy, mesh), params
    )


def serve_param_shardings(params, mesh: Mesh,
                          policy: ShardingPolicy | None = None):
    """NamedSharding pytree for a SERVING params tree on a tensor-parallel
    mesh: the Megatron TP rules above (QKV column-parallel on heads,
    attention-out / mlp_down row-parallel) applied to the inference
    weights, everything else — embeddings, norms, lm head — replicated.
    No fsdp: a serve replica wants whole layers resident, not gathered
    per tick."""
    policy = policy if policy is not None else ShardingPolicy(tp=True)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _leaf_spec(path, leaf, policy, mesh)
        ),
        params,
    )


def serve_pool_pspec() -> P:
    """PartitionSpec for one paged-KV pool leaf: the value pools
    ``[num_pages, page_size, heads * head_dim]`` and the int8 cache's fp32
    scale pools ``[num_pages, page_size, heads]`` both carry their heads
    on the LAST axis, which shards over ``model`` in contiguous blocks of
    ``heads / N`` heads — each shard owns its own page pool at 1/N width.
    Page indices, block tables and the allocator arithmetic are untouched
    (they address the page axis, which stays whole)."""
    return P(None, None, "model")


def serve_pool_shardings(pools, mesh: Mesh):
    """NamedSharding pytree for the engine's paged K/V pools (value pools,
    plus scale pools when the cache is int8)."""
    sharding = NamedSharding(mesh, serve_pool_pspec())
    return jax.tree.map(lambda _: sharding, pools)


def state_shardings(state: TrainState, policy: ShardingPolicy, mesh: Mesh):
    """NamedSharding pytree for the full TrainState.

    One path-based rule applied uniformly to every array in the state:
    Adam moments live at paths like ``opt_state[1].mu.bert.layer_0...kernel``
    — the parameter path is a suffix — so the same TP/FSDP matcher that
    shards a kernel shards its moments identically, and scalars (step,
    schedule count, RNG key) fall through to replicated.
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, _leaf_spec(path, leaf, policy, mesh)),
        state,
    )


def shard_state(state: TrainState, shardings: TrainState) -> TrainState:
    """Place the state onto its shardings (initial placement).

    Single-process: plain ``device_put``. Multi-process: ``device_put`` onto
    a global (non-addressable) sharding is disallowed, so each process
    materializes only its addressable shards via
    ``jax.make_array_from_callback`` from the host value — every process
    holds the same full arrays after the (identically seeded) init, which
    is exactly the callback contract. PRNG-key leaves are placed through
    ``key_data``/``wrap_key_data`` (extended dtypes can't ride the raw
    callback path).
    """
    if jax.process_count() == 1:
        return jax.tree.map(jax.device_put, state, shardings)

    import numpy as np

    def _place(x, sh):
        if jax.dtypes.issubdtype(getattr(x, "dtype", None), jax.dtypes.prng_key):
            data = np.asarray(jax.device_get(jax.random.key_data(x)))
            repl = NamedSharding(sh.mesh, P())  # keys are always replicated
            placed = jax.make_array_from_callback(
                data.shape, repl, lambda idx: data[idx]
            )
            return jax.random.wrap_key_data(
                placed, impl=jax.random.key_impl(x)
            )
        host = np.asarray(jax.device_get(x))
        return jax.make_array_from_callback(
            host.shape, sh, lambda idx: host[idx]
        )

    return jax.tree.map(_place, state, shardings)
