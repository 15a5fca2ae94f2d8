"""Device-mesh construction and sharding-spec helpers.

The mesh is the framework's single abstraction for ALL parallelism — the
TPU-native replacement for the reference's per-strategy machinery (DDP
process groups for data parallelism, hand-placed ``.to(device)`` calls for
model parallelism; reference test_model_parallelism.py:98-103,190-191).
Canonical axes ``(data, fsdp, stage, model)`` — see
``utils.config.MeshConfig``. The batch shards over ``(data, fsdp)``;
parameters shard over ``fsdp`` (ZeRO-style), ``stage`` (pipeline) and
``model`` (tensor/branch) as the sharding policy dictates. XLA then inserts
the actual ICI/DCN collectives (psum for gradients = DDP's NCCL allreduce,
collective-permute for stage transfer = the reference's ``.to(device)``
activation shuttling).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_training_tpu.utils.config import MeshConfig
from pytorch_distributed_training_tpu.utils.logging import get_logger

_log = get_logger(__name__)

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_STAGE = "stage"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"
AXIS_NAMES = MeshConfig.AXIS_NAMES

# Batch dimension shards over both flavors of data parallelism.
BATCH_AXES = (AXIS_DATA, AXIS_FSDP)

# Train batches are laid out [grad_accum, micro_batch, ...]: the accumulation
# axis stays whole (lax.scan walks it), the micro-batch dim shards. The data
# pipeline places batches with this spec and the train step declares it as
# in_sharding — single source of truth for the layout contract.
TRAIN_BATCH_PSPEC = P(None, BATCH_AXES)


# The most recently built mesh. Ops that must open an explicit-SPMD region
# inside model code (ring attention's shard_map) need the concrete Mesh
# object, which flax module calls can't thread through their signatures —
# build_mesh records it here and ``current_mesh()`` hands it back.
_CURRENT_MESH: Mesh | None = None


def current_mesh() -> Mesh | None:
    return _CURRENT_MESH


def set_current_mesh(mesh: Mesh) -> None:
    """Re-pin the mesh mesh-registry consumers (ring attention) resolve
    against. ``Trainer.run`` calls this so retraces during ITS run always see
    ITS mesh even if another mesh was built later in the same process."""
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def build_mesh(
    config: MeshConfig | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a 4-axis logical mesh over the given (default: all) devices.

    ``mesh_utils.create_device_mesh`` lays physical devices out so that the
    fastest-varying logical axes map to physically adjacent chips — i.e. the
    ``model``/``stage`` axes (which carry per-step activation/weight
    collectives) ride ICI, while ``data`` (one gradient psum per step) can
    span DCN. This is the mesh-axis→interconnect mapping that replaces the
    reference's NCCL-vs-Gloo backend choice (SURVEY.md §5).
    """
    config = config or MeshConfig()
    if devices is None:
        devices = jax.devices()
    shape = config.resolved_shape(len(devices))
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    except (ValueError, AssertionError, NotImplementedError) as e:
        # create_device_mesh rejects what it cannot map onto the physical
        # torus (a subset of a host's chips, an exotic shape). A plain
        # reshape is always valid but ignores ICI locality — say so.
        _log.warning(
            "create_device_mesh rejected mesh shape %s over %d device(s) "
            "(%r); falling back to device order, which is NOT "
            "locality-optimized", shape, len(devices), e,
        )
        dev_array = np.asarray(devices).reshape(shape)
    global _CURRENT_MESH
    _CURRENT_MESH = Mesh(dev_array, AXIS_NAMES)
    return _CURRENT_MESH


def batch_pspec(extra_dims: int = 0) -> P:
    """PartitionSpec for a batch-leading array: shard dim 0 over data+fsdp.

    This single spec IS the framework's data parallelism: with the batch
    sharded and parameters replicated (or fsdp-sharded), jit emits the
    gradient AllReduce over ICI that DDP did through NCCL (reference
    test_data_parallelism.py:146; SURVEY.md §2b).
    """
    return P(BATCH_AXES, *([None] * extra_dims))


def replicated() -> P:
    return P()


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_batch(mesh: Mesh, batch):
    """Device-put a host-global batch pytree with batch-axis sharding."""
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, batch_pspec())), batch
    )


def axis_size(mesh: Mesh, *axes: str) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def dp_degree(mesh: Mesh) -> int:
    """Total data-parallel degree (number of batch shards)."""
    return axis_size(mesh, *BATCH_AXES)
