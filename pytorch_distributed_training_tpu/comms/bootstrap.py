"""Process bootstrap / rendezvous — the NCCL/Gloo + launcher replacement.

The reference bootstraps three different ways: ``torch.distributed.run`` env
rendezvous (reference README.md:13), ``Accelerator()`` implicit init
(test_data_parallelism.py:55), and a hand-rolled
``MASTER_ADDR/MASTER_PORT + init_process_group("gloo")`` (test_model_
parallelism.py:166-171) chosen because NCCL can't back a DDP replica that
spans multiple devices. On TPU there is exactly ONE path:
``jax.distributed.initialize`` (one process per host) and a single XLA
collective backend that rides ICI intra-slice and DCN inter-slice — the
NCCL-vs-Gloo split disappears (SURVEY.md §5, last bullet).

Single-process runs (tests, one-chip benchmarks) skip distributed init
entirely; the same training code runs unchanged because all distribution is
expressed through the mesh, not through process-level branching.
"""

from __future__ import annotations

import dataclasses
import os

import jax

from pytorch_distributed_training_tpu.utils.logging import get_logger

_log = get_logger(__name__)
_INITIALIZED = False


@dataclasses.dataclass(frozen=True)
class RuntimeInfo:
    """What the reference prints as its rank/device banner
    (test_data_parallelism.py:58-60; test_model_parallelism.py:179-182)."""

    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int
    backend: str

    @property
    def is_main(self) -> bool:
        return self.process_index == 0


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> RuntimeInfo:
    """Initialize multi-host JAX if a multi-process environment is detected.

    Resolution order:
    1. explicit arguments,
    2. env vars (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
       ``JAX_PROCESS_ID`` — the launcher contract, analogous to
       RANK/WORLD_SIZE/MASTER_ADDR under ``torch.distributed.run``),
    3. ``JAX_DIST_AUTO_INIT=1`` opts into a bare
       ``jax.distributed.initialize()`` so cloud-TPU cluster auto-detection
       can fill everything in (opt-in because the bare call raises/hangs on
       plain single-process hosts).

    Safe to call in a single-process run: if nothing indicates a
    multi-process job, this is a no-op and the single-process defaults
    (process 0 of 1) apply.
    """
    global _INITIALIZED
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])

    auto = os.environ.get("JAX_DIST_AUTO_INIT") == "1"
    if not _INITIALIZED and (
        coordinator_address is not None or num_processes is not None or auto
    ):
        if coordinator_address is None and num_processes is None:
            jax.distributed.initialize()  # cluster auto-detection
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        _INITIALIZED = True
        # utils/logging stamps records with this, without touching jax
        os.environ["JAX_PROCESS_ID"] = str(jax.process_index())

    info = runtime_info()
    if info.is_main:
        _log.info(
            "runtime: %d process(es), %d local / %d global device(s), backend=%s",
            info.process_count,
            info.local_device_count,
            info.global_device_count,
            info.backend,
        )
    return info


def runtime_info() -> RuntimeInfo:
    """Device-count discovery — replaces ``torch.cuda.device_count()``
    (reference test_model_parallelism.py:331; SURVEY.md §2b last row)."""
    return RuntimeInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
        backend=jax.default_backend(),
    )
