"""Test harness configuration.

The reference repo has no test suite at all (SURVEY.md §4) — its only
verification is end-to-end convergence. This framework instead follows the
standard JAX simulated-distributed strategy: run every test single-process on
8 virtual CPU devices (``--xla_force_host_platform_device_count=8``) so mesh /
pjit / psum code paths execute real SPMD partitioning with no TPU attached.

This module MUST run before anything imports jax, which pytest guarantees for
a root conftest: ``JAX_PLATFORMS`` and ``XLA_FLAGS`` are read when the
backend starts.

PDT_TPU_TESTS=1 inverts the setup: the backend is left on the real chip and
only the ``@pytest.mark.tpu`` tier runs — the kernel paths the CPU suite
can't see (pltpu.prng_random_bits is all-zeros in interpret mode; NOTES.md).
Usage, on the chip: PDT_TPU_TESTS=1 python -m pytest tests/ -m tpu -q
"""

import os

_TPU_TIER = os.environ.get("PDT_TPU_TESTS") == "1"

# Zero-egress image: don't let HF datasets/hub spend ~20s discovering there
# is no network before the offline synthetic fallback kicks in.
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("HF_DATASETS_OFFLINE", "1")

if not _TPU_TIER:
    # hermetic: no persistent compile cache leaks in from the caller's
    # shell (entry points leave CPU uncached unless this is set —
    # train/compile.py — and tests pin the uncached records)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if _TPU_TIER:
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"PDT_TPU_TESTS=1 asks for the on-chip tier but the backend is "
            f"{jax.default_backend()!r} ({jax.devices()}): run it on the "
            f"machine with the chip"
        )
    from pytorch_distributed_training_tpu.train.compile import (  # noqa: E402
        enable_compile_cache,
    )

    enable_compile_cache()
elif len(jax.devices()) != 8:  # pragma: no cover - depends on launch env
    raise RuntimeError(
        f"conftest failed to set up the 8-device CPU mesh "
        f"(got {jax.devices()})"
    )

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """tpu-marked tests run only on the real chip (PDT_TPU_TESTS=1 tier);
    everything else runs only on the CPU mesh — one suite, two tiers."""
    skip_tpu = pytest.mark.skip(
        reason="on-TPU tier: run with PDT_TPU_TESTS=1 -m tpu on the chip"
    )
    skip_cpu = pytest.mark.skip(
        reason="CPU-mesh test: run without PDT_TPU_TESTS"
    )
    for item in items:
        is_tpu = "tpu" in item.keywords
        if is_tpu and not _TPU_TIER:
            item.add_marker(skip_tpu)
        elif not is_tpu and _TPU_TIER:
            item.add_marker(skip_cpu)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {len(devices)}"
    return devices


@pytest.fixture(autouse=True)
def _clear_kernel_dispatch_ctx():
    """A Trainer registers its mesh as the global kernel-dispatch context
    (ops/dispatch.py) and that registration intentionally outlives it in a
    real process; between TESTS it must not leak (an interpret-mode parity
    test after a Trainer test would silently shard_map over the stale
    mesh)."""
    yield
    from pytorch_distributed_training_tpu.ops.dispatch import (
        set_kernel_mesh,
    )

    set_kernel_mesh(None)
