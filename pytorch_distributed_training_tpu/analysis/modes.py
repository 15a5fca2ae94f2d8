"""Guard-mode resolution, jax-free: the fleet coordinator resolves the mode
it forwards to its replicas without loading the guard layer (and jax)."""

from __future__ import annotations

import os

MODES = ("off", "record", "strict")


def guard_mode_from_env(default: str = "record") -> str:
    mode = os.environ.get("PDT_TPU_GUARDS", default)
    if mode not in MODES:
        raise ValueError(
            f"PDT_TPU_GUARDS must be one of {MODES}, got {mode!r}"
        )
    return mode
