"""Weights from `--seed`, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the driver installs
them into the program and hands the same arrays' recipe (the seed) to the
plain reference, which makes its own copy. A weight spec is an ordered
dict `name -> (shape, kind)`; `kind` is `normal` (N(0, 0.02), also for
biases so that no leaf is idle) or `scale` (1 + N(0, 0.02), LayerNorm
gains). Leaves stacked over layers carry the layer count as their leading
dimension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A threefry key from any non-negative whole number (the driver's
    seeds pass 2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32"), seed >> 31
    )


def _leaf(key, shape, kind, std):
    x = std * jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + x
    if kind != "normal":
        raise ValueError(f"unknown weight kind {kind!r}")
    return x


def generate(spec: dict, key, std: float = STD) -> dict:
    """Traceable: every leaf of `spec` from `key`, float32."""
    keys = jax.random.split(key, len(spec))
    return {
        name: _leaf(k, tuple(shape), kind, std)
        for k, (name, (shape, kind)) in zip(keys, spec.items())
    }


def make(spec: dict, seed: int, std: float = STD) -> dict:
    """All leaves in one jitted call on the device."""
    return jax.jit(lambda key: generate(spec, key, std))(seed_key(seed))


def std_of(config: dict) -> float:
    """A configuration may state another spread than the published 0.02
    (the tests' tiny models need a wider one to be more than an echo of
    their input token)."""
    return float(config.get("weights", {}).get("std", STD))
