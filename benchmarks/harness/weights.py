"""Weights from `--seed`, made on the device, whole or one leaf at a time.

The benchmark, not the program, makes the weights: the driver installs
them into the program and hands the same recipe (a `Source`: spec, seed,
spread, the family's kinds) to the plain reference, which makes its own
copy. A weight spec is an ordered dict `name -> (shape, kind)`; `kind` is
`normal` (N(0, 0.02), also for biases so that no leaf is idle), `scale`
(1 + N(0, 0.02), LayerNorm gains), or one the family's file brings
(`init(kind, key, shape, std)`: a decay rate or a step bias wants a
positive range). Leaf `i` of the spec is drawn from key `i` of
`split(key, len(spec))`, so a leaf made alone (`Source.leaf`) holds the
same values as in the whole (`Source.whole`), bit for bit.

Which of the two a reference takes. The two families here stack their
layers' leaves (`layers.q_w` [L, h, h]) and take the source whole, one
jitted call: at 1.4 GB in float32 that fits beside nothing else, which is
how the reference runs. A family sized to the chip (some GB in the served
type, twice that in float32) must name its leaves per layer in its spec
(`layers.3.experts_in`, not a stacked `layers.experts_in`) and take them
leaf by leaf, layer by layer, so that it never holds more than one layer
in float32; `adapters.install` fills the program's tree in groups of
source leaves under a byte cap for the same reason, and refuses a single
leaf over the cap.
"""

from __future__ import annotations

import math

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


def _leaf(key, shape, kind, std, init=None):
    if kind in ("normal", "scale"):
        x = std * jax.random.normal(key, shape, jnp.float32)
        return 1.0 + x if kind == "scale" else x
    if init is None:
        raise ValueError(f"unknown weight kind {kind!r}, and the family "
                         f"brings no init(kind, key, shape, std)")
    return init(kind, key, shape, std).astype(jnp.float32)


def generate(spec: dict, key, std: float = STD, init=None, names=None) -> dict:
    """Traceable: the leaves of `spec` (all, or those in `names`) from
    `key`, float32, each from its own key of the one split."""
    keys = jax.random.split(key, len(spec))
    return {
        name: _leaf(k, tuple(shape), kind, std, init)
        for k, (name, (shape, kind)) in zip(keys, spec.items())
        if names is None or name in names
    }


def make(spec: dict, seed: int, std: float = STD, init=None) -> dict:
    """All leaves in one jitted call on the device."""
    return jax.jit(lambda key: generate(spec, key, std, init))(seed_key(seed))


def leaf(spec: dict, seed: int, name: str, std: float = STD, init=None):
    """One leaf alone, the same values as `make(...)[name]`."""
    if name not in spec:
        raise KeyError(f"no leaf {name!r} in the weight spec")
    return jax.jit(
        lambda key: generate(spec, key, std, init, {name})[name])(seed_key(seed))


def nbytes(spec: dict, name: str) -> int:
    """Bytes of a leaf as it is generated (float32)."""
    return 4 * math.prod(spec[name][0])


class Source:
    """The recipe of a run's weights, handed to the install and to the
    reference: take it `whole()` or `leaf(name)` by leaf."""

    def __init__(self, spec: dict, seed: int, std: float = STD, init=None):
        self.spec, self.seed, self.std, self.init = spec, seed, std, init

    def key(self):
        return seed_key(self.seed)

    def generate(self, key, names=None) -> dict:
        return generate(self.spec, key, self.std, self.init, names)

    def whole(self) -> dict:
        return make(self.spec, self.seed, self.std, self.init)

    def leaf(self, name: str):
        return leaf(self.spec, self.seed, name, self.std, self.init)


def std_of(config: dict) -> float:
    """A configuration may state another spread than the published 0.02
    (the tests' tiny models need a wider one to be more than an echo of
    their input token)."""
    return float(config.get("weights", {}).get("std", STD))
