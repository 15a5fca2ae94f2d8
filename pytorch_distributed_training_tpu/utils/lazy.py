"""Lazy package exports (PEP 562).

``serve``, ``train``, ``telemetry`` and ``analysis`` each hold jax-free
modules (router, fleet, manifest, registry, concurrency) beside jax-heavy
ones. The fleet coordinator imports the former and must never load jax:
a chip belongs to one process, and the coordinator's replicas need it.
So a package ``__init__`` names its exports in a table and resolves each
on first use instead of importing every submodule up front.
"""

from __future__ import annotations

import importlib


def lazy_exports(package: str, table: dict):
    """``__getattr__`` for ``package``: ``table`` maps an exported name to
    the submodule that defines it (``None`` = the name IS a submodule)."""

    def __getattr__(name: str):
        if name not in table:
            raise AttributeError(
                f"module {package!r} has no attribute {name!r}"
            )
        target = table[name]
        if target is None:
            return importlib.import_module(f"{package}.{name}")
        return getattr(
            importlib.import_module(f"{package}.{target}"), name
        )

    return __getattr__
