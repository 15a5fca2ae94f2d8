"""Correctness tooling: static linters + runtime guard layers.

Three halves of one goal — make the classic JAX perf/correctness
regressions (silent per-shape recompiles, implicit host<->device
transfers in hot loops, dropped buffer donations, tracer leaks, reused
PRNG keys) AND the classic threading regressions (unlocked shared state,
lock-order inversions, unbounded shutdown waits) impossible to ship
rather than merely hard to write:

- **Static linter** (``lint.py`` + ``rules/``): an AST pass over the
  package with JAX-specific and thread-safety rules. Driven by
  ``scripts/lint.py``; every finding is either fixed or explicitly
  waived in ``waivers.toml`` with a one-line reason, so
  ``scripts/lint.py --check`` gates a clean tree.
- **Runtime guards** (``guards.py``): a recompile counter around jitted
  entry points (retracing after warm-up is a violation), a
  ``jax.transfer_guard``-based implicit-transfer detector armed around
  the Trainer step and the serve tick, and post-lower donation/sharding
  audits. Violations emit ``recompile`` / ``implicit_transfer`` /
  ``donation_audit`` / ``sharding_audit`` telemetry records (surfaced by
  ``scripts/summarize_metrics.py``) and, in strict mode, raise.
- **Runtime lock registry** (``concurrency/``): instrumented
  ``lock()``/``rlock()`` factories recording contention/hold/wait per
  lock, detecting lock-order inversions against the orders actually
  observed live, and flagging locks held across device boundaries.

This ``__init__`` is LAZY (PEP 562): ``guards``/``lint`` pull in jax,
but ``analysis.concurrency`` must stay importable from the jax-free
fleet/router processes — importing the package must not pay (or break)
a jax import nobody asked for.
"""

from pytorch_distributed_training_tpu.utils.lazy import lazy_exports

_LAZY = {
    "GuardSet": "guards",
    "GuardViolation": "guards",
    "RecompileError": "guards",
    "TransferGuardError": "guards",
    "donation_audit": "guards",
    "guard_mode_from_env": "modes",
    "sharding_audit": "guards",
    "Finding": "lint",
    "LintReport": "lint",
    "lint_paths": "lint",
    "lint_source": "lint",
    "Waiver": "waivers",
    "load_waivers": "waivers",
    "concurrency": None,        # subpackage (jax-free)
}

__all__ = sorted(_LAZY)
__getattr__ = lazy_exports(__name__, _LAZY)
