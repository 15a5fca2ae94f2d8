"""Runtime guard layer: recompiles, implicit transfers, donation, sharding.

The static linter (``analysis/lint.py``) catches what's visible in
source; this module catches what only shows up live:

- **Recompile detector** — ``GuardSet.wrap_jit(name, fn)`` wraps a jitted
  callable; after its warm-up compile, any call that builds a new
  program is a violation: a ``recompile`` telemetry record + counter, and
  a ``RecompileError`` in strict mode. AOT-``Compiled`` objects cannot
  rebuild and pass through trivially (but still get transfer arming).
- **Implicit-transfer detector** — warm guarded calls run under
  ``jax.transfer_guard``: ``"disallow"`` in strict mode (the classic bug
  — an un-placed host array fed to a warm step forces a per-call H2D
  copy — raises, is recorded as an ``implicit_transfer`` record, and
  re-raises as ``TransferGuardError``); ``"log"`` in record mode.
  ``GuardSet.transfer_scope(name)`` arms the same detector around
  arbitrary host regions (the serve tick, custom loops).
- **Donation audit** — ``donation_audit(name, lowered_or_compiled)``
  parses the lowering/HLO text for input-output aliasing and emits a
  ``donation_audit`` record; requesting donation that XLA dropped is a
  violation (the input buffer stays live, doubling resident HBM).
- **Sharding audit** — ``sharding_audit(params, mesh)`` flags
  above-threshold leaves left fully replicated while the mesh has
  non-trivial fsdp/model/stage axes (a sharding policy that silently
  didn't apply), as a ``sharding_audit`` record.
- **Collective audit** — ``wrap_jit(..., comm_manifest=...)`` checks the
  warmed program's compiled HLO against an expected-collective manifest
  (``analysis/spmd/manifest.py``): post-first-compile the call re-lowers
  AND re-compiles against the warm-up avals, extracts every collective,
  and ``comm_audit`` emits a ``comm_audit`` record (strict: raises on
  deviation). Opt-in per call site — the extra compile is real money, so
  only deliberately-warmed programs pass a manifest.

Modes (``PDT_TPU_GUARDS`` env or ``TrainConfig.guards`` / serve
``--guards``): ``off`` — pass-through; ``record`` (default) — detect,
count, emit telemetry, never raise; ``strict`` — record AND raise (what
the tier-1 guard tests run under).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Any, Optional

import jax

from pytorch_distributed_training_tpu.analysis.concurrency.locks import (
    get_lock_registry,
    held_lock_names,
)
from pytorch_distributed_training_tpu.analysis.modes import (
    MODES as _MODES,
    guard_mode_from_env,
)

# ------------------------------------------------------- build accounting
#
# Recompile detection rides jax.monitoring: every jaxpr -> MLIR lowering
# fires a '/jax/core/compile/jaxpr_to_mlir_module_duration' event IN THE
# CALLING THREAD, once per program jit actually builds, and a warm
# executable fires none. A thread-local counter scoped around each guarded
# call is therefore an exact "did THIS call build a new program" probe —
# immune to other threads compiling concurrently (prefetch placement, a
# second engine) and to persistent-cache hits that skip the backend
# compile. The jaxpr-trace event is NOT that probe: jit emits it on every
# dispatch that misses the C++ fast path, tracing-cache hit or not, and a
# fast-path miss is legal on a warm program (a typed PRNG key fed back
# through a jit with explicit shardings misses it once, on the third
# call, and builds nothing).

_BUILD_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_tls = threading.local()
_listener_lock = threading.Lock()
_listener_installed = False


def _on_duration(name: str, *args, **kwargs) -> None:
    if name == _BUILD_EVENT:
        _tls.builds = getattr(_tls, "builds", 0) + 1


def _ensure_build_listener() -> None:
    global _listener_installed
    with _listener_lock:
        if not _listener_installed:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_installed = True


def _build_count() -> int:
    return getattr(_tls, "builds", 0)


class GuardViolation(RuntimeError):
    """A runtime correctness guard tripped (strict mode)."""


class RecompileError(GuardViolation):
    """A jitted entry point built a new program after warm-up."""


class TransferGuardError(GuardViolation):
    """An implicit host<->device transfer happened in a guarded region."""


def _registry_or_default(registry):
    if registry is not None:
        return registry
    from pytorch_distributed_training_tpu.telemetry.registry import (
        get_registry,
    )

    return get_registry()


class GuardedCall:
    """Wrapper installed by ``GuardSet.wrap_jit`` around one jitted entry
    point. Transparent to the call contract; adds per-call recompile
    accounting, transfer-guard arming once warm, a lock-across-device
    check (dispatching compiled work while holding an instrumented lock
    serializes every thread needing it behind the accelerator), and —
    with ``audit_donation`` — a one-shot post-first-compile donation
    audit built from the warm-up call's avals. An AOT ``Compiled``
    (no ``_cache_size`` trace cache) gets NO warm-up allowance — it can
    never legally trace; a jit gets exactly one warm-up call."""

    def __init__(self, name: str, fn, guards: "GuardSet",
                 audit_donation: bool = False, comm_manifest=None):
        self.name = name
        self.fn = fn
        self.guards = guards
        self._warm = not hasattr(fn, "_cache_size")
        self._audit_donation = audit_donation
        self._comm_manifest = comm_manifest
        self.comm_record = None  # the one-shot comm audit's record
        self.calls = 0
        self.recompiles = 0

    @property
    def warm(self) -> bool:
        return self._warm

    @staticmethod
    def _aval(a):
        """Shape/dtype spec of one warm-up operand, KEEPING its
        NamedSharding: dropping it would re-lower the single-device
        program, and a tensor-parallel comm audit would then inspect HLO
        with no collectives at all — a false "required kind absent"."""
        sh = getattr(a, "sharding", None)
        if isinstance(sh, jax.sharding.NamedSharding):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def _donation_audit_from(self, args, kwargs) -> None:
        """Cheap post-first-compile donation audit: re-lower against the
        warm-up call's avals (shape/dtype metadata stays readable on
        donated buffers; no backend compile, no data touched) and parse
        the aliasing out of the lowering text."""
        try:
            specs = jax.tree.map(self._aval, (args, dict(kwargs)))
            lowered = self.fn.lower(*specs[0], **specs[1])
        except Exception as e:  # pragma: no cover - lowering quirk
            self.guards.registry.emit({
                "record": "donation_audit", "name": self.name,
                "aliased": None, "ok": None, "error": str(e)[:200],
            })
            return
        donation_audit(
            self.name, lowered,
            registry=self.guards.registry, mode=self.guards.mode,
        )

    def _comm_audit_from(self, args, kwargs) -> None:
        """Post-first-call collective audit. Unlike the donation audit
        this needs the COMPILED program (SPMD-partitioner collectives
        don't exist in the lowering), so it re-lowers AND re-compiles
        against the warm-up avals — acceptable only because manifests are
        opt-in at the wrap site."""
        from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
            comm_audit,
        )

        try:
            specs = jax.tree.map(self._aval, (args, dict(kwargs)))
            compiled = self.fn.lower(*specs[0], **specs[1]).compile()
        except Exception as e:  # pragma: no cover - lowering quirk
            self.guards.registry.emit({
                "record": "comm_audit", "name": self.name,
                "manifest": self._comm_manifest.name, "ok": None,
                "error": str(e)[:200],
            })
            return
        self.comm_record = comm_audit(
            self.name, compiled, self._comm_manifest,
            registry=self.guards.registry, mode=self.guards.mode,
        )

    def __call__(self, *args, **kwargs):
        g = self.guards
        if g.mode == "off":
            return self.fn(*args, **kwargs)
        held = held_lock_names()
        if held:
            g._lock_boundary_violation(self.name, held)
        self.calls += 1
        warm = self._warm
        ctx = g._transfer_context() if warm else contextlib.nullcontext()
        builds_before = _build_count()
        try:
            with ctx:
                out = self.fn(*args, **kwargs)
        except jax.errors.JaxRuntimeError as e:
            if "Disallowed" in str(e) and "transfer" in str(e):
                g._transfer_violation(self.name, e)
            raise
        built = _build_count() - builds_before
        if not warm:
            self._warm = True  # the one expected warm-up compile
            if self._audit_donation:
                self._donation_audit_from(args, kwargs)
            if self._comm_manifest is not None:
                self._comm_audit_from(args, kwargs)
        elif built:
            self.recompiles += 1
            g._recompile_violation(self, built)
        return out

    def __getattr__(self, item):  # .lower/.trace/... pass through
        return getattr(self.fn, item)


@dataclasses.dataclass
class GuardSet:
    """One guard policy + its wrapped entry points + violation counters."""

    mode: str = "record"
    registry: Any = None
    transfer: bool = True  # arm jax.transfer_guard around warm calls

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(
                f"guards mode must be one of {_MODES}, got {self.mode!r}"
            )
        self.registry = _registry_or_default(self.registry)
        self.wrapped: dict[str, GuardedCall] = {}
        self.recompile_violations = 0
        self.transfer_violations = 0
        if self.mode != "off":
            _ensure_build_listener()

    # ------------------------------------------------------------- wrapping

    def wrap_jit(self, name: str, fn, *, audit_donation: bool = False,
                 comm_manifest=None):
        """Wrap a jitted (or AOT-compiled) callable; idempotent. With
        ``audit_donation`` the first (warm-up) call also audits that the
        donation requested at jit time survived to the executable —
        the serve programs\' post-first-compile hook. With
        ``comm_manifest`` (a ``spmd.CommManifest``) the first call also
        audits the compiled program\'s collective footprint against its
        manifest — at the cost of one extra compile, so pass it only on
        deliberately-warmed programs."""
        if isinstance(fn, GuardedCall):
            return fn
        wrapped = GuardedCall(
            name, fn, self,
            audit_donation=audit_donation, comm_manifest=comm_manifest,
        )
        self.wrapped[name] = wrapped
        return wrapped

    # ------------------------------------------------------------ transfers

    def _transfer_context(self):
        if not self.transfer or self.mode == "off":
            return contextlib.nullcontext()
        return jax.transfer_guard("disallow" if self.mode == "strict" else "log")

    def _lock_boundary_violation(self, name: str, held) -> None:
        """A compiled call/device region entered with instrumented locks
        held: record it (the lock registry emits ``lock_across_device``);
        strict mode raises — the accelerator\'s latency just became every
        waiter\'s latency."""
        get_lock_registry().check_device_boundary(name)
        if self.mode == "strict":
            raise GuardViolation(
                f"device boundary {name!r} entered while holding "
                f"instrumented lock(s) {list(held)} — dispatching device "
                f"work under a lock serializes every thread needing it"
            )

    @contextlib.contextmanager
    def transfer_scope(self, name: str):
        """Arm the implicit-transfer detector around a host code region
        (e.g. one serve tick). Violations emit ``implicit_transfer`` and,
        in strict mode, re-raise as ``TransferGuardError``. Also checks
        no instrumented lock is held across the scope\'s entry."""
        held = held_lock_names()
        if held and self.mode != "off":
            self._lock_boundary_violation(name, held)
        try:
            with self._transfer_context():
                yield
        except jax.errors.JaxRuntimeError as e:
            if "Disallowed" in str(e) and "transfer" in str(e):
                self._transfer_violation(name, e)
            raise

    def _transfer_violation(self, name: str, exc: Exception) -> None:
        self.transfer_violations += 1
        self.registry.inc("guards/implicit_transfers")
        self.registry.emit({
            "record": "implicit_transfer",
            "name": name,
            "error": str(exc).split("\n")[0][:300],
        })
        raise TransferGuardError(
            f"implicit transfer in guarded region {name!r}: "
            f"{str(exc).splitlines()[0]}"
        ) from exc

    # ------------------------------------------------------------ recompiles

    def _recompile_violation(self, call: GuardedCall, built: int) -> None:
        self.recompile_violations += 1
        self.registry.inc("guards/recompiles")
        self.registry.emit({
            "record": "recompile",
            "name": call.name,
            "calls": call.calls,
            "builds": built,
            "recompiles": call.recompiles,
        })
        if self.mode == "strict":
            raise RecompileError(
                f"jitted entry point {call.name!r} recompiled after warm-up "
                f"(call {call.calls} built {built} new program(s)) — a shape/"
                f"dtype/static-arg is varying per call"
            )

    @property
    def violations(self) -> int:
        return self.recompile_violations + self.transfer_violations


# ---------------------------------------------------------------- donation

# lowering text marks donated params with tf.aliasing_output — or, when
# inputs carry explicit shardings (the tensor-parallel serve programs),
# with jax.buffer_donor: aliasing is then decided at compile time, and the
# donor annotation is the lowering-level proof donation survived. Compiled
# HLO carries an input_output_alias map with one (may|must)-alias entry.
_ALIAS_PATTERNS = (
    re.compile(r"tf\.aliasing_output"),
    re.compile(r"jax\.buffer_donor"),
    re.compile(r"(?:may|must)[-_]alias"),
)


def count_aliased_buffers(hlo_text: str) -> int:
    """Donated-input count visible in a lowering / compiled-HLO dump."""
    return max(len(p.findall(hlo_text)) for p in _ALIAS_PATTERNS)


def donation_audit(
    name: str,
    stage,
    *,
    expected: bool = True,
    registry=None,
    mode: str = "record",
) -> dict:
    """Post-lower audit: did the donation requested at jit time survive to
    the executable? ``stage`` is a ``Lowered`` or ``Compiled`` (anything
    with ``as_text()``). Emits a ``donation_audit`` record; strict mode
    raises when donation was expected but zero buffers alias."""
    registry = _registry_or_default(registry)
    try:
        text = stage.as_text()
    except Exception as e:  # pragma: no cover - backend without text dump
        record = {
            "record": "donation_audit", "name": name, "aliased": None,
            "ok": None, "error": str(e)[:200],
        }
        registry.emit(record)
        return record
    aliased = count_aliased_buffers(text)
    ok = (aliased > 0) if expected else True
    record = {
        "record": "donation_audit",
        "name": name,
        "aliased": aliased,
        "expected": expected,
        "ok": ok,
    }
    registry.emit(record)
    if not ok:
        registry.inc("guards/donation_dropped")
        if mode == "strict":
            raise GuardViolation(
                f"donation audit {name!r}: donate_argnums was requested but "
                f"no input aliases an output — the donated buffer stays "
                f"live across every call"
            )
    return record


# ---------------------------------------------------------------- sharding

_SHARDED_AXES = ("fsdp", "model", "stage")


def sharding_audit(
    params,
    mesh,
    *,
    min_bytes: int = 1 << 20,
    registry=None,
    mode: str = "record",
    name: str = "params",
) -> dict:
    """Flag large leaves left fully replicated on a mesh whose fsdp/model/
    stage axes say they should be sharded. Data-parallel-only meshes
    (every non-data axis == 1) replicate by design and audit clean."""
    registry = _registry_or_default(registry)
    shard_capacity = 1
    for ax in _SHARDED_AXES:
        shard_capacity *= dict(mesh.shape).get(ax, 1)
    flagged: list[dict] = []
    if shard_capacity > 1:
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        for path, leaf in flat:
            nbytes = getattr(leaf, "nbytes", 0)
            sharding = getattr(leaf, "sharding", None)
            if nbytes < min_bytes or sharding is None:
                continue
            if sharding.is_fully_replicated:
                flagged.append({
                    "path": jax.tree_util.keystr(path),
                    "bytes": int(nbytes),
                })
    record = {
        "record": "sharding_audit",
        "name": name,
        "mesh_shape": dict(mesh.shape),
        "min_bytes": min_bytes,
        "flagged": flagged,
        "replicated_bytes": sum(f["bytes"] for f in flagged),
        "ok": not flagged,
    }
    registry.emit(record)
    if flagged:
        registry.inc("guards/replicated_large_params", len(flagged))
        if mode == "strict":
            worst = max(flagged, key=lambda f: f["bytes"])
            raise GuardViolation(
                f"sharding audit {name!r}: {len(flagged)} leaf/leaves >= "
                f"{min_bytes}B fully replicated on a "
                f"{dict(mesh.shape)} mesh (largest: {worst['path']} at "
                f"{worst['bytes']}B) — the sharding policy did not apply"
            )
    return record
