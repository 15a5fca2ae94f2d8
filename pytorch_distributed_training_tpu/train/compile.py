"""Warm-start compilation: persistent XLA cache + AOT step compiles.

Two independent levers against cold-start latency:

- ``enable_compile_cache()`` — called first by every entry point that
  compiles (the Trainer for the three train CLIs, ``serve_lm``,
  ``generate_lm``, ``chip_smoke.py``) — turns on JAX's
  persistent compilation cache so a second run of the same program loads
  compiled executables instead of re-invoking XLA. WHERE the cache lives
  is decided outside the program: ``JAX_COMPILATION_CACHE_DIR`` when the
  environment sets it, else — on the TPU backend — one fixed directory in
  the checkout (``<repo>/.jax_cache``). The directory must not move: a
  machine that keeps its cache between runs only finds it again under the
  same path. The thresholds are dropped to zero so even sub-second
  compiles persist — warm start must cover the tiny configs tests
  exercise, not just minute-long TPU compiles.
- ``aot_warm_start(...)`` lowers and compiles the train/eval steps against
  the loaders' ``batch_spec()`` BEFORE epoch 0, so the first step of the
  run is a normal steady-state step: compile wall time moves out of the
  step stream into its own ``compile`` telemetry record (with a cache-hit
  flag and the share that was tracing and lowering), the per-step ``compile_inclusive`` flag disappears, and the
  watchdog can arm from step 1.

The compiled executables keep the jitted functions' donation and sharding
contracts (AOT lowering carries ``donate_argnums``/``in_shardings``), so
the Trainer swaps them in place of the jit wrappers and the step loop is
unchanged.
"""

from __future__ import annotations

import os

import jax
from jax.sharding import NamedSharding

from pytorch_distributed_training_tpu.telemetry.spans import setup_phase

#: the cache's home when the environment names none: fixed, inside the
#: checkout, git-ignored — never a temp name, a pid or a timestamp
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str | None:
    """Enable JAX's persistent compilation cache; returns its directory
    (None when this run stays uncached).

    WHERE is never a flag: ``JAX_COMPILATION_CACHE_DIR`` when the
    environment sets it — JAX already reads it, so no directory is set in
    code — else, on the TPU backend, ``REPO_CACHE_DIR``. Any other backend
    stays uncached by default: XLA:CPU compiles in seconds and answers
    every reload of a cached executable with kilobytes of "machine
    features don't match ... could lead to SIGILL" (it aborted outright on
    older builds). Process-global and idempotent: every jit compile from
    here on — state init, calibration, train/eval/serve programs — reads
    and writes the cache. Call it after ``comms.initialize()``: asking for
    the backend starts it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() != "tpu":
            return None
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entry_count(cache_dir: str | None) -> int | None:
    """Number of cache files currently on disk (None when the run is
    uncached; 0 before JAX has created the directory)."""
    if not cache_dir:
        return None
    n = 0
    for _, _, files in os.walk(cache_dir):
        n += sum(1 for f in files if not f.startswith("."))
    return n


def _attach_shardings(spec_tree, mesh, pspec):
    """ShapeDtypeStructs -> sharded ShapeDtypeStructs under ``pspec`` (the
    exact placement ``make_global_batch`` commits real batches to)."""
    sharding = NamedSharding(mesh, pspec)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        spec_tree,
    )


def aot_warm_start(
    *,
    train_step,
    eval_step,
    state,
    train_spec,
    eval_spec,
    mesh,
    train_pspec,
    eval_pspec,
    cache_dir: str | None = None,
    registry=None,
    guard_mode: str = "off",
    comm_manifest=None,
):
    """AOT-compile the steps against abstract batches; returns
    ``(compiled_train, compiled_eval, record)``.

    ``train_spec``/``eval_spec`` are the loaders' ``batch_spec()`` pytrees;
    ``state`` is the concrete (already sharded) TrainState, which pins the
    state avals exactly. Raises on lowering/compile failure — the caller
    decides whether to fall back to the lazy jit path.

    With ``guard_mode`` != "off" the compiled train step gets the
    post-lower donation audit (analysis/guards.py): the step donates its
    state, and an executable that aliases nothing means XLA dropped the
    donation — optimizer state would sit double-resident in HBM. The
    audit emits a ``donation_audit`` record through ``registry`` (strict:
    raises).

    With a ``comm_manifest`` (``analysis/spmd/manifest.CommManifest``,
    typically ``train_manifest(mesh)``) the compiled train step's
    collective footprint is also audited — the compiled object is already
    in hand here, so the comm audit costs one ``as_text()`` parse, not an
    extra compile.
    """
    entries_before = cache_entry_count(cache_dir)

    def lower_and_compile(step, name, spec, pspec):
        """(compiled, seconds lowering, seconds in all): tracing and
        lowering are the program's own work on the host; compiling is
        XLA's, or a load from the cache."""
        batch = _attach_shardings(spec, mesh, pspec)
        with setup_phase(f"warm_start.{name}.lower", registry=registry) as lo:
            lowered = step.lower(state, batch)
        with setup_phase(
                f"warm_start.{name}.compile", registry=registry) as co:
            compiled = lowered.compile()
        return compiled, lo.dur_s, lo.dur_s + co.dur_s

    with setup_phase("warm_start", registry=registry):
        compiled_train, train_lower_s, train_s = lower_and_compile(
            train_step, "train", train_spec, train_pspec)
        if guard_mode != "off":
            from pytorch_distributed_training_tpu.analysis.guards import (
                donation_audit,
            )

            donation_audit(
                "train_step", compiled_train,
                registry=registry, mode=guard_mode,
            )
            if comm_manifest is not None:
                from pytorch_distributed_training_tpu.analysis.spmd.manifest import (  # noqa: E501
                    comm_audit,
                )

                comm_audit(
                    "train_step", compiled_train, comm_manifest,
                    registry=registry, mode=guard_mode,
                )
        compiled_eval, eval_lower_s, eval_s = lower_and_compile(
            eval_step, "eval", eval_spec, eval_pspec)
    entries_after = cache_entry_count(cache_dir)
    cache_hit = None
    if entries_before is not None:
        # no new entries appeared and the cache wasn't empty -> every
        # compile was served from disk
        cache_hit = entries_before > 0 and entries_after == entries_before
    record = {
        "record": "compile",
        "aot": True,
        # lowering plus compiling; the ``*_lower_s`` are the first part
        "train_compile_s": train_s,
        "eval_compile_s": eval_s,
        "train_lower_s": train_lower_s,
        "eval_lower_s": eval_lower_s,
        "compile_s": train_s + eval_s,
        "cache_dir": cache_dir,
        "cache_hit": cache_hit,
        "cache_entries": entries_after,
        "backend": jax.default_backend(),
    }
    return compiled_train, compiled_eval, record
