"""The Trainer: epochs → jitted steps → eval → metrics, on any mesh/policy.

Capability twin of both reference training functions (reference
test_data_parallelism.py:53-166; test_model_parallelism.py:174-315) as ONE
engine: the parallelism regime is entirely a (mesh shape, sharding policy,
model) choice, so the DP entry point and the hybrid DP×MP entry point differ
only in configuration — where the reference needed two divergent scripts
(Accelerate-managed vs hand-rolled process groups).

Per epoch: train over all global batches (each step is one compiled call
consuming an [accum, micro, ...] sharded batch), then a masked eval pass and
a process-0 metrics print (the reference's per-epoch ``accelerator.print``/
rank-0 print, :164-166/:312-315) — plus samples/sec/chip, the driver's
north-star metric (BASELINE.md).
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.analysis.guards import (
    GuardSet,
    GuardViolation,
    guard_mode_from_env,
    sharding_audit,
)
from pytorch_distributed_training_tpu.comms import initialize
from pytorch_distributed_training_tpu.comms.mesh import build_mesh
from pytorch_distributed_training_tpu.faults.inject import get_plan
from pytorch_distributed_training_tpu.faults.preemption import (
    GracefulShutdown,
    Preempted,
)
from pytorch_distributed_training_tpu.faults.watchdog import (
    Watchdog,
    set_watchdog,
)
from pytorch_distributed_training_tpu.data import ShardedLoader, load_task_arrays
from pytorch_distributed_training_tpu.models import BertForSequenceClassification
from pytorch_distributed_training_tpu.parallel import ShardingPolicy, state_shardings
from pytorch_distributed_training_tpu.parallel.sharding import shard_state
from pytorch_distributed_training_tpu.train import checkpoint as ckpt
from pytorch_distributed_training_tpu.train.metrics import MetricAccumulator
from pytorch_distributed_training_tpu.train.optim import adamw_with_schedule
from pytorch_distributed_training_tpu.train.state import create_train_state
from pytorch_distributed_training_tpu.train.step import make_eval_step, make_train_step
from pytorch_distributed_training_tpu.telemetry import (
    JsonlSink,
    MetricsRegistry,
    epoch_straggler_stats,
    run_metadata,
    set_registry,
)
from pytorch_distributed_training_tpu.utils.config import (
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from pytorch_distributed_training_tpu.utils.logging import log0, set_log_format
from pytorch_distributed_training_tpu.utils.profiling import (
    annotate,
    maybe_profile,
    set_debug_nans,
)


class Trainer:
    def __init__(
        self,
        model_config: ModelConfig,
        train_config: TrainConfig,
        mesh_config: MeshConfig | None = None,
        policy: ShardingPolicy | None = None,
        *,
        task: str = "auto",
        model=None,
        model_factory=None,
        hf_checkpoint=None,
        train_step_factory=None,
    ):
        self.mcfg = model_config
        self.tcfg = train_config
        self.info = initialize()
        self.mesh = build_mesh(mesh_config)
        # kernels (fused LN / dal / mask-scale / flash) shard over this
        # mesh via shard_map instead of falling back to XLA math on
        # multi-chip runs (ops/dispatch.py; VERDICT r2 #3)
        from pytorch_distributed_training_tpu.ops.dispatch import (
            set_kernel_mesh,
        )

        set_kernel_mesh(self.mesh)

        # ------------------------------------------------------- telemetry
        # Installed before data/checkpoint construction so every layer that
        # records through the default registry (loaders, checkpointer,
        # supervisor) lands in THIS run's window. The JSONL sink is built on
        # every process but writes on process 0 only (telemetry/sink.py);
        # the run-metadata header is emitted at the end of __init__, once
        # the resolved geometry (steps_per_epoch) is known.
        set_log_format(train_config.log_format)
        # Persistent compilation cache FIRST: every compile below (state
        # init, quant calibration, the AOT warm start) should read/write it
        from pytorch_distributed_training_tpu.train.compile import (
            enable_compile_cache,
        )

        self.compile_cache_dir = enable_compile_cache()
        self.registry = MetricsRegistry()
        set_registry(self.registry)
        # Runtime correctness guards (analysis/guards.py): recompile
        # detection around the jitted steps, transfer-guard arming (strict),
        # donation/sharding audits. PDT_TPU_GUARDS overrides the config.
        self.guards = GuardSet(
            mode=guard_mode_from_env(default=train_config.guards),
            registry=self.registry,
        )
        self.metrics_sink = None
        self._first_step_done = False
        self._log_pending = None  # (step, device loss) awaiting a non-blocking fetch
        # (step record, device loss): emitted once the next step is dispatched
        self._step_pending = None
        if train_config.metrics_dir:
            self.metrics_sink = JsonlSink(train_config.metrics_dir)
            self.registry.attach_sink(self.metrics_sink)

        self.policy = policy or ShardingPolicy()
        if model is None and model_factory is not None:
            # mesh-dependent models (e.g. the GPipe pipeline classifier,
            # parallel/pipeline.py) are built here, after bootstrap + mesh
            model = model_factory(self.mesh)
        if train_config.debug_nans:
            set_debug_nans(True)

        # ------------------------------------------------------------ data
        from pytorch_distributed_training_tpu.data.glue import resolve_task

        task = resolve_task(task)  # once, so both splits agree
        self.objective = "causal_lm" if task == "lm" else "classification"
        if (self.objective == "causal_lm") != bool(model_config.causal):
            raise ValueError(
                f"task {task!r} implies objective {self.objective!r} but the "
                f"model config has causal={model_config.causal} — use a "
                f"decoder preset (gpt2-*) with --task lm, an encoder preset "
                f"with classification tasks"
            )
        from pytorch_distributed_training_tpu.data import synthetic

        # Synthetic tasks generate rows at requested size directly (hub tasks
        # still load the full split and get truncated below).
        sizes = (
            train_config.train_size or synthetic.MRPC_TRAIN_SIZE,
            train_config.eval_size or synthetic.MRPC_EVAL_SIZE,
        )
        train_data, num_labels = load_task_arrays(
            task, "train",
            max_length=train_config.max_seq_length,
            vocab_path=train_config.vocab_path,
            vocab_size=model_config.vocab_size,
            seed=train_config.seed,
            synthetic_sizes=sizes,
        )
        from pytorch_distributed_training_tpu.data.glue import eval_splits

        eval_datas = {}  # suffix -> arrays (MNLI evaluates both val splits)
        for suffix, split in eval_splits(task):
            eval_datas[suffix], _ = load_task_arrays(
                task, split,
                max_length=train_config.max_seq_length,
                vocab_path=train_config.vocab_path,
                vocab_size=model_config.vocab_size,
                seed=train_config.seed,
                synthetic_sizes=sizes,
            )
        if train_config.train_size:
            train_data = {
                k: v[: train_config.train_size] for k, v in train_data.items()
            }
        if train_config.eval_size:
            eval_datas = {
                s: {k: v[: train_config.eval_size] for k, v in d.items()}
                for s, d in eval_datas.items()
            }
        if num_labels:
            self.mcfg.num_labels = num_labels
        self.train_loader = self._make_loader(
            train_data, train_config, train=True
        )
        self.eval_loaders = {
            suffix: self._make_loader(d, train_config, train=False)
            for suffix, d in eval_datas.items()
        }

        # ----------------------------------------------------------- model
        if model is None:
            if self.mcfg.causal:
                from pytorch_distributed_training_tpu.models.gpt2 import (
                    GPT2LMModel,
                )

                model = GPT2LMModel(self.mcfg)
            else:
                model = BertForSequenceClassification(self.mcfg)
        self.model = model
        total_updates = self.train_loader.steps_per_epoch * train_config.num_epochs
        tx, self.schedule = adamw_with_schedule(train_config, total_updates)
        example = {
            "input_ids": jnp.ones(
                (2, train_config.max_seq_length), jnp.int32
            ),
            "attention_mask": jnp.ones(
                (2, train_config.max_seq_length), jnp.int32
            ),
            "token_type_ids": jnp.zeros(
                (2, train_config.max_seq_length), jnp.int32
            ),
        }
        state = create_train_state(
            self.model,
            tx,
            jax.random.key(train_config.seed, impl=train_config.prng_impl),
            example
        )
        if hf_checkpoint is not None:
            from pytorch_distributed_training_tpu.models import hf_loader

            load = (
                hf_loader.load_gpt2_lm
                if self.mcfg.causal
                else hf_loader.load_bert_classifier
            )
            state = state.replace(params=load(hf_checkpoint, self.mcfg))
        self.shardings = state_shardings(state, self.policy, self.mesh)
        self.state = shard_state(state, self.shardings)

        self.checkpointer = (
            ckpt.Checkpointer(
                train_config.checkpoint_dir,
                verify=train_config.checkpoint_verify,
            )
            if train_config.checkpoint_dir
            else None
        )
        restored = False
        if train_config.resume and self.checkpointer:
            if self.checkpointer.latest_step() is not None:
                self.state = self.checkpointer.restore(self.state)
                restored = True
        if self.state.quant is not None and not restored:
            # delayed int8 scaling: observe step-0 amaxes on one microbatch
            # of real rows (a restored run already carries its scales — no
            # point compiling a forward just to overwrite it). Built straight
            # from the dataset arrays — NOT by peeking the train loader:
            # abandoning a native-loader generator mid-epoch leaks its
            # prefetch slot and races the calibration batch's async H2D
            # against the next epoch's slot reuse.
            from pytorch_distributed_training_tpu.comms.ingest import (
                make_global_batch,
            )
            from pytorch_distributed_training_tpu.comms.mesh import BATCH_AXES
            from pytorch_distributed_training_tpu.train.step import (
                calibrate_quant,
            )
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_training_tpu.data.pipeline import (
                resolve_batch_geometry,
            )

            # per-host slice of the first global microbatch (the same
            # contract both loaders use) — so the calibration forward runs
            # at exactly the training microbatch geometry: no duplicated
            # rows across hosts, no extra compile at a different shape
            pidx, _, micro_global, micro_local, _ = resolve_batch_geometry(
                self.mesh,
                global_batch_size=train_config.global_batch_size,
                grad_accum_steps=train_config.grad_accum_steps,
                train=True,
            )
            take = np.arange(micro_global) % len(
                next(iter(train_data.values()))
            )  # wrap tiny datasets
            local = take[pidx * micro_local : (pidx + 1) * micro_local]
            rows = {k: np.asarray(v)[local] for k, v in train_data.items()}
            micro0 = make_global_batch(self.mesh, rows, pspec=P(BATCH_AXES))
            self.state = calibrate_quant(
                self.state, micro0,
                objective=self.objective,
                loss_scale=1.0 / train_config.grad_accum_steps,
            )

        chain = train_config.chain_steps
        if chain > 1:
            # chained dispatch must tile every step-indexed cadence: a chain
            # crossing an epoch (or checkpoint/crash point) would tear the
            # per-epoch eval/resume contract
            spe = self.train_loader.steps_per_epoch
            bad = next(
                (
                    (what, n)
                    for what, n in (
                        ("steps_per_epoch", spe),
                        ("checkpoint_every_steps",
                         train_config.checkpoint_every_steps),
                        ("crash_at_step", train_config.crash_at_step),
                    )
                    if n and n % chain
                ),
                None,
            )
            if bad:
                raise ValueError(
                    f"chain_steps={chain} must divide {bad[0]}={bad[1]}"
                )
        if train_config.unroll_accum not in ("auto", "on", "off"):
            raise ValueError(
                f"unroll_accum must be auto/on/off, got "
                f"{train_config.unroll_accum!r}"
            )
        if train_step_factory is not None:
            # custom schedules (the 1F1B pipeline step,
            # parallel/pipeline.py) replace the standard step wholesale;
            # they own their accumulation/loss contract — reject knobs they
            # would silently ignore rather than let an OOM-motivated
            # unroll_accum="off" change nothing
            if chain > 1:
                raise ValueError(
                    "chain_steps > 1 is not supported with a custom "
                    "train_step_factory"
                )
            if train_config.unroll_accum != "auto":
                raise ValueError(
                    "unroll_accum is not supported with a custom "
                    "train_step_factory (the schedule owns its scan policy)"
                )
            self.train_step = train_step_factory(self.mesh, self.shardings)
            self._custom_train_step = True
        else:
            self._custom_train_step = False
            self.train_step = make_train_step(
                grad_accum_steps=train_config.grad_accum_steps,
                mesh=self.mesh,
                state_shardings=self.shardings,
                objective=self.objective,
                accum_dtype=train_config.grad_accum_dtype,
                chain_steps=chain,
                unroll_accum={"auto": None, "on": True, "off": False}[
                    train_config.unroll_accum
                ],
            )
        self.eval_step = make_eval_step(
            mesh=self.mesh, state_shardings=self.shardings,
            objective=self.objective,
            # pipeline models evaluate through their serial trunk (same
            # params, no schedule) — see GPipeClassifier.serial_apply
            apply_fn=getattr(self.model, "serial_apply", None),
        )
        self.history: list[dict] = []
        if self.metrics_sink is not None:
            self.metrics_sink.emit(
                run_metadata(
                    self.mesh, self.mcfg, train_config,
                    steps_per_epoch=self.train_loader.steps_per_epoch,
                    objective=self.objective,
                )
            )
        if self.guards.mode != "off":
            # committed placement is final: large params still fully
            # replicated on a sharded (fsdp/model/stage) mesh mean the
            # policy silently didn't apply — record it (strict: raise).
            # After the run-metadata emit so the stream keeps its
            # header-first contract.
            sharding_audit(
                self.state.params, self.mesh,
                registry=self.registry, mode=self.guards.mode,
            )

    def _make_loader(self, data, train_config, *, train: bool):
        """ONE loader factory for both splits: the native C++ prefetching
        batcher when configured/available (train batches AND eval batches —
        identity order + padded tail + valid mask, VERDICT r3 weak-#6),
        else the Python ShardedLoader. Same iteration contract either way.
        The TRAIN loader additionally gets the depth-k latency-hiding
        pipeline (data/prefetch.py, ``--prefetch-depth``): batch i+1..i+k
        assemble and ship H2D while step i computes, for either engine."""
        mode = train_config.native_loader
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"native_loader must be auto/on/off, got {mode!r}")
        if train_config.prefetch_depth < 0:
            raise ValueError(
                f"prefetch_depth must be >= 0, got "
                f"{train_config.prefetch_depth}"
            )
        what = "train" if train else "eval"
        batch = (
            train_config.global_batch_size
            if train
            else train_config.eval_batch_size
        )
        accum = train_config.grad_accum_steps if train else 1
        loader = None
        if mode != "off":
            from pytorch_distributed_training_tpu.native import native_available

            if native_available():
                from pytorch_distributed_training_tpu.data.native_loader import (
                    NativeShardedLoader,
                )

                try:
                    loader = NativeShardedLoader(
                        data, self.mesh,
                        global_batch_size=batch, grad_accum_steps=accum,
                        train=train, seed=train_config.seed,
                    )
                except TypeError as e:  # non-integer dataset arrays
                    if mode == "on":
                        raise
                    log0(
                        f"native {what} loader declined ({e}); using the "
                        f"Python loader"
                    )
                else:
                    log0(f"{what} loader: native C++ prefetching batcher")
            elif mode == "on":
                raise RuntimeError(
                    "native_loader='on' but the C++ batcher is unavailable "
                    "(no toolchain?)"
                )
        if loader is None:
            log0(f"{what} loader: Python ShardedLoader")
            loader = ShardedLoader(
                data, self.mesh,
                global_batch_size=batch, grad_accum_steps=accum,
                train=train, seed=train_config.seed,
            )
        if train and train_config.prefetch_depth > 0:
            from pytorch_distributed_training_tpu.data.prefetch import (
                PrefetchingLoader,
            )

            loader = PrefetchingLoader(
                loader, depth=train_config.prefetch_depth
            )
        return loader

    # ------------------------------------------------------------------ run

    def run(self) -> list[dict]:
        from pytorch_distributed_training_tpu.comms.mesh import set_current_mesh

        set_current_mesh(self.mesh)  # ring attention retraces resolve to OUR mesh
        set_registry(self.registry)  # layers record into OUR window/sink
        cfg = self.tcfg
        n_chips = self.info.global_device_count
        spe = max(self.train_loader.steps_per_epoch, 1)
        done_steps = int(jax.device_get(self.state.step))
        start_epoch = done_steps // spe
        # Mid-epoch resume: the loader's per-epoch order is deterministic
        # (seeded by epoch index), so skipping the first `step % spe` batches
        # of the resumed epoch continues the exact optimizer/data trajectory —
        # no sample is trained twice and the LR schedule stays on its course.
        skip_in_first_epoch = done_steps % spe
        log0(
            f"training: {cfg.num_epochs} epochs × "
            f"{self.train_loader.steps_per_epoch} updates "
            f"(global batch {cfg.global_batch_size} = "
            f"{cfg.grad_accum_steps} × {cfg.global_batch_size // cfg.grad_accum_steps}), "
            f"mesh {dict(self.mesh.shape)}, {n_chips} chip(s)"
        )
        if start_epoch < cfg.num_epochs:
            # AOT warm start: compile the steps NOW, against the loaders'
            # abstract batch specs, so epoch 0's first step is a normal
            # steady-state step and compile wall time gets its own record
            self._warm_start()
        if self.guards.mode != "off":
            # guard the compiled entry points: a retrace after warm-up (or,
            # strict, an implicit transfer inside a warm call) is a recorded
            # violation. Wrapped AFTER the warm start so .lower() above saw
            # the raw jit objects; the wrapper forwards everything else.
            self.train_step = self.guards.wrap_jit("train_step", self.train_step)
            self.eval_step = self.guards.wrap_jit("eval_step", self.eval_step)
        # Hung-step watchdog: armed around device-blocking sections here and
        # (via the module install) around checkpoint joins + host collectives
        self.watchdog = (
            Watchdog(
                stall_factor=cfg.watchdog_stall_factor,
                min_stall_s=cfg.watchdog_min_stall_s,
                hard_timeout_s=cfg.watchdog_hard_timeout_s,
            )
            if cfg.watchdog
            else None
        )
        prev_watchdog = set_watchdog(self.watchdog)
        # Preemption-safe shutdown: handlers only set a flag; the step loop
        # notices at the next boundary and exits through _preempt_exit
        self._shutdown = (
            GracefulShutdown().install() if cfg.handle_preemption else None
        )
        try:
            self._run_epochs(cfg, n_chips, start_epoch, skip_in_first_epoch)
            from pytorch_distributed_training_tpu.ops import dispatch

            # the XLA fallbacks are silent by design; this is where a run
            # says whether its fused ops took the kernels
            log0(dispatch.summary())
        finally:
            if self._shutdown is not None:
                self._shutdown.uninstall()
            set_watchdog(prev_watchdog)
            if self.watchdog is not None:
                self.watchdog.close()
            # release native-loader worker threads / checkpoint threadpools
            # even when a train step raises (NaN abort, OOM, interrupt)
            if self.checkpointer:
                self.checkpointer.close()
            for loader in (self.train_loader, *self.eval_loaders.values()):
                close = getattr(loader, "close", None)
                if close:
                    close()
            # crash path: the stream stays OPEN (the supervisor's restart
            # event and the next attempt append to it) but is pushed to disk
            # — restart/preemption/stall records must survive the process
            if self.metrics_sink is not None:
                self.metrics_sink.flush(fsync=True)
        # Closed on the CLEAN path only: after a crash the stream stays open
        # (every record is already flushed) so the supervisor's restart event
        # and the next attempt's header append to the same file.
        if self.metrics_sink is not None:
            self.metrics_sink.close()
        return self.history

    def _warm_start(self) -> None:
        """AOT ``.lower().compile()`` of the train/eval steps (train/
        compile.py) before the first step. Skipped — falling back to lazy
        jit compilation on first call — for configurations whose batch
        layout this method can't reproduce: custom ``train_step_factory``
        schedules (they own their batch contract), ``chain_steps > 1``
        (the chain stack's device-side layout is XLA's choice), and
        seq-sharded meshes (batch shardings are inherited per-leaf from
        the loader). A failure raises on the TPU backend; elsewhere it is
        logged and the lazy path takes over."""
        cfg = self.tcfg
        if not cfg.aot_warmup or self._first_step_done:
            return
        if (
            self._custom_train_step
            or cfg.chain_steps > 1
            or self.mesh.shape.get("seq", 1) > 1
        ):
            log0(
                "AOT warm start skipped (custom step/chained dispatch/"
                "seq-sharded batches); first step compiles lazily"
            )
            return
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_training_tpu.comms.mesh import (
            BATCH_AXES,
            TRAIN_BATCH_PSPEC,
        )
        from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
            train_manifest,
        )
        from pytorch_distributed_training_tpu.train.compile import (
            aot_warm_start,
        )

        # the manifest REQUIRES an all-gather only when some param is
        # actually laid out over the fsdp axis — a policy that's on but
        # never applied (all leaves under fsdp_min_size) legally gathers
        # nothing
        fsdp_sharded = any(
            any(
                "fsdp" in (ax if isinstance(ax, tuple) else (ax,))
                for ax in s.spec
                if ax is not None
            )
            for s in jax.tree.leaves(self.shardings)
        )
        try:
            compiled_train, compiled_eval, record = aot_warm_start(
                train_step=self.train_step,
                eval_step=self.eval_step,
                state=self.state,
                train_spec=self.train_loader.batch_spec(),
                eval_spec=self.eval_loader.batch_spec(),
                mesh=self.mesh,
                train_pspec=TRAIN_BATCH_PSPEC,
                eval_pspec=P(BATCH_AXES),
                cache_dir=self.compile_cache_dir,
                registry=self.registry,
                guard_mode=self.guards.mode,
                comm_manifest=train_manifest(
                    self.mesh, fsdp_sharded=fsdp_sharded
                ),
            )
        except Exception as e:  # noqa: BLE001 — off-chip warm start is best-effort
            # a strict audit failure is a finding, not a compile hiccup; and
            # on the chip whatever the compiler refused (a Mosaic kernel, a
            # VMEM limit, an OOM) IS the error — the lazy path would pay the
            # same compile again to fail one step later
            if isinstance(e, GuardViolation) or jax.default_backend() == "tpu":
                raise
            log0(f"AOT warm start failed ({e!r}); first step compiles lazily")
            return
        self.train_step = compiled_train
        self.eval_step = compiled_eval
        self._first_step_done = True  # step 0 is no longer compile-inclusive
        self.registry.emit(record)
        hit = record["cache_hit"]
        log0(
            f"AOT warm start: train {record['train_compile_s']:.2f}s + eval "
            f"{record['eval_compile_s']:.2f}s"
            + (f" (persistent cache {'hit' if hit else 'miss'})"
               if hit is not None else "")
        )

    def _preempt_exit(self, signum: int, step_no: int) -> None:
        """SIGTERM/SIGINT arrived: emergency-save inside the grace window,
        record the preemption, and exit RESUMABLE (code 75) — the supervisor
        must not burn a restart on a host that is being taken away."""
        cfg = self.tcfg
        t0 = time.perf_counter()
        saved_step = None
        if self.checkpointer is not None:
            # duplicate-step saves (preempted right after a periodic save)
            # are skipped by the Checkpointer, not errors
            self.checkpointer.save(self.state)
            self.checkpointer.wait()
            saved_step = int(jax.device_get(self.state.step))
        save_wall_s = time.perf_counter() - t0
        if save_wall_s > cfg.preempt_grace_s:
            log0(
                f"emergency checkpoint took {save_wall_s:.1f}s, over the "
                f"{cfg.preempt_grace_s:.0f}s grace window — the checkpoint "
                f"landed but the infra may have SIGKILLed peers; consider "
                f"more frequent periodic saves"
            )
        self.registry.inc("preemptions")
        self.registry.emit({
            "record": "preemption",
            "signal": signum,
            "step": step_no,
            "saved_step": saved_step,
            "save_wall_s": save_wall_s,
            "grace_s": cfg.preempt_grace_s,
        })
        if self.metrics_sink is not None:
            self.metrics_sink.flush(fsync=True)
        log0(
            f"preempted at step {step_no}: emergency checkpoint "
            f"{'at step ' + str(saved_step) if saved_step is not None else 'skipped (no checkpoint_dir)'}, "
            f"exiting resumable"
        )
        raise Preempted(signum, step=step_no)

    def _run_epochs(self, cfg, n_chips, start_epoch, skip_in_first_epoch):
        # Per-step telemetry (metrics_dir set) emits each step's record one
        # step late: step k's record goes out once step k+1 is dispatched,
        # its loss read then (computed, or computing with step k+1 queued
        # behind it), so the loop never drains the device to write it. The
        # device's own time per step is a profile's (the train.* scopes of
        # train/step.py); without metrics only wall-clock step times are
        # collected, for the epoch-boundary straggler gather.
        per_step = bool(cfg.metrics_dir)
        reg = self.registry
        with maybe_profile(cfg.profile_dir):
            for epoch in range(start_epoch, cfg.num_epochs):
                epoch_t0 = time.perf_counter()
                samples = 0
                losses = []
                step_times: list[float] = []
                data_waits: list[float] = []
                # plain host-side counter mirrors state.step (one increment
                # per train_step) — reading state.step back would force a
                # host-device sync every step and serialize dispatch
                step_no = epoch * self.train_loader.steps_per_epoch
                skip = skip_in_first_epoch if epoch == start_epoch else 0
                chain = cfg.chain_steps
                if chain > 1 and skip % chain:
                    # cadence validation (__init__) keeps every checkpoint
                    # on a chain boundary, so a legal resume never lands here
                    raise RuntimeError(
                        f"resume step {skip} is mid-chain (chain_steps="
                        f"{chain}) — checkpoint written by a different "
                        f"chain configuration?"
                    )
                buf = []
                t_prev = t_last_dispatch = time.perf_counter()
                for i, batch in enumerate(self.train_loader.epoch(epoch)):
                    if (
                        self._shutdown is not None
                        and self._shutdown.requested is not None
                    ):
                        self._flush_step_record()
                        self._preempt_exit(self._shutdown.requested, step_no)
                    t_batch = time.perf_counter()
                    data_wait = t_batch - t_prev
                    if i < skip:
                        step_no += 1
                        t_prev = t_last_dispatch = time.perf_counter()
                        continue
                    if chain > 1:
                        # ONE dispatch per chain_steps updates: stack the
                        # placed batches on a leading chain dim (device-side
                        # concat; the extra copy is batch-sized, ~negligible
                        # next to a step) and let the scan-chained step
                        # (train/step.py) run them back-to-back
                        buf.append(batch)
                        if len(buf) < chain:
                            continue
                        batch = jax.tree.map(
                            lambda *xs: jnp.stack(xs), *buf
                        )
                        buf.clear()
                    compile_inclusive = not self._first_step_done
                    # watchdog arms over dispatch + (per_step) the previous
                    # step's loss read: a hung collective inside a step
                    # surfaces here. The compile-inclusive first step is
                    # exempt — tracing+XLA time is unbounded-ish and is not
                    # a hang
                    guard = (
                        self.watchdog.guard("train_step", step=step_no + chain)
                        if self.watchdog is not None and not compile_inclusive
                        else contextlib.nullcontext()
                    )
                    with annotate("train_step"), guard:
                        self.state, metrics = self.train_step(self.state, batch)
                        self._first_step_done = True
                        t_dispatched = time.perf_counter()
                        self._flush_step_record()
                    t_done = time.perf_counter()
                    samples += cfg.global_batch_size * chain
                    losses.append(metrics["loss"])
                    step_no += chain
                    step_times.append(t_done - t_prev)
                    data_waits.append(data_wait)
                    reg.observe("train/data_wait_s", data_wait)
                    if per_step:
                        step_s = t_dispatched - t_last_dispatch
                        reg.observe("train/dispatch_s", t_dispatched - t_batch)
                        reg.observe("train/step_s", step_s)
                        step_rec = {
                            "record": "step",
                            "epoch": epoch,
                            "step": step_no,
                            "data_wait_s": data_wait,
                            "dispatch_s": t_dispatched - t_batch,
                            # dispatch to dispatch: the device's step once
                            # the loop runs a step ahead of it
                            "step_s": step_s,
                            "compile_inclusive": compile_inclusive,
                        }
                        occ = getattr(
                            self.train_loader, "last_occupancy", None
                        )
                        if occ is not None:  # prefetch pipeline active
                            step_rec["prefetch_occupancy"] = occ
                        self._step_pending = (step_rec, metrics["loss"])
                    t_last_dispatch = t_dispatched
                    if cfg.log_every and (
                        step_no // cfg.log_every
                        > (step_no - chain) // cfg.log_every
                    ):
                        # non-blocking: fetch the PREVIOUS logged step's
                        # loss (long since computed) and queue this one —
                        # a device_get of the current step's loss here
                        # would stall the async dispatch stream
                        self._flush_pending_log()
                        self._log_pending = (step_no, metrics["loss"])
                    if (
                        self.checkpointer
                        and cfg.checkpoint_every_steps
                        and step_no % cfg.checkpoint_every_steps == 0
                    ):
                        self.checkpointer.save(self.state)
                    if (
                        cfg.crash_at_step
                        and step_no == cfg.crash_at_step
                        and jax.process_index() == cfg.crash_rank
                    ):
                        # fault injection: die like a preempted/killed host
                        # (no python cleanup, no checkpoint flush)
                        import os as _os

                        jax.block_until_ready(self.state.params)
                        self._flush_step_record()
                        if self.checkpointer:
                            # join async saves: the injected fault models a
                            # crash AFTER the last periodic checkpoint
                            # committed, not a torn write race
                            self.checkpointer.wait()
                        # plain print: log0 is process-0-gated and the
                        # crashing rank is usually not 0
                        print(
                            f"injected crash at step {step_no} "
                            f"(rank {jax.process_index()})",
                            flush=True,
                        )
                        _os._exit(13)
                    # PDT_TPU_FAULT step faults (faults/inject.py): raise an
                    # InjectedCrash (supervisor-retryable), self-SIGTERM
                    # (preemption path) or hang (watchdog path) right after
                    # completing this update
                    plan = get_plan()
                    if plan.specs:
                        # an armed plan may end the process here: this
                        # step's record goes out first
                        self._flush_step_record()
                    plan.fire_step_fault(step_no)
                    t_prev = time.perf_counter()
                with (
                    self.watchdog.guard("epoch_block", step=step_no)
                    if self.watchdog is not None
                    else contextlib.nullcontext()
                ):
                    # the loop never drains the device per step: this join
                    # is where a wedged device/collective actually surfaces
                    jax.block_until_ready(self.state.params)
                # the last step record and queued log line (everything is
                # ready post-join)
                self._flush_step_record()
                self._flush_pending_log()
                train_time = time.perf_counter() - epoch_t0
                # every host contributes its step-time stats; process 0's
                # epoch record then names the slowest host (telemetry/
                # straggler.py) — a collective, same cadence as eval
                straggler = epoch_straggler_stats(step_times, data_waits)
                eval_metrics = self.evaluate()
                record = {
                    "epoch": epoch,
                    # ONE transfer for the whole epoch's losses (not one
                    # device_get per step)
                    "train_loss": float(np.mean(jax.device_get(losses)))
                    if losses
                    else float("nan"),
                    "samples_per_sec": samples / train_time,
                    "samples_per_sec_per_chip": samples / train_time / n_chips,
                    **eval_metrics,
                }
                self.history.append(record)
                log0(f"epoch {epoch}: {record}")
                if self.checkpointer:
                    self.checkpointer.save(self.state)
                # epoch record last, so the checkpoint-save submit and eval
                # wall time land inside this epoch's telemetry window
                reg.emit({
                    "record": "epoch",
                    **record,
                    "train_wall_s": train_time,
                    "straggler": straggler,
                    "telemetry": reg.snapshot(reset=True),
                })

    def _flush_step_record(self) -> None:
        """Emit the step record that waits for its loss (``--metrics-dir``
        runs): called once the next step is dispatched, so the read waits
        at most for a step the device is already running."""
        if self._step_pending is None:
            return
        rec, loss = self._step_pending
        self._step_pending = None
        rec["loss"] = float(jax.device_get(loss))
        self.registry.emit(rec)

    def _flush_pending_log(self) -> None:
        """Emit the queued --log-every line (its loss is ready by now)."""
        if self._log_pending is None:
            return
        p_step, p_loss = self._log_pending
        self._log_pending = None
        log0(
            f"step {p_step}: loss={float(jax.device_get(p_loss)):.4f} "
            f"lr={float(self.schedule(p_step)):.2e}"
        )

    @property
    def eval_loader(self):
        """The primary eval split's loader (the only one for every task but
        MNLI, whose loaders are keyed "matched"/"mismatched")."""
        return next(iter(self.eval_loaders.values()))

    def evaluate(self) -> dict:
        eval_t0 = time.perf_counter()
        out = {}
        for suffix, loader in self.eval_loaders.items():
            if self.objective == "causal_lm":
                from pytorch_distributed_training_tpu.train.metrics import (
                    LMMetricAccumulator,
                )

                acc = LMMetricAccumulator()
            else:
                acc = MetricAccumulator(self.mcfg.num_labels)
            # accumulate the per-batch counts ON DEVICE: one host transfer
            # per split at the end, instead of a device_get sync per eval
            # batch tearing the dispatch stream
            totals = None
            for batch in loader.epoch():
                with annotate("eval_step"):
                    counts = self.eval_step(self.state, batch)
                totals = (
                    counts
                    if totals is None
                    else jax.tree.map(jnp.add, totals, counts)
                )
            if totals is not None:
                acc.update(jax.device_get(totals))
            raw = acc.compute()
            # first (primary) split also keeps unprefixed keys so existing
            # consumers (tests, HISTORY artifacts) read the same fields
            if not out and suffix:
                out.update(raw)
            out.update(
                {f"{k}_{suffix}": v for k, v in raw.items()} if suffix else raw
            )
        self.registry.observe("eval/wall_s", time.perf_counter() - eval_t0)
        return out
