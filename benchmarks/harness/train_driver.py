"""Drives a training cell through `cli.train_dp.main`, in this process.

`train_dp.Trainer` is replaced by a subclass that (1) installs the
benchmark's seeded weights in place of the program's own, (2) wraps
`self.train_loader.epoch` so that the harness sees every batch the step is
fed. The wrapper lets the first updates pass while it notes what the
output check needs (each loss, Adam's first moment after one update, the
parameters' change after three), lets the remaining warm steps pass,
blocks on `state.params`, starts the clock, counts the batches it hands
out for `--seconds`, stops handing out, blocks again and stops the clock.
The compiled step and its state that the check followed are the ones the
window drives. No run passes `--metrics-dir` (it makes the loop block on
every step's loss): the compile plane's time is a span of the subclass
around `_warm_start`. A traced run measures its rates over the first part
of its seconds and then opens `jax.profiler` for a few more steps.

After `main` has returned and the program's state is freed, the plain
reference follows the same first updates from the same seed and batches,
and `compare.training_checks` decides `correct`.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import os
import shutil
import time

import numpy as np

from harness import adapters, compare, family, trace_reduce

BATCH_KEYS = ("input_ids", "attention_mask", "token_type_ids", "labels")


def _norms_by_name(tree, fam) -> dict:
    import jax.numpy as jnp

    return {
        adapters.leaf_name(k, fam): jnp.sqrt(
            jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in adapters.flat(tree).items()}


def _find_mu(opt_state):
    """Adam's first moment, wherever the optimizer chain keeps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            mu = _find_mu(s)
            if mu is not None:
                return mu
    return None


class Window:
    """What the epoch wrapper notes; one per run."""

    def __init__(self, ctx, source, n_check):
        self.ctx, self.source, self.n_check = ctx, source, n_check
        self.losses = []          # device scalars, one per dispatched step
        self.batches = []         # host copies of the checked batches
        self.grad1 = None         # {leaf: device scalar}
        self.delta = None
        self.steps = 0
        self.data_wait_s = 0.0
        self.t0 = self.t1 = None
        self.setup_s = None
        self.trace_dir = None
        self.trace_steps = 0
        self.compile_s = None
        self.trainer = None
        self.early_end = False


def _epoch_wrapper(win: Window, trainer, orig_epoch):
    import jax

    ctx = win.ctx
    traffic = ctx["traffic"]
    b1 = ctx["config"]["recipe"]["adam_b1"]
    key = win.source.key()
    fam = family.of(ctx["config"])

    def grad1_norms(mu):
        return {k: v / (1.0 - b1)
                for k, v in _norms_by_name(mu, fam).items()}

    def delta_norms(params, key):
        start = adapters.to_program(win.source.generate(key), params, fam)
        return _norms_by_name(
            jax.tree.map(lambda a, b: a - b, params, start), fam)

    def bounded():
        # never more than two steps ahead of the device, as a loop that
        # logs its loss would be: the window then ends within two steps
        # of --seconds
        if len(win.losses) >= 3:
            jax.block_until_ready(win.losses[-3])

    def epoch(*a, **k):
        inner = orig_epoch(*a, **k)
        step = trainer.train_step
        if ctx.get("break_step"):
            # tests only: the timed path broken underneath the harness
            step = ctx["break_step"](step)

        def recorded(state, batch):
            out = step(state, batch)
            win.losses.append(out[1]["loss"])
            return out

        trainer.train_step = recorded
        try:
            for i in range(traffic["warm_steps"]):
                batch = next(inner)
                if i < win.n_check:
                    # a copy: on a CPU the device array may alias the
                    # loader's slot, which the next batch overwrites
                    win.batches.append({
                        k: np.array(jax.device_get(batch[k]), copy=True)
                        for k in BATCH_KEYS})
                yield batch
                # resumed: update i+1 is dispatched, trainer.state is new
                if i == 0:
                    win.grad1 = jax.jit(grad1_norms)(
                        _find_mu(trainer.state.opt_state))
                if i == win.n_check - 1:
                    win.delta = jax.jit(delta_norms)(trainer.state.params, key)
            jax.block_until_ready(trainer.state.params)
            win.t0 = time.perf_counter()
            win.setup_s = win.t0 - ctx["process_t0"]
            # a traced run measures for the first part of its seconds and
            # then traces a few steps, so that starting and stopping the
            # profiler falls outside what its rates are taken over
            seconds = ctx["seconds"] * (
                traffic["traced_rate_share"] if ctx["trace"] else 1.0)
            while time.perf_counter() - win.t0 < seconds:
                tw = time.perf_counter()
                try:
                    batch = next(inner)
                except StopIteration:
                    win.early_end = True
                    break
                win.data_wait_s += time.perf_counter() - tw
                yield batch
                win.steps += 1
                bounded()
            jax.block_until_ready(trainer.state.params)
            win.t1 = time.perf_counter()
            if ctx["trace"] and not win.early_end:
                jax.profiler.start_trace(win.trace_dir)
                try:
                    for _ in range(traffic["trace_steps"]):
                        yield next(inner)
                        win.trace_steps += 1
                        bounded()
                    jax.block_until_ready(trainer.state.params)
                finally:
                    jax.profiler.stop_trace()
        finally:
            trainer.train_step = None
            close = getattr(inner, "close", None)
            if close:
                close()

    return epoch


def run(ctx) -> dict:
    import jax

    from pytorch_distributed_training_tpu.cli import train_dp

    config, traffic = ctx["config"], ctx["traffic"]
    reference = importlib.import_module("reference." + config["reference"])
    # looked for before anything is built: a configuration whose family
    # brings no count for a training cell exits here, naming it
    family.count(config, "train_flops_per_sample")
    fam = family.of(config)
    source = family.source(
        config, reference.weight_spec(config["model"]), ctx["seed"])
    n_check = config["check"]["updates"]
    win = Window(ctx, source, n_check)
    win.trace_dir = os.path.join(ctx["work_dir"], "trace")
    shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    os.makedirs(ctx["work_dir"], exist_ok=True)

    class BenchTrainer(train_dp.Trainer):
        def __init__(self, model_config, *a, **k):
            model_config = dataclasses.replace(
                model_config, **config.get("program_model", {}))
            super().__init__(model_config, *a, **k)
            win.trainer = self
            old, self.state = self.state.params, self.state.replace(params=None)
            self.state = self.state.replace(
                params=adapters.install(old, source, fam))
            del old
            self.train_loader.epoch = _epoch_wrapper(
                win, self, self.train_loader.epoch)

        def _warm_start(self):
            # the compile plane's span, taken at its boundary
            t = time.perf_counter()
            super()._warm_start()
            win.compile_s = time.perf_counter() - t

    chips = ctx["cell"]["chips"]
    recipe = config["recipe"]
    global_batch = recipe["global_batch_per_chip"] * chips
    max_steps = traffic["warm_steps"] + int(
        ctx["seconds"] * traffic["max_steps_per_second"]) + 8
    control = list(config["control"]["argv"]) if ctx.get("control") else []
    argv = list(config["argv"]) + list(traffic.get("argv", [])) + control + [
        "--seed", str(ctx["seed"]),
        "--global-batch-size", str(global_batch),
        "--micro-batch-size", str(recipe["micro_batch_per_chip"] * chips),
        "--train-size", str(global_batch * max_steps),
    ]
    # never --metrics-dir: it makes the loop block on every step's loss
    print(f"benchmark: cli.train_dp.main({argv})", flush=True)

    original = train_dp.Trainer
    train_dp.Trainer = BenchTrainer
    try:
        train_dp.main(argv)
    finally:
        train_dp.Trainer = original
    if win.t1 is None or win.early_end or win.steps == 0:
        raise SystemExit(
            f"benchmark: the window did not run to its end (steps "
            f"{win.steps}, epoch ended early: {win.early_end}); raise "
            f"max_steps_per_second in the traffic file")

    window_s = win.t1 - win.t0
    samples = win.steps * global_batch
    rate = samples / window_s / chips
    prog = {
        "losses": [float(x) for x in jax.device_get(win.losses[:n_check])],
        "grad1_norms": {k: float(v) for k, v in jax.device_get(win.grad1).items()},
        "delta_norms": {k: float(v) for k, v in jax.device_get(win.delta).items()},
    }
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices())
    from pytorch_distributed_training_tpu.ops import dispatch

    print(f"benchmark: window {window_s:.3f}s {win.steps} steps "
          f"{samples} samples; data wait {win.data_wait_s:.4f}s; setup "
          f"{win.setup_s:.2f}s; dispatch paths {dict(dispatch.DISPATCH_PATHS)}",
          flush=True)

    # free the program's state before the reference takes the chip
    trainer, win.trainer = win.trainer, None
    trainer.state = None
    trainer.train_step = trainer.eval_step = None
    del trainer
    win.losses.clear()
    gc.collect()

    trace = None
    if ctx["trace"]:
        # the trace stays in the work directory until the next run clears it
        planes = trace_reduce.load(win.trace_dir)
        if trace_reduce.device_ops(planes) or not ctx["rehearsal"]:
            trace = trace_reduce.reduce(planes)
            trace["steps"] = win.trace_steps

    t_ref = time.perf_counter()
    ref = reference.train(config, source, win.batches)
    ref_s = time.perf_counter() - t_ref
    checks, where = compare.training_checks(prog, ref, config["limits"])
    if ctx.get("read_faults"):
        # calibration only: each fault planted in the reference put in the
        # program's place, read by the same numbers
        where["faults"] = {}
        plant = [("half_batch", dict(fault="half_batch"))]
        lower = config.get("control", {}).get("reference_precision")
        if lower:
            plant.append(("reference_" + lower, dict(precision=lower)))
        for name, how in plant:
            other = reference.train(config, source, win.batches, **how)
            where["faults"][name] = {
                c.name: c.value for c in compare.training_checks(
                    other, ref, config["limits"])[0]}
    return {
        "attempted": win.steps,
        "failed": 0,
        "end_to_end": {
            "train.samples_per_s_per_chip": rate,
            "setup_s": win.setup_s,
        },
        "memory_peak_bytes": peak,
        "checks": checks,
        "notes": dict(where, reference_s=ref_s, window_s=window_s,
                      steps=win.steps, losses=prog["losses"],
                      ref_losses=ref["losses"]),
        "observations": {
            "window_s": window_s, "steps": win.steps, "samples": samples,
            "chips": chips, "data_wait_s": win.data_wait_s,
            "compile_s": win.compile_s, "trace": trace,
            "seq": recipe["max_seq_length"],
            "micro_rows": recipe["micro_batch_per_chip"],
        },
    }
