"""Device time of the compiled train step, split by the step's own named
scopes: what `train.grad_ms`, `train.accum_ms`, `train.optimizer_ms` and
`train.step_idle_ms` read.

The trainer's step opens three `jax.named_scope`s (`train/step.py`): one
micro-batch's forward and backward (`train.grad`), the fp32 carry add
(`train.accumulate`) and the optimizer update with the gradient norm
(`train.optimizer`). Its warm start audits the compiled step and keeps a
`program_scopes` record of it in the process (`analysis/spmd/manifest.py::
PROGRAM_SCOPES`): for each scope, the compiled instructions under it.
`harness/train_driver.py` attaches no sink, and the run is this process, so
the record is read from there; a program without that list (an older one)
leaves nothing to read, and every function here then gives None and raises
nothing.

The trace is the run's own (`harness/step_phases.py::planes`). Its
device plane has a line of module executions above the line of single
operations; every event of the modules' line named after the train step is
one run of it. All of them count: `harness/train_driver.py` blocks on the
state before it starts the profiler and again before it stops it, so no run
is cut (unlike the serving trace, whose first and last module events
`step_phases.steps_of` leaves out). Under one whole run there is nothing to
read.

A run's time under a scope is the union of its operations' intervals,
without the waits of asynchronous copies; the three scopes and what lies
under none (`unscoped`: the loop's slices, the zeroed carry, the loss sum)
add up to the run's `busy` time, and `idle` is the rest of the run: time
inside the program in which no operation ran, apart from the gaps between
runs. Each is in milliseconds per run.
"""

from __future__ import annotations

from harness import step_phases, trace_reduce

#: the name of the audit's record and of the module event of the step
PROGRAM = "train_step"
MODULE = "jit_train_step"
SCOPES = ("train.grad", "train.accumulate", "train.optimizer")
UNSCOPED = "unscoped"
RUN_END = "end of run"


def program_scopes():
    """`{scope: instruction names}` of the newest `train_step` record the
    program keeps in this process, or None where it keeps none."""
    try:
        from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
            PROGRAM_SCOPES,
        )
    except ImportError:  # a program without the list
        return None
    records = list(PROGRAM_SCOPES)
    scopes = {s: step_phases.scope_names(records, PROGRAM, (s,))
              for s in SCOPES}
    return scopes if any(scopes.values()) else None


def runs_of(loaded, module: str = MODULE):
    """`[(start_ns, end_ns)]` of every run of `module` on the busiest device
    plane, and that plane's leaf operations without the asynchronous
    copies' waits, both sorted."""
    if not loaded:
        return [], []
    per_core = trace_reduce.device_ops(loaded)
    if not per_core:
        return [], []
    lead = max(per_core, key=lambda k: trace_reduce.busy_ns(per_core[k]))
    runs = sorted((s, s + d) for name, s, d in
                  loaded[lead].get(step_phases.MODULES_LINE, ())
                  if module in name)
    ops = [ev for ev in trace_reduce.leaves(per_core[lead])
           if not trace_reduce.short_name(ev[0]).startswith(
               step_phases.ASYNC_WAITS)]
    return runs, ops


def _scope_of(name: str, scopes: dict) -> str:
    short = trace_reduce.short_name(name)
    return next((s for s, names in scopes.items() if short in names),
                UNSCOPED)


def breakdown(loaded, scopes: dict, module: str = MODULE):
    """Milliseconds per run of `module` under each of `scopes`, under none
    (`unscoped`), in all (`busy`) and in none (`idle`), with the number of
    runs (`runs`). None under one whole run."""
    runs, ops = runs_of(loaded, module)
    if not runs:
        return None
    inside = list(step_phases._inside(runs, ops))
    by = {scope: [] for scope in (*scopes, UNSCOPED)}
    for ev in inside:
        by[_scope_of(ev[0], scopes)].append(ev)
    n = len(runs)
    out = {scope: trace_reduce.busy_ns(evs) / 1e6 / n
           for scope, evs in by.items()}
    out["busy"] = trace_reduce.busy_ns(inside) / 1e6 / n
    out["idle"] = sum(e - s for s, e in runs) / 1e6 / n - out["busy"]
    out["runs"] = n
    return out


def idle_by_next_scope(loaded, scopes: dict, module: str = MODULE):
    """The idle time inside the runs of `module`, in milliseconds per run,
    by the scope of the operation that follows each gap (`end of run` for
    the gap after a run's last operation). The values add up to
    `breakdown`'s `idle`. None under one whole run."""
    runs, ops = runs_of(loaded, module)
    if not runs:
        return None
    inside = list(step_phases._inside(runs, ops))
    by: dict = {}
    i = 0
    for a, b in runs:
        cursor = a
        while i < len(inside) and inside[i][1] < b:
            name, s, d = inside[i]
            if s > cursor:
                label = _scope_of(name, scopes)
                by[label] = by.get(label, 0) + (s - cursor)
            cursor = max(cursor, s + d)
            i += 1
        if b > cursor:
            by[RUN_END] = by.get(RUN_END, 0) + (b - cursor)
    return {label: ns / 1e6 / len(runs) for label, ns in by.items()}


def read(obs, part: str):
    """What a reader hands back: `part` of `breakdown` over the run's own
    trace, where the program keeps its scopes; None where a scope lists no
    instruction."""
    if not obs.get("trace"):
        return None
    scopes = program_scopes()
    if not scopes or (part in scopes and not scopes[part]):
        return None
    parts = breakdown(step_phases.planes(), scopes)
    return None if parts is None else parts[part]
