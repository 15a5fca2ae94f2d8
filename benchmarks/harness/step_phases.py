"""Device time of a compiled program's steps, split by the program's own
named scopes: what `serve.moe_ms` and `serve.sparse_attn_ms` read.

The profiler's device plane has a line of module executions (`XLA
Modules`: one event a run of a compiled program, named after the jitted
function, `jit_decode(...)`) above the line of single operations (`XLA
Ops`). An operation belongs to the step whose module event encloses it. A
trace event is named by its HLO instruction (`%fusion.810 = ...`) and
carries no metadata, so a `jax.named_scope` does not reach it (PERF.md,
Open question 11). The program closes that gap itself: its audit of the
hot program writes a `program_scopes` record that lists, for each of the
model's named scopes, the compiled instructions under it
(`analysis/spmd/hlo.scope_instructions`). A fused operation counts under
the scope its fusion's metadata names.

The reduced trace a reader is handed (`trace_reduce.reduce`) keeps the
operations and drops the modules' line, and the observations carry no
path: so the trace's file is read again here, once, from the one work
directory `run.py` gives every run (`.bench_work/` of the checkout, emptied
before each). A step's time under a scope is the union of its operations'
intervals over the WHOLE runs in the trace, without the waits of
asynchronous copies; scopes that share no instruction add up, with what
lies under none, to the step's busy time (`breakdown`).

A program without the record (the parent of the PR that brought it), a
trace without the module line, or no trace at all leaves nothing to read:
every function here then gives None and raises nothing.
"""

from __future__ import annotations

import os

from harness import trace_reduce

MODULES_LINE = "XLA Modules"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".bench_work", "trace")

_cache: dict = {}


def planes(trace_dir: str = TRACE_DIR):
    """The newest trace under `trace_dir`, or None."""
    if trace_dir not in _cache:
        try:
            _cache[trace_dir] = trace_reduce.load(trace_dir)
        except (FileNotFoundError, OSError, ImportError):
            _cache[trace_dir] = None
    return _cache[trace_dir]


def scope_names(records, program: str, scopes) -> set:
    """The instruction names the newest `program_scopes` record of
    `program` lists under any scope of `scopes` (exact names, or a scope
    and everything `scope.<more>` under it)."""
    found = [r for r in records or ()
             if r.get("record") == "program_scopes" and r.get("name") == program]
    if not found:
        return set()
    return {
        name
        for scope, names in found[-1].get("scopes", {}).items()
        if any(scope == s or scope.startswith(s + ".") for s in scopes)
        for name in names}


#: the two halves of an asynchronous copy: their events on the ops line
#: are the issue and the wait, no work of the core, and they lie over the
#: operations that run meanwhile
ASYNC_WAITS = ("copy-start", "copy-done")


def steps_of(loaded, module: str):
    """`[(start_ns, end_ns)]` of the WHOLE runs of the compiled program
    whose module name contains `module`, on the busiest device plane, and
    that plane's leaf operations without the asynchronous copies' waits.
    A run that the trace's start or end cut can only be the first or the
    last event of the modules' line: those two are left out."""
    if not loaded:
        return [], []
    per_core = trace_reduce.device_ops(loaded)
    if not per_core:
        return [], []
    lead = max(per_core, key=lambda k: trace_reduce.busy_ns(per_core[k]))
    modules = sorted(loaded[lead].get(MODULES_LINE, ()), key=lambda e: e[1])
    runs = [(s, s + d) for name, s, d in modules[1:-1] if module in name]
    ops = [ev for ev in trace_reduce.leaves(per_core[lead])
           if not trace_reduce.short_name(ev[0]).startswith(ASYNC_WAITS)]
    return runs, ops


def _inside(runs, ops):
    """The operations that lie inside one of `runs` (both sorted)."""
    i = 0
    for ev in ops:
        _, start, dur = ev
        while i < len(runs) and runs[i][1] <= start:
            i += 1
        if i == len(runs):
            return
        if runs[i][0] <= start and start + dur <= runs[i][1]:
            yield ev


def breakdown(loaded, module: str, scopes: dict):
    """Milliseconds of device time a whole step of `module` spends under
    each of `scopes` ({scope: instruction names}), outside all of them
    (`unscoped`) and in all (`busy`), each the union of its operations'
    intervals averaged over the whole runs (`steps`). Scopes that share no
    instruction add up, with `unscoped`, to `busy`. None where the trace
    holds no whole run."""
    runs, ops = steps_of(loaded, module)
    if not runs:
        return None
    inside = list(_inside(runs, ops))
    scoped = set().union(*scopes.values()) if scopes else set()

    def ms(pick):
        return trace_reduce.busy_ns(
            ev for ev in inside if pick(trace_reduce.short_name(ev[0]))
        ) / 1e6 / len(runs)

    out = {scope: ms(lambda n, names=names: n in names)
           for scope, names in scopes.items()}
    out.update(steps=len(runs), busy=ms(lambda n: True),
               unscoped=ms(lambda n: n not in scoped))
    return out


def read(obs, program: str, module: str, scopes):
    """What a reader hands back: the milliseconds a whole step of the
    run's own trace spends in the instructions the program lists under
    `scopes`."""
    if not obs.get("trace"):
        return None
    names = scope_names(obs.get("records"), program, scopes)
    if not names:
        return None
    parts = breakdown(planes(), module, {"scoped": names})
    if not parts or not parts["scoped"]:
        return None
    return parts["scoped"]
