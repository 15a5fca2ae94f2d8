"""The faults a cell can have, planted UNDER the harness: each breaks the
program's timed path the way a wrong optimisation would, and the output
check has to say `correct: false`. Used by `fault_run.py` scripts that the
tests write into a temporary checkout; never by a benchmark run."""

from __future__ import annotations


def unchanged_state(step):
    """A step that returns its state unchanged (and a loss all the same)."""
    import jax

    def broken(state, batch):
        kept = jax.tree.map(lambda x: x.copy(), state)  # the call donates
        _, metrics = step(state, batch)
        return kept, metrics

    return broken


def half_batch(step):
    """Half of every micro-batch left out, the mean taken over the rest:
    the second half of the rows is the first half again."""
    import jax.numpy as jnp

    def broken(state, batch):
        def first_half_twice(x):
            h = x.shape[1] // 2
            return jnp.concatenate([x[:, :h], x[:, :h]], axis=1)

        return step(state, {k: first_half_twice(v) for k, v in batch.items()})

    return broken


def altered_token():
    """Every token altered where it is produced: the engine's in-jit
    sampler answers the id after the one it chose."""
    from pytorch_distributed_training_tpu.serve import engine

    sample = engine.device_sample

    def broken(logits, *a, **k):
        out = sample(logits, *a, **k)
        return (out + 1) % logits.shape[-1]

    engine.device_sample = broken


FAULT_RUN = '''
import json, os, sys
sys.path[:0] = [os.path.join(os.getcwd(), "benchmarks"), {tests_dir!r}]
import run, faults
cell, fault, seed, seconds = sys.argv[1:5]
ctx = run.prepare(cell)
ctx.update(seed=int(seed), seconds=float(seconds), trace=False)
if fault in ("unchanged_state", "half_batch"):
    ctx["break_step"] = getattr(faults, fault)
elif fault == "altered_token":
    ctx["sabotage"] = faults.altered_token
elif fault == "control":
    ctx["read_faults"] = True
elif fault != "none":
    raise SystemExit("unknown fault " + fault)
print(json.dumps(run.execute(ctx)))
'''
