"""The train step's scope readers (`train.grad_ms`, `train.accum_ms`,
`train.optimizer_ms`, `train.step_idle_ms`, `harness/train_phases.py`) on a
hand-made trace: a modules line of three runs of the step, operations of
each scope with gaps between them, an asynchronous copy's wait over a gap,
a loop event over its body, and another program's run between the steps.
The program's `program_scopes` record is read from the program's
in-process list, as in a run."""

import collections

import pytest

import run

MS = 1_000_000  # ns
PLANE = "/device:TPU:0"
GRAD, ACCUM, OPT, FREE = (
    "%fusion.1 = bf16[8,128,1024] fusion(%p)",
    "%select_add_fusion.2 = f32[1024,4096] fusion(%g)",
    "%fusion.3 = f32[1024,4096] fusion(%m)",
    "%dynamic-slice.4 = s32[8,128] dynamic-slice(%b)")
RECORD = {"record": "program_scopes", "name": "train_step", "scopes": {
    "train.grad": ["fusion.1"], "train.accumulate": ["select_add_fusion.2"],
    "train.optimizer": ["fusion.3"]}}
METRICS = ("train.grad_ms", "train.accum_ms", "train.optimizer_ms",
           "train.step_idle_ms")


def one_run(t0):
    """A run of 10.5 ms: idle 0.5 before the grad op (4), 0.5 before the
    accumulate op (2), 1.0 before the optimizer op (1.5), then the loop's
    slice (0.5) and 0.5 idle to the run's end."""
    ops = [(GRAD, t0 + MS // 2, 4 * MS),
           (ACCUM, t0 + 5 * MS, 2 * MS),
           (OPT, t0 + 8 * MS, 3 * MS // 2),
           (FREE, t0 + 19 * MS // 2, MS // 2),
           # the wait of an asynchronous copy, over the 1 ms gap: no work
           ("%copy-done.5 = f32[1024,4096] copy-done(%c)", t0 + 7 * MS, MS),
           # a loop spans its body's operations on the same line
           ("%while.6 = (f32[]) while(%t)", t0 + MS // 2, 13 * MS // 2)]
    return ops, ("jit_train_step(7)", t0, 21 * MS // 2)


def trace(n_runs=3):
    ops, modules = [], []
    for i in range(n_runs):
        run_ops, module = one_run(i * 20 * MS)
        ops += run_ops
        modules.append(module)
        # the eval step between two runs: neither its module nor its op
        ops.append(("%fusion.9 = f32[8,2] fusion(%e)", i * 20 * MS + 12 * MS, MS))
        modules.append(("jit_eval_step(8)", i * 20 * MS + 12 * MS, MS))
    return {PLANE: {"XLA Ops": ops, "XLA Modules": modules}}


@pytest.fixture
def program(monkeypatch):
    """The program's in-process list holding the step's record, and the
    trace the readers load."""
    from harness import step_phases

    from pytorch_distributed_training_tpu.analysis.spmd import manifest

    monkeypatch.setattr(manifest, "PROGRAM_SCOPES",
                        collections.deque([RECORD], maxlen=16))
    loaded = {"trace": trace()}
    monkeypatch.setattr(step_phases, "planes", lambda *a: loaded["trace"])
    return manifest, loaded


def read(metric, obs=None):
    return run.load_reader(metric).read(
        {"trace": {"steps": 3}} if obs is None else obs)


def test_scopes_busy_and_idle_per_run(program):
    assert read("train.grad_ms") == pytest.approx(4.0)
    assert read("train.accum_ms") == pytest.approx(2.0)
    assert read("train.optimizer_ms") == pytest.approx(1.5)
    assert read("train.step_idle_ms") == pytest.approx(2.5)


def test_scopes_add_up_to_the_run(program):
    from harness import step_phases, train_phases

    parts = train_phases.breakdown(
        step_phases.planes(), train_phases.program_scopes())
    assert parts["runs"] == 3
    assert parts["unscoped"] == pytest.approx(0.5)
    assert (parts["train.grad"] + parts["train.accumulate"]
            + parts["train.optimizer"] + parts["unscoped"]) == pytest.approx(
        parts["busy"])
    assert parts["busy"] + parts["idle"] == pytest.approx(10.5)


def test_idle_split_by_the_next_operation_scope(program):
    split = run.load_reader("train.step_idle_ms").split({"trace": {"steps": 3}})
    assert split == pytest.approx({
        "train.grad": 0.5, "train.accumulate": 0.5, "train.optimizer": 1.0,
        "end of run": 0.5})
    assert sum(split.values()) == pytest.approx(read("train.step_idle_ms"))


def test_one_run_is_enough(program):
    _, loaded = program
    loaded["trace"] = trace(n_runs=1)
    assert read("train.grad_ms") == pytest.approx(4.0)
    assert read("train.step_idle_ms") == pytest.approx(2.5)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read(metric, program, monkeypatch):
    manifest, loaded = program
    assert read(metric, {"trace": None}) is None  # an untraced run
    assert read(metric, {}) is None
    loaded["trace"] = {PLANE: {"XLA Ops": trace()[PLANE]["XLA Ops"],
                               "XLA Modules": []}}
    assert read(metric) is None  # no run of the step in the trace
    loaded["trace"] = None
    assert read(metric) is None  # no trace file
    loaded["trace"] = trace()
    manifest.PROGRAM_SCOPES.clear()
    assert read(metric) is None  # the program kept no record
    manifest.PROGRAM_SCOPES.append(
        {"record": "program_scopes", "name": "serve_decode",
         "scopes": {"moe": ["fusion.1"]}})
    assert read(metric) is None  # only another program's
    monkeypatch.delattr(manifest, "PROGRAM_SCOPES")
    assert read(metric) is None  # a parent: a program without the list
