"""Idle time inside one run of the compiled train step: the run's length
less the union of its operations, apart from the gaps between runs,
averaged over the runs in the traced window (`harness/train_phases.py`).
`split` says where that idle falls: by the scope of the operation that
follows each gap. Nothing to read where the program keeps no scopes record
or the trace holds no run of the step."""

from harness import step_phases, train_phases


def read(obs):
    return train_phases.read(obs, "idle")


def split(obs):
    """`{scope of the next operation: idle ms per run}`, adding up to what
    `read` gives; None where `read` gives None."""
    if read(obs) is None:
        return None
    return train_phases.idle_by_next_scope(
        step_phases.planes(), train_phases.program_scopes())
