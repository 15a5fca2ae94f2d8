"""Device time of the state-space path in one decode step: the nine Mamba
layers' convolution, projections to step, B and C, the state's update, the
read-out and the gate (the program's `ssm` scope, `models/sambay.py`,
`ops/selective_scan.py`) and the seven Gated Memory Units that reuse one
layer's scan output (`gmu`), averaged over the decode steps of the traced
window. The instructions come from the program's own `program_scopes`
record (`harness/step_phases.py`): nothing to read where the program
writes none or the trace holds no decode step."""

from harness import step_phases


def read(obs):
    return step_phases.read(obs, "serve_decode", "jit_decode", ("ssm", "gmu"))
