"""Device time, in one decode step, of the attention that goes through THE
shared K/V page pool: the full-attention layer's write and read and the
seven cross layers' reads of the same pool through the same block table,
with their projections and differential combines (the program's
`shared_attn` scope, `models/sambay.py`), averaged over the decode steps
of the traced window. The instructions come from the program's own
`program_scopes` record (`harness/step_phases.py`): nothing to read where
the program writes none or the trace holds no decode step."""

from harness import step_phases


def read(obs):
    return step_phases.read(obs, "serve_decode", "jit_decode", ("shared_attn",))
