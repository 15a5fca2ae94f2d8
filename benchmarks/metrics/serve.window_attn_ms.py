"""Device time of the window layers' attention in one decode step: their
projections, the write of the step's row into each slot's ring, the read
of the ring's live rows and the differential combine (the program's
`window_attn` scope, `models/sambay.py`), averaged over the decode steps
of the traced window. The instructions come from the program's own
`program_scopes` record (`harness/step_phases.py`): nothing to read where
the program writes none or the trace holds no decode step."""

from harness import step_phases


def read(obs):
    return step_phases.read(obs, "serve_decode", "jit_decode", ("window_attn",))
