"""Device time of the expert layers in one decode step: the router, the
routed experts this chip holds and the shared expert (the program's `moe`
scopes: `ops/moe.py`, `models/latent_moe.py::ExpertLayer`), averaged over
the decode steps of the traced window. The instructions come from the
program's own `program_scopes` record (`harness/step_phases.py`): nothing
to read where the program writes none or the trace holds no decode step."""

from harness import step_phases


def read(obs):
    return step_phases.read(obs, "serve_decode", "jit_decode", ("moe",))
