"""Device time of learned sparse attention in one decode step: the indexer
scores over the indexer pool, the top-k, the look-up and gather of the
chosen latent rows through the block table, and the attention over them
(the program's `sparse_attn.*` scopes, `ops/latent_attention.py`),
averaged over the decode steps of the traced window. The projections
around them are not in it. The instructions come from the program's own
`program_scopes` record (`harness/step_phases.py`): nothing to read where
the program writes none or the trace holds no decode step."""

from harness import step_phases


def read(obs):
    return step_phases.read(
        obs, "serve_decode", "jit_decode", ("sparse_attn",))
