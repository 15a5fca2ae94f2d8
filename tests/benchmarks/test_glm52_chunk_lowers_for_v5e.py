"""`glm52_agent_decode`'s prefill chunk compiled for a described v5e:2x2
(`glm52_lowering.py`): fits the chip, re-lays no pool out."""

import glm52_lowering


def test_glm52_chunk_program_compiles_for_v5e():
    glm52_lowering.check("chunk")
