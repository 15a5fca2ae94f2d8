"""`dots3_notes_decode`'s prefill chunk compiled for a described v5e:2x2
(`dots3_lowering.py`): fits the chip, re-lays no pool out."""

import dots3_lowering


def test_dots3_chunk_program_compiles_for_v5e():
    dots3_lowering.check("chunk")
