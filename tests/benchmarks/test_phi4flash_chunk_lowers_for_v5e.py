"""`phi4flash_reason_decode`'s prefill chunk compiled for a described
v5e:2x2 (`phi4flash_lowering.py`): fits the chip beside its weights."""

import phi4flash_lowering


def test_phi4flash_chunk_program_compiles_for_v5e(monkeypatch):
    phi4flash_lowering.check("chunk", monkeypatch)
