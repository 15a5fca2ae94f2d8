"""`phi4flash_reason_decode`'s decode step compiled for a described v5e:2x2
(`phi4flash_lowering.py`): fits the chip beside its weights, reads pool and
rings through the page walk."""

import phi4flash_lowering


def test_phi4flash_decode_program_compiles_for_v5e(monkeypatch):
    phi4flash_lowering.check("decode", monkeypatch)
