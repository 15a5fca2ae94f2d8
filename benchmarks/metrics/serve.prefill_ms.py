"""One prefill as the engine waits for it, serially inside a tick: median
over every `prefill` phase of its start (operands) to the end of the fetch
of its first token, from the program's `serve_tick` records."""

from harness import spans


def read(obs):
    return spans.median_ms(
        b - a for r in spans.tick_records(obs)
        for a, b in spans.prefill_intervals(r["phases"]))
