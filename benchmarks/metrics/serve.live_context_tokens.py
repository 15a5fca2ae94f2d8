"""The load a decode step carried: the sum of the active slots' context
lengths at dispatch (`live_tokens` of the program's `serve_tick` records),
median over the ticks in which a decode step ran. A description of the
window's fill, judged nowhere: where the shared pool's read costs what is
live, a tick's length follows it. Nothing to read where the records lack
the attribute."""

import statistics

from harness import spans


def read(obs):
    live = [r["live_tokens"] for r in spans.tick_records(obs)
            if r.get("live_tokens") is not None]
    return statistics.median(live) if live else None
