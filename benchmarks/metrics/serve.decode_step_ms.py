"""The decode step as the engine waits for it: median, over the ticks in
which one ran, of dispatch start to the end of the fetch of the sampled
tokens (`serve_tick` records; a tick with a draft lane sums its pairs)."""

from harness import spans


def read(obs):
    return spans.median_ms(
        sum(b - a for a, b in spans.decode_intervals(r["phases"]))
        for r in spans.tick_records(obs))
