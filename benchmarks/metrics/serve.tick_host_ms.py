"""Host time of an engine tick: over the ticks in which a decode step ran,
the median of the tick less every prefill (start to the end of its wait)
and less the decode step (dispatch start to the end of its wait), from the
program's `serve_tick` records. Every tick of the run counts, ramp and
drain included: the records carry no window."""

from harness import spans


def read(obs):
    return spans.median_ms(map(spans.tick_host_s, spans.tick_records(obs)))
