"""How unevenly a decode step's tokens fall on the experts this chip
holds: the busiest held expert's tokens over the mean (`expert_tokens_max`
/ `expert_tokens_mean` of the `serve_tick` records, summed over the expert
layers), median over the ticks in which a decode step routed any. 1 is
even; nothing to read where the program counts no routing."""

import statistics

from harness import spans


def read(obs):
    ratios = [
        r["expert_tokens_max"] / r["expert_tokens_mean"]
        for r in spans.tick_records(obs)
        if r.get("expert_tokens_mean")]
    return statistics.median(ratios) if ratios else None
