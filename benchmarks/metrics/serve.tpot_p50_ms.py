"""Median gap between consecutive tokens, client side, over the requests
due in the window: the steady tick, beside the tail the users feel."""

from harness import window


def read(obs):
    p50 = window.percentile(obs.get("token_gaps_s") or [], 50)
    return None if p50 is None else 1e3 * p50
