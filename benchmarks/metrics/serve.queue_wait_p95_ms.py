"""95th percentile of the admission queue's own wait, from the program's
`serve_request` telemetry records (every request of the run, ramp
included: the records carry no due time to cut a window by)."""

from harness import window


def read(obs):
    waits = [r["queue_wait_s"] for r in obs.get("records", ())
             if r.get("record") == "serve_request"
             and r.get("queue_wait_s") is not None]
    p95 = window.percentile(waits, 95)
    return None if p95 is None else 1e3 * p95
