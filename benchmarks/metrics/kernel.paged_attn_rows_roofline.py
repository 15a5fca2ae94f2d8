"""Share (%) of the chip's HBM bandwidth that the page-walk kernel in rows
mode (`paged_attn_rows`, `ops/paged_attention.py`: the decode step's read
of the shared K/V pool, eight times a step, and of the eight window rings)
reaches over the traced window: the bytes of the LIVE rows its calls had
to read (the family's `page_walk_read_bytes` over the contexts of the
tokens decoded in the traced window) over the device time of the Mosaic
calls of that name and the chip's bytes per second. Whole pages and idle
slots' one page are read besides and not counted, so the share is a floor.
Nothing to read where the trace holds no such call (the reads fell back to
XLA, another family's program, no device trace) or the family counts no
such bytes."""

import re

from harness import family, trace_reduce

KERNEL = re.compile(r"paged_attn_rows(\.\d+)*$")


def read(obs):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    contexts = (trace or {}).get("contexts")
    if not trace or not peaks or not contexts:
        return None
    ns = sum(
        d for name, _, d in trace.get("events", ())
        if " custom-call(" in name and KERNEL.match(trace_reduce.short_name(name)))
    if not ns:
        return None
    config = obs.get("config") or {}
    if not config.get("adapter"):
        return None
    count = getattr(family.of(config), "page_walk_read_bytes", None)
    if count is None:
        return None
    return 100.0 * count(config, contexts) / (ns / 1e9) / peaks[
        "hbm_bytes_per_s"]
