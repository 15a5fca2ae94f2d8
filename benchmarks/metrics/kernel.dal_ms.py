"""Device time per step in the fused add-LayerNorm kernels, forward and
backward: the Mosaic custom calls that the trace names after the flax
module that called them (`%attention_norm.528`, `%mlp_norm.77`). Nothing
to read where the trace holds no such call (the kernels fell back to XLA,
or there is no device trace)."""

import re

from harness import trace_reduce

KERNEL = re.compile(r"(attention_norm|mlp_norm)(\.\d+)*$")


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("steps"):
        return None
    ns = sum(
        d for name, _, d in trace["events"]
        if " custom-call(" in name and KERNEL.match(trace_reduce.short_name(name)))
    if not ns:
        return None
    return ns / 1e6 / trace["steps"]
