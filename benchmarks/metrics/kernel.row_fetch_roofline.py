"""Share (%) of the chip's HBM bandwidth that the decode step's row fetch
(`row_fetch`, `ops/row_fetch.py`: one Mosaic call a selection group, the
chosen tokens' rows of every layer of the group, side by side) reaches over
the traced window: the bytes of the VALID rows its calls had to read
(`row_fetch_read_bytes` over the contexts of the tokens decoded in the
traced window) over the device time of the Mosaic calls of that name and
the chip's bytes per second. The kernel reads each valid row's whole 8-row
tile and writes the whole output, valid or not; neither is counted, so the
share is a floor. Nothing to read where the trace holds no such call (the
fetch fell back to XLA, a parent's program, no device trace) or the
configuration is not a latent one."""

import re

from harness import trace_reduce

KERNEL = re.compile(r"row_fetch(\.\d+)*$")


def _groups(config: dict):
    """(values of a group's wide row, rows a context reads: None for the
    chosen `index_topk`, else the window) of each selection group of the
    decode step: a choosing layer and the layers that share its choice, or
    a run of window layers, side by side in one pool of padded rows."""
    m, serving = config.get("model") or {}, config.get("serving") or {}
    if "layer_types" in m:      # full layers each choose; windows are a run
        kinds = ["window" if t == "sliding_attention" else "full"
                 for t in m["layer_types"]]
    else:
        kinds = list(m.get("indexer_types") or ())
    groups, before = [], None
    for kind in kinds:
        if kind == "full" or (kind == "window" and before != "window"):
            groups.append([kind, 0])
        if groups:
            groups[-1][1] += 1
        before = kind
    out = []
    for kind, layers in groups:
        if kind == "window":
            out.append((layers * serving["window_row_padded"],
                        m["sliding_window_size"]))
        else:
            out.append((layers * serving["latent_row_padded"], None))
    return out


def row_fetch_read_bytes(config: dict, contexts, bytes_per_value: int = 2) -> int:
    """What the row fetch had to read for the tokens decoded at `contexts`:
    a context `c` reads `min(index_topk, c)` wide rows in each choosing
    group and `min(window, c)` in a window group (a decoded token at
    context `c` sees positions `0 .. c - 1`). glm52_share16 at context
    18,000: 2,048 x (4 + 2) x 640 values x 2 B."""
    m = config["model"]
    return sum(
        width * bytes_per_value * min(m["index_topk"] if rows is None else rows, c)
        for width, rows in _groups(config) for c in contexts)


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
    serving = config.get("serving") or {}
    if "latent_row_padded" not in serving or "index_topk" not in (
            config.get("model") or {}):
        return None
    return 100.0 * row_fetch_read_bytes(config, contexts) / (ns / 1e9) / peaks[
        "hbm_bytes_per_s"]
