"""`kernel.row_fetch_roofline`: nothing on a parent's records (a program
without the `row_fetch` kernel), a share on a trace that holds its calls,
and the bytes it divides by against a count by hand."""

import json
import os

import pytest

import run

CONFIGS = os.path.join(os.path.dirname(run.__file__), "configs")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READER = run.load_reader("kernel.row_fetch_roofline")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _trace(events, contexts):
    return {"window_s": 2.0, "busy_s": 1.9, "cores": 1, "steps": 67,
            "events": events, "contexts": contexts,
            "breakdown": {"device_ops": [], "idle_gaps": []}}


# what the parent's GLM program leaves in a trace: XLA's look-ups and row
# gathers, no Mosaic call of the kernel's name
PARENT_EVENTS = [
    ("%fusion.12 = s32[98304]{0} fusion(%a, %b)", 100, 3_400_000),
    ("%fusion.13 = bf16[98304,2560]{1,0} fusion(%p, %r)", 200, 3_400_000),
    ("%paged_attn.3 = bf16[48,1,1024] custom-call(%q)", 300, 40),
]
KERNEL_EVENTS = [
    ("%row_fetch = bf16[48,2048,2560]{2,1,0} custom-call(%c, %w, %t, %p)",
     100, 2_000_000),
    ("%row_fetch.1 = bf16[48,2048,1280]{2,1,0} custom-call(%c, %w, %t, %p)",
     200, 1_000_000),
    ("%fusion.9 = bf16[48,2048,2560]{2,1,0} fusion(%row_fetch)", 300, 5_000),
]


@pytest.mark.parametrize("config", ["glm52_share16", "dots3_share8"])
def test_nothing_on_a_parents_records(config):
    obs = {"trace": _trace(PARENT_EVENTS, [17000, 18000]), "peaks": PEAKS,
           "config": _config(config)}
    assert READER.read(obs) is None
    assert READER.read({"trace": None, "peaks": PEAKS}) is None


def test_a_share_on_a_trace_with_the_kernel():
    config = _config("glm52_share16")
    contexts = [17000, 18000, 1000]
    obs = {"trace": _trace(KERNEL_EVENTS, contexts), "peaks": PEAKS,
           "config": config}
    want = 100.0 * READER.row_fetch_read_bytes(config, contexts) / 3e-3 / 819e9
    assert READER.read(obs) == pytest.approx(want)
    assert 0 < READER.read(obs) < 100


def test_glm_read_bytes_by_hand():
    """glm52_share16's two groups: layers 0-3 (four rows of 640 values
    side by side) and layers 4-5 (two), 2 B a value; 2,048 chosen rows
    where the context has that many."""
    config = _config("glm52_share16")
    wide = (4 + 2) * 640 * 2
    assert READER.row_fetch_read_bytes(config, [1000]) == 1000 * wide
    assert READER.row_fetch_read_bytes(config, [18000]) == 2048 * wide
    assert READER.row_fetch_read_bytes(config, [1000, 18000]) == 3048 * wide


def test_dots3_read_bytes_by_hand():
    """dots3_share8: two full layers, each its own group of one 640-value
    row, and ONE window group of three 1,152-value rows over 513 rows."""
    config = _config("dots3_share8")
    per = 2 * 2048 * 640 * 2 + 513 * 3 * 1152 * 2
    assert READER.row_fetch_read_bytes(config, [18000]) == per
    assert READER.row_fetch_read_bytes(config, [300]) == (
        2 * 300 * 640 * 2 + 300 * 3 * 1152 * 2)
