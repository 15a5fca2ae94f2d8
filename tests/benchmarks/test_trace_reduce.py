"""Trace reduction on a hand-made event list, and on a small recorded
trace of the chip (`benchmarks/traces/`)."""

import json
import os

import pytest

from harness import trace_reduce as tr

US = 1000  # events in ns


def hand_made():
    """One core: two matmuls with an idle gap between, a kernel inside the
    second (overlapping), then a long gap and a last op. A second core runs
    half as much."""
    core0 = [
        ("fusion.1", 0, 100 * US),
        ("fusion.2", 150 * US, 100 * US),
        ("dal_kernel", 200 * US, 100 * US),   # overlaps fusion.2 by 50us
        ("fusion.1", 600 * US, 100 * US),
    ]
    core1 = [("fusion.1", 0, 150 * US), ("fusion.1", 600 * US, 100 * US)]
    return {
        "/device:TPU:0": {tr.OPS_LINE: core0, "XLA Modules": [("jit_step", 0, 700 * US)]},
        "/device:TPU:1": {tr.OPS_LINE: core1},
        "/host:CPU": {"python": [("train_step", 290 * US, 250 * US)]},
    }


def test_busy_is_the_union_of_intervals():
    events = hand_made()["/device:TPU:0"][tr.OPS_LINE]
    assert tr.merge((s, s + d) for _, s, d in events) == [
        (0, 100 * US), (150 * US, 300 * US), (600 * US, 700 * US)]
    assert tr.busy_ns(events) == 350 * US
    assert tr.gaps(events, 0, 700 * US) == [
        (100 * US, 150 * US), (300 * US, 600 * US)]


def test_per_name_time_and_top_ten():
    events = hand_made()["/device:TPU:0"][tr.OPS_LINE]
    assert tr.per_name_ns(events) == {
        "fusion.1": 200 * US, "fusion.2": 100 * US, "dal_kernel": 100 * US}
    assert tr.top(events, 2) == [["fusion.1", 200e-6], ["fusion.2", 100e-6]]
    many = [(f"op{i}", i * 10, i + 1) for i in range(30)]
    top = tr.top(many)
    assert len(top) == 10 and top[0][0] == "op29"


def test_reduce_reports_window_busy_and_breakdown():
    host = hand_made()["/host:CPU"]["python"]
    out = tr.reduce(hand_made(), host_events=host)
    assert out["cores"] == 2
    assert out["window_s"] == pytest.approx(700e-6)
    # averaged over the cores: (350 + 250) / 2 us
    assert out["busy_s"] == pytest.approx(300e-6)
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", 200e-6]
    # the long gap falls under the host's train_step span, the short under none
    assert out["breakdown"]["idle_gaps"] == [
        ["train_step", 300e-6], ["unattributed", 50e-6]]
    assert out["per_name_s"]["dal_kernel"] == pytest.approx(100e-6)


@pytest.mark.parametrize("line", ["python", "python3", ""])
def test_host_spans_are_the_phases_of_any_host_line(line):
    """On the chip the program's spans sit on a line named after the
    command, `python3`, beside the Python tracer's frames and the
    runtime's threads: every `/host:` line is read, kept to phase names."""
    planes = hand_made()
    planes["/host:CPU"] = {
        line: [("train_step", 290 * US, 250 * US),
               ("$threading.py:1002 run", 0, 700 * US),
               ("serve_tick.prefill_wait", 100 * US, 50 * US)],
        "pjrt-tpu-tasks/7": [("ThreadpoolListener::Run", 0, 700 * US)],
        "main/1": [("serve_setup.warmup.prefill_64", 0, 10 * US),
                   ("warm_start.train.lower", 0, 10 * US), ("eval_step", 0, 1)],
    }
    planes["/device:TPU:0"]["python"] = [("serve_tick", 0, 1)]  # no host plane
    assert sorted(e[0] for e in tr.host_spans(planes)) == [
        "eval_step", "serve_setup.warmup.prefill_64", "serve_tick.prefill_wait",
        "train_step", "warm_start.train.lower"]
    out = tr.reduce(planes)
    assert out["breakdown"]["idle_gaps"] == [
        ["train_step", 300e-6], ["serve_tick.prefill_wait", 50e-6]]


def test_idle_share_reads_the_reduction_and_nothing_without_a_trace():
    obs = {"trace": tr.reduce(hand_made())}
    assert tr.idle_share(obs) == pytest.approx(100.0 * (1 - 300 / 700))
    assert tr.idle_share({"trace": None}) is None and tr.idle_share({}) is None


def test_leaves_drop_the_operations_that_enclose_others():
    events = [("while", 0, 100), ("a", 0, 10), ("b", 20, 10), ("call", 50, 50),
              ("c", 60, 10), ("d", 200, 5)]
    assert [e[0] for e in tr.leaves(events)] == ["a", "b", "c", "d"]
    assert tr.group_key(
        "%fusion.7230 = bf16[8,128,1024]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[4096,1024]{1,0} %x)"
    ) == "fusion bf16[8,128,1024]"
    assert tr.short_name("%attention_norm.552 = (bf16[1024,1024]{1,0}) custom-call(") \
        == "attention_norm.552"


def test_clip_cuts_events_at_the_window():
    events = hand_made()["/device:TPU:0"][tr.OPS_LINE]
    out = tr.reduce(hand_made(), window=(50 * US, 250 * US))
    assert out["window_s"] == pytest.approx(200e-6)
    assert tr.busy_ns(tr.clip(events, 50 * US, 250 * US)) == 150 * US


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.reduce({"/host:CPU": {"python": [("x", 0, 1)]}})


RECORDED = os.path.join(
    os.path.dirname(__file__), "..", "..", "benchmarks", "traces")


@pytest.mark.parametrize(
    "name", sorted(f for f in os.listdir(RECORDED) if f.endswith(".json"))
    if os.path.isdir(RECORDED) else [])
def test_recorded_trace_reduces_to_its_noted_numbers(name):
    with open(os.path.join(RECORDED, name)) as f:
        rec = json.load(f)
    planes = {
        plane: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
        for plane, lines in rec["planes"].items()
    }
    out = tr.reduce(planes)
    assert out["window_s"] == pytest.approx(rec["expect"]["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert out["breakdown"]["device_ops"][0][0] == rec["expect"]["top_op"]
    assert 0.0 < out["busy_s"] <= out["window_s"]
