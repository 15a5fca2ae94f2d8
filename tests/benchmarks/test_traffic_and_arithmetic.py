"""The generator, the operation counts (each family's own file, found
from the configuration's `adapter`) and the window arithmetic: all plain
numbers, no device."""

import json
import os

import pytest

from harness import family, flops, traffic, window

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")


def load(rel):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


MIXES = ["chat_decode", "summarize_prefill"]


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_seed_same_schedule_other_seed_other_order(mix_name):
    mix = load(f"traffic/{mix_name}.json")
    a = traffic.schedule(mix, 2**31 + 5, 40.0)
    b = traffic.schedule(mix, 2**31 + 5, 40.0)
    c = traffic.schedule(mix, 6, 40.0)
    assert a == b
    assert a != c
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("mix_name", MIXES)
def test_every_seed_gets_the_same_work(mix_name):
    mix = load(f"traffic/{mix_name}.json")
    runs = [traffic.schedule(mix, seed, 40.0) for seed in (1, 2, 3_000_000_000)]
    sizes = [sorted((r.prompt_len, r.max_new_tokens) for r in run) for run in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    gaps = []
    for run in runs:
        due = [0.0] + [r.due_s for r in run]
        gaps.append(sorted(round(b - a, 9) for a, b in zip(due, due[1:])))
    assert gaps[0] == gaps[1] == gaps[2]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for r in runs[0]:
        assert p["min"] <= r.prompt_len <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert len(r.prompt.encode()) == r.prompt_len  # one byte a token
        assert json.loads(json.dumps(r.prompt)) == r.prompt


def test_rate_and_bursts_shape_the_arrivals():
    mix = dict(load("traffic/chat_decode.json"), rate_rps=5.0)
    calm = traffic.schedule(mix, 1, 200.0)
    assert len(calm) == pytest.approx(1000, rel=0.1)
    stormy = dict(mix, bursts=[[50.0, 20.0]], burst_rate_x=3.0)
    burst = traffic.schedule(stormy, 1, 200.0)
    inside = [r for r in burst if 50.0 <= r.due_s < 70.0]
    assert len(inside) == pytest.approx(300, rel=0.2)
    assert all(r.burst for r in inside)


def test_shared_prefix_tenants():
    mix = dict(load("traffic/chat_decode.json"), tenants=3, shared_prefix_len=64)
    reqs = traffic.schedule(mix, 9, 60.0)
    heads = {r.tenant: r.prompt[:64] for r in reqs}
    assert len(heads) == 3 and len(set(heads.values())) == 3
    assert all(r.prompt.startswith(heads[r.tenant]) for r in reqs)
    assert all(r.prompt_len > 64 for r in reqs)


def test_bert_large_sample_needs_237_gflop():
    config = load("configs/bert_large_dp.json")
    count = family.count(config, "train_flops_per_sample")
    assert count(config, 128) == pytest.approx(2.37e11, rel=0.01)
    # the family asks for the dense block's count, to the last digit
    assert count(config, 128) == flops.dense_train_flops_per_sample(
        config["model"], 128) == 3 * (128 * (
            2 * 24 * (4 * 1024**2 + 2 * 1024 * 4096) + 24 * 4 * 128 * 1024)
            + 2 * 1024**2)


def test_gpt2_medium_token_keeps_98304_bytes_of_kv():
    config = load("configs/gpt2_medium_paged.json")
    fam = family.of(config)
    assert fam.cache_bytes_per_token(config) == 98_304
    assert fam.cache_read_bytes(config, [100, 300]) == 400 * 98_304
    # a decoded token: two operations per weight, attention over its context
    dense = 2 * 24 * (4 * 1024**2 + 2 * 1024 * 4096) + 2 * 1024 * 50257
    decode = family.count(config, "decode_flops")
    assert decode(config, 0) == dense
    assert decode(config, 512) == dense + 24 * 4 * 512 * 1024
    # a prompt costs about its length in decoded tokens, less the head
    prefill = family.count(config, "prefill_flops")
    assert prefill(config, 512) == pytest.approx(
        512 * (dense - 2 * 1024 * 50257), rel=0.05)
    assert prefill(config, 512) == 512 * 2 * 24 * (
        4 * 1024**2 + 2 * 1024 * 4096) + 24 * 4 * 1024 * 512 * 513 / 2 \
        + 2 * 1024 * 50257


@pytest.mark.parametrize("name,has,lacks", [
    ("bert_large_dp", "train_flops_per_sample", "prefill_flops"),
    ("gpt2_medium_paged", "decode_flops", "train_flops_per_sample"),
])
def test_a_count_the_family_lacks_is_an_exit_that_names_it(name, has, lacks):
    config = load(f"configs/{name}.json")
    assert callable(family.count(config, has))
    with pytest.raises(SystemExit) as e:
        family.count(config, lacks)
    assert lacks in str(e.value) and f"families/{config['adapter']}.py" in str(e.value)
    with pytest.raises(SystemExit, match="no file benchmarks/families/never.py"):
        family.of(dict(config, adapter="never"))


def test_percentile_is_nearest_rank():
    assert window.percentile([], 95) is None
    assert window.percentile([7.0], 95) == 7.0
    values = list(range(1, 101))
    assert window.percentile(values, 95) == 95
    assert window.percentile(values, 50) == 50
    assert window.percentile(list(range(1, 21)), 95) == 19


def synthetic_log(stall_at=None, stall_s=0.0):
    """A request every 0.1 s for 10 s, 5 tokens each, first token 50 ms
    after its due time and one every 20 ms; with a stall, no token leaves
    between `stall_at` and `stall_at + stall_s`: they all come at its end."""
    log = []
    for i in range(100):
        due = 100.0 + 0.1 * i
        times = [due + 0.05 + 0.02 * j for j in range(5)]
        if stall_at is not None:
            held = stall_at + stall_s
            times = [held if stall_at <= t < held else t for t in times]
        log.append({"due": due, "tokens": times, "done": times[-1], "failed": False})
    return log


def test_rate_counts_every_token_of_the_window_over_all_its_time():
    log = synthetic_log()
    assert window.tokens_per_s(log, 101.0, 109.0) == pytest.approx(50.0, rel=0.02)
    assert len(window.in_window(log, 101.0, 109.0)) == 80


def test_a_stall_shows_in_the_rate_and_in_both_tails():
    calm = synthetic_log()
    stalled = synthetic_log(stall_at=104.0, stall_s=6.5)  # ends after w1
    w = (101.0, 109.0)
    assert window.tokens_per_s(stalled, *w) < 0.5 * window.tokens_per_s(calm, *w)
    p95 = lambda xs: window.percentile(xs, 95)  # noqa: E731
    calm_ttft = p95(window.ttfts(calm, *w, miss_at=120.0))
    stall_ttft = p95(window.ttfts(stalled, *w, miss_at=120.0))
    assert calm_ttft == pytest.approx(0.05, abs=1e-6)
    assert stall_ttft > 5.0  # timed from when each was due, so the wait counts
    # the requests in flight when it began each carry one gap as long as it
    assert max(window.token_gaps(stalled, *w)) > 6.0 > max(window.token_gaps(calm, *w))


def test_a_failed_request_is_a_miss():
    log = synthetic_log()
    for e in log[20:30]:  # 10 of the window's 80: over 5%
        e["failed"], e["tokens"] = True, []
    ttft = window.ttfts(log, 101.0, 109.0, miss_at=130.0)
    assert len(ttft) == 80
    assert window.percentile(ttft, 95) > 20.0
    assert window.percentile(ttft, 50) == pytest.approx(0.05, abs=1e-6)
