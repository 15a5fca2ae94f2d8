"""The five per-layer readers that read the program's own spans
(`benchmarks/metrics/serve.tick_host_ms.py`, `serve.decode_step_ms.py`,
`serve.prefill_ms.py`, `serve.warmup_s.py`, `train.lower_s.py`): on
hand-made records with known answers, on nothing, and in one traced
rehearsal of each tiny cell, with entries this test adds for them."""

import pytest

import rehearsal
import run


def tick(n, t0, t1, phases, decode_active=3):
    return {"record": "serve_tick", "tick": n, "t0_s": t0, "t1_s": t1,
            "decode_active": decode_active, "admitted": 0,
            "prefill_tokens": 0, "phases": phases}


def decode_phases(t):
    """A decode tick of 100 ms at `t`: 6 ms of host, 94 ms of step."""
    return [
        ["expire", t, t + .001, None], ["admit", t + .001, t + .002, None],
        ["chunks", t + .002, t + .002, None],
        ["operands", t + .002, t + .003, None],
        ["dispatch", t + .003, t + .005, None],
        ["decode_wait", t + .005, t + .097, None],
        ["emit", t + .097, t + .099, None],
        ["publish", t + .099, t + .100, None],
    ]


PLAIN = tick(1, 10.0, 10.1, decode_phases(10.0))
# two prefills of 40 ms (a wait inside each, 2 ms of host after it) ahead
# of the same decode step: 4 + 1 ms more of host
TWO_PREFILLS = tick(2, 20.0, 20.185, [
    ["expire", 20.0, 20.0, None], ["admit", 20.0, 20.001, None],
    ["prefill", 20.001, 20.043, "a", {"bucket": 64, "prompt_len": 50}],
    ["prefill_wait", 20.003, 20.041, "a"],
    ["admit", 20.043, 20.043, None],
    ["prefill", 20.043, 20.085, "b"],
    ["prefill_wait", 20.045, 20.083, "b"],
] + [[n, a + 10.085, b + 10.085, r] for n, a, b, r in decode_phases(10.0)[1:]])
# admitted and finished inside one tick: no decode step ran, so no reader
# counts it
NO_DECODE = tick(3, 30.0, 30.05, [
    ["admit", 30.0, 30.001, None], ["prefill", 30.001, 30.049, "c"],
    ["prefill_wait", 30.002, 30.048, "c"], ["publish", 30.049, 30.05, None],
], decode_active=0)
WARMUP = {"record": "span", "name": "serve_setup.warmup",
          "component": "setup", "dur_s": 12.5}


def read(metric, obs):
    return run.load_reader(metric).read(obs)


@pytest.mark.parametrize("metric, records, expected", [
    ("serve.tick_host_ms", [PLAIN], 6.0),
    ("serve.tick_host_ms", [TWO_PREFILLS], 11.0),
    ("serve.tick_host_ms", [PLAIN, TWO_PREFILLS, PLAIN, NO_DECODE], 6.0),
    ("serve.decode_step_ms", [PLAIN], 94.0),
    ("serve.decode_step_ms", [TWO_PREFILLS, NO_DECODE], 94.0),
    ("serve.prefill_ms", [TWO_PREFILLS], 40.0),
    ("serve.prefill_ms", [PLAIN], None),
    ("serve.prefill_ms", [NO_DECODE], None),
    ("serve.warmup_s", [dict(WARMUP, dur_s=3.0), PLAIN, WARMUP], 12.5),
])
def test_reader_on_hand_made_records(metric, records, expected):
    got = read(metric, {"records": records})
    assert got == pytest.approx(expected, abs=1e-6)


@pytest.mark.parametrize("metric", [
    "serve.tick_host_ms", "serve.decode_step_ms", "serve.prefill_ms",
    "serve.warmup_s"])
@pytest.mark.parametrize("obs", [
    {}, {"records": []},
    # the parent of the PR that brought the spans: records, none of them these
    {"records": [{"record": "serve_request", "queue_wait_s": 0.1},
                 {"record": "span", "name": "prefill", "dur_s": 1.0}]}])
def test_reader_gives_none_where_there_is_nothing_to_read(metric, obs):
    assert read(metric, obs) is None


def test_a_dispatch_with_no_wait_counts_its_own_span_only():
    # a page copy dispatched inside an admission, fetched by nobody
    t = tick(4, 0.0, 0.11, [
        ["admit", 0.0, 0.01, None], ["dispatch", 0.002, 0.004, "a"],
    ] + [[n, a - 9.99, b - 9.99, r] for n, a, b, r in decode_phases(10.0)[2:]])
    assert read("serve.tick_host_ms", {"records": [t]}) == pytest.approx(
        110 - 2 - 94, abs=1e-6)
    assert read("serve.decode_step_ms", {"records": [t]}) == pytest.approx(
        2 + 94, abs=1e-6)


def test_train_lower_s_sums_the_newest_lowerings(monkeypatch):
    from pytorch_distributed_training_tpu.telemetry import spans

    def span(name, dur):
        return {"record": "span", "name": name, "component": "setup",
                "dur_s": dur}

    monkeypatch.setattr(spans, "SETUP_SPANS", [])
    assert read("train.lower_s", {}) is None
    spans.SETUP_SPANS.extend([
        span("warm_start.train.lower", 9.0), span("warm_start.eval.lower", 9.0),
        span("warm_start.train.lower", 2.0), span("warm_start.train.compile", 5.0)])
    assert read("train.lower_s", {}) == pytest.approx(11.0)
    monkeypatch.delattr(spans, "SETUP_SPANS")  # a program without the spans
    assert read("train.lower_s", {}) is None


def entry(name, unit, layer, moves, cell):
    return {"name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": [cell]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_tiny_checkout(
        str(tmp_path_factory.mktemp("bench")), per_layer=[
            entry("serve.tick_host_ms", "ms", "engine tick",
                  "serve.tpot_p95_ms", "tiny_chat1"),
            entry("serve.decode_step_ms", "ms", "model step",
                  "serve.tpot_p95_ms", "tiny_chat1"),
            entry("serve.prefill_ms", "ms", "model step",
                  "serve.tpot_p95_ms", "tiny_chat1"),
            entry("serve.warmup_s", "s", "serve set-up", "setup_s",
                  "tiny_chat1"),
            entry("train.lower_s", "s", "compile plane", "setup_s",
                  "tiny_dp1"),
        ])


def test_traced_serving_rehearsal_reports_the_four_span_metrics(checkout):
    rc, result, out, err = rehearsal.run_cell(
        checkout, "tiny_chat1", seconds=3, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # host durations of the program, so a rehearsal may read them
    assert 0 < got["serve.tick_host_ms"] < got["serve.tpot_p50_ms"]
    assert 0 < got["serve.decode_step_ms"] < got["serve.tpot_p50_ms"] * 1.5
    assert got["serve.prefill_ms"] > 0
    # the warm-up compiled two prefill buckets and the decode step
    assert 0.1 < got["serve.warmup_s"] < result["metrics"].get(
        "setup_s", {"value": 600})["value"]
    assert result["metrics"]["serve.warmup_s"]["unit"] == "s"


def test_traced_training_rehearsal_reports_lower_s(checkout):
    rc, result, out, err = rehearsal.run_cell(checkout, "tiny_dp1", trace=1)
    assert rc == 0, err[-3000:]
    got = result["metrics"]
    assert got["train.lower_s"]["unit"] == "s"
    assert got["train.lower_s"]["value"] > 0.05  # two steps traced and lowered
