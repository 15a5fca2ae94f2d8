"""EVERY reader under `benchmarks/metrics/`, handed what a PARENT of the PR
that brought it would have recorded: a traced run of every cell reads every
listed metric with the benchmark's newest files over the parent's program,
so a reader that raises there refuses the PR that added it (PR 33). Each
must give a number or None and never raise: on nothing, on records that
lack every newer attribute (`live_tokens`, routing counts, `cached_tokens`),
on a trace with no `program_scopes` record and no kernel of the newer
names, on no trace at all. Cases are added here; the cases of
`test_span_readers.py` stay as they are."""

import glob
import os

import pytest

import run

METRICS = sorted(
    os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(os.path.dirname(run.__file__), "metrics", "*.py")))

# what the oldest serving program wrote: ticks with their phases and the
# first three counters, request records with a queue wait, a set-up span
OLD_TICK = {
    "record": "serve_tick", "tick": 1, "t0_s": 10.0, "t1_s": 10.1,
    "decode_active": 3, "admitted": 0, "prefill_tokens": 0,
    "phases": [["dispatch", 10.003, 10.005, None],
               ["decode_wait", 10.005, 10.097, None]]}
OLD_RECORDS = [
    OLD_TICK,
    {"record": "serve_request", "id": "q0", "prompt_len": 50, "queue_wait_s": 0.1},
    {"record": "span", "name": "serve_setup.warmup", "component": "setup",
     "dur_s": 12.5},
    {"record": "span", "name": "warm_start.train.lower", "component": "setup",
     "dur_s": 3.0},
]
# a trace of a program with no scopes record and none of the newer kernels
OLD_TRACE = {
    "window_s": 2.0, "busy_s": 1.5, "cores": 1, "steps": 10,
    "per_name_s": {"%fusion.1 = bf16[48,1024] fusion(%x)": 1.5},
    "events": [("%fusion.1 = bf16[48,1024] fusion(%x)", 100, 50),
               ("%attention_norm.7 = bf16[48,1024] custom-call(%x)", 200, 20),
               ("%paged_attn.3 = bf16[48,1,1024] custom-call(%q)", 300, 40)],
    "breakdown": {"device_ops": [], "idle_gaps": []},
    "contexts": [120, 340],
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GPT2 = {"adapter": "gpt2", "model": {"n_embd": 1024, "n_layer": 24, "n_head": 16}}
CASES = {
    "nothing": {},
    "empty records": {"records": [], "trace": None},
    "a parent's records, no trace": {
        "records": OLD_RECORDS, "trace": None, "ttft_s": [0.1],
        "token_gaps_s": [0.02, 0.03], "window_s": 30.0, "chips": 1,
        "peaks": PEAKS, "prefill_flops": 1e12, "decode_flops": 2e12,
        "config": GPT2, "end_to_end": {}},
    "a parent's records and trace": {
        "records": OLD_RECORDS, "trace": OLD_TRACE, "ttft_s": [0.1],
        "token_gaps_s": [0.02, 0.03], "window_s": 30.0, "chips": 1,
        "peaks": PEAKS, "prefill_flops": 1e12, "decode_flops": 2e12,
        "config": GPT2, "end_to_end": {}},
    "a rehearsal: a trace and no peaks": {
        "records": OLD_RECORDS, "trace": OLD_TRACE, "peaks": None,
        "window_s": 1.0, "chips": 1, "prefill_flops": 0.0, "decode_flops": 0.0,
        "config": GPT2},
}


def test_every_metric_file_is_covered():
    assert len(METRICS) >= 23 and "serve.live_context_tokens" in METRICS


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_a_number_or_nothing_and_never_raises(
        metric, case, monkeypatch, tmp_path):
    from harness import step_phases

    # no trace file of another run under the checkout's work directory
    monkeypatch.setattr(step_phases, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(step_phases, "_cache", {})
    monkeypatch.setattr(
        step_phases, "planes",
        lambda trace_dir=str(tmp_path), real=step_phases.planes: real(trace_dir))
    got = run.load_reader(metric).read(dict(CASES[case]))
    assert got is None or isinstance(got, (int, float)), (metric, case, got)
    if metric in ("serve.ssm_ms", "serve.window_attn_ms", "serve.shared_attn_ms",
                  "serve.live_context_tokens", "kernel.paged_attn_rows_roofline",
                  "serve.moe_ms", "serve.sparse_attn_ms", "serve.moe_imbalance",
                  "serve.prefix_hit_share"):
        # what a parent of its PR has nothing of: left out, not nought
        assert got is None, (metric, case, got)
