"""Every reader whose `workloads` gain `dots3_notes_decode`, handed what a
PARENT program records: a traced run of every cell reads every listed
metric with the benchmark's newest files over the parent's program, and
the new cell lists these. Each gives a number or None and never raises:
on the cases of `test_readers_on_a_parents_records.py`, on a latent
model's records that name no `window_attn` scope (the parent's GLM
program), and, where it has something to read, on the new program's
records and trace (a decode step with instructions under `window_attn`)."""

import json
import os

import pytest

import run
from test_readers_on_a_parents_records import CASES, OLD_RECORDS, PEAKS

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
    LISTED = sorted(
        m["name"] for m in json.load(f)["per_layer"]
        if "dots3_notes_decode" in m.get("workloads", ()))

TICK = {"record": "serve_tick", "tick": 2, "t0_s": 11.0, "t1_s": 11.03,
        "decode_active": 5, "admitted": 0, "prefill_tokens": 0,
        "cached_tokens": 0, "chunks": 0, "expert_tokens_max": 3,
        "expert_tokens_mean": 1.25, "absent_pairs": 30, "live_tokens": 84000,
        "phases": [["dispatch", 11.001, 11.004, None],
                   ["decode_wait", 11.004, 11.028, None]]}
SCOPES = ("sparse_attn.index_scores", "sparse_attn.topk",
          "sparse_attn.gather", "sparse_attn.attend", "moe")
GLM_PARENT = [
    *OLD_RECORDS, TICK,
    {"record": "serve_request", "id": "q0", "prompt_len": 16512,
     "cached_tokens": 16384, "queue_wait_s": 0.1},
    {"record": "program_scopes", "name": "serve_decode",
     "scopes": {s: ["fusion.7"] for s in SCOPES}},
]
NEW = [*GLM_PARENT[:-1], {
    "record": "program_scopes", "name": "serve_decode",
    "scopes": {**{s: ["fusion.7"] for s in SCOPES},
               "window_attn": ["fusion.9", "gather.3"]}}]
PLANES = {"/device:TPU:0": {
    "XLA Modules": [("jit_decode(1)", 0, 900), ("jit_decode(1)", 1000, 900),
                    ("jit_decode(1)", 2000, 900), ("jit_decode(1)", 3000, 900)],
    "XLA Ops": [(f"%{name} = bf16[8] fusion(%y)", start + off, 100)
                for start in (0, 1000, 2000, 3000)
                for name, off in (("fusion.7", 100), ("fusion.9", 300),
                                  ("gather.3", 500))]}}
TRACE = {"window_s": 2.0, "busy_s": 1.2, "cores": 1, "steps": 4,
         "per_name_s": {}, "events": [], "contexts": [16500, 17000],
         "breakdown": {"device_ops": [], "idle_gaps": []}}
OBS = {"trace": TRACE, "ttft_s": [0.2], "token_gaps_s": [0.03, 0.028],
       "window_s": 30.0, "chips": 1, "peaks": PEAKS, "prefill_flops": 1e12,
       "decode_flops": 2e12, "end_to_end": {},
       "config": {"adapter": "dots3_note", "model": {}}}
MORE = {
    "the parent's latent program, traced": dict(OBS, records=GLM_PARENT),
    "the new program, traced": dict(OBS, records=NEW),
}


def test_the_new_cell_lists_the_issues_readers():
    assert LISTED == sorted([
        "serve.tpot_p50_ms", "serve.mfu", "serve.device_idle",
        "serve.tick_host_ms", "serve.decode_step_ms", "serve.prefill_ms",
        "serve.warmup_s", "serve.moe_ms", "serve.moe_imbalance",
        "serve.sparse_attn_ms", "serve.prefix_hit_share",
        "serve.live_context_tokens", "serve.window_attn_ms"])


@pytest.mark.parametrize("case", [*CASES, *MORE])
@pytest.mark.parametrize("metric", LISTED)
def test_reader_gives_a_number_or_nothing_and_never_raises(
        metric, case, monkeypatch, tmp_path):
    from harness import step_phases

    monkeypatch.setattr(step_phases, "_cache", {})
    traced = case in MORE
    monkeypatch.setattr(
        step_phases, "planes",
        lambda trace_dir=str(tmp_path): PLANES if traced else None)
    got = run.load_reader(metric).read(dict({**CASES, **MORE}[case]))
    assert got is None or isinstance(got, (int, float)), (metric, case, got)
    if metric == "serve.window_attn_ms":
        # the parent's latent program names no window scope: nothing, not
        # nought; the new one's two instructions, 200 ns a whole step
        want = 200e-6 if case == "the new program, traced" else None
        assert got == (pytest.approx(want) if want else None), (case, got)
