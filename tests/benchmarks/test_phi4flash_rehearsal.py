"""A CPU rehearsal of `phi4flash_reason_decode`'s files through
`benchmarks/run.py` at a tiny size, as `test_glm52_rehearsal.py` does for
the latent family: the same driver, reference, family file, readers and
kind of mix (one chunk in, a long answer out, no shared prefixes), added to
a copy of the benchmark as files and entries only."""

import pytest
import rehearsal
from test_phi4flash_files import CONFIG

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "intermediate_size": 128, "sliding_window": 8,
    "mb_per_layer": 2, "layer_norm_eps": 1e-5, "vocab_size": 512,
    "vocab_blocks": 2, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 8, "cache_len": 64,
}
TINY_HYBRID = {
    "name": "tiny_hybrid", "rehearsal": True, "source": "tests only",
    "driver": "serve_driver", "reference": CONFIG["reference"],
    "adapter": CONFIG["adapter"], "weights": {"std": 0.2}, "model": MODEL,
    "serving": {"page_size": 4},
    "argv": ["--model", "sambay-tiny", "--warmup", "--num-slots", "4",
             "--prompt-buckets", "16", "--max-new-tokens-cap", "48",
             "--queue-depth", "256", "--page-size", "4", "--num-pages", "65",
             "--prefill-chunk", "16", "--stall-timeout-s", "120"],
    "control": {"argv": [], "reference_precision": "int8,window_all,stale_state"},
    "check": {"tokens": 40, "max_requests": 4},
    # float32 on both sides: sound runs read 0 (CPU); the controls' mean gap
    # over a request (43 tokens: one block) 0.047 (a stale state), 0.090
    # (int8), 0.42 (a window layer shown everything)
    "limits": {"max_logit_gap": 0.01},
}
TINY_REASON = {
    "kind": "open_loop", "shape_seed": 6, "rate_rps": 4.0, "ramp_s": 1.5,
    "drain_s": 60,
    "prompt_tokens": {"median": 10, "sigma": 0.3, "min": 4, "max": 16},
    "output_tokens": {"median": 36, "sigma": 0.1, "min": 28, "max": 48},
    "trace_after_s": 0.5, "trace_s": 0.5,
}
CELL = {"name": "tiny_reason1", "config": "tiny_hybrid",
        "traffic": "tiny_reason", "chips": 1, "why": "tests only"}
LISTED = [(m, "tiny_reason1") for m in (
    "serve.tpot_p50_ms", "serve.mfu", "serve.device_idle",
    "serve.tick_host_ms", "serve.decode_step_ms", "serve.prefill_ms",
    "serve.warmup_s", "serve.ssm_ms", "serve.window_attn_ms",
    "serve.shared_attn_ms", "serve.live_context_tokens",
    "kernel.paged_attn_rows_roofline")]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_checkout(
        str(tmp_path_factory.mktemp("phi4flash")), configs=[TINY_HYBRID],
        traffic=[("tiny_reason", TINY_REASON)], cells=[CELL],
        end_to_end_cells=[("serve.tpot_p95_ms", "tiny_reason1")], listed=LISTED)


def test_the_cells_files_run_through_run_py(checkout):
    rc, result, out, err = rehearsal.run_cell(
        checkout, "tiny_reason1", seed=2147490034, seconds=1.5, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 3
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # what the program's own records feed reads on the CPU too: contexts
    # pass three windows of 8
    assert metrics["serve.live_context_tokens"] > 24
    assert metrics["serve.decode_step_ms"] > 0 and metrics["serve.prefill_ms"] > 0
    # no device trace on the CPU: the trace's readers say nothing, the
    # line leaves them out and nothing raises
    for name in ("serve.ssm_ms", "serve.window_attn_ms", "serve.shared_attn_ms",
                 "kernel.paged_attn_rows_roofline"):
        assert name not in metrics
    assert result["checks"]["max_logit_gap"]["value"] <= 0.01
    assert '"kv_pool_readers": 2' in out and '"state_bytes_per_slot"' in out


def test_the_controls_read_over_the_limit(checkout, tmp_path):
    """`harness/calibrate.py`'s reading of a sound run with the faults and
    the int8 reference put in the program's place."""
    script = tmp_path / "faults.py"
    script.write_text(
        "import json, sys\n"
        "sys.path.insert(0, 'benchmarks')\n"
        "import run\n"
        "ctx = run.prepare('tiny_reason1')\n"
        "ctx.update(seed=2147490035, seconds=1.0, trace=False, read_faults=True)\n"
        "r = run.execute(ctx)\n"
        "print(json.dumps({'sound': r['checks']['max_logit_gap']['value'],\n"
        "                  'faults': r['notes']['faults']}))\n")
    rc, result, err = rehearsal.run_script(checkout, str(script))
    assert rc == 0, err[-2000:]
    assert result["sound"] <= 0.01
    worst, = result["faults"].values()
    assert worst["max_logit_gap"] > 3 * 0.01
