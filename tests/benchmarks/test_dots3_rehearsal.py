"""A CPU rehearsal of `dots3_notes_decode`'s files through
`benchmarks/run.py` at a tiny size, as `test_glm52_rehearsal.py` does for
its cell: the same driver, reference, family file, readers and kind of mix
(tenants sharing a prefix, served by `--prefix-cache` and
`--prefill-chunk`), added to a copy of the benchmark as files and entries
only."""

import pytest
import rehearsal
from test_dots3_files import CONFIG

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 80000000, "attention_gate_type": "headwise",
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 128, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    "swa_attention_gate_type": "headwise", "sliding_window_size": 9,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "router_experts": 8, "experts_held": [0, 2], "expert_block": 1,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 8,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "vocab_size": 512, "rms_norm_eps": 1e-5, "index_norm_eps": 1e-6,
    "cache_len": 64,
}
TINY_DOTS3 = {
    "name": "tiny_dots3", "rehearsal": True, "source": "tests only",
    "driver": "serve_driver", "reference": CONFIG["reference"],
    "adapter": CONFIG["adapter"], "weights": {"std": 0.2}, "model": MODEL,
    "serving": {"page_size": 4},
    "argv": ["--model", "dots3-tiny", "--warmup", "--num-slots", "4",
             "--prompt-buckets", "48", "--max-new-tokens-cap", "16",
             "--queue-depth", "256", "--page-size", "4", "--num-pages", "96",
             "--prefix-cache", "--prefill-chunk", "8",
             "--stall-timeout-s", "120"],
    "control": {"argv": [], "reference_precision": "int8,window_all"},
    "check": {"tokens": 40, "max_requests": 4, "gap_block": 8},
    # float32 on both sides: sound runs read 0 (CPU)
    "limits": {"max_logit_gap": 0.01},
}
TINY_NOTES = {
    "kind": "open_loop", "shape_seed": 5, "rate_rps": 6.0, "ramp_s": 1.5,
    "drain_s": 30, "tenants": 2, "shared_prefix_len": 32,
    "prompt_tokens": {"median": 38, "sigma": 0.05, "min": 34, "max": 48},
    "output_tokens": {"median": 10, "sigma": 0.3, "min": 4, "max": 16},
    "trace_after_s": 0.5, "trace_s": 0.5,
}
CELL = {"name": "tiny_notes1", "config": "tiny_dots3", "traffic": "tiny_notes",
        "chips": 1, "why": "tests only"}
LISTED = [(m, "tiny_notes1") for m in (
    "serve.tpot_p50_ms", "serve.mfu", "serve.device_idle",
    "serve.tick_host_ms", "serve.decode_step_ms", "serve.prefill_ms",
    "serve.warmup_s", "serve.moe_ms", "serve.sparse_attn_ms",
    "serve.moe_imbalance", "serve.prefix_hit_share",
    "serve.live_context_tokens", "serve.window_attn_ms")]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_checkout(
        str(tmp_path_factory.mktemp("dots3")), configs=[TINY_DOTS3],
        traffic=[("tiny_notes", TINY_NOTES)], cells=[CELL],
        end_to_end_cells=[("serve.tpot_p95_ms", "tiny_notes1")], listed=LISTED)


def test_the_cells_files_run_through_run_py(checkout):
    rc, result, out, err = rehearsal.run_cell(
        checkout, "tiny_notes1", seed=2147490037, seconds=1.5, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 3
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["serve.moe_imbalance"] >= 1.0
    assert 60 < metrics["serve.prefix_hit_share"] <= 100 * 32 / 34
    assert metrics["serve.live_context_tokens"] > 0
    # no device trace on the CPU: the trace's readers say nothing
    for name in ("serve.moe_ms", "serve.sparse_attn_ms", "serve.window_attn_ms"):
        assert name not in metrics
    assert result["checks"]["max_logit_gap"]["value"] <= 0.01
    assert '"window_row_gathers": 1' in out and '"latent_row_gathers": 2' in out
    assert '"window_rows_per_slot": 9' in out
