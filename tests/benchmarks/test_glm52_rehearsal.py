"""A CPU rehearsal of `glm52_agent_decode`'s files through
`benchmarks/run.py` at a tiny size, as `rehearsal.py` does for the two
older cells: the same driver, reference, family file, readers and kind of
mix (tenants sharing a prefix, served by `--prefix-cache` and
`--prefill-chunk`), added to a copy of the benchmark as files and entries
only."""

import pytest
import rehearsal
from test_glm52_files import CONFIG

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "router_experts": 8, "experts_held": [0, 2], "expert_block": 1,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 8,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "indexer_types": ["full", "shared", "full", "shared"],
    "vocab_size": 512,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "index_norm_eps": 1e-6, "cache_len": 64,
}
TINY_LATENT = {
    "name": "tiny_latent", "rehearsal": True, "source": "tests only",
    "driver": "serve_driver", "reference": CONFIG["reference"],
    "adapter": CONFIG["adapter"], "weights": {"std": 0.2}, "model": MODEL,
    "serving": {"page_size": 4},
    "argv": ["--model", "latent-moe-tiny", "--warmup", "--num-slots", "4",
             "--prompt-buckets", "48", "--max-new-tokens-cap", "16",
             "--queue-depth", "256", "--page-size", "4", "--num-pages", "96",
             "--prefix-cache", "--prefill-chunk", "8",
             "--stall-timeout-s", "120"],
    "control": {"argv": [], "reference_precision": "int8,select_latest"},
    "check": {"tokens": 40, "max_requests": 4, "gap_block": 8},
    # float32 on both sides: sound runs read 0 (CPU); the controls 1 to 6
    "limits": {"max_logit_gap": 0.01},
}
TINY_AGENT = {
    "kind": "open_loop", "shape_seed": 5, "rate_rps": 6.0, "ramp_s": 1.5,
    "drain_s": 30, "tenants": 2, "shared_prefix_len": 32,
    "prompt_tokens": {"median": 38, "sigma": 0.05, "min": 34, "max": 48},
    "output_tokens": {"median": 10, "sigma": 0.3, "min": 4, "max": 16},
    "trace_after_s": 0.5, "trace_s": 0.5,
}
CELL = {"name": "tiny_agent1", "config": "tiny_latent", "traffic": "tiny_agent",
        "chips": 1, "why": "tests only"}
LISTED = [(m, "tiny_agent1") for m in (
    "serve.tpot_p50_ms", "serve.mfu", "serve.device_idle",
    "serve.tick_host_ms", "serve.decode_step_ms", "serve.prefill_ms",
    "serve.warmup_s", "serve.moe_ms", "serve.sparse_attn_ms",
    "serve.moe_imbalance", "serve.prefix_hit_share")]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_checkout(
        str(tmp_path_factory.mktemp("glm52")), configs=[TINY_LATENT],
        traffic=[("tiny_agent", TINY_AGENT)], cells=[CELL],
        end_to_end_cells=[("serve.tpot_p95_ms", "tiny_agent1")], listed=LISTED)


def test_the_cells_files_run_through_run_py(checkout):
    rc, result, out, err = rehearsal.run_cell(
        checkout, "tiny_agent1", seed=2147490007, seconds=1.5, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 3
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # what the program's own records feed reads on the CPU too
    assert metrics["serve.moe_imbalance"] >= 1.0
    # 32 of some 38 prompt tokens come from cached pages once a tenant's
    # first request has prefilled them
    assert 60 < metrics["serve.prefix_hit_share"] <= 100 * 32 / 34
    assert metrics["serve.decode_step_ms"] > 0 and metrics["serve.prefill_ms"] > 0
    # no device trace on the CPU: the trace's readers say nothing, the
    # line leaves them out and nothing raises
    assert "serve.moe_ms" not in metrics and "serve.sparse_attn_ms" not in metrics
    assert result["checks"]["max_logit_gap"]["value"] <= 0.01
    assert '"kv_pool_relayout_ops": 0' in out


def test_an_unknown_workload_exits_at_once_naming_it(checkout):
    rc, result, out, err = rehearsal.run_cell(checkout, "glm52_no_such_cell")
    assert rc == 2 and result is None
    assert "glm52_no_such_cell" in err and "not in BENCHMARK.json" in err
