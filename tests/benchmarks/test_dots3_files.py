"""The files of `dots3_notes_decode`: the configuration against its source
and against the program's preset, its `reduced`, `published` and
`assumed`, the family file's counts on hand-worked cases, and the mix
file's lengths."""

import importlib
import json
import os
import types

import pytest

from harness import traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
# the source's published config.json, as name, URL and config
PUBLISHED = os.path.join(os.path.dirname(__file__), "dots3_published.json")

fam = importlib.import_module("families.dots3_note")


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = load("configs", "dots3_share8")
MIX = load("traffic", "notes_long_decode")
REDUCED = {"num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size", "max_position_embeddings"}

# a layer's attention, by hand: q_a, q_b, kv_a (latent + rotary key),
# kv_b (key and value halves), o, the gate
FULL = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 128 * 128 * 5120 + 5120 * 128)
INDEXER = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
WINDOW = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
          + 64 * 128 * 5120 + 5120 * 64)
DENSE = 3 * 5120 * 13824
EXPERT = 3 * 5120 * 1536
SPARSE = 5120 * 256 + EXPERT + (8 * 32 / 256) * EXPERT   # router, shared, 1 held
HEAD = 2 * 5120 * 19008


# ------------------------------------------------------------ configuration


def test_configuration_keeps_the_published_widths():
    with open(PUBLISHED) as f:
        row = json.load(f)
    assert row["name"] == "dots3-note-prev"
    assert CONFIG["source"] == row["source_url"]
    reduced = set(CONFIG["reduced"])
    assert reduced == REDUCED
    for key, value in row["config"].items():
        if key in reduced:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    published = CONFIG["published"]
    assert set(published) == reduced
    for key in ("num_hidden_layers", "n_routed_experts", "vocab_size",
                "max_position_embeddings"):
        assert published[key] == row["config"][key], key
    # no width among them
    assert not [k for k in reduced if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # floors: the leading dense layer and a whole period of (full, sliding
    # x3), four layers after the dense one, 8 experts or more, an eighth
    # of the vocabulary
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:5]
    assert CONFIG["layer_types"][1:] == (
        ["full_attention"] + ["sliding_attention"] * 3)
    assert CONFIG["num_hidden_layers"] == 5 and CONFIG["first_k_dense_replace"] == 1
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["max_position_embeddings"] == CONFIG["model"]["cache_len"]


def test_assumed_names_every_convention_the_config_leaves_open():
    assumed = CONFIG["assumed"]
    for key in ("window", "gate", "lora_rescale", "indexer", "rope",
                "weights", "spreads", "routing", "towers"):
        assert assumed.get(key), key
    assert "513" in assumed["window"] and "t - 512" in assumed["window"]
    assert "2505.06708" in assumed["gate"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8


def test_model_group_and_program_preset_are_the_same_sizes():
    from pytorch_distributed_training_tpu.utils.config import model_preset

    model = CONFIG["model"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rope_theta", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "index_n_heads",
                "index_head_dim", "index_topk", "layer_types", "vocab_size",
                "rms_norm_eps", "swa_num_attention_heads", "swa_q_lora_rank",
                "swa_kv_lora_rank", "swa_qk_nope_head_dim",
                "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
                "attention_gate_type", "swa_attention_gate_type"):
        assert model[key] == CONFIG[key], key
    assert model["sliding_window_size"] == CONFIG["sliding_window_size"] == 513
    assert model["router_experts"] == CONFIG["published"]["n_routed_experts"]
    assert model["experts_held"] == [0, CONFIG["n_routed_experts"]]
    preset = model_preset(CONFIG["argv"][CONFIG["argv"].index("--model") + 1])
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rope_theta", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "index_n_heads",
                "index_head_dim", "index_topk", "vocab_size", "rms_norm_eps",
                "sliding_window_size", "swa_num_attention_heads",
                "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim",
                "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
                "attention_gate_type", "swa_attention_gate_type"):
        assert getattr(preset, key) == model[key], key
    assert preset.n_routed_experts == model["router_experts"]
    assert list(preset.experts_held) == model["experts_held"]
    assert preset.expert_block == model["expert_block"]
    assert list(preset.mlp_layer_types) == model["mlp_layer_types"]
    assert [{"full": "full_attention", "window": "sliding_attention"}[t]
            for t in preset.indexer_types] == model["layer_types"]
    assert preset.max_position_embeddings == CONFIG["published"]["max_position_embeddings"]
    serving, argv = CONFIG["serving"], CONFIG["argv"]
    assert preset.latent_row == serving["latent_row_padded"] == 640
    assert preset.window_row == serving["window_row_padded"] == 1152
    assert model["cache_len"] == (
        serving["prompt_buckets"][-1] + serving["max_new_tokens_cap"])
    for flag, value in (("--num-slots", serving["num_slots"]),
                        ("--num-pages", serving["num_pages"]),
                        ("--prefill-chunk", serving["prefill_chunk"]),
                        ("--page-size", serving["page_size"]),
                        ("--max-new-tokens-cap", serving["max_new_tokens_cap"])):
        assert argv[argv.index(flag) + 1] == str(value)
    assert "--prefix-cache" in argv and "--warmup" in argv
    assert argv[argv.index("--weights-dtype") + 1] == "bfloat16"
    # the pool holds what the mix keeps: 4 shared prefixes, 64 private tails
    page = serving["page_size"]
    private = -(-(model["cache_len"] - MIX["shared_prefix_len"]) // page)
    assert serving["num_pages"] == (
        MIX["tenants"] * MIX["shared_prefix_len"] // page
        + serving["num_slots"] * private + 1) == 14337
    # 9,984 B a token in the program's padded rows: 2.290 GB of pools
    assert preset.cache_values_per_token() * 2 == 9984
    assert 2.28e9 < 9984 * serving["num_pages"] * page < 2.30e9


def test_weight_spec_is_per_layer_and_fits_the_install():
    from harness import adapters, weights

    spec = importlib.import_module("reference.dots3_share8").weight_spec(
        CONFIG["model"])
    assert "layers.0.index_q" in spec and "layers.1.index_q" in spec
    assert "layers.2.index_q" not in spec and "layers.4.gate" in spec
    assert spec["layers.0.gate"][0] == (5120, 128)
    assert spec["layers.3.gate"][0] == (5120, 64)
    assert spec["layers.3.kv_b_k"][0] == (1024, 64 * 192)
    assert "layers.0.mlp_gate" in spec and "layers.0.router" not in spec
    assert "layers.4.experts_gate.3" in spec and "layers.4.experts_gate.4" not in spec
    assert max(weights.nbytes(spec, n) for n in spec) <= adapters.INSTALL_GROUP_BYTES
    total = sum(weights.nbytes(spec, n) for n in spec) / 4
    # 4.087 G parameters: 8.174 GB in bfloat16
    assert 4.08e9 < total < 4.10e9
    assert {kind for _, kind in spec.values()} == {
        "router_bias", "window_qk", "normal", "scale"}
    # only a window layer's query and key paths are drawn wider
    wide = {n for n, (_, kind) in spec.items() if kind == "window_qk"}
    assert wide == {f"layers.{i}.{leaf}" for i in (2, 3, 4)
                    for leaf in ("q_b", "kv_a_rope", "kv_b_k")}


# ------------------------------------------------------------------- counts


def test_decode_flops_by_hand():
    def by_hand(context):
        layers = 2 * (2 * FULL + 2 * INDEXER + 3 * WINDOW + DENSE + 4 * SPARSE)
        attend = (2 * 2 * 128 * min(2048, context) * (576 + 512)
                  + 3 * 2 * 64 * min(513, context) * (1088 + 1024))
        index = 2 * 2 * 64 * 128 * context
        return layers + attend + index + HEAD

    for context in (100, 513, 2048, 18000):
        assert fam.decode_flops(CONFIG, context) == pytest.approx(by_hand(context))
    # past index_topk only the indexers grow
    grow = fam.decode_flops(CONFIG, 18000) - fam.decode_flops(CONFIG, 10000)
    assert grow == pytest.approx(2 * 2 * 64 * 128 * 8000)


def test_cache_counts_by_hand():
    assert fam.cache_bytes_per_token(CONFIG) == (
        2 * (576 + 128) + 3 * 1088) * 2 == 9344
    assert fam.cache_read_bytes(CONFIG, [18000, 100]) == 2 * (
        (2 * (128 * 18000 + 576 * 2048) + 3 * 1088 * 513)
        + (2 * (128 * 100 + 576 * 100) + 3 * 1088 * 100))


def test_prefix_hits_are_not_counted_as_prefill():
    request = types.SimpleNamespace(prefix_len=16384)
    hit = {"request": request, "engine": {"prefix_cache": {"prefix_hits": 40}}}
    tail = 16512 - 16384
    want = tail * fam.token_flops(CONFIG, 16384 + (tail + 1) / 2) + HEAD
    assert fam.prefill_flops(CONFIG, 16512, hit) == pytest.approx(want)
    whole = 16512 * fam.token_flops(CONFIG, (16512 + 1) / 2) + HEAD
    assert fam.prefill_flops(CONFIG, 16512) == pytest.approx(whole)
    assert fam.prefill_flops(CONFIG, 16512, hit) < 0.02 * whole


# ---------------------------------------------------------------------- mix


@pytest.mark.parametrize("seed", [1, 37, 2147491234])
def test_every_request_is_a_short_turn_on_a_shared_document(seed):
    requests = traffic.schedule(MIX, seed, MIX["ramp_s"] + 30)
    assert len(requests) > 30
    rows = CONFIG["model"]["vocab_size"]
    bucket = CONFIG["serving"]["prompt_buckets"][-1]
    prefixes = set()
    for r in requests:
        assert r.prefix_len == MIX["shared_prefix_len"] == 16384
        assert 16 <= r.prompt_len - r.prefix_len <= 512
        assert r.prompt_len <= bucket
        assert 1024 <= r.max_new_tokens <= CONFIG["serving"]["max_new_tokens_cap"]
        assert max(map(ord, r.prompt)) < rows
        prefixes.add(r.prompt[: r.prefix_len])
    assert len(prefixes) == MIX["tenants"] == 4
    assert MIX["kind"] == "open_loop" and "bursts" not in MIX
    assert MIX["output_tokens"]["median"] == 1536
    assert MIX["output_tokens"]["sigma"] == 0.2
    assert MIX["prompt_tokens"]["median"] - MIX["shared_prefix_len"] == 128
    assert (MIX["trace_after_s"], MIX["trace_s"]) == (3, 2)
    assert MIX["rate_rps"] == pytest.approx(0.6 * MIX["knee_rps"], abs=0.005)
    assert MIX["check"] == {k: CONFIG["check"][k] for k in ("tokens", "max_requests")}


def test_the_limit_lies_between_its_two_readings_with_room():
    """The readings `limits_from` records (one v5e chip): the largest of 5
    sound runs and the smallest reading of each control over two seeds, by
    the judged number (the widest mean gap over `check.gap_block` served
    tokens): both controls lie 3 times over the limit, the sound runs 3
    times under it."""
    sound, int8, window_all = 0.007922, 0.104326, 0.156861
    limit = CONFIG["limits"]["max_logit_gap"]
    assert 3 * sound <= limit <= min(int8, window_all) / 3
    for reading in ("0.007922", "0.104326", "0.156861"):
        assert reading in CONFIG["limits_from"]
    assert CONFIG["check"]["gap_block"] == 256
    assert CONFIG["control"]["reference_precision"] == "int8,window_all"
