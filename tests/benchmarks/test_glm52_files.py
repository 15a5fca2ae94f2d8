"""The files of `glm52_agent_decode`: the configuration against its source
and against the program's preset, the family file's counts on hand-worked
cases, the mix file's lengths, and the four new per-layer readers on
synthetic records and a synthetic trace."""

import importlib
import importlib.util
import json
import os
import types

import pytest

from harness import step_phases, traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

fam = importlib.import_module("families.glm_moe_dsa")


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def reader(metric):
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"),
        os.path.join(BENCH, "metrics", metric + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


CONFIG = load("configs", "glm52_share16")
MIX = load("traffic", "agent_long_decode")

# one layer's attention, by hand: q_a, q_b, kv_a (latent + rotary key),
# kv_b (key and value halves), o
ATTN = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 + 64 * 256 * 6144
INDEXER = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
DENSE = 3 * 6144 * 12288
EXPERT = 3 * 6144 * 2048
SPARSE = 6144 * 256 + EXPERT + (8 * 16 / 256) * EXPERT   # router, shared, 0.5 held
HEAD = 2 * 6144 * 19360


# ------------------------------------------------------------ configuration


def test_configuration_keeps_the_published_widths():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "GLM-5.2"]
    assert CONFIG["source"] == row["source_url"]
    reduced = set(CONFIG["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert CONFIG[key] != value and key in CONFIG["published"]
        else:
            assert CONFIG[key] == value, key
    assert reduced == {
        "num_hidden_layers", "mlp_layer_types", "indexer_types",
        "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers", "max_position_embeddings"}
    # no width among them
    assert not [k for k in reduced if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # floors: a whole period and four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 5
    assert CONFIG["indexer_types"][:4] == ["full", "shared", "shared", "shared"]
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the published layers 2..7, in the published order
    assert CONFIG["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:8]
    assert CONFIG["indexer_types"] == row["config"]["indexer_types"][2:8]


def test_model_group_and_program_preset_are_the_same_sizes():
    from pytorch_distributed_training_tpu.utils.config import model_preset

    model = CONFIG["model"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "index_n_heads", "index_head_dim",
                "index_topk", "mlp_layer_types", "indexer_types", "vocab_size",
                "rope_parameters", "rms_norm_eps"):
        assert model[key] == CONFIG[key], key
    assert model["router_experts"] == CONFIG["published"]["n_routed_experts"] == 256
    assert model["experts_held"] == [0, CONFIG["n_routed_experts"]]
    preset = model_preset(CONFIG["argv"][CONFIG["argv"].index("--model") + 1])
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "index_n_heads", "index_head_dim",
                "index_topk", "vocab_size", "rms_norm_eps"):
        assert getattr(preset, key) == model[key], key
    assert preset.n_routed_experts == model["router_experts"]
    assert list(preset.experts_held) == model["experts_held"]
    assert preset.expert_block == model["expert_block"]
    assert list(preset.mlp_layer_types) == model["mlp_layer_types"]
    assert list(preset.indexer_types) == model["indexer_types"]
    assert preset.rope_theta == model["rope_parameters"]["rope_theta"]
    assert preset.max_position_embeddings == CONFIG["published"]["max_position_embeddings"]
    serving, argv = CONFIG["serving"], CONFIG["argv"]
    assert preset.latent_row == serving["latent_row_padded"] == 640
    assert model["cache_len"] == (
        serving["prompt_buckets"][-1] + serving["max_new_tokens_cap"]
    ) == CONFIG["max_position_embeddings"]
    for flag, value in (("--num-slots", serving["num_slots"]),
                        ("--num-pages", serving["num_pages"]),
                        ("--prefill-chunk", serving["prefill_chunk"]),
                        ("--page-size", serving["page_size"]),
                        ("--max-new-tokens-cap", serving["max_new_tokens_cap"])):
        assert argv[argv.index(flag) + 1] == str(value)
    assert "--prefix-cache" in argv
    assert argv[argv.index("--weights-dtype") + 1] == "bfloat16"
    # the pool holds what the mix keeps: 4 shared prefixes, 48 private tails
    page = serving["page_size"]
    private = -(-(serving["prompt_buckets"][-1] + serving["max_new_tokens_cap"]
                  - MIX["shared_prefix_len"]) // page)
    assert serving["num_pages"] == (
        MIX["tenants"] * MIX["shared_prefix_len"] // page
        + serving["num_slots"] * private + 1)


def test_weight_spec_is_per_layer_and_fits_the_install():
    from harness import adapters, weights

    spec = importlib.import_module("reference.glm52_share16").weight_spec(
        CONFIG["model"])
    assert "layers.3.experts_gate.1" in spec and "layers.1.index_q" not in spec
    assert "layers.0.index_q" in spec and "layers.4.index_q" in spec
    assert "layers.0.mlp_gate" in spec and "layers.0.router" not in spec
    assert max(weights.nbytes(spec, n) for n in spec) <= adapters.INSTALL_GROUP_BYTES
    total = sum(weights.nbytes(spec, n) for n in spec) / 4
    # 9.4 GB in bfloat16
    assert 4.68e9 < total < 4.70e9
    kinds = {kind for _, kind in spec.values()}
    assert kinds == {"router_bias", "normal", "scale"}


def test_the_limit_lies_between_its_two_readings_with_room():
    """The readings `limits_from` records (chip, PR 29): the largest sound
    run and the smallest int8 control, by the judged number (the widest
    mean gap over `check.gap_block` served tokens)."""
    sound, control = 0.01432, 0.14464
    limit = CONFIG["limits"]["max_logit_gap"]
    assert 3 * sound <= limit <= control / 3
    for reading in ("0.01432", "0.14464"):
        assert reading in CONFIG["limits_from"]
    assert CONFIG["check"]["gap_block"] == 256
    assert "int8" in CONFIG["control"]["reference_precision"]
    # the mix samples no more than the configuration reckons with
    assert MIX["check"] == {k: CONFIG["check"][k] for k in ("tokens", "max_requests")}


# ------------------------------------------------------------------- counts


def test_decode_flops_by_hand():
    def by_hand(context):
        selected = min(2048, context)
        layers = 2 * (6 * ATTN + 2 * INDEXER + DENSE + 5 * SPARSE)
        attend = 6 * 2 * 64 * selected * (576 + 512)
        index = 2 * 2 * 32 * 128 * context
        return layers + attend + index + HEAD

    for context in (100, 2048, 18000):
        assert fam.decode_flops(CONFIG, context) == pytest.approx(by_hand(context))
    # the selected latents are capped at index_topk, the indexer is not
    grow = fam.decode_flops(CONFIG, 18000) - fam.decode_flops(CONFIG, 10000)
    assert grow == pytest.approx(2 * 2 * 32 * 128 * 8000)
    # a token meets half a routed expert here, not eight and not sixteen
    one_layer = 2 * SPARSE
    assert one_layer == pytest.approx(2 * (6144 * 256 + 1.5 * EXPERT))


def test_indexer_counts_in_full_layers_only():
    shared = json.loads(json.dumps(CONFIG))
    shared["model"]["indexer_types"] = ["full"] + ["shared"] * 5
    less = fam.decode_flops(CONFIG, 4096) - fam.decode_flops(shared, 4096)
    assert less == pytest.approx(2 * INDEXER + 2 * 32 * 128 * 4096)
    assert fam.cache_bytes_per_token(CONFIG) == (6 * 576 + 2 * 128) * 2 == 7424
    assert fam.cache_bytes_per_token(shared) == (6 * 576 + 128) * 2
    assert fam.cache_read_bytes(CONFIG, [18000, 100]) == 2 * (
        (2 * 128 * 18000 + 6 * 576 * 2048) + (2 * 128 * 100 + 6 * 576 * 100))


def test_prefix_hits_are_not_counted_as_prefill():
    request = types.SimpleNamespace(prefix_len=16384)
    hit = {"request": request, "engine": {"prefix_cache": {"prefix_hits": 40}}}
    cold = {"request": request, "engine": {"prefix_cache": {"prefix_hits": 0}}}
    tail = 16512 - 16384
    want = tail * fam.token_flops(CONFIG, 16384 + (tail + 1) / 2) + HEAD
    assert fam.prefill_flops(CONFIG, 16512, hit) == pytest.approx(want)
    whole = 16512 * fam.token_flops(CONFIG, (16512 + 1) / 2) + HEAD
    assert fam.prefill_flops(CONFIG, 16512, cold) == pytest.approx(whole)
    assert fam.prefill_flops(CONFIG, 16512) == pytest.approx(whole)
    assert fam.prefill_flops(CONFIG, 16512, hit) < 0.02 * whole
    # a prompt that IS its prefix still prefills its last token
    assert fam.cached_prompt_tokens(CONFIG, 16384, hit) == 16368


# ---------------------------------------------------------------------- mix


@pytest.mark.parametrize("seed", [1, 29, 2147491234])
def test_every_request_is_a_short_turn_on_a_shared_context(seed):
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
    assert MIX["rate_rps"] <= 0.6 * MIX["knee_rps"] + 1e-9


# ------------------------------------------------------------------ readers


def tick(**attrs):
    return {"record": "serve_tick", "decode_active": 3, "t0_s": 0.0,
            "t1_s": 0.02, "phases": [], **attrs}


def test_moe_imbalance_is_the_median_of_max_over_mean():
    read = reader("serve.moe_imbalance")
    records = [tick(expert_tokens_max=4, expert_tokens_mean=2.0),
               tick(expert_tokens_max=3, expert_tokens_mean=1.0),
               tick(expert_tokens_max=9, expert_tokens_mean=1.0),
               tick(expert_tokens_max=0, expert_tokens_mean=0.0),   # routed none
               {"record": "serve_tick", "decode_active": 0}]
    assert read({"records": records}) == 3.0
    assert read({"records": [tick()]}) is None       # the parent's records
    assert read({"records": []}) is None


def test_prefix_hit_share_is_of_the_windows_requests():
    read = reader("serve.prefix_hit_share")

    def request(i, cached, prompt=16512):
        return {"record": "serve_request", "id": f"q{i}", "prompt_len": prompt,
                "cached_tokens": cached}

    records = [request(0, 0), request(1, 0), request(10, 16384),
               request(2, 16384), request(11, 16384, 16484)]
    # two requests were due in the window: the last two by number
    obs = {"records": records, "ttft_s": [0.1, 0.2]}
    assert read(obs) == pytest.approx(100 * 2 * 16384 / (16512 + 16484))
    assert read({"records": records, "ttft_s": [0.1] * 5}) == pytest.approx(
        100 * 3 * 16384 / (4 * 16512 + 16484))
    assert read({"records": [{"record": "serve_request", "id": "q0",
                              "prompt_len": 5}], "ttft_s": [0.1]}) is None
    assert read({"records": [], "ttft_s": []}) is None


PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_chunk(2)", 0, 500), ("jit_decode(1)", 1000, 1000),
                        ("jit_chunk(2)", 3000, 1000), ("jit_decode(1)", 5000, 1000),
                        ("jit_decode(1)", 7000, 400)],   # cut by the trace's end
        "XLA Ops": [
            ("%sort.1 = f32[48] sort(%x)", 100, 300),          # another program
            ("%sort.1 = f32[48] sort(%x)", 1100, 100),
            ("%copy-start.2 = (bf16[8], bf16[8]) copy-start(%p)", 1210, 20),
            ("%fusion.7 = bf16[8] fusion(%y)", 1300, 200),
            ("%copy-done.2 = bf16[8] copy-done(%copy-start.2)", 1400, 250),
            ("%fusion.9 = bf16[8] fusion(%y)", 1600, 50),      # no scope
            ("%sort.1 = f32[48] sort(%x)", 3100, 400),         # another program
            ("%sort.1 = f32[48] sort(%x)", 5100, 300),
            ("%fusion.7 = bf16[8] fusion(%y)", 5500, 200),
            ("%fusion.8 = bf16[8] fusion(%y)", 5600, 200),     # laps fusion.7
            ("%sort.1 = f32[48] sort(%x)", 7100, 250),         # the cut run's
        ],
    },
}
SCOPES = {"record": "program_scopes", "name": "serve_decode", "scopes": {
    "sparse_attn.topk": ["sort.1"], "sparse_attn.gather": [],
    "moe": ["fusion.7", "fusion.8"]}}


def test_step_phases_reads_a_scope_per_decode_step(monkeypatch):
    names = step_phases.scope_names([SCOPES], "serve_decode", ("sparse_attn",))
    assert names == {"sort.1"}
    moe = step_phases.scope_names([SCOPES], "serve_decode", ("moe",))
    assert step_phases.scope_names([SCOPES], "serve_chunk", ("moe",)) == set()
    # two whole decode steps (the modules' line's last event is left out,
    # an asynchronous copy's two halves are no work): (100 + 300) / 2 ns
    # of top-k; (200 + 300) / 2 of moe, whose two fusions lap by 100 ns;
    # 50 / 2 under no scope; and the parts add up to the device's busy time
    parts = step_phases.breakdown(
        PLANES, "jit_decode", {"sparse_attn": names, "moe": moe})
    assert parts["steps"] == 2
    assert parts["sparse_attn"] == pytest.approx(200e-6)
    assert parts["moe"] == pytest.approx(250e-6)
    assert parts["unscoped"] == pytest.approx(25e-6)
    assert parts["busy"] == pytest.approx(
        parts["sparse_attn"] + parts["moe"] + parts["unscoped"])
    assert step_phases.breakdown(PLANES, "jit_verify", {"moe": moe}) is None
    assert step_phases.breakdown(None, "jit_decode", {"moe": moe}) is None
    monkeypatch.setattr(step_phases, "planes", lambda: PLANES)
    obs = {"trace": {"window_s": 1.0}, "records": [SCOPES]}
    assert reader("serve.sparse_attn_ms")(obs) == pytest.approx(200e-6)
    assert reader("serve.moe_ms")(obs) == pytest.approx(250e-6)
    # the parent: a trace, and no record of scopes; and no trace at all
    assert reader("serve.moe_ms")({"trace": {"window_s": 1.0}, "records": []}) is None
    assert reader("serve.sparse_attn_ms")({"trace": None, "records": [SCOPES]}) is None
    # a scope the trace holds no operation of reads nothing, not nought
    none = dict(SCOPES, scopes={"moe": ["fusion.99"]})
    assert reader("serve.moe_ms")({"trace": {"window_s": 1.0}, "records": [none]}) is None


def test_step_phases_finds_no_trace_quietly(tmp_path):
    assert step_phases.planes(str(tmp_path)) is None
