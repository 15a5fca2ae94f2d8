"""The files of `phi4flash_reason_decode`: the configuration against its
source and against the program's preset, the weight spec under the
install's cap, the family file's counts on hand-worked cases, the mix
file's shapes, and the five new per-layer readers on hand-made records and
a hand-made trace."""

import importlib
import importlib.util
import json
import os

import pytest

from harness import step_phases, traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

fam = importlib.import_module("families.phi4flash")


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


CONFIG = load("configs", "phi4_mini_flash")
MIX = load("traffic", "reason_long_decode")

# by hand, parameters a layer (weights a token is multiplied with)
H, F, DI = 2560, 10240, 5120
MLP = 3 * H * F
MAMBA = H * 2 * DI + 4 * DI + DI * (160 + 32) + 160 * DI + DI * H
ATTN = 2 * H * H + 2 * H * 1280          # q, o; k, v
CROSS = 2 * H * H
GMU = 2 * H * DI
HEAD = 2 * H * 200064


# ------------------------------------------------------------ configuration


def test_configuration_keeps_every_published_number_but_the_context():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Phi-4-mini-flash-reasoning"]
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["max_position_embeddings"]
    for key, value in row["config"].items():
        if key == "max_position_embeddings":
            assert CONFIG[key] == 4608 != value == CONFIG["published"][key]
        else:
            assert CONFIG[key] == value, key
    model = CONFIG["model"]
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "sliding_window",
                "mb_per_layer", "layer_norm_eps", "vocab_size"):
        assert model[key] == CONFIG[key] == row["config"][key], key
    # every size the catalog's config lacks is said to be assumed
    for key, said in (("mamba_d_state", "d_state 16"), ("mamba_d_conv", "d_conv 4"),
                      ("mamba_expand", "expand 2"), ("mamba_dt_rank", "= 160")):
        assert key not in row["config"] and said in CONFIG["assumed"]["mamba"]
    assert set(CONFIG["assumed"]) == {
        "mamba", "attention", "layers", "mlp", "embedding", "weights",
        "tokenizer"}


def test_model_group_and_program_preset_are_the_same_sizes():
    from pytorch_distributed_training_tpu.utils.config import model_preset

    model, serving, argv = CONFIG["model"], CONFIG["serving"], CONFIG["argv"]
    preset = model_preset(argv[argv.index("--model") + 1])
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "sliding_window",
                "mb_per_layer", "layer_norm_eps", "vocab_size", "vocab_blocks",
                "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"):
        assert getattr(preset, key) == model[key], key
    assert preset.max_position_embeddings == CONFIG["published"][
        "max_position_embeddings"]
    assert list(preset.layer_kinds) == fam.layer_kinds(model)
    assert [fam.layer_kinds(model).count(k) for k in (
        "mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert model["cache_len"] == (
        serving["prompt_buckets"][-1] + serving["max_new_tokens_cap"]
    ) == CONFIG["max_position_embeddings"]
    for flag, value in (("--num-slots", serving["num_slots"]),
                        ("--num-pages", serving["num_pages"]),
                        ("--prefill-chunk", serving["prefill_chunk"]),
                        ("--page-size", serving["page_size"]),
                        ("--max-new-tokens-cap", serving["max_new_tokens_cap"])):
        assert argv[argv.index(flag) + 1] == str(value)
    assert "--prefix-cache" not in argv and "--spec-k" not in argv
    assert argv[argv.index("--weights-dtype") + 1] == "bfloat16"
    # ONE pool's worst case a slot, and the null page
    assert serving["num_pages"] == (
        serving["num_slots"] * model["cache_len"] // serving["page_size"] + 1)


def test_weight_spec_is_per_layer_and_fits_the_install():
    from harness import adapters, weights

    spec = importlib.import_module("reference.phi4_mini_flash").weight_spec(
        CONFIG["model"])
    assert "layers.16.A_log" in spec and "layers.17.k_w" in spec
    assert "layers.19.q_w" in spec and "layers.19.k_w" not in spec
    assert "layers.18.in_proj" in spec and "layers.18.q_w" not in spec
    assert "embed.3" in spec and "embed" not in spec and "head" not in spec
    assert max(weights.nbytes(spec, n) for n in spec) <= adapters.INSTALL_GROUP_BYTES
    total = sum(weights.nbytes(spec, n) for n in spec) / 4
    # 3.852 G parameters: 7.70 GB in bfloat16
    assert 3.84e9 < total < 3.86e9
    kinds = {kind for _, kind in spec.values()}
    assert kinds == {"normal", "scale", "conv", "x_proj", "dt_bias", "a_log",
                     "lambda"}
    by_kind = {kind: fam.init(kind, __import__("jax").random.key(0), shape, 0.02)
               for kind, shape in (("conv", (4, 64)), ("x_proj", (64, 24)),
                                   ("dt_bias", (512,)), ("a_log", (8, 16)),
                                   ("lambda", (64,)))}
    import jax

    step = jax.nn.softplus(by_kind["dt_bias"])
    assert 1e-3 * 0.99 <= float(step.min()) and float(step.max()) <= 1e-1 * 1.01
    assert abs(float(by_kind["a_log"][0, 15]) - 2.7726) < 0.1
    # every program leaf has a reference leaf
    from pytorch_distributed_training_tpu.models import sambay
    from pytorch_distributed_training_tpu.utils.config import model_preset

    shapes = jax.eval_shape(lambda: sambay.SambaYLM(
        model_preset("phi-4-mini-flash")).init(
        jax.random.key(0), jax.numpy.ones((1, 8), "int32")))["params"]
    names = {adapters.leaf_name(path, fam) for path in adapters.flat(shapes)}
    assert names == set(spec)


def test_the_limit_lies_between_its_readings_with_room():
    """The readings `limits_from` records (chip): the largest sound run
    and the smallest control, the limit their geometric middle."""
    limit = CONFIG["limits"]["max_logit_gap"]
    readings = CONFIG["limits_readings"]
    sound, control = max(readings["sound"]), min(
        min(v) for v in readings["controls"].values())
    assert len(readings["sound"]) >= 7
    assert set(readings["controls"]) >= {"int8", "window_all"}
    assert 2 * sound <= limit <= control / 2
    assert abs(limit - (sound * control) ** 0.5) / limit < 0.15
    for reading in (sound, control):
        assert f"{reading:.4f}".rstrip("0") in CONFIG["limits_from"]
    assert "int8" in CONFIG["control"]["reference_precision"]
    assert MIX["check"] == CONFIG["check"]


@pytest.mark.parametrize("gaps, judged", [
    # one token a whole logit off among 4,095 sound ones: a 256th of it
    ([0.0] * 1000 + [1.0] + [0.0] * 3095, 1.0 / 256),
    # one block of 256 that lost the reference's stream
    ([0.0] * 512 + [3.0] * 256 + [0.0] * 512, 3.0),
    # a tail shorter than half a block joins the block before it
    ([0.0] * 256 + [1.0] * 100, 100.0 / 356),
    # a request shorter than a block is one block
    ([0.5] * 40, 0.5),
], ids=["one_wide_gap", "a_lost_block", "short_tail", "short_request"])
def test_what_is_judged_is_the_widest_mean_over_a_block_of_served_tokens(
        gaps, judged):
    import numpy as np

    ref = importlib.import_module("reference.phi4_mini_flash")
    assert ref.GAP_BLOCK == 256
    quiet = np.zeros(300)
    summary = ref._summary([quiet, np.asarray(gaps)])
    assert summary["judged"] == pytest.approx(judged)
    assert summary["max"] == max(gaps) and summary["tokens"] == 300 + len(gaps)
    assert "gap_block" not in CONFIG["check"] and "gap_block" not in MIX["check"]


# ------------------------------------------------------------------- counts


def test_decode_flops_by_hand():
    def by_hand(context):
        layers = 2 * (9 * MAMBA + 9 * ATTN + 7 * CROSS + 7 * GMU + 32 * MLP)
        scan = 9 * 6 * DI * 16
        attend = 2 * 40 * 3 * 64 * (8 * min(512, context) + 8 * context)
        return layers + scan + attend + HEAD

    for context in (100, 512, 4000):
        assert fam.decode_flops(CONFIG, context) == pytest.approx(by_hand(context))
    # past the window only the shared pool's eight readers grow
    grow = fam.decode_flops(CONFIG, 4000) - fam.decode_flops(CONFIG, 1000)
    assert grow == pytest.approx(2 * 40 * 3 * 64 * 8 * 3000)
    # the parameters a token meets: 3.852 G less the embedding's other rows
    params = 9 * MAMBA + 9 * ATTN + 7 * CROSS + 7 * GMU + 32 * MLP
    assert 3.33e9 < params < 3.35e9


def test_prefill_runs_the_upper_half_for_one_token():
    prompt = 300
    lower = 2 * (9 * MAMBA + 9 * ATTN + 18 * MLP) + 9 * 6 * DI * 16
    mean = (prompt + 1) / 2
    lower_attend = 2 * 40 * 3 * 64 * (8 * mean + 1 * mean)
    upper = 2 * (7 * CROSS + 7 * GMU + 14 * MLP) + 2 * 40 * 3 * 64 * 7 * prompt
    assert fam.prefill_flops(CONFIG, prompt) == pytest.approx(
        prompt * (lower + lower_attend) + upper + HEAD)
    # 14 of 32 layers meet one token of the prompt, not all of them
    assert fam.prefill_flops(CONFIG, prompt) < 0.62 * prompt * fam.token_flops(
        CONFIG, mean)


def test_the_shared_pool_is_kept_once_and_read_eight_times():
    assert fam.cache_bytes_per_token(CONFIG) == 2 * 1280 * 2 == 5120
    kept = fam.slot_bytes(CONFIG)
    assert kept["ring"] == 8 * 512 * 5120
    assert kept["state"] == 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert fam.cache_read_bytes(CONFIG, [2000, 100]) == (
        8 * 5120 * 2000 + 8 * 5120 * 512 + 2 * kept["state"]
        + 8 * 5120 * 100 + 8 * 5120 * 100 + 2 * kept["state"])
    # what the cell holds for 64 slots, beside 7.70 GB of weights
    serving = CONFIG["serving"]
    resident = (serving["num_pages"] * serving["page_size"] * 5120
                + serving["num_slots"] * (kept["ring"] + kept["state"]))
    assert 3.0e9 < resident < 3.2e9
    assert fam.page_walk_bytes(1280, 1000) == 2 * 1280 * 2 * 1000


# ---------------------------------------------------------------------- mix


@pytest.mark.parametrize("seed", [1, 34, 2147491234])
def test_every_request_is_one_chunk_in_and_thousands_of_tokens_out(seed):
    requests = traffic.schedule(MIX, seed, MIX["ramp_s"] + 30)
    assert len(requests) > 30
    serving = CONFIG["serving"]
    for r in requests:
        assert 64 <= r.prompt_len <= 512 == serving["prefill_chunk"]
        assert 2048 <= r.max_new_tokens <= serving["max_new_tokens_cap"]
        assert r.prefix_len == 0 and r.tenant is None
        assert max(map(ord, r.prompt)) < CONFIG["model"]["vocab_size"]
    assert MIX["kind"] == "open_loop" and "tenants" not in MIX
    # of the issue's allowed changes: (a) one burst episode inside the ramp
    # that changes no rate (it pins the arrival times), (b) output sigma 0.10
    (start, length), = MIX["bursts"]
    assert MIX["burst_rate_x"] == 1.0 and start + length < MIX["ramp_s"]
    assert MIX["output_tokens"]["sigma"] == 0.10
    assert "(a)" in MIX["steadiness"] and "(b)" in MIX["steadiness"]
    other = traffic.schedule(MIX, seed + 1, MIX["ramp_s"] + 30)
    assert [r.due_s for r in other] == [r.due_s for r in requests]
    assert sorted(r.max_new_tokens for r in other) == sorted(
        r.max_new_tokens for r in requests)
    assert 0.5 * MIX["knee_rps"] - 1e-9 <= MIX["rate_rps"] <= 0.7 * MIX["knee_rps"] + 1e-9
    # a median lifetime of ramp, so the window sees the steady fill
    assert MIX["ramp_s"] == 75 and MIX["drain_s"] == 120


# ------------------------------------------------------------------ readers


def tick(**attrs):
    return {"record": "serve_tick", "decode_active": 3, "t0_s": 0.0,
            "t1_s": 0.02, "phases": [], **attrs}


def test_live_context_tokens_is_the_median_over_decode_ticks():
    read = reader("serve.live_context_tokens")
    records = [tick(live_tokens=60000), tick(live_tokens=80000),
               tick(live_tokens=70000),
               {"record": "serve_tick", "decode_active": 0, "live_tokens": 0}]
    assert read({"records": records}) == 70000
    assert read({"records": [tick()]}) is None       # the parent's records
    assert read({"records": []}) is None and read({}) is None


PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_chunk(2)", 0, 500), ("jit_decode(1)", 1000, 1000),
                        ("jit_decode(1)", 3000, 1000), ("jit_decode(1)", 5000, 400)],
        "XLA Ops": [
            ("%fusion.1 = f32[64,16,5120] fusion(%x)", 1100, 100),     # ssm
            ("%fusion.2 = bf16[64,2560] fusion(%y)", 1200, 50),        # gmu
            ("%paged_attn_rows.3 = f32[64,48,1280] custom-call(%q)", 1300, 200),
            ("%paged_attn_rows.4 = f32[64,48,1280] custom-call(%q)", 1500, 300),
            ("%fusion.9 = bf16[64,200064] fusion(%h)", 1800, 100),     # no scope
            ("%fusion.1 = f32[64,16,5120] fusion(%x)", 3100, 100),
            ("%fusion.2 = bf16[64,2560] fusion(%y)", 3200, 50),
            ("%paged_attn_rows.3 = f32[64,48,1280] custom-call(%q)", 3300, 200),
            ("%paged_attn_rows.4 = f32[64,48,1280] custom-call(%q)", 3500, 300),
            ("%fusion.9 = bf16[64,200064] fusion(%h)", 3800, 100),
        ],
    },
}
SCOPES = {"record": "program_scopes", "name": "serve_decode", "scopes": {
    "ssm": ["fusion.1"], "gmu": ["fusion.2"],
    "window_attn": ["paged_attn_rows.3"], "shared_attn": ["paged_attn_rows.4"]}}


def test_the_three_scope_readers_add_up_to_the_steps_busy_time(monkeypatch):
    # the modules' line's first and last events are left out: one whole step
    monkeypatch.setattr(step_phases, "planes", lambda: PLANES)
    obs = {"trace": {"window_s": 1.0}, "records": [SCOPES]}
    ssm = reader("serve.ssm_ms")(obs)
    window = reader("serve.window_attn_ms")(obs)
    shared = reader("serve.shared_attn_ms")(obs)
    assert ssm == pytest.approx(150e-6)          # the scan and the units
    assert window == pytest.approx(200e-6) and shared == pytest.approx(300e-6)
    names = {s: set(v) for s, v in SCOPES["scopes"].items()}
    parts = step_phases.breakdown(PLANES, "jit_decode", names)
    assert parts["busy"] == pytest.approx(
        ssm + window + shared + parts["unscoped"])
    for metric in ("serve.ssm_ms", "serve.window_attn_ms", "serve.shared_attn_ms"):
        # the parent: a trace and no record of scopes; a record of another
        # family's scopes; no trace at all
        assert reader(metric)({"trace": {"window_s": 1.0}, "records": []}) is None
        other = dict(SCOPES, scopes={"moe": ["fusion.7"]})
        assert reader(metric)({"trace": {"window_s": 1.0}, "records": [other]}) is None
        assert reader(metric)({"trace": None, "records": [SCOPES]}) is None


def test_the_kernels_roofline_share_counts_live_rows_only():
    read = reader("kernel.paged_attn_rows_roofline")
    events = PLANES["/device:TPU:0"]["XLA Ops"]
    peaks = {"hbm_bytes_per_s": 819e9}
    # two tokens decoded in the traced window, at contexts 2,000 and 100
    trace = {"events": events, "contexts": [2000, 100], "window_s": 1.0}
    rows = 8 * 2000 + 8 * 512 + 8 * 100 + 8 * 100
    want = 100 * (5120 * rows) / 1000e-9 / 819e9      # 1,000 ns in the kernel
    obs = {"trace": trace, "peaks": peaks, "config": CONFIG}
    assert read(obs) == pytest.approx(want)
    # nothing to read: no call of that name, no contexts, no trace, no
    # peaks (a rehearsal), another family's configuration
    quiet = dict(trace, events=[e for e in events if "paged_attn_rows" not in e[0]])
    assert read(dict(obs, trace=quiet)) is None
    assert read(dict(obs, trace=dict(trace, contexts=[]))) is None
    assert read(dict(obs, trace=None)) is None and read({}) is None
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, config={"adapter": "gpt2", "model": {}})) is None
