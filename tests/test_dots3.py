"""The latent expert decoder with window layers (`models/latent_moe.py` at
the `dots3-tiny` preset: full layers that each choose with their own
indexer, a group of three window layers of another latent width and head
count, headwise gates) against its plain reference
(`benchmarks/reference/dots3_share8.py`) at a small size on the CPU:
hidden 64, `index_topk` 8, a window of 9 positions (2 pages of 4 and the
query), 8 routed experts of which 2 are held, contexts of 40 past both,
seeded weights, float32.

Tolerance: both sides are float32 and follow the same equations in
another order of summation (the program attends in the latent over
gathered rows in the decode step and expands a span of rows in a prefill,
the reference expands per-head keys over each block of queries), so
logits of spread 1.5 agree to 1e-4; a program without its gates, and the
reference's faults, read 0.3 to 9.
"""

import dataclasses
import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import adapters, family  # noqa: E402

from pytorch_distributed_training_tpu.models import latent_moe as lm  # noqa: E402
from pytorch_distributed_training_tpu.serve.paged_cache import (  # noqa: E402
    strip_tables,
    with_tables,
)

ref = importlib.import_module("reference.dots3_share8")

TOL = 1e-4
SEQ = 40
PAGE = 4
WINDOW = 9
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 80000000, "attention_gate_type": "headwise",
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 128, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    "swa_attention_gate_type": "headwise", "sliding_window_size": WINDOW,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "router_experts": 8, "experts_held": [0, 2], "expert_block": 1,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": 8,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "vocab_size": 512, "rms_norm_eps": 1e-5, "index_norm_eps": 1e-6,
    "cache_len": 48,
}
CONFIG = {"name": "tiny_dots3", "adapter": "dots3_note", "model": MODEL,
          "weights": {"std": 0.2}, "serving": {"page_size": PAGE},
          "check": {"gap_block": 4}}


def _install(cfg, seed=7):
    source = family.source(CONFIG, ref.weight_spec(MODEL), seed)
    model = lm.LatentMoELM(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    return source, model, adapters.install(params, source, family.of(CONFIG))


@pytest.fixture(scope="module")
def world():
    """The tiny preset with the benchmark's seeded weights installed, and
    the reference's logits over one sequence."""
    cfg = lm.preset("dots3-tiny")
    source, model, params = _install(cfg)
    ids = np.random.RandomState(0).randint(0, 512, (1, SEQ)).astype(np.int32)
    logits = ref.forward(MODEL, source, ids[0], np.arange(SEQ))
    return dict(source=source, cfg=cfg, model=model, ids=ids, params=params,
                ref_logits=logits)


def _paged(cfg, pages=48, **over):
    cfg = dataclasses.replace(
        cfg, decode=True, kv_num_pages=pages, kv_page_size=PAGE, **over)
    model = lm.LatentMoELM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32)))["cache"]
    pools = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), strip_tables(shapes))
    return model, pools


class _Static:
    """A model as a static argument of `jax.jit`, hashed by identity."""

    def __init__(self, model):
        self.model = model

    def __hash__(self):
        return id(self.model)

    def __eq__(self, other):
        return self.model is other.model


@functools.partial(jax.jit, static_argnums=0)
def _apply(held, params, cache, ids, position_ids, kw):
    return held.model.apply(
        {"params": params, "cache": cache}, ids, position_ids=position_ids,
        mutable=["cache", "selection", "routing"], **kw)


def _step(model, params, pools, ids, ctx0, bt_rows, **kw):
    """`ids` [b, n] appended at `ctx0` [b]; returns (logits, pools, vars)."""
    ids = jnp.asarray(ids)
    ctx0 = jnp.asarray(ctx0, jnp.int32)
    cache = with_tables(pools, jnp.asarray(bt_rows), ctx0)
    logits, vars_ = _apply(
        _Static(model), params, cache, ids,
        ctx0[:, None] + jnp.arange(ids.shape[1])[None], kw)
    return logits, strip_tables(vars_["cache"]), vars_


def _table(rows=2, width=12):
    bt = np.zeros((rows, width), np.int32)
    bt[1, :11] = np.arange(3, 25, 2)        # scattered pages, 44 tokens
    return bt


@pytest.fixture(scope="module")
def served(world):
    """The paged decode model and its multi-token view, empty pools."""
    model, pools = _paged(world["cfg"])
    mq, _ = _paged(world["cfg"], paged_multiquery=True)
    return model, mq, pools


def test_two_latent_widths_under_one_block_table(world, served):
    p = world["params"]
    for i in (0, 1):
        assert {"index_q", "gate"} <= set(p[f"layer_{i}"]["attention"])
        assert p[f"layer_{i}"]["attention"]["gate"].shape == (64, 4)
    for i in (2, 3, 4):
        assert "index_q" not in p[f"layer_{i}"]["attention"]
        assert p[f"layer_{i}"]["attention"]["gate"].shape == (64, 2)
        assert p[f"layer_{i}"]["attention"]["kv_b_k"].shape == (128, 2, 24)
    _, _, pools = served
    # each full layer is a group of its own with its indexer pool; the
    # three window layers are ONE group, their 136-value rows (256 lanes)
    # side by side
    assert set(pools) == {"latents_0", "latents_1", "latents_2",
                          "layer_0", "layer_1"}
    assert pools["latents_0"]["latent_pages"].shape == (48, PAGE, 128)
    assert pools["latents_1"]["latent_pages"].shape == (48, PAGE, 128)
    assert pools["latents_2"]["latent_pages"].shape == (48, PAGE, 3 * 256)
    cfg = world["cfg"]
    assert cfg.selection_groups == (
        ((0, 0), (1, 0), (2, 0), (2, 1), (2, 2)), (1, 1, 3))
    assert cfg.group_windows == (False, False, True)
    assert cfg.cache_values_per_token() == 2 * 128 + 3 * 256 + 2 * 16
    assert world["model"].trace_scopes[-1] == "window_attn"
    assert "window_attn" not in lm.LatentMoELM(
        lm.preset("latent-moe-tiny")).trace_scopes


def test_expanded_forward_matches_reference(world):
    logits = jax.jit(lambda p, i: world["model"].apply({"params": p}, i))(
        world["params"], world["ids"])
    want = world["ref_logits"]
    assert float(jnp.abs(logits[0] - want).max()) < TOL
    assert float(jnp.std(want)) > 0.3


@pytest.mark.parametrize("prefill", ["bucket", "chunked"])
def test_prefill_then_decode_through_pools_matches_reference(
        world, served, prefill):
    """A bucket prefill of 20 tokens, or chunks of 8 (blocks of 4 queries),
    then decode steps to 40 beside an idle slot: every logit the
    reference's full forward gives. Contexts pass the window (9) and
    index_topk (8)."""
    model, mq, pools = served
    params, bt, ids = world["params"], _table(), world["ids"]
    want = world["ref_logits"]
    if prefill == "bucket":
        logits, pools, _ = _step(model, params, pools, ids[:, :20], [0], bt[1:2])
        got = logits[0]
    else:
        parts = []
        for start in (0, 8, 16):
            n = min(8, 20 - start)
            logits, pools, _ = _step(
                mq, params, pools, ids[:, start:start + n], [start], bt[1:2])
            parts.append(logits[0])
        got = jnp.concatenate(parts)
    assert float(jnp.abs(got - want[:20]).max()) < TOL
    for t in range(20, SEQ):
        tokens = np.array([[0], [ids[0, t]]], np.int32)
        logits, pools, _ = _step(
            model, params, pools, tokens, [0, t], bt,
            token_mask=jnp.array([[False], [True]]))
        assert float(jnp.abs(logits[1, 0] - want[t]).max()) < TOL, t


def test_prefix_hit_at_a_page_boundary_inside_the_window(world, served):
    """A prefix-cache hit: another block-table row maps the first
    sequence's 5 whole pages (20 tokens); the tail from 20 diverges and is
    prefilled from the cached boundary, then decoded. The tail's first
    windows reach 8 positions back into the mapped pages, whose window
    rows nobody snapshotted: they ARE the pages. Its logits are the
    reference's over the other sequence."""
    model, mq, pools = served
    params, ids = world["params"], world["ids"]
    bt = _table()
    _, pools, _ = _step(model, params, pools, ids[:, :24], [0], bt[1:2])
    hit = np.zeros((1, 12), np.int32)
    hit[0, :5] = bt[1, :5]
    hit[0, 5:9] = [30, 32, 34, 36]
    other = np.array(ids[:, :SEQ])
    other[0, 20:] = (other[0, 20:] + 7) % 512
    want = ref.forward(MODEL, world["source"], other[0], np.arange(20, 32))
    tail, pools, _ = _step(mq, params, pools, other[:, 20:28], [20], hit)
    assert float(jnp.abs(tail[0] - want[:8]).max()) < TOL
    for t in range(28, 32):
        logits, pools, _ = _step(
            model, params, pools, other[:, t:t + 1], [t], hit)
        assert float(jnp.abs(logits[0, 0] - want[t - 20]).max()) < TOL, t


@pytest.mark.parametrize("back,read", [(WINDOW - 1, True), (WINDOW, False)],
                         ids=["8_back_counts", "9_back_does_not"])
def test_the_windows_edge(world, served, back, read):
    """The decode step at position 30 through the window group's pool: the
    rows of a position 8 back are read (a changed row changes the logits),
    a position 9 back is not (NaN in its rows leaves every logit as it
    was, bit for bit). The full layers' pools are untouched, so only the
    window layers can see it."""
    model, _, pools = served
    params, ids, bt, t = world["params"], world["ids"], _table(), 30
    _, pools, _ = _step(model, params, pools, ids[:, :t], [0], bt[1:2])
    clean, _, vars_ = _step(model, params, pools, ids[:, t:t + 1], [t], bt[1:2])
    sel = vars_["selection"]["layer_2"]["attention"]
    positions = np.asarray(sel["positions"][0][0, 0])
    assert positions.tolist() == list(range(t - WINDOW + 1, t + 1))
    pos = t - back
    page, off = bt[1, pos // PAGE], pos % PAGE
    leaf = pools["latents_2"]["latent_pages"]
    changed = dict(pools, latents_2={"latent_pages": leaf.at[page, off].set(
        jnp.nan if not read else leaf[page, off] + 1.0)})
    got, _, _ = _step(model, params, changed, ids[:, t:t + 1], [t], bt[1:2])
    if read:
        assert float(jnp.abs(got - clean).max()) > 1e-3
    else:
        assert np.array_equal(np.asarray(got), np.asarray(clean))


def test_a_program_without_its_gates_fails_the_tolerance(world):
    """The same weights in a program whose attention has no gates: its
    logits lie far outside TOL of the reference, where the gated
    program's lie within it."""
    cfg = dataclasses.replace(
        world["cfg"], attention_gate_type="none", swa_attention_gate_type="none")
    _, model, params = _install(cfg)
    assert "gate" not in params["layer_2"]["attention"]
    logits = jax.jit(lambda p, i: model.apply({"params": p}, i))(
        params, world["ids"])
    assert float(jnp.abs(logits[0] - world["ref_logits"]).max()) > 100 * TOL


def test_shares_add_up_to_the_uncut_layer(world):
    """The share ties to the model: the routed parts that shares 0..3 give
    (experts 2i, 2i+1 each), plus the shared expert once, add up to what
    the uncut reference gives for the whole layer (scaling 1, no group
    limit)."""
    glm = importlib.import_module("reference.glm52_share16")
    uncut = dict(MODEL, experts_held=[0, 8])
    source = family.source(
        dict(CONFIG, model=uncut), ref.weight_spec(uncut), 11)
    w = glm.layer_weights(source, 1)
    x = jax.random.normal(jax.random.key(3), (24, 64), jnp.float32)
    whole_routed, whole_shared = glm.expert_layer(w, uncut, x)
    total = 0.0
    for share in range(4):
        first = 2 * share
        cfg = dataclasses.replace(world["cfg"], experts_held=(first, 2))
        params = {
            "router": w["router"], "router_bias": w["router_bias"],
            "shared": {k: w["shared_" + k] for k in ("gate", "up", "down")},
        }
        for j in range(2):
            for name in ("gate", "up", "down"):
                params[f"experts_{j}_{name}"] = w[f"experts_{name}.{first + j}"]
        out = lm.ExpertLayer(cfg).apply({"params": params}, x[None])[0]
        # this share's routed part: what it gives less the shared expert,
        # which every share computes alike
        total = total + out - whole_shared
    assert float(jnp.abs(total - whole_routed).max()) < TOL
    assert float(jnp.abs(whole_routed).max()) > 0.1


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8",))
def test_a_fault_fails_the_comparison(world, fault):
    """The window fault (every window layer shown the whole context) and
    int8 operands, put in the program's place: the tokens they would serve
    lie far below the sound reference's best."""
    want = world["ref_logits"]
    kw = {"fault": fault} if fault in ref.FAULTS else {"precision": fault}
    low = ref.forward(MODEL, world["source"], world["ids"][0], np.arange(SEQ), **kw)
    first = jnp.argmax(low, -1)
    gap = float((want.max(-1) - jnp.take_along_axis(
        want, first[:, None], 1)[:, 0]).max())
    assert gap > 0.3, (fault, gap)
    assert float(jnp.abs(low - want).max()) > 100 * TOL


def test_served_token_gaps_reads_sound_and_controls(world):
    ids = world["ids"][0]
    served = np.asarray(jnp.argmax(world["ref_logits"][23:39], -1))
    one = ref.served_token_gaps(
        CONFIG, world["source"], [(ids[:24].tolist(), served[:1].tolist())],
        control="int8,window_all")
    assert one["tokens"] == 1 and one["max_logit_gap"] == 0.0
    assert set(one["controls"]) == {"int8", "window_all"}
    assert one["control_max_logit_gap"] == min(one["controls"].values())


def test_decode_program_gathers_each_group_once(world):
    """The counters: the compiled decode step holds one gather of latent
    rows a full layer (2) and ONE of the window group's wide rows; a chunk
    gathers once a window layer."""
    from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
        CommManifest, comm_audit)
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry)

    cfg = world["cfg"]
    model, pools = _paged(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32)))["params"]

    def program(chunk):
        def step(params, pools, ids, bt, ctx):
            return model.apply(
                {"params": params, "cache": with_tables(pools, bt, ctx)}, ids,
                position_ids=ctx[:, None] + jnp.arange(chunk)[None],
                mutable=["cache"])
        return jax.jit(step).lower(
            shapes, pools, jnp.zeros((2, chunk), jnp.int32),
            jnp.zeros((2, 12), jnp.int32), jnp.zeros((2,), jnp.int32)).compile()

    manifest = CommManifest("decode", latent_row=cfg.latent_row,
                            window_row=cfg.window_row)
    record = comm_audit("decode", program(1), manifest,
                        registry=MetricsRegistry(), mode="record")
    assert (record["latent_row_gathers"], record["window_row_gathers"]) == (2, 1)
    record = comm_audit("chunk", program(3), manifest,
                        registry=MetricsRegistry(), mode="record")
    assert record["window_row_gathers"] == 3
