"""The latent-attention expert decoder (`models/latent_moe.py`) against its
plain reference (`benchmarks/reference/glm52_share16.py`) at a small size
on the CPU: hidden 64, 4 heads, `index_topk` 8, 8 routed experts of which
2 are held, contexts past `index_topk`, seeded weights, float32.

Tolerance: both sides are float32 and follow the same equations in another
order of summation (the program attends in the latent over gathered rows,
the reference expands per-head keys over the whole causal square), so
logits of spread 1.6 agree to 1e-4; the faults below read 0.5 to 9.
"""

import dataclasses
import functools
import importlib
import os
import sys

import flax.linen as nn
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
from pytorch_distributed_training_tpu.ops import moe  # noqa: E402
from pytorch_distributed_training_tpu.serve.paged_cache import (  # noqa: E402
    strip_tables,
    with_tables,
)

ref = importlib.import_module("reference.glm52_share16")

TOL = 1e-4
SEQ = 40
PAGE = 4
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
    "rms_norm_eps": 1e-5, "index_norm_eps": 1e-6, "cache_len": 48,
}
CONFIG = {"name": "tiny_latent", "adapter": "glm_moe_dsa", "model": MODEL,
          "weights": {"std": 0.2}, "serving": {"page_size": PAGE},
          "check": {"gap_block": 4}}


@pytest.fixture(scope="module")
def world():
    """The tiny preset with the benchmark's seeded weights installed, its
    plain forward over one sequence, and the reference's."""
    source = family.source(CONFIG, ref.weight_spec(MODEL), 7)
    cfg = lm.preset("latent-moe-tiny")
    model = lm.LatentMoELM(cfg)
    ids = np.random.RandomState(0).randint(0, 512, (1, SEQ)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.key(0), ids[:, :8])["params"]
    params = adapters.install(params, source, family.of(CONFIG))
    kept = {}
    logits = ref.forward(MODEL, source, ids[0], np.arange(SEQ), keep=kept)
    return dict(source=source, cfg=cfg, model=model, ids=ids, params=params,
                ref_logits=logits, ref_masks=kept)


def _paged(world, pages=48, **over):
    cfg = dataclasses.replace(
        world["cfg"], decode=True, kv_num_pages=pages, kv_page_size=PAGE, **over)
    model = lm.LatentMoELM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32)))["cache"]
    pools = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), strip_tables(shapes))
    return model, pools


class _Static:
    """A model as a static argument of `jax.jit`, hashed by identity (its
    config dataclass does not hash); jit's cache keeps it alive."""

    def __init__(self, model):
        self.model = model

    def __hash__(self):
        return id(self.model)

    def __eq__(self, other):
        return self.model is other.model


@functools.partial(jax.jit, static_argnums=0)
def _apply(held, params, cache, ids, position_ids, kw):
    # compiled once a model and shape: an eager apply costs 8-10 s a call
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


def _mask_of(selection, seq):
    pos = np.asarray(selection["positions"][0][0])
    valid = np.asarray(selection["valid"][0][0])
    mask = np.zeros((pos.shape[0], seq), bool)
    for t in range(pos.shape[0]):
        mask[t, pos[t][valid[t]]] = True
    return mask


def test_tree_has_no_indexer_in_shared_layers(world):
    p = world["params"]
    assert "index_q" in p["layer_0"]["attention"]
    assert "index_q" not in p["layer_1"]["attention"]
    _, pools = _paged(world)
    # one latent pool a selection group (full, shared | full, shared), an
    # indexer pool only in the layers that choose
    assert set(pools) == {"latents_0", "latents_1", "layer_0", "layer_2"}
    for g, full in enumerate(("layer_0", "layer_2")):
        assert set(pools[f"latents_{g}"]) == {"latent_pages"}
        assert set(pools[full]["attention"]) == {"index_pages"}
        # lane-dense rows: the latent row pads to whole 128-lane tiles,
        # the group's two layers side by side
        assert pools[f"latents_{g}"]["latent_pages"].shape == (
            48, PAGE, 2 * 128)
    assert world["cfg"].selection_groups == (
        ((0, 0), (0, 1), (1, 0), (1, 1)), (2, 2))


def test_prefill_then_decode_through_pools_matches_reference(world):
    """(a) a bucket prefill of 24 tokens, then 16 decode steps beside an
    idle slot, against the reference's one full forward."""
    model, pools = _paged(world)
    bt, ids, want = _table(), world["ids"], world["ref_logits"]
    logits, pools, _ = _step(
        model, world["params"], pools, ids[:, :24], [0], bt[1:2])
    assert float(jnp.abs(logits[0] - want[:24]).max()) < TOL
    for t in range(24, SEQ):
        tokens = np.array([[0], [ids[0, t]]], np.int32)
        logits, pools, _ = _step(
            model, world["params"], pools, tokens, [0, t], bt,
            token_mask=jnp.array([[False], [True]]))
        assert float(jnp.abs(logits[1, 0] - want[t]).max()) < TOL


@pytest.fixture(scope="module")
def served(world):
    """One paged model and its empty pools for the decode-step tests below
    (one model: `_step` compiles once a model and shape)."""
    return _paged(world)


def _chosen(vars_, layer):
    """The valid chosen positions of `layer`'s one query."""
    sel = vars_["selection"][f"layer_{layer}"]["attention"]
    pos, valid = np.asarray(sel["positions"][0]), np.asarray(sel["valid"][0])
    return set(pos[0, 0][valid[0, 0]].tolist())


@pytest.mark.parametrize(
    "case", ["context_below_topk", "current_chosen", "current_not_chosen"])
def test_decode_through_the_group_pool_matches_reference(world, served, case):
    """The decode step reads a group's rows out of ONE gather that ran
    before the group's later layers wrote the current token's row: the
    reference's logits where that token is among the chosen (always, while
    the context is under index_topk; by the indexer's choice past it) and
    where it is not."""
    model, pools = served
    bt, ids, want = _table(), world["ids"], world["ref_logits"]
    topk = MODEL["index_topk"]
    _, pools, _ = _step(model, world["params"], pools, ids[:, :4], [0], bt[1:2])
    met = False
    for t in range(4, SEQ):
        logits, pools, vars_ = _step(
            model, world["params"], pools, ids[:, t:t + 1], [t], bt[1:2])
        assert float(jnp.abs(logits[0, 0] - want[t]).max()) < TOL, t
        inside = [t in _chosen(vars_, layer) for layer in range(4)]
        assert inside[1] == inside[0] and inside[3] == inside[2]
        if t < topk:
            assert all(inside)      # every seen position is chosen
            met = met or case == "context_below_topk"
        elif case == "current_chosen":
            met = met or all(inside)
        elif case == "current_not_chosen":
            met = met or not any(inside)
        if met and t >= topk:
            break
    assert met, case


def test_stale_rows_of_the_current_token_never_reach_the_logits(world, served):
    """The pin of the substitution: NaN in the shared layers' columns at
    the row the step is about to write. The group's gather fetches that row
    before those layers write it (the token is among the chosen: context
    under index_topk), and the logits equal the clean step's bit for bit:
    the fresh row, not the pool, supplies it. The step then writes it."""
    model, pools = served
    bt, ids, t = _table(), world["ids"], 5
    _, pools, _ = _step(model, world["params"], pools, ids[:, :t], [0], bt[1:2])
    page, off = bt[1, t // PAGE], t % PAGE
    width = world["cfg"].latent_row
    dirty = dict(pools)
    for g in (0, 1):
        leaf = pools[f"latents_{g}"]["latent_pages"]
        dirty[f"latents_{g}"] = {
            "latent_pages": leaf.at[page, off, width:].set(jnp.nan)}
    clean, clean_pools, vars_ = _step(
        model, world["params"], pools, ids[:, t:t + 1], [t], bt[1:2])
    assert all(t in _chosen(vars_, layer) for layer in range(4))
    got, got_pools, _ = _step(
        model, world["params"], dirty, ids[:, t:t + 1], [t], bt[1:2])
    assert np.array_equal(np.asarray(got), np.asarray(clean))
    assert not np.isnan(np.asarray(got)).any()
    for a, b in zip(jax.tree.leaves(got_pools), jax.tree.leaves(clean_pools)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_group_pool_columns_hold_each_layers_rows(world, served):
    """The write path: after a prefill of 6 and 14 decode steps, layer j of
    a group finds in columns [j * 128, (j + 1) * 128) of the group's pool,
    at its tokens' page slots, the row a pool of its own held before:
    `[c_kv | k_rope | 0]`, made here from the plain forward's normed
    latents and rotated keys. Nothing else in the pool is touched."""
    from pytorch_distributed_training_tpu.ops import latent_attention as la

    model, pools = served
    cfg, params, ids, bt = world["cfg"], world["params"], world["ids"], _table()
    n, width = 20, world["cfg"].latent_row
    _, pools, _ = _step(model, params, pools, ids[:, :6], [0], bt[1:2])
    for t in range(6, n):
        _, pools, _ = _step(model, params, pools, ids[:, t:t + 1], [t], bt[1:2])
    _, kept = jax.jit(lambda p, i: world["model"].apply(
        {"params": p}, i, capture_intermediates=True))(params, ids[:, :n])
    kept = kept["intermediates"]
    cos, sin = la.rope_angles(
        jnp.arange(n)[None], cfg.qk_rope_head_dim, cfg.rope_theta)
    places, _ = cfg.selection_groups
    pages, offs = bt[1, np.arange(n) // PAGE], np.arange(n) % PAGE
    for layer, (group, place) in enumerate(places):
        at = kept[f"layer_{layer}"]
        ckv = at["attention"]["kv_a_norm"]["__call__"][0]
        x = at["attention_norm"]["__call__"][0]
        k_rope = la.apply_rope(
            x @ params[f"layer_{layer}"]["attention"]["kv_a_rope"], cos, sin)
        want = jnp.concatenate(
            [ckv, k_rope, jnp.zeros((1, n, width - 40))], -1)[0]
        pool = np.asarray(pools[f"latents_{group}"]["latent_pages"])
        got = pool[pages, offs, place * width:(place + 1) * width]
        assert float(np.abs(got - np.asarray(want)).max()) < TOL, layer
        assert float(np.abs(got).max()) > 0.1
    for g in (0, 1):
        pool = np.array(pools[f"latents_{g}"]["latent_pages"])
        pool[pages, offs] = 0.0
        assert not pool.any()


@pytest.mark.parametrize("indexer_types,groups", [
    (("full", "shared", "full", "shared"), 2),
    (("full", "shared", "shared", "shared"), 1),
    (("full", "full", "full", "full"), 4),
], ids=["two_groups", "one_group", "every_layer_chooses"])
def test_decode_program_gathers_latent_rows_once_a_group(
        world, indexer_types, groups):
    """The counter that says the group gather engaged: the compiled decode
    program holds one gather of latent rows a selection group, which is one
    a layer (the program as it was) when every layer chooses. The chunk
    program keeps one a layer."""
    from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
        CommManifest, comm_audit)
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry)

    model, pools = _paged(world, indexer_types=indexer_types)
    assert len(model.config.selection_groups[1]) == groups
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32)))["params"]

    def program(chunk):
        def step(params, pools, ids, bt, ctx):
            cache = with_tables(pools, bt, ctx)
            return model.apply(
                {"params": params, "cache": cache}, ids,
                position_ids=ctx[:, None] + jnp.arange(chunk)[None],
                mutable=["cache"])
        return jax.jit(step).lower(
            shapes, pools, jnp.zeros((2, chunk), jnp.int32),
            jnp.zeros((2, 12), jnp.int32), jnp.zeros((2,), jnp.int32)).compile()

    manifest = CommManifest("decode", latent_row=model.config.latent_row)
    record = comm_audit("decode", program(1), manifest,
                        registry=MetricsRegistry(), mode="record")
    assert record["latent_row_gathers"] == groups
    record = comm_audit("chunk", program(3), manifest,
                        registry=MetricsRegistry(), mode="record")
    assert record["latent_row_gathers"] == 4
    # a program without a latent pool is not asked
    assert "latent_row_gathers" not in comm_audit(
        "decode", program(1), CommManifest("decode"),
        registry=MetricsRegistry(), mode="record")


def test_chosen_positions_equal_the_reference_exactly(world):
    """(b) float32, every layer: a full layer's positions are the
    reference's, a shared layer holds its full layer's; contexts pass
    index_topk, so the choice is a real one."""
    _, vars_ = jax.jit(lambda params, ids: world["model"].apply(
        {"params": params}, ids, mutable=["selection"]))(
            world["params"], world["ids"])
    masks = [_mask_of(vars_["selection"][f"layer_{i}"]["attention"], SEQ)
             for i in range(4)]
    for i in range(4):
        assert (masks[i] == world["ref_masks"][i]).all()
        assert masks[i].sum(1).max() == MODEL["index_topk"]
    assert (masks[1] == masks[0]).all() and (masks[3] == masks[2]).all()
    assert (masks[2] != masks[0]).any()
    # not simply the latest 8: the indexer really chooses
    latest = np.tril(np.ones((SEQ, SEQ), bool)) & ~np.tril(
        np.ones((SEQ, SEQ), bool), -MODEL["index_topk"])
    assert (masks[0] != latest).any()


def test_paged_steps_choose_the_same_positions(world):
    """(b), through the pools: the decode step's positions at t are the
    reference's row t, in the full and in the shared layers."""
    model, pools = _paged(world)
    bt, ids = _table(), world["ids"]
    _, pools, _ = _step(model, world["params"], pools, ids[:, :30], [0], bt[1:2])
    _, pools, vars_ = _step(
        model, world["params"], pools, ids[:, 30:31], [30], bt[1:2])
    for i in range(4):
        got = _mask_of(vars_["selection"][f"layer_{i}"]["attention"], 48)
        assert (got[0, :SEQ] == world["ref_masks"][i][30]).all()


def test_chunked_bucket_and_prefix_hit_give_the_same_logits(world):
    """(c) cold bucket prefill, chunked prefill (chunks of 8 at a growing
    context, in blocks of 4 queries), and a prefix-cache hit: another
    block-table row mapping the first row's 5 full pages, the sixth page
    copied on write (2 of its 4 lanes shared), the tail prefilled from the
    cached boundary: the same logits."""
    params, ids, want = world["params"], world["ids"], world["ref_logits"]
    model, pools = _paged(world)
    mq, _ = _paged(world, paged_multiquery=True)
    bt = _table()
    cold, cold_pools, _ = _step(model, params, pools, ids[:, :32], [0], bt[1:2])
    assert float(jnp.abs(cold[0] - want[:32]).max()) < TOL
    chunked = []
    for start in range(0, 32, 8):
        logits, pools, _ = _step(
            mq, params, pools, ids[:, start:start + 8], [start], bt[1:2])
        chunked.append(logits[0])
    assert float(jnp.abs(jnp.concatenate(chunked) - cold[0]).max()) < TOL
    # a hit: 22 tokens cached = 5 whole pages + 2 lanes of the sixth
    hit = np.zeros((1, 12), np.int32)
    hit[0, :5] = bt[1, :5]
    hit[0, 5:9] = [30, 31, 32, 33]
    pools = jax.tree.map(
        lambda leaf: leaf.at[30].set(leaf[bt[1, 5]]), cold_pools)  # the COW
    other = np.array(ids[:, :32])
    other[0, 22:] = (other[0, 22:] + 1) % 512         # diverges mid-page
    tail, pools, _ = _step(mq, params, pools, other[:, 22:30], [22], hit)
    full = ref.forward(MODEL, world["source"], other[0], np.arange(22, 30))
    assert float(jnp.abs(tail[0] - full).max()) < TOL
    # the shared pages are untouched: the first sequence still decodes
    logits, _, _ = _step(mq, params, pools, ids[:, 32:33], [32], bt[1:2])
    assert float(jnp.abs(logits[0, 0] - want[32]).max()) < TOL


def test_shares_add_up_to_the_uncut_layer(world):
    """(d) the share ties to the model: the routed parts that shares 0..3
    give (experts 2i, 2i+1 each), plus the shared expert once, add up to
    what the uncut reference gives for the whole layer."""
    uncut = dict(MODEL, experts_held=[0, 8])
    source = family.source(
        dict(CONFIG, model=uncut), ref.weight_spec(uncut), 11)
    w = ref.layer_weights(source, 1)
    x = jax.random.normal(jax.random.key(3), (24, 64), jnp.float32)
    whole_routed, whole_shared = ref.expert_layer(w, uncut, x)
    routed_sum = 0.0
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
        layer = lm.ExpertLayer(cfg)
        out = layer.apply({"params": params}, x[None])[0]
        shared = lm.GatedMLP(cfg, 32).apply({"params": params["shared"]}, x)
        assert float(jnp.abs(shared - whole_shared).max()) < TOL
        routed_sum = routed_sum + (out - shared)
        # and the share's own reference agrees with the program's share
        part, _ = ref.expert_layer(
            {**w, **{f"experts_{n}.{j}": w[f"experts_{n}.{first + j}"]
                     for j in range(2) for n in ("gate", "up", "down")}},
            dict(MODEL, experts_held=[first, 2]), x)
        assert float(jnp.abs((out - shared) - part).max()) < TOL
    assert float(jnp.abs(routed_sum - whole_routed).max()) < TOL
    assert float(jnp.abs(whole_routed).max()) > 0.1


def test_absorbed_and_expanded_attention_agree(world):
    """(e) one layer's attention over a fresh sequence: the expanded path
    (per-head keys and values from the sequence's own latents) against
    the absorbed path (write, select, gather, attend in the latent), fed
    the same weights and input."""
    cfg = world["cfg"]
    p = world["params"]["layer_0"]["attention"]
    x = jax.random.normal(jax.random.key(5), (1, 24, 64), jnp.float32)
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    expanded, sel_e = lm.LatentAttention(cfg, True).apply(
        {"params": p}, x, pos, None)
    dcfg = dataclasses.replace(
        cfg, decode=True, kv_num_pages=16, kv_page_size=PAGE,
        paged_multiquery=True)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, pos):
            return lm.LatentAttention(dcfg, True, name="attention")(
                x, pos, None, lm.LatentPool(dcfg, 1, name="latents")())

    cache = {
        "latents": {
            "latent_pages": jnp.zeros((16, PAGE, cfg.latent_row)),
            "block_table": jnp.arange(1, 9, dtype=jnp.int32)[None],
            "context_len": jnp.zeros((1,), jnp.int32)},
        "attention": {
            "index_pages": jnp.zeros((16, PAGE, cfg.index_head_dim))},
    }
    (absorbed, sel_a), _ = Layer().apply(
        {"params": {"attention": p}, "cache": cache}, x, pos,
        mutable=["cache"])
    assert float(jnp.abs(absorbed - expanded).max()) < 1e-5
    assert (jnp.sort(jnp.where(sel_a.valid, sel_a.positions, -1), -1)
            == jnp.sort(jnp.where(sel_e.valid, sel_e.positions, -1), -1)).all()


def test_routing_chooses_by_score_plus_bias_and_weighs_by_score():
    """(f) expert 2 wins its place by the bias alone; its weight is its
    own score's share, the bias nowhere in it."""
    x = jnp.eye(4, dtype=jnp.float32)[:1] * 2.0                  # [1, 4]
    router = jnp.array([[1.0, 0.5, -1.0, 0.0]] + [[0.0] * 4] * 3)  # logits 2, 1, -2, 0
    bias = jnp.array([0.0, 0.0, 1.0, 0.0])
    chosen, weights = moe.route(x, router, bias, top_k=2, scale=2.5)
    g = jax.nn.sigmoid(jnp.array([2.0, 1.0, -2.0, 0.0]))
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 2]      # not [0, 1]
    want = 2.5 * g[np.asarray(chosen[0])] / (g[0] + g[2])
    assert np.allclose(np.asarray(weights[0]), np.asarray(want), atol=1e-6)
    # the reference's routing is the same function
    w = {"router": router, "router_bias": bias}
    m = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.5}
    r_chosen, r_weights = ref.route(w, m, x)
    assert (r_chosen == chosen).all()
    assert np.allclose(np.asarray(r_weights), np.asarray(weights), atol=1e-6)


def test_grouped_and_dense_expert_products_agree():
    """The two products over the held experts are one function: pairs
    sorted by expert in row blocks against every expert over every token,
    experts nobody chose and tokens that chose no held expert included."""
    keys = jax.random.split(jax.random.key(9), 8)
    x = jax.random.normal(keys[0], (37, 16), jnp.float32)
    experts = [tuple(0.3 * jax.random.normal(k, s, jnp.float32)
                     for k, s in zip(keys[1 + 3 * j:4 + 3 * j],
                                     ((2, 16, 8), (2, 16, 8), (2, 8, 16))))
               for j in range(2)]
    chosen = jax.random.randint(keys[7], (37, 3), 0, 12)
    chosen = chosen.at[:, 1].set((chosen[:, 0] + 1) % 12)
    chosen = chosen.at[:, 2].set((chosen[:, 0] + 5) % 12)
    chosen = jnp.where(chosen == 5, 11, chosen)    # held expert 5 - 3 = 2: idle
    weights = jax.random.uniform(keys[0], (37, 3))
    dense = moe.dense_experts(x, chosen, weights, 3, experts)
    grouped = moe.grouped_experts(x, chosen, weights, 3, experts, row_block=4)
    assert float(jnp.abs(dense).max()) > 0.1
    assert float(jnp.abs(dense - grouped).max()) < 1e-5
    per_expert, absent = moe.routing_counts(chosen, 3, 4)
    assert int(per_expert[2]) == 0 and int(per_expert.sum() + absent) == 37 * 3


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8",))
def test_a_fault_in_each_mechanism_fails_the_comparison(world, fault):
    """(g) the same mathematics with one mechanism broken, put in the
    program's place: the tokens it would serve lie far below the sound
    reference's best, where the program's own lie within TOL."""
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
    out = ref.served_token_gaps(
        CONFIG, world["source"], [(ids[:24].tolist(), served.tolist())])
    # teacher forcing the reference's own tokens from position 24 on
    # changes the context, so only the first served token is its argmax
    assert out["tokens"] == 16 and out["max_logit_gap"] >= 0.0
    one = ref.served_token_gaps(
        CONFIG, world["source"], [(ids[:24].tolist(), served[:1].tolist())],
        control="int8,select_latest")
    assert one["max_logit_gap"] == 0.0
    assert set(one["controls"]) == {"int8", "select_latest"}
    assert one["control_max_logit_gap"] == min(one["controls"].values())


def test_the_judged_gap_is_the_widest_block_mean():
    """One wide gap among a block's tokens counts by its share of the
    block; a tail shorter than half a block joins the block before."""
    gaps = np.zeros(19)
    gaps[5] = 1.6
    assert ref.widest_block_mean(gaps, 8) == pytest.approx(0.2)
    assert ref.widest_block_mean(gaps, 100) == pytest.approx(1.6 / 19)
    gaps[16:] = 0.4           # blocks [0:8], [8:19]: the tail of 3 joins
    assert ref.widest_block_mean(gaps, 8) == pytest.approx(0.2)
    gaps[11:] = 0.4           # [8:19] holds 8 of 11 at 0.4
    assert ref.widest_block_mean(gaps, 8) == pytest.approx(0.4 * 8 / 11)
    assert ref.widest_block_mean(gaps[:3], 8) == pytest.approx(0.0)
    # blocks of one token: the widest single gap
    assert ref.widest_block_mean(gaps, 1) == pytest.approx(1.6)


@pytest.mark.parametrize("n,k", [(300, 8), (300, 128), (1000, 256), (64, 100)])
def test_select_topk_is_jax_top_k_without_a_sort(n, k):
    """The sort-free selection against `jax.lax.top_k` as sets: random
    scores, heavy ties (scores on a grid of 7 values, zeros and minus
    zeros among them), rows with fewer than k seen positions."""
    from pytorch_distributed_training_tpu.ops import latent_attention as la

    rng = np.random.default_rng(n + k)
    rows = np.stack([
        rng.normal(size=n).astype(np.float32),
        rng.integers(-3, 4, n).astype(np.float32) * 0.5,
        np.where(rng.random(n) < 0.5, 0.0, -0.0).astype(np.float32),
        -np.abs(rng.normal(size=n)).astype(np.float32),
    ])[None]                                            # [1, 4, n]
    q_positions = np.array([[n - 1, n // 2, 3, n - 7]], np.int32)
    seen = np.arange(n)[None, None, :] <= q_positions[..., None]
    scores = jnp.where(seen, rows, -jnp.inf)
    got = la.select_topk(scores, k, jnp.asarray(q_positions))
    values, want = jax.lax.top_k(scores, min(k, n))
    for r in range(4):
        mine = set(np.asarray(got.positions[0, r])[np.asarray(got.valid[0, r])])
        theirs = set(np.asarray(want[0, r])[np.asarray(values[0, r]) > -np.inf])
        assert mine == theirs, (r, sorted(mine ^ theirs))
        assert len(mine) == min(k, n, int(q_positions[0, r]) + 1)
    assert got.positions.shape == (1, 4, min(k, n))
