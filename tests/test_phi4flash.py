"""The state-space hybrid decoder (`models/sambay.py`: Mamba layers, window
attention, one full-attention layer whose K/V the cross layers read, Gated
Memory Units) against its plain reference
(`benchmarks/reference/phi4_mini_flash.py`) at a small size on the CPU:
hidden 64, 8 query heads over 4 K/V heads, window 8, 8 layers (M, W, M, W,
M*, F, G, X), contexts of more than three windows, seeded weights, float32.

Tolerance: both sides are float32 and follow the same equations in another
order of summation, and every pair's output passes an RMSNorm that divides
by a small norm, so logits of spread 7 agree to 2e-3; the faults below read
0.1 to 9.
"""

import dataclasses
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

from pytorch_distributed_training_tpu.models import sambay  # noqa: E402
from pytorch_distributed_training_tpu.serve import EngineConfig  # noqa: E402
from pytorch_distributed_training_tpu.serve.paged_cache import (  # noqa: E402
    PageAllocator,
    strip_tables,
    with_tables,
)
from pytorch_distributed_training_tpu.utils.config import model_preset  # noqa: E402

ref = importlib.import_module("reference.phi4_mini_flash")

pytestmark = [pytest.mark.serve]

TOL = 2e-3
SEQ = 40          # five windows of 8
PAGE = 4
SLOTS = 3
MODEL = {
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "intermediate_size": 128, "sliding_window": 8,
    "mb_per_layer": 2, "layer_norm_eps": 1e-5, "vocab_size": 512,
    "vocab_blocks": 2, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 8, "cache_len": 48,
}
CONFIG = {"name": "tiny_hybrid", "adapter": "phi4flash",
          "reference": "phi4_mini_flash", "model": MODEL,
          "weights": {"std": 0.2}, "serving": {"page_size": PAGE}}


@pytest.fixture(scope="module")
def world():
    source = family.source(CONFIG, ref.weight_spec(MODEL), 21)
    cfg = model_preset("sambay-tiny")
    assert isinstance(cfg, sambay.SambaYConfig)
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "sliding_window",
                "vocab_size", "vocab_blocks", "mamba_d_state", "mamba_dt_rank"):
        assert getattr(cfg, key) == MODEL[key], key
    assert list(cfg.layer_kinds) == ref.layer_kinds(MODEL) == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross"]
    model = sambay.SambaYLM(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    params = adapters.install(params, source, family.of(CONFIG))
    ids = np.random.default_rng(3).integers(1, 512, (SLOTS, SEQ)).astype(np.int32)
    forward = jax.jit(lambda ids: model.apply({"params": params}, ids))
    with jax.default_matmul_precision("highest"):
        want = np.stack([
            np.asarray(ref.forward(MODEL, source, row, np.arange(SEQ)))
            for row in ids])
    keep = {}
    with jax.default_matmul_precision("highest"):
        ref.forward(MODEL, source, ids[0], np.arange(SEQ), keep=keep)
    return dict(source=source, cfg=cfg, model=model, params=params, ids=ids,
                forward=forward, want=want, keep=keep)


def close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), want, atol=tol, rtol=0)


# ------------------------------------------------- the forward, no cache


def test_forward_without_a_cache_matches_the_reference(world):
    close(world["forward"](world["ids"]), world["want"])
    assert np.abs(world["want"]).max() > 3      # no echo of the input


def test_the_published_one_leaf_embedding_is_the_blocks_joined(world):
    """`vocab_blocks` is a layout the benchmark's install forced (ROADMAP
    C12): the default, one leaf as published, gives the same logits."""
    import dataclasses

    one = dataclasses.replace(world["cfg"], vocab_blocks=1)
    assert sambay.SambaYConfig.__dataclass_fields__["vocab_blocks"].default == 1
    params = {k: v for k, v in world["params"].items()
              if not k.startswith("embed_")}
    params["embed_0"] = jnp.concatenate(
        [world["params"][f"embed_{j}"] for j in range(world["cfg"].vocab_blocks)])
    got = jax.jit(lambda ids: sambay.SambaYLM(one).apply(
        {"params": params}, ids))(world["ids"][:1])
    close(got, world["forward"](world["ids"][:1]), tol=1e-5)


@pytest.mark.parametrize("fault", ref.FAULTS + ("int8",))
def test_each_fault_of_the_reference_reads_far_over_the_tolerance(world, fault):
    how = {"fault": fault} if fault in ref.FAULTS else {"precision": fault}
    with jax.default_matmul_precision("highest"):
        low = ref.forward(MODEL, world["source"], world["ids"][0],
                          np.arange(SEQ), **how)
    assert np.abs(np.asarray(low) - world["want"][0]).max() > 30 * TOL


@pytest.mark.parametrize("layer", range(8))
def test_each_layer_alone_matches_the_reference(world, layer):
    """One layer at a time on the reference's own input to it: a mixer of
    each kind (and the memory and K/V handed to the two that take them)."""
    keep = world["keep"]
    cfg, kind = world["cfg"], world["cfg"].layer_kinds[layer]
    x = keep[layer - 1][None] if layer else None
    if x is None:
        # layer 0's input is the embedding
        table = np.concatenate([
            np.asarray(world["params"][f"embed_{j}"]) for j in range(2)])
        x = table[world["ids"][0]][None]
    memory = shared = None
    if kind in ("gmu", "cross"):
        # what the stack hands down, from the program's own lower half
        _, state = world["model"].apply(
            {"params": world["params"]}, world["ids"][:1],
            capture_intermediates=lambda m, _: isinstance(
                m, (sambay.MambaMixer, sambay.DifferentialAttention)))
        inter = state["intermediates"]
        memory = inter["layer_4"]["mixer"]["__call__"][0][1]
        shared = inter["layer_5"]["mixer"]["__call__"][0][1]
    positions = jnp.arange(SEQ, dtype=jnp.int32)[None]
    out, _, _ = sambay.DecoderLayer(cfg, kind, layer).apply(
        {"params": world["params"][f"layer_{layer}"]}, jnp.asarray(x), memory,
        shared, positions)
    close(out[0], keep[layer], 2e-4 * max(1.0, np.abs(keep[layer]).max()))


def test_moving_the_shared_keys_moves_every_cross_layer():
    """Twelve layers (two cross layers): with layer 7's key projection
    perturbed, every cross layer's attention output moves, and no window
    layer's does."""
    cfg = sambay.preset("sambay-tiny", num_hidden_layers=12,
                        initializer_range=0.2)
    kinds = cfg.layer_kinds
    assert kinds[7] == "full" and [i for i, k in enumerate(kinds)
                                   if k == "cross"] == [9, 11]
    model = sambay.SambaYLM(cfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(1, 512, (1, 24)))
    params = model.init(jax.random.key(1), ids)["params"]

    def attention_outputs(params):
        _, state = model.apply(
            {"params": params}, ids, capture_intermediates=lambda m, _:
            isinstance(m, sambay.DifferentialAttention))
        return {name: np.asarray(v["mixer"]["__call__"][0][0])
                for name, v in state["intermediates"].items()}

    base = attention_outputs(params)
    moved = jax.tree.map(lambda x: x, params)
    moved["layer_7"]["mixer"]["k"] = params["layer_7"]["mixer"]["k"] * 1.5
    after = attention_outputs(moved)
    for layer in (9, 11):
        assert "k" not in params[f"layer_{layer}"]["mixer"]    # no K/V of its own
        assert np.abs(after[f"layer_{layer}"] - base[f"layer_{layer}"]).max() > 1e-2
    for layer in (1, 3, 5):
        assert np.array_equal(after[f"layer_{layer}"], base[f"layer_{layer}"])


# ------------------------------------- through state, rings and pages


class Stepper:
    """The programs of the engine, written out: a prefill chunk into one
    slot and a decode step of every slot, over one resident cache tree."""

    def __init__(self, world, *, garbage=None):
        cfg = world["cfg"]
        self.pages_per_slot = MODEL["cache_len"] // PAGE
        self.dcfg = dataclasses.replace(
            cfg, decode=True, kv_page_size=PAGE,
            kv_num_pages=SLOTS * self.pages_per_slot + 1, kv_num_slots=SLOTS)
        self.model = sambay.SambaYLM(self.dcfg)
        self.memory = self.dcfg.slot_memory()
        self.params = world["params"]
        shapes = jax.eval_shape(lambda: self.model.init(
            jax.random.key(0), jnp.ones((1, 1), jnp.int32),
            position_ids=jnp.zeros((1, 1), jnp.int32)))["cache"]
        rng = np.random.default_rng(9)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype) if garbage is None
            else jnp.asarray(garbage * rng.standard_normal(s.shape), s.dtype),
            strip_tables(shapes))
        self.pages = PageAllocator(
            self.dcfg.kv_num_pages, PAGE, self.pages_per_slot, SLOTS)

    def prefill(self, slot, ids, start, chunk):
        """Tokens `ids[start:start + chunk]` (padded to `chunk`) into
        `slot`; returns the logits of the last real one."""
        real = min(chunk, len(ids) - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :real] = ids[start:start + real]
        ctx0 = jnp.asarray([start], jnp.int32)
        cache = with_tables(
            self.cache, jnp.asarray(self.pages.block_table[slot:slot + 1]),
            ctx0, memory=self.memory, slot=jnp.asarray([slot], jnp.int32),
            chunk_len=jnp.asarray([real], jnp.int32))
        logits, vars_ = self.model.apply(
            {"params": self.params, "cache": cache}, jnp.asarray(padded),
            position_ids=ctx0[:, None] + jnp.arange(chunk)[None],
            mutable=["cache"], logit_index=jnp.asarray([real - 1], jnp.int32))
        self.cache = strip_tables(vars_["cache"])
        assert logits.shape == (1, 1, MODEL["vocab_size"])
        return logits[0, 0]

    def decode(self, tokens, contexts):
        """One token a slot at `contexts` (0: the slot is idle or still
        prefilling); returns the logits [slots, vocab]."""
        ctx = jnp.asarray(contexts, jnp.int32)
        table = np.where(np.asarray(contexts)[:, None] > 0,
                         self.pages.block_table, 0)
        cache = with_tables(self.cache, jnp.asarray(table), ctx,
                            memory=self.memory)
        logits, vars_ = self.model.apply(
            {"params": self.params, "cache": cache},
            jnp.asarray(tokens, jnp.int32)[:, None], position_ids=ctx[:, None],
            mutable=["cache"])
        self.cache = strip_tables(vars_["cache"])
        return logits[:, 0]


@pytest.mark.parametrize("chunk", [40, 8, 16], ids=["bucket", "chunks_of_a_window",
                                                    "chunks_of_two"])
def test_prefill_then_decode_through_the_memories_matches_the_forward(world, chunk):
    """Slots of unequal length in one decode step, one of them idle at
    first and admitted while the others decode; contexts pass three
    windows, so every ring wraps; a prefill's one row of logits is the full
    forward's at that position."""
    ids, want = world["ids"], world["want"]
    step = Stepper(world)
    prompts = {0: 27, 1: 9}          # slot -> prompt length; slot 2 idle
    for slot, n in prompts.items():
        step.pages.admit(slot, step.pages_per_slot)
        for start in range(0, n, chunk):
            last = step.prefill(slot, ids[slot][:n], start, chunk)
        close(last, want[slot][n - 1])
    contexts = [27, 9, 0]
    for tick in range(12):
        if tick == 4:                # slot 2 is admitted mid-flight
            step.pages.admit(2, step.pages_per_slot)
            for start in range(0, 21, chunk):
                last = step.prefill(2, ids[2][:21], start, chunk)
            close(last, want[2][20])
            contexts[2] = 21
        tokens = [ids[s][c] if c else 0 for s, c in enumerate(contexts)]
        logits = step.decode(tokens, contexts)
        for s, c in enumerate(contexts):
            if c:
                close(logits[s], want[s][c])
                contexts[s] = c + 1
    assert contexts[0] == 39 > 3 * MODEL["sliding_window"]


def test_a_slots_last_request_does_not_show_in_its_next(world):
    """Every pool, ring and state full of another request's numbers (as
    after a longer request in the same slot), and a prefill still running
    in slot 1 while slot 0 decodes: neither stale rows nor the decode
    steps in between reach the outputs."""
    ids, want = world["ids"], world["want"]
    step = Stepper(world, garbage=3.0)
    step.pages.admit(0, step.pages_per_slot)
    step.pages.admit(1, step.pages_per_slot)
    close(step.prefill(0, ids[0][:6], 0, 8), want[0][5])
    context = 6
    for start in range(0, 30, 8):            # slot 1 streams in over ticks
        last = step.prefill(1, ids[1][:30], start, 8)
        logits = step.decode([ids[0][context], 0, 0], [context, 0, 0])
        close(logits[0], want[0][context])
        context += 1
    close(last, want[1][29])
    logits = step.decode([ids[0][context], ids[1][30], 0], [context, 30, 0])
    close(logits[0], want[0][context])
    close(logits[1], want[1][30])


def test_the_full_models_cache_is_one_pool_eight_rings_nine_states():
    """At the published sizes: ONE paged K/V pool in its lane-dense shape,
    read by eight layers; rings and states sized by the slots alone, so a
    context of any length is held to the ring's bound."""
    cfg = dataclasses.replace(
        model_preset("phi-4-mini-flash"), decode=True, kv_page_size=16,
        kv_num_pages=18433, kv_num_slots=64)
    shapes = jax.eval_shape(lambda: sambay.SambaYLM(cfg).init(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32)))
    cache = strip_tables(shapes["cache"])
    nodes = {name: node["mixer"] for name, node in cache.items()}
    pools = [n for n in nodes.values() if "k_pages" in n]
    rings = [n for n in nodes.values() if "k_ring" in n]
    states = [n for n in nodes.values() if "ssm" in n]
    assert (len(pools), len(rings), len(states)) == (1, 8, 9) and len(nodes) == 18
    assert pools[0]["k_pages"].shape == pools[0]["v_pages"].shape == (18433, 16, 1280)
    for ring in rings:
        assert ring["k_ring"].shape == ring["v_ring"].shape == (64, 512, 1280)
    for state in states:
        assert state["ssm"].shape == (64, 16, 5120)
        assert state["ssm"].dtype == jnp.float32
        assert state["conv"].shape == (64, 3, 5120)
    memory = cfg.slot_memory()
    assert [m.kind for m in memory].count("pages") == 1
    pages, = [m for m in memory if m.kind == "pages"]
    assert pages.readers == 8 and pages.bytes_per_token == 5120
    assert sum(m.bytes_per_slot for m in memory if m.kind == "ring") == (
        8 * 512 * 5120)
    assert sum(m.bytes_per_slot for m in memory if m.kind == "state") == (
        9 * (16 * 5120 * 4 + 3 * 5120 * 2))
    params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["params"]))
    assert 3.84e9 < params < 3.86e9


@pytest.mark.parametrize("flag, engine", [
    ("--tp", dict(tp=2)), ("--spec-k", dict(spec_k=2)),
    ("--prefix-cache", dict(prefix_cache=True)),
    ("--kv-dtype int8", dict(kv_dtype="int8")),
    ("--weights-dtype int8", dict(weights_dtype="int8")),
])
def test_what_the_family_cannot_serve_is_refused_by_the_flags_name(flag, engine):
    cfg = model_preset("sambay-tiny")
    with pytest.raises(ValueError, match=flag):
        cfg.check_serving(EngineConfig(
            num_slots=2, prompt_buckets=(16,), max_new_tokens=8, **engine))
    cfg.check_serving(EngineConfig(
        num_slots=2, prompt_buckets=(16,), max_new_tokens=8,
        weights_dtype="bfloat16", prefill_chunk=8))
