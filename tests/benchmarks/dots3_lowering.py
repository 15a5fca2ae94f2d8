"""`dots3_notes_decode`'s own programs compiled for the chip without the
chip, after `glm52_lowering.py`: the decode step (64 slots over 1,184-page
block tables) and the prefill chunk (512 tokens at a context) of the
latent expert decoder with window layers at the published widths, from
abstract shapes, through XLA:TPU for a described `v5e:2x2`. Shared by the
two test files (one program each, so that xdist runs them side by side)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest
from glm52_lowering import BENCH, HBM_BYTES, one_chip


def compile_program(which: str):
    """(compiled, element counts of the pools, bytes of weights and pools,
    the model's config) of the cell's `decode` or `chunk` program."""
    with open(os.path.join(BENCH, "configs", "dots3_share8.json")) as f:
        cfg = json.load(f)
    serving = cfg["serving"]
    device = one_chip()
    try:
        from pytorch_distributed_training_tpu.models import latent_moe as lm
        from pytorch_distributed_training_tpu.ops.moe import routing_totals
        from pytorch_distributed_training_tpu.serve.engine import EngineConfig
        from pytorch_distributed_training_tpu.serve.paged_cache import (
            strip_tables,
            with_tables,
        )
        from pytorch_distributed_training_tpu.serve.sampling import device_sample
        from pytorch_distributed_training_tpu.utils.config import model_preset

        mcfg = model_preset(cfg["argv"][cfg["argv"].index("--model") + 1])
        econf = EngineConfig(
            num_slots=serving["num_slots"],
            prompt_buckets=tuple(serving["prompt_buckets"]),
            max_new_tokens=serving["max_new_tokens_cap"], kv_layout="paged",
            page_size=serving["page_size"], num_pages=serving["num_pages"],
            sampling="device", prefill_chunk=serving["prefill_chunk"],
            prefix_cache=True, weights_dtype="bfloat16")
        dcfg = dataclasses.replace(
            mcfg, decode=True, kv_page_size=econf.page_size,
            kv_num_pages=econf.total_pages)
        decode_model = lm.LatentMoELM(dcfg)
        chunk_model = lm.LatentMoELM(
            dataclasses.replace(dcfg, paged_multiquery=True))
        shapes = jax.eval_shape(lambda: decode_model.init(
            jax.random.key(0), jnp.ones((1, 1), jnp.int32),
            position_ids=jnp.zeros((1, 1), jnp.int32)))
        pools = strip_tables(shapes["cache"])
    except (ImportError, TypeError, AttributeError, KeyError, ValueError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    slots, width, chunk = econf.num_slots, econf.pages_per_slot, econf.prefill_chunk
    assert econf.cache_len == cfg["model"]["cache_len"]

    def decode(params, pools, tokens, bt, ctx, seeds, steps, temps, top_ks):
        cache = with_tables(pools, bt, ctx)
        logits, vars_ = decode_model.apply(
            {"params": params, "cache": cache}, tokens[:, None],
            position_ids=ctx[:, None], mutable=["cache", "routing"],
            token_mask=(ctx > 0)[:, None])
        last = logits[:, 0, :].astype(jnp.float32)
        return ((device_sample(last, seeds, steps, temps, top_ks),
                 routing_totals(vars_["routing"])),
                strip_tables(vars_["cache"]))

    def prefill_chunk(params, pools, ids, ctx0, sample_idx, bt_row, seed, temp,
                      top_k):
        cache = with_tables(pools, bt_row, ctx0)
        logits, vars_ = chunk_model.apply(
            {"params": params, "cache": cache}, ids,
            position_ids=ctx0[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None],
            mutable=["cache"])
        last = jnp.take_along_axis(
            logits, sample_idx[None, None, None], axis=1)[0, 0].astype(jnp.float32)
        token = device_sample(
            last[None], seed[None], jnp.zeros((1,), jnp.int32), temp[None],
            top_k[None])[0]
        return token, strip_tables(vars_["cache"])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=device), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    if which == "decode":
        fn, args = decode, (
            shapes["params"], pools, i32(slots), i32(slots, width), i32(slots),
            i32(slots), i32(slots), f32(slots), i32(slots))
    else:
        fn, args = prefill_chunk, (
            shapes["params"], pools, i32(1, chunk), i32(1), i32(),
            i32(1, width), i32(), f32(), i32())
    try:
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(*(on(a) for a in args))
    except (TypeError, AttributeError, KeyError, ValueError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    nbytes = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    elements = {math.prod(x.shape) for x in jax.tree.leaves(pools)}
    return (lowered.compile(), elements, nbytes(shapes["params"]),
            nbytes(pools), dcfg)


def check(which: str):
    from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
        count_relayouts, count_row_gathers, count_space_moves)

    compiled, pool_elements, weights, pools, dcfg = compile_program(which)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"{which}: weights {weights} B, pools {pools} B, the compiler's "
          f"whole figure {total} B (temporaries {m.temp_size_in_bytes} B)")
    # 8.17 GB of bfloat16 weights and 2.29 GB of pools: two thirds of the
    # chip, and the compiler's whole figure under it
    assert 8.1e9 < weights < 8.25e9 and 2.2e9 < pools < 2.4e9
    assert 0.6 * HBM_BYTES < total < HBM_BYTES
    text = compiled.as_text()
    moves = count_space_moves(text, pool_elements)
    assert count_relayouts(text, pool_elements) == moves <= 2
    if which == "decode":
        # one gather of latent rows a full layer, ONE of the window
        # group's wide rows: the counters the engine's stats print
        assert count_row_gathers(text, "sparse_attn.gather", dcfg.latent_row) == 2
        assert count_row_gathers(text, "window_attn", dcfg.window_row) == 1
