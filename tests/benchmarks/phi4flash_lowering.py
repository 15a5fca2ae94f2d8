"""`phi4flash_reason_decode`'s own programs compiled for the chip without
the chip, after `glm52_lowering.py`: the decode step (64 slots over
288-page block tables, 8 rings and 9 states beside the one page pool) and
the prefill chunk (512 tokens into one slot) of the state-space hybrid at
the published widths, all 32 layers and the whole vocabulary, from abstract
shapes, through XLA:TPU for a described `v5e:2x2`. Shared by the two test
files (one program each, so that xdist runs them side by side)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest
from glm52_lowering import BENCH, HBM_BYTES, one_chip


def compile_program(which: str, monkeypatch):
    """(compiled, bytes of weights, bytes of pools, rings and states) of
    the cell's `decode` or `chunk` program."""
    with open(os.path.join(BENCH, "configs", "phi4_mini_flash.json")) as f:
        cfg = json.load(f)
    serving = cfg["serving"]
    device = one_chip()
    try:
        from pytorch_distributed_training_tpu.models import sambay
        from pytorch_distributed_training_tpu.ops import dispatch
        from pytorch_distributed_training_tpu.serve.engine import EngineConfig
        from pytorch_distributed_training_tpu.serve.paged_cache import (
            strip_tables,
            with_tables,
        )
        from pytorch_distributed_training_tpu.serve.sampling import device_sample
        from pytorch_distributed_training_tpu.utils.config import model_preset

        # the gate answers as one chip does: the page walk reads the pool
        # and the rings
        monkeypatch.setattr(dispatch, "mode", lambda: "direct")
        mcfg = model_preset(cfg["argv"][cfg["argv"].index("--model") + 1])
        econf = EngineConfig(
            num_slots=serving["num_slots"],
            prompt_buckets=tuple(serving["prompt_buckets"]),
            max_new_tokens=serving["max_new_tokens_cap"], kv_layout="paged",
            page_size=serving["page_size"], num_pages=serving["num_pages"],
            sampling="device", prefill_chunk=serving["prefill_chunk"],
            weights_dtype="bfloat16")
        dcfg = dataclasses.replace(
            mcfg, decode=True, kv_page_size=econf.page_size,
            kv_num_pages=econf.total_pages, kv_num_slots=econf.num_slots)
        memory = dcfg.slot_memory()
        model = sambay.SambaYLM(dcfg)
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.ones((1, 1), jnp.int32),
            position_ids=jnp.zeros((1, 1), jnp.int32)))
        pools = strip_tables(shapes["cache"])
    except (ImportError, TypeError, AttributeError, KeyError, ValueError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    slots, width, chunk = econf.num_slots, econf.pages_per_slot, econf.prefill_chunk
    assert econf.cache_len == cfg["model"]["cache_len"]

    def decode(params, pools, tokens, bt, ctx, seeds, steps, temps, top_ks):
        cache = with_tables(pools, bt, ctx, memory=memory)
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens[:, None],
            position_ids=ctx[:, None], mutable=["cache"])
        last = logits[:, 0, :].astype(jnp.float32)
        return (device_sample(last, seeds, steps, temps, top_ks),
                strip_tables(vars_["cache"]))

    def prefill_chunk(params, pools, ids, ctx0, sample_idx, bt_row, seed, temp,
                      top_k, slot, real):
        cache = with_tables(pools, bt_row, ctx0, memory=memory, slot=slot,
                            chunk_len=real)
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, ids,
            position_ids=ctx0[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None],
            mutable=["cache"], logit_index=sample_idx[None])
        token = device_sample(
            logits[0, 0].astype(jnp.float32)[None], seed[None],
            jnp.zeros((1,), jnp.int32), temp[None], top_k[None])[0]
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
            i32(1, width), i32(), f32(), i32(), i32(1), i32(1))
    try:
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(*(on(a) for a in args))
    except (TypeError, AttributeError, KeyError, ValueError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    nbytes = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree))
    paths = dict(dispatch.DISPATCH_PATHS)
    return lowered.compile(), nbytes(shapes["params"]), nbytes(pools), paths


def check(which: str, monkeypatch):
    compiled, weights, kept, paths = compile_program(which, monkeypatch)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"phi4flash {which}: weights {weights / 1e9:.3f} GB, pools, rings "
          f"and states {kept / 1e9:.3f} GB, the compiler's whole figure "
          f"{total / 1e9:.3f} GB (temporaries {m.temp_size_in_bytes / 1e9:.3f})")
    # 7.70 GB of bfloat16 weights; 1.51 GB of pages, 1.34 of rings and
    # 0.21 of states: two thirds of the chip, the compiler's whole figure
    # under it
    assert 7.6e9 < weights < 7.8e9 and 2.9e9 < kept < 3.2e9
    assert 0.6 * HBM_BYTES < total < HBM_BYTES
    if which == "decode":
        # the shared pool's eight reads and the eight rings' go through
        # the page walk, chosen by the gate
        assert paths.get("paged_attn_rows:direct", 0) >= 2
        assert not paths.get("paged_attn_rows:xla")
    return compiled, total
