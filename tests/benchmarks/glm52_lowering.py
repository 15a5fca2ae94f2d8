"""`glm52_agent_decode`'s own programs compiled for the chip without the
chip, after `test_cells_lower_for_v5e.py`: the decode step (48 slots over
1,184-page block tables) and the prefill chunk (512 tokens at a context)
of the latent expert decoder at the published widths, from abstract shapes,
through XLA:TPU for a described `v5e:2x2`. Shared by the two test files
(one program each, so that xdist runs them side by side and each stays
under a minute)."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
HBM_BYTES = 16e9


def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:  # noqa: BLE001 - whatever libtpu says, it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_program(which: str):
    """(compiled, element counts of the pools, bytes of weights and pools)
    of the cell's `decode` or `chunk` program."""
    with open(os.path.join(BENCH, "configs", "glm52_share16.json")) as f:
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
    return lowered.compile(), elements, nbytes(shapes["params"]), nbytes(pools)


def check(which: str):
    from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
        count_relayouts, count_space_moves)

    compiled, pool_elements, weights, pools = compile_program(which)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    # 9.4 GB of bfloat16 weights and 1.5 GB of pools: over half the chip,
    # and the compiler's whole figure under it
    assert 9.3e9 < weights < 9.5e9 and 1.4e9 < pools < 1.7e9
    assert 0.5 * HBM_BYTES < total < HBM_BYTES
    # the pools keep one layout from parameter to donated result: the one
    # whole-pool copy the compiler may make (decode: layer 4's 48 MB
    # indexer pool, fetched into on-chip memory ahead of its scores and
    # written back) changes the memory space alone
    text = compiled.as_text()
    moves = count_space_moves(text, pool_elements)
    assert count_relayouts(text, pool_elements) == moves <= 1
