"""The cells' own shapes, compiled for the chip without the chip: cell 1's
training step (bert-large, seq 128, 12 x 8 rows, bf16, no dropout) and
cell 2's decode program (gpt2-medium, 48 slots over a paged pool of 1024
tokens a slot) through XLA:TPU and Mosaic for a described `v5e:2x2`, so a
later PR that breaks what the benchmark times hears it here, at no chip
time. The topology is described inside a fixture, never at import: only
one process may load the TPU's library, and every xdist worker imports
every test file.

These tests reach under the CLIs (`make_train_step`, the engine's decode
program). Later PRs may not edit this file, so where the program's
internals have moved the tests SKIP and say what moved: the guard is then
a benchmark PR's to rewrite, not a refactor's to trip over.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
HBM_BYTES = 16e9


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
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


@pytest.fixture
def kernels_on(monkeypatch):
    """Answer the kernel dispatch gate as a one-chip TPU backend does."""
    try:
        from pytorch_distributed_training_tpu.ops import dispatch
    except ImportError as e:
        pytest.skip(f"program internals moved: {e}")
    monkeypatch.setattr(dispatch, "mode", lambda: "direct")
    dispatch.DISPATCH_PATHS.clear()
    yield dispatch
    dispatch.DISPATCH_PATHS.clear()


def on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def fits(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_bert_large_dp1_step_compiles_for_v5e(one_chip, kernels_on):
    cfg = config("bert_large_dp")
    recipe = cfg["recipe"]
    try:
        from pytorch_distributed_training_tpu.models import (
            BertForSequenceClassification,
        )
        from pytorch_distributed_training_tpu.train.optim import adamw_with_schedule
        from pytorch_distributed_training_tpu.train.state import create_train_state
        from pytorch_distributed_training_tpu.train.step import make_train_step
        from pytorch_distributed_training_tpu.utils.config import (
            TrainConfig,
            model_preset,
        )

        mcfg = model_preset("bert-large-cased", compute_dtype="bfloat16",
                            **cfg["program_model"])
        tcfg = TrainConfig()
        model = BertForSequenceClassification(mcfg)
        tx, _ = adamw_with_schedule(tcfg, 1000)
        seq, micro = recipe["max_seq_length"], recipe["micro_batch_per_chip"]
        accum = recipe["global_batch_per_chip"] // micro
        example = {k: jnp.ones((2, seq), jnp.int32)
                   for k in ("input_ids", "attention_mask", "token_type_ids")}
        state = jax.eval_shape(lambda: create_train_state(
            model, tx, jax.random.key(0, impl=tcfg.prng_impl), example))
        step = make_train_step(grad_accum_steps=accum)
    except (ImportError, TypeError, AttributeError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    assert (mcfg.hidden_size, mcfg.num_layers, mcfg.num_heads,
            mcfg.intermediate_size, mcfg.vocab_size) == tuple(
        cfg["model"][k] for k in ("hidden_size", "num_hidden_layers",
                                  "num_attention_heads", "intermediate_size",
                                  "vocab_size"))
    batch = {k: jax.ShapeDtypeStruct((accum, micro, seq), jnp.int32)
             for k in ("input_ids", "attention_mask", "token_type_ids")}
    batch["labels"] = jax.ShapeDtypeStruct((accum, micro), jnp.int32)
    compiled = step.lower(on(one_chip, state), on(one_chip, batch)).compile()
    paths = dict(kernels_on.DISPATCH_PATHS)
    assert paths.get("dal:direct", 0) > 0 and not paths.get("dal:xla"), paths
    assert "tpu_custom_call" in compiled.as_text()
    # state 4.0 GB + temporaries: a quarter to a half of the chip
    assert 0.25 * HBM_BYTES < fits(compiled) < 0.6 * HBM_BYTES


def test_gpt2_medium_decode_program_compiles_for_v5e(one_chip, kernels_on):
    cfg = config("gpt2_medium_paged")
    serving = cfg["serving"]
    try:
        from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
        from pytorch_distributed_training_tpu.serve.engine import (
            DecodeEngine,
            EngineConfig,
        )
        from pytorch_distributed_training_tpu.utils.config import model_preset

        mcfg = model_preset("gpt2-medium")
        econf = EngineConfig(
            num_slots=serving["num_slots"],
            prompt_buckets=tuple(serving["prompt_buckets"]),
            max_new_tokens=serving["max_new_tokens_cap"],
            kv_layout="paged", page_size=serving["page_size"],
            sampling="device", warmup=False)
        slots, pages = econf.num_slots, econf.total_pages
        per_slot = econf.pages_per_slot
    except (ImportError, TypeError, AttributeError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    assert econf.cache_len == cfg["model"]["n_positions"]
    # the pool the cell's memory floor rests on: 48 x 1024 tokens of bf16 K, V
    pool_bytes = pages * serving["page_size"] * 2 * 24 * 1024 * 2
    assert 0.25 * HBM_BYTES < pool_bytes + 1.42e9 < 0.5 * HBM_BYTES
    try:
        import dataclasses

        from pytorch_distributed_training_tpu.serve.paged_cache import (
            strip_tables,
            with_tables,
        )
        from pytorch_distributed_training_tpu.serve.sampling import device_sample

        dcfg = dataclasses.replace(
            mcfg, decode=True, kv_layout="paged",
            kv_page_size=econf.page_size, kv_num_pages=pages)
        model = GPT2LMModel(dcfg)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.ones((slots, 1), jnp.int32)))
        pools = strip_tables(shapes["cache"])
    except (ImportError, TypeError, AttributeError, KeyError, ValueError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    del DecodeEngine

    def decode(params, pools, tokens, bt, ctx, seeds, steps, temps, top_ks):
        cache = with_tables(pools, bt, ctx)
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens[:, None],
            position_ids=ctx[:, None], mutable=["cache"])
        last = logits[:, 0, :].astype(jnp.float32)
        return (device_sample(last, seeds, steps, temps, top_ks),
                strip_tables(vars_["cache"]))

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = (shapes["params"], pools, i32(slots), i32(slots, per_slot),
            i32(slots), i32(slots), i32(slots),
            jax.ShapeDtypeStruct((slots,), jnp.float32), i32(slots))
    try:
        lowered = jax.jit(decode, donate_argnums=(1,)).lower(
            *(on(one_chip, a) for a in args))
    except (TypeError, AttributeError, KeyError, ValueError) as e:
        pytest.skip(f"program internals moved: {type(e).__name__}: {e}")
    compiled = lowered.compile()
    assert 0.25 * HBM_BYTES < fits(compiled) < 0.9 * HBM_BYTES
