"""The latent-attention expert decoder through the serving stack itself:
`InferenceServer` -> `DecodeEngine` ticks -> `PageAllocator`, block tables,
`PrefixCache`, device sampling: the same server, queue, tick and allocator
`gpt2-medium` is served by. Float32 at the tiny size, so the greedy streams
of every engine variant are the model's own greedy continuation token for
token, and the cell's own check (`served_token_gaps`) reads nought.
"""

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
from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel  # noqa: E402
from pytorch_distributed_training_tpu.serve import (  # noqa: E402
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.server import wait_until  # noqa: E402
from pytorch_distributed_training_tpu.utils.config import model_preset  # noqa: E402
from test_latent_moe import CONFIG, MODEL  # noqa: E402

ref = importlib.import_module("reference.glm52_share16")

pytestmark = [pytest.mark.serve]

NEW = 6


@pytest.fixture(scope="module")
def world():
    source = family.source(CONFIG, ref.weight_spec(MODEL), 21)
    cfg = model_preset("latent-moe-tiny")
    assert isinstance(cfg, lm.LatentMoEConfig)
    model = lm.LatentMoELM(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    params = adapters.install(params, source, family.of(CONFIG))
    rng = np.random.default_rng(4)
    prefix = rng.integers(1, 512, 22).astype(np.int32)   # ends mid-page
    prompts = [np.concatenate([prefix, rng.integers(1, 512, n).astype(np.int32)])
               for n in (9, 5, 12)]

    # one compiled forward at one padded length (causal: the padding after
    # a position cannot reach it), not an eager apply at every length
    forward = jax.jit(lambda ids: model.apply({"params": params}, ids))
    padded = max(len(p) for p in prompts) + NEW

    def greedy(prompt):
        seq = np.zeros((1, padded), np.int32)
        seq[0, :len(prompt)] = prompt
        for n in range(len(prompt), len(prompt) + NEW):
            seq[0, n] = int(jnp.argmax(forward(seq)[0, n - 1]))
        return seq[0, len(prompt):len(prompt) + NEW]

    return dict(source=source, model=model, params=params, prompts=prompts,
                want=[greedy(p) for p in prompts])


def _serve(world, **engine):
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    records = []

    class Sink:
        def emit(self, record):
            records.append(dict(record))

        def flush(self, **kw):
            pass

    registry = MetricsRegistry()
    registry.attach_sink(Sink())
    config = EngineConfig(
        num_slots=3, prompt_buckets=(40,), max_new_tokens=8, page_size=4,
        **engine)
    server = InferenceServer(
        world["model"], world["params"], config, registry=registry).start()
    try:
        streams = []
        for p in world["prompts"]:          # one at a time: inserts, then hits
            r = server.submit(p, max_new_tokens=NEW)
            assert wait_until(r.done.is_set, timeout=300), r.status
            assert r.status == "done", r.status
            streams.append(np.asarray(r.tokens, np.int32))
        return streams, server.stats(), records
    finally:
        server.close(drain=False)


@pytest.mark.parametrize("engine", [
    dict(),
    dict(prefill_chunk=8),
    dict(prefill_chunk=8, prefix_cache=True),
    dict(prefix_cache=True, warmup=True),
], ids=["bucket", "chunked", "chunked_prefix_cache", "bucket_prefix_cache_warm"])
def test_every_engine_variant_serves_the_models_own_greedy_tokens(world, engine):
    streams, stats, records = _serve(world, **engine)
    for got, want in zip(streams, world["want"]):
        assert got.tolist() == want.tolist()
    # the cell's own check over what was served: nought in float32
    gaps = ref.served_token_gaps(
        CONFIG, world["source"],
        [(p.tolist(), s.tolist()) for p, s in zip(world["prompts"], streams)])
    assert gaps["tokens"] == 3 * NEW and gaps["max_logit_gap"] < 1e-4
    # counters: routed experts, the pools' bytes, the prefix cache
    assert stats["moe"]["steps"] > 0
    assert stats["moe"]["held_tokens"] + stats["moe"]["absent_pairs"] == (
        3 * (NEW - 1) * 3 * MODEL["num_experts_per_tok"])
    cfg = world["model"].config
    assert stats["kv_bytes_per_token"] == 4 * (4 * cfg.latent_row + 2 * 16)
    # the warmed engine audits its decode program: one gather of latent
    # rows a selection group (full, shared | full, shared), not one a layer
    assert stats["latent_row_gathers"] == (2 if engine.get("warmup") else None)
    # a step's routing counts ride the record of the tick that RETIRED it
    # (one tick after its dispatch, where a step stays in flight)
    ticks = [r for r in records if r.get("record") == "serve_tick"]
    dispatched = [t for t in ticks if t["decode_active"]]
    retired = [t for t in ticks if "expert_tokens_max" in t]
    assert len(retired) == len(dispatched) == stats["moe"]["steps"] > 0
    assert all(
        t["expert_tokens_max"] >= t["expert_tokens_mean"] >= 0
        for t in retired)
    requests = [r for r in records if r.get("record") == "serve_request"]
    if engine.get("prefix_cache"):
        # 22 shared tokens = 5 whole pages and 2 lanes of a sixth: the
        # later two prompts map the pages and copy the sixth on write
        assert stats["prefix_cache"]["prefix_hits"] == 2
        assert stats["prefix_cache"]["cow_copies"] == 2
        assert stats["prefix_cached_tokens"] == 2 * 22
        assert [r["cached_tokens"] for r in requests] == [0, 22, 22]
        assert sum(t["cached_tokens"] for t in records
                   if t.get("record") == "serve_tick") == 44
    else:
        assert stats["prefix_cached_tokens"] == 0
    if engine.get("prefill_chunk"):
        assert sum(t["chunks"] for t in records
                   if t.get("record") == "serve_tick") == stats["prefill_chunks"] > 0
    if engine.get("warmup"):
        assert stats["kv_pool_relayout_ops"] == 0


@pytest.mark.parametrize("engine,flag", [
    (dict(tp=2), "--tp"),
    (dict(spec_k=2), "--spec-k"),
    (dict(weights_dtype="int8"), "--weights-dtype int8"),
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(kv_layout="dense"), "--kv-layout dense"),
    (dict(sampling="host"), "--sampling host"),
])
def test_unsupported_flags_are_refused_by_name_at_build(world, engine, flag):
    # the last two by ``EngineConfig`` itself, for every family
    with pytest.raises(ValueError, match=flag):
        config = EngineConfig(
            num_slots=2, prompt_buckets=(16,), max_new_tokens=4, **engine)
        InferenceServer(world["model"], world["params"], config)


@pytest.mark.parametrize("argv,flag", [
    (["--tp", "2"], "--tp"),
    (["--spec-k", "2"], "--spec-k"),
    (["--weights-dtype", "int8"], "--weights-dtype int8"),
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
    (["--kv-layout", "dense"], "--kv-layout dense"),
    (["--sampling", "host"], "--sampling host"),
])
def test_cli_refuses_them_at_start_up_before_anything_loads(
        argv, flag, capsys):
    from pytorch_distributed_training_tpu.cli import serve_lm

    with pytest.raises(SystemExit) as e:
        serve_lm.main(["--model", "latent-moe-tiny", *argv])
    if e.value.code == 2:
        # argparse's own exit: the flag has one choice left, and the
        # message on stderr names it and the value given
        said = capsys.readouterr().err
        assert f"argument {argv[0]}: invalid choice: '{argv[1]}'" in said
    else:
        assert flag in str(e.value.code)


def test_cli_help_names_the_preset_flags():
    from pytorch_distributed_training_tpu.cli import serve_lm

    text = serve_lm.build_parser().format_help()
    assert "bfloat16" in text
    assert "glm-5.2-share16" in serve_lm.__doc__
    assert isinstance(model_preset("glm-5.2-share16"), lm.LatentMoEConfig)
    with pytest.raises(KeyError, match="glm-5.2-share16"):
        model_preset("no-such-model")


def test_weights_dtype_bfloat16_keeps_every_floating_leaf_in_bfloat16():
    cfg = model_preset(
        "gpt2-tiny", attention_impl="reference", hidden_dropout=0.0,
        attention_dropout=0.0)
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    config = EngineConfig(num_slots=2, prompt_buckets=(16,), max_new_tokens=4,
                          weights_dtype="bfloat16")
    server = InferenceServer(model, params, config).start()
    try:
        assert {str(x.dtype) for x in jax.tree.leaves(server.engine.params)} == {
            "bfloat16"}
        r = server.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        assert wait_until(r.done.is_set, timeout=120) and r.status == "done"
        assert server.stats()["weights_dtype"] == "bfloat16"
    finally:
        server.close(drain=False)
    with pytest.raises(ValueError, match="float32/bfloat16/int8"):
        EngineConfig(weights_dtype="float16")
