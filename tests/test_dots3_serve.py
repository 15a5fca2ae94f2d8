"""The decoder with window layers (`dots3-tiny`) through the serving stack
itself: `InferenceServer` -> `DecodeEngine` ticks -> `PageAllocator`, block
tables, `PrefixCache`, device sampling, and `cli.serve_lm.main`. Float32 at
the tiny size, so every engine variant serves the model's own greedy
continuation token for token and the cell's check reads nought. The shared
prefix (22 tokens) ends mid-page, so a hit maps 5 pages and copies the
sixth: the tails' windows (9) reach back into mapped pages."""

import importlib
import io
import json
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
from pytorch_distributed_training_tpu.serve import (  # noqa: E402
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.server import wait_until  # noqa: E402
from pytorch_distributed_training_tpu.utils.config import model_preset  # noqa: E402
from test_dots3 import CONFIG, MODEL  # noqa: E402

ref = importlib.import_module("reference.dots3_share8")

pytestmark = [pytest.mark.serve]

NEW = 6


@pytest.fixture(scope="module")
def world():
    source = family.source(CONFIG, ref.weight_spec(MODEL), 21)
    cfg = model_preset("dots3-tiny")
    assert isinstance(cfg, lm.LatentMoEConfig)
    model = lm.LatentMoELM(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    params = adapters.install(params, source, family.of(CONFIG))
    rng = np.random.default_rng(4)
    prefix = rng.integers(1, 512, 22).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(1, 512, n).astype(np.int32)])
               for n in (9, 5, 12)]
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


@pytest.mark.parametrize("engine", [
    dict(),
    dict(prefill_chunk=8, prefix_cache=True, warmup=True),
], ids=["bucket", "chunked_prefix_cache_warm"])
def test_every_engine_variant_serves_the_models_own_greedy_tokens(world, engine):
    config = EngineConfig(
        num_slots=3, prompt_buckets=(40,), max_new_tokens=8, page_size=4,
        **engine)
    server = InferenceServer(world["model"], world["params"], config).start()
    try:
        streams = []
        for p in world["prompts"]:          # one at a time: inserts, then hits
            r = server.submit(p, max_new_tokens=NEW)
            assert wait_until(r.done.is_set, timeout=300), r.status
            assert r.status == "done", r.status
            streams.append(np.asarray(r.tokens, np.int32))
        stats = server.stats()
    finally:
        server.close(drain=False)
    for got, want in zip(streams, world["want"]):
        assert got.tolist() == want.tolist()
    gaps = ref.served_token_gaps(
        CONFIG, world["source"],
        [(p.tolist(), s.tolist()) for p, s in zip(world["prompts"], streams)])
    assert gaps["tokens"] == 3 * NEW and gaps["max_logit_gap"] < 1e-4
    cfg = world["model"].config
    # two latent widths: 2 full rows + 2 indexer keys + 3 window rows
    assert stats["kv_bytes_per_token"] == 4 * (2 * 128 + 2 * 16 + 3 * 256)
    assert stats["window_rows_per_slot"] == cfg.sliding_window_size == 9
    warm = engine.get("warmup")
    # the warmed engine audits its decode program: one gather a full
    # layer's selection, ONE of the window group's wide rows
    assert stats["latent_row_gathers"] == (2 if warm else None)
    assert stats["window_row_gathers"] == (1 if warm else None)
    if engine.get("prefix_cache"):
        assert stats["prefix_cache"]["prefix_hits"] == 2
        assert stats["prefix_cached_tokens"] == 2 * 22
        assert stats["kv_pool_relayout_ops"] == 0


def test_cli_serves_the_preset_with_the_prefix_cache():
    from pytorch_distributed_training_tpu.cli import serve_lm

    lines = "".join(json.dumps({"id": f"r{i}", "prompt": "notes on a long doc",
                                "max_new_tokens": 4}) + "\n" for i in range(2))
    out = io.StringIO()
    serve_lm.main(["--model", "dots3-tiny", "--prompt-buckets", "32",
                   "--max-new-tokens-cap", "8", "--page-size", "4",
                   "--num-pages", "40", "--prefix-cache", "--prefill-chunk", "8"],
                  in_stream=io.StringIO(lines), out_stream=out)
    events = [json.loads(ln) for ln in out.getvalue().splitlines()
              if ln.startswith("{")]
    done = [e for e in events if e.get("event") == "done"]
    assert len(done) == 2 and all(e["status"] == "done" for e in done)
    assert "dots3-note-share8" in serve_lm.__doc__
    assert isinstance(model_preset("dots3-note-share8"), lm.LatentMoEConfig)


@pytest.mark.parametrize("argv,flag", [
    (["--tp", "2"], "--tp"),
    (["--spec-k", "2"], "--spec-k"),
    (["--weights-dtype", "int8"], "--weights-dtype int8"),
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
])
def test_cli_refuses_each_unsupported_flag_by_name(argv, flag):
    from pytorch_distributed_training_tpu.cli import serve_lm

    with pytest.raises(SystemExit) as e:
        serve_lm.main(["--model", "dots3-tiny", *argv])
    assert flag in str(e.value.code)
