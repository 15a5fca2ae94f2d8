"""The state-space hybrid decoder through the serving stack itself
(`InferenceServer` -> `DecodeEngine` ticks -> `PageAllocator`, block tables,
device sampling: the same server, queue, tick and allocator the older
families are served by), and the page walk in rows mode that its decode
step reads pool and rings through, in the Pallas interpreter. Float32 at the
tiny size, so the greedy streams of every engine variant are the model's
own greedy continuation token for token, and the cell's own check
(`served_token_gaps`) reads nought. Sizes, weights and the reference come
from `test_phi4flash.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_phi4flash import (  # noqa: F401  (world: the module's fixture)
    CONFIG,
    PAGE,
    SLOTS,
    TOL,
    ref,
    world,
)

from pytorch_distributed_training_tpu.models import sambay
from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops import paged_attention as pa
from pytorch_distributed_training_tpu.ops.flash_attention import (
    tpu_interpret_mode,
)
from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.utils.config import model_preset

pytestmark = [pytest.mark.serve]

# ------------------------------------------------ the serving stack itself

NEW = 10


@pytest.fixture(scope="module")
def served(world):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 512, n).astype(np.int32) for n in (30, 11, 37, 5, 26)]
    padded = 48

    def greedy(prompt):
        seq = np.zeros((1, padded), np.int32)
        seq[0, :len(prompt)] = prompt
        for n in range(len(prompt), len(prompt) + NEW):
            seq[0, n] = int(jnp.argmax(world["forward"](seq)[0, n - 1]))
        return seq[0, len(prompt):len(prompt) + NEW]

    return prompts, [greedy(p) for p in prompts]


@pytest.mark.parametrize("engine", [
    dict(), dict(prefill_chunk=8), dict(prefill_chunk=16, warmup=True),
], ids=["bucket", "chunked", "chunked_warm"])
def test_every_engine_variant_serves_the_models_own_greedy_tokens(
        world, served, engine):
    """Five requests of unequal length at once on three slots: slots are
    reused after longer requests, idle at the start and the end, and the
    contexts pass three windows."""
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    prompts, want = served
    records = []

    class Sink:
        def emit(self, record):
            records.append(dict(record))

        def flush(self, **kw):
            pass

    registry = MetricsRegistry()
    registry.attach_sink(Sink())
    config = EngineConfig(num_slots=SLOTS, prompt_buckets=(40,),
                          max_new_tokens=12, page_size=PAGE, **engine)
    server = InferenceServer(
        world["model"], world["params"], config, registry=registry).start()
    try:
        requests = [server.submit(p, max_new_tokens=NEW) for p in prompts]
        for r in requests:
            assert wait_until(r.done.is_set, timeout=300), r.status
            assert r.status == "done", r.status
        stats = server.stats()
    finally:
        server.close(drain=False)
    streams = [np.asarray(r.tokens, np.int32) for r in requests]
    for got, expected in zip(streams, want):
        assert got.tolist() == expected.tolist()
    # the cell's own check over what was served: nought in float32
    with jax.default_matmul_precision("highest"):
        gaps = ref.served_token_gaps(
            CONFIG, world["source"],
            [(p.tolist(), s.tolist()) for p, s in zip(prompts, streams)])
    assert gaps["tokens"] == 5 * NEW and gaps["max_logit_gap"] < TOL
    # the three kinds of memory, counted: float32 here
    assert stats["kv_bytes_per_token"] == 2 * 4 * 8 * 4
    assert stats["ring_bytes_per_slot"] == 2 * (2 * 8 * 32 * 4)
    assert stats["state_bytes_per_slot"] == 3 * 128 * (4 * 4 + 3 * 4)
    assert stats["kv_pool_readers"] == 2
    assert stats["kv_pages_total"] == SLOTS * (52 // PAGE)   # ONE pool's pages
    ticks = [r for r in records if r.get("record") == "serve_tick"
             and r.get("decode_active")]
    assert ticks and all(t["live_tokens"] >= t["decode_active"] for t in ticks)
    assert max(t["live_tokens"] for t in ticks) > 2 * 30
    if engine.get("warmup"):
        scopes, = [r for r in records if r.get("record") == "program_scopes"]
        assert set(scopes["scopes"]) == set(sambay.SambaYLM.trace_scopes)
        assert all(scopes["scopes"][s] for s in scopes["scopes"])


def test_an_older_familys_stats_carry_the_new_counters_as_nothing():
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel

    cfg = model_preset("gpt2-tiny")
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    server = InferenceServer(model, params, EngineConfig(
        num_slots=2, prompt_buckets=(16,), max_new_tokens=4)).start()
    try:
        stats = server.stats()
    finally:
        server.close(drain=False)
    assert stats["state_bytes_per_slot"] == stats["ring_bytes_per_slot"] == 0
    assert stats["kv_pool_readers"] == 1


# ------------------------------ the page walk in rows mode (interpreter)


def _pool_case(dtype, lengths, heads=8, kv_heads=4, d=64, page=16, windows=24):
    rng = np.random.default_rng(0)
    slots = len(lengths)
    num_pages = 1 + slots * windows
    table = np.zeros((slots, windows), np.int32)
    ids = rng.permutation(np.arange(1, num_pages))
    at = 0
    for b, n in enumerate(lengths):
        live = -(-n // page)
        table[b, :live] = ids[at:at + live]
        at += live
    shape = (num_pages, page, kv_heads * d)
    k = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    v = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    q = jnp.asarray(rng.standard_normal((slots, heads, d), np.float32), dtype)
    return q, k, v, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_the_rows_page_walk_is_the_formula_over_pool_and_ring(dtype, tol):
    """`paged_attn_rows` in the Pallas interpreter against the XLA formula:
    fewer K/V heads than query heads, two softmaxes over one value, one
    token to three blocks a slot; and a ring read as a slot's fixed run of
    pages, by row `b` and by a named slot."""
    q, k, v, table, lengths = _pool_case(dtype, (1, 17, 300, 129, 128))
    scale = 64 ** -0.5
    dispatch.DISPATCH_PATHS.clear()
    want = pa.differential_paged_decode(q, k, v, table, lengths, scale)
    assert dispatch.DISPATCH_PATHS["paged_attn_rows:xla"] == 1
    with tpu_interpret_mode():
        got = pa.differential_paged_decode(q, k, v, table, lengths, scale)
    assert dispatch.DISPATCH_PATHS["paged_attn_rows:direct"] == 1
    assert got.shape == want.shape == (5, 2, 2, 2, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    rng = np.random.default_rng(1)
    ring = [jnp.asarray(rng.standard_normal((5, 32, 256), np.float32), dtype)
            for _ in range(2)]
    live = jnp.asarray([1, 5, 32, 16, 17], jnp.int32)
    for slot, rows in ((None, slice(None)), (jnp.asarray([3], jnp.int32), slice(1))):
        args = (q[rows], *ring, slot, live[rows], scale, 16)
        want = pa.differential_ring_decode(*args)
        with tpu_interpret_mode():
            got = pa.differential_ring_decode(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    dispatch.DISPATCH_PATHS.clear()
