"""Tensor-parallel serving tests (serve/engine.py tp mesh,
parallel/sharding.py serve rules, analysis/spmd/manifest.py
serve_tp_manifest): bit-identity of tp=2 streams against tp=1 and one-shot
generate() — greedy, fixed-seed sampled, speculative, chunked prefill —
head-divisibility rejection, paged-pool sharding arithmetic (page axis
whole, head axis split, allocator unchanged), sharded hot-swap with zero
retraces under strict guards, the per-layer all-reduce comm manifest on
the hot program, and the deviation path when weights are deliberately
replicated. Runs on the suite's 8 virtual CPU devices; tier-1.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
    extract_collectives,
    summarize_collectives,
)
from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
    serve_tp_manifest,
)
from pytorch_distributed_training_tpu.models.generate import generate
from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.utils.config import model_preset

pytestmark = [pytest.mark.serve, pytest.mark.tp]


# gpt2-tiny: 2 layers, hidden 64, 4 heads (tp=2 -> 2 heads per shard)
LAYERS, HIDDEN, HEADS = 2, 64, 4


class ListSink:
    """In-memory telemetry sink (same contract as JsonlSink.emit)."""

    def __init__(self):
        self.records = []

    def emit(self, record):
        rec = dict(record)
        rec.setdefault("ts", time.time())
        self.records.append(rec)

    def flush(self, **kw):
        pass

    def of(self, kind):
        return [r for r in self.records if r.get("record") == kind]


@pytest.fixture(scope="module")
def lm():
    cfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))[
        "params"
    ]
    return model, params


def _registry():
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    return reg, sink


def _prompts(model, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, model.config.vocab_size, n).astype(np.int32)
        for n in lengths
    ]


def _want(model, params, prompts, T):
    return [
        np.asarray(generate(model, params, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]


def _run_server(model, params, prompts, T, *, tp=1, temperature=0.0,
                top_k=0, seed=0, guards=None, registry=None, **cfg_kw):
    reg, sink = (registry, None) if registry is not None else _registry()
    cfg_kw.setdefault("prompt_buckets", (4, 8, 16))
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, max_new_tokens=T, kv_layout="paged",
            sampling="device", page_size=4, tp=tp, **cfg_kw,
        ),
        queue_depth=16, registry=reg, guards=guards,
    ).start()
    try:
        reqs = [
            server.submit(
                p, max_new_tokens=T, temperature=temperature, top_k=top_k,
                seed=seed + i,
            )
            for i, p in enumerate(prompts)
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]
    toks = [np.asarray(r.tokens, np.int32) for r in reqs]
    return toks, server.stats(), sink


# ------------------------------------------------------- stream identity


def test_tp_greedy_bit_identical_to_tp1_and_generate(lm):
    """The acceptance pin: a tp=2 engine's greedy streams are bit-identical
    to the single-device engine's AND to one-shot generate() — tensor
    parallelism is a partitioning knob, not a numerics change."""
    model, params = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = _want(model, params, prompts, T)
    tp1, stats1, _ = _run_server(model, params, prompts, T, tp=1)
    tp2, stats2, _ = _run_server(model, params, prompts, T, tp=2)
    for i, (a, b, ref) in enumerate(zip(tp1, tp2, want)):
        np.testing.assert_array_equal(a, ref, err_msg=f"request {i} (tp1)")
        np.testing.assert_array_equal(b, ref, err_msg=f"request {i} (tp2)")
    assert stats1["tp"] == 1 and stats2["tp"] == 2


def test_tp_fixed_seed_sampled_identical(lm):
    """Fixed-seed sampled decode survives sharding exactly: the logits the
    sampler folds in are the SAME f32 values after the per-layer
    all-reduces, so (seed, step) streams match token for token."""
    model, params = lm
    T = 6
    prompts = _prompts(model, [3, 7, 12], seed=3)
    kw = dict(temperature=0.8, top_k=5, seed=11)
    tp1, _, _ = _run_server(model, params, prompts, T, tp=1, **kw)
    tp2, _, _ = _run_server(model, params, prompts, T, tp=2, **kw)
    for i, (a, b) in enumerate(zip(tp1, tp2)):
        assert len(b) == T
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


def test_tp_spec_and_chunked_identical(lm):
    """Speculation and chunked prefill compose with sharding: the verify
    and chunk programs run under the same mesh and stay greedy-exact
    against the unsharded reference."""
    model, params = lm
    T = 5
    prompts = _prompts(model, [3, 9, 14, 16, 5], seed=2)
    want = _want(model, params, prompts, T)
    spec2, stats_s, _ = _run_server(
        model, params, prompts, T, tp=2, spec_k=3,
    )
    chunk2, stats_c, _ = _run_server(
        model, params, prompts, T, tp=2, prefill_chunk=4,
    )
    for i, (s, c, ref) in enumerate(zip(spec2, chunk2, want)):
        np.testing.assert_array_equal(s, ref, err_msg=f"request {i} (spec)")
        np.testing.assert_array_equal(
            c, ref, err_msg=f"request {i} (chunked)"
        )
    assert stats_s["spec_dispatches"] > 0
    assert stats_c["prefill_chunks"] > 0


# ------------------------------------------------------------ validation


def test_tp_head_divisibility_rejected(lm):
    """tp must divide num_heads and intermediate_size; the error names the
    offending axis and sizes instead of failing deep inside GSPMD."""
    model, params = lm
    with pytest.raises(ValueError, match=r"tp=3 does not divide.*num_heads=4"):
        InferenceServer(
            model, params,
            EngineConfig(
                num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
                kv_layout="paged", sampling="device", tp=3,
            ),
        )


def test_tp_requires_paged_device_sampling():
    with pytest.raises(ValueError, match="--kv-layout dense: .*PR 31"):
        EngineConfig(
            num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
            kv_layout="dense", sampling="host", tp=2,
        )


# -------------------------------------------------- pool sharding layout


def test_tp_pool_sharding_arithmetic(lm):
    """The lane-dense paged pools [N, P, H*D] shard ONLY on their last
    axis: the page axis stays whole (allocator arithmetic and block tables
    are tp-invariant), each shard holds a contiguous block of heads/tp
    heads, and pool capacity matches the tp=1 engine's exactly."""
    from pytorch_distributed_training_tpu.parallel.sharding import (
        serve_pool_pspec,
    )

    model, params = lm

    def engine(tp):
        return InferenceServer(
            model, params,
            EngineConfig(
                num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
                kv_layout="paged", sampling="device", page_size=4, tp=tp,
            ),
        ).engine

    e1, e2 = engine(1), engine(2)
    pool_leaves = jax.tree.leaves(e2._cache)
    assert pool_leaves
    want_spec = serve_pool_pspec()
    for leaf in pool_leaves:
        assert leaf.sharding.spec == want_spec
        num_pages, page_size, lanes = leaf.shape
        shard = leaf.sharding.shard_shape(leaf.shape)
        # page and page-size axes whole, the folded heads axis split
        assert shard[0] == num_pages and shard[1] == page_size
        assert shard[2] == lanes // 2 == HIDDEN // 2
    # allocator arithmetic is untouched by sharding: identical capacity
    s1, s2 = e1.stats(), e2.stats()
    assert s1["kv_pages_total"] == s2["kv_pages_total"]
    assert s1["kv_page_size"] == s2["kv_page_size"]


# ------------------------------- strict scope, comm manifest, hot swap


def test_tp_strict_scope_comm_manifest_and_sharded_swap_no_retrace(lm):
    """One strict-guard session covers the tick-wide contracts: the hot
    decode program's comm audit CONFORMS to serve_tp_manifest (exactly
    2 all-reduces per layer — attention-out + mlp_down — bounded bytes,
    no weight all-gather), cache donation survives sharded lowering, and a
    live hot swap lands as per-shard device_puts: zero retraces, zero
    implicit transfers, post-swap streams identical to serving the new
    weights from scratch."""
    from pytorch_distributed_training_tpu.analysis.guards import GuardSet

    model, pA = lm
    pB = jax.tree.map(lambda x: x + 0.01 * jnp.sign(x + 0.5), pA)
    reg, sink = _registry()
    gs = GuardSet(mode="strict", registry=reg)
    server = InferenceServer(
        model, pA,
        EngineConfig(
            num_slots=2, prompt_buckets=(4, 8), max_new_tokens=4,
            kv_layout="paged", sampling="device", page_size=4,
            warmup=True, tp=2,
        ),
        queue_depth=16, registry=reg, guards=gs, weights_step=1,
    ).start()
    try:
        prompts = _prompts(model, [3, 6, 2, 7], seed=4)
        reqs = [
            server.submit(p, max_new_tokens=4, seed=i)
            for i, p in enumerate(prompts)
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        )
        ticket = server.engine.request_swap(pB, 2)
        assert ticket.done.wait(30) and ticket.ok
        prompt = _prompts(model, [5], seed=9)[0]
        r_post = server.submit(prompt, max_new_tokens=4)
        assert wait_until(r_post.done.is_set, timeout=120)
    finally:
        server.close()

    # swapped weights answer, bit-identical to a fresh unsharded serve
    want = np.asarray(
        generate(model, pB, prompt[None], max_new_tokens=4)
    )[0, len(prompt):]
    np.testing.assert_array_equal(np.asarray(r_post.tokens), want)

    stats = server.stats()
    assert stats["tp"] == 2 and stats["weights_step"] == 2
    assert stats["swaps"] == 1 and stats["swap_rollbacks"] == 0
    # the swap reused the load-time shardings: same placement, same
    # shapes -> the sharded programs never retraced
    assert stats["guard_recompiles"] == 0
    assert stats["guard_implicit_transfers"] == 0
    assert not sink.of("recompile") and not sink.of("implicit_transfer")

    (comm,) = sink.of("comm_audit")
    assert comm["name"] == "serve_decode" and comm["ok"] is True
    assert comm["deviations"] == []
    ar = comm["by_kind"]["all-reduce"]
    assert ar["count"] == 2 * LAYERS
    # payload per all-reduce: [slots=2, 1, hidden] f32 activations
    assert ar["bytes"] == 2 * LAYERS * (2 * 1 * HIDDEN * 4)
    assert "all-gather" not in comm["by_kind"]
    donations = [
        r for r in sink.of("donation_audit") if r["name"] == "serve_decode"
    ]
    assert donations and all(r.get("aliased") for r in donations)


def test_tp_manifest_catches_replicated_weights(lm):
    """The deviation path: compile the same model with every weight
    REPLICATED over the mesh — GSPMD then inserts no collectives at all —
    and the serve manifest must flag the missing required all-reduce."""
    from pytorch_distributed_training_tpu.comms.mesh import (
        MeshConfig,
        build_mesh,
    )

    model, params = lm
    mesh = build_mesh(
        MeshConfig(data=1, fsdp=1, stage=1, model=2, seq=1),
        devices=jax.devices()[:2],
    )
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    params_r = jax.device_put(params, jax.tree.map(lambda _: repl, params))
    tokens = jax.device_put(jnp.ones((2, 4), jnp.int32), repl)
    txt = (
        jax.jit(lambda p, t: model.apply({"params": p}, t))
        .lower(params_r, tokens)
        .compile()
        .as_text()
    )
    summary = summarize_collectives(extract_collectives(txt, world_size=2))
    manifest = serve_tp_manifest(
        2, layers=LAYERS, hidden=HIDDEN, max_q_tokens=2,
    )
    deviations = manifest.check(summary)
    assert any(
        "required" in d and "all-reduce" in d for d in deviations
    ), deviations


def test_tp_manifest_moved_bytes_ceiling():
    """The ring-cost ceiling trips on an oversized footprint even when the
    kind set is legal."""
    manifest = serve_tp_manifest(2, layers=LAYERS, hidden=HIDDEN,
                                 max_q_tokens=2)
    assert manifest.required == ("all-reduce",)
    big = {
        "count": 4,
        "by_kind": {"all-reduce": {"count": 4}},
        "total_bytes": manifest.max_bytes,
        "total_moved_bytes": manifest.max_moved_bytes + 1,
    }
    deviations = manifest.check(big)
    assert any("moved-bytes ceiling" in d for d in deviations), deviations
