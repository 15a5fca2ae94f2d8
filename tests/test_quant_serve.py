"""Quantized serving tests (serve/engine.py precision variants,
ops/quant.py serve-param quantization, ops/paged_attention.py int8 pools,
serve/hotswap.py variant-stamped publish): weight-only int8 greedy streams
bit-identical to fp32 on the snapped grid (and to one-shot generate()),
int8-KV accuracy bands at the ops and engine levels, allocator/admission
arithmetic invariant under pool dtype, tp=2 int8 bit-equal to tp=1 int8
with sharded scale pools, the strict-guard fp32<->int8 live-swap drill
(zero failed requests, zero retraces, variant recorded), scale-pool and
config validation in the named-axis error style, and the variant-stamped
publish -> load_swap_params roundtrip. Tier-1.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models.generate import generate
from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
from pytorch_distributed_training_tpu.ops.paged_attention import (
    paged_attention,
)
from pytorch_distributed_training_tpu.ops.quant import (
    dequantize_serve_params,
    quantize_kv,
    quantize_serve_params,
    serve_params_variant,
)
from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.utils.config import model_preset

from kv_pools import fold_heads  # sibling module (pytest sys.path)

pytestmark = [pytest.mark.serve]


# gpt2-tiny: 2 layers, hidden 64, 4 heads of head_dim 16
LAYERS, HIDDEN, HEADS, HEAD_DIM = 2, 64, 4, 16


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


@pytest.fixture(scope="module")
def snapped(lm):
    """fp32 weights snapped onto the int8 grid: quantization is idempotent
    on this tree, so an fp32 engine and a weight-int8 engine run
    numerically IDENTICAL projection weights."""
    _, params = lm
    return dequantize_serve_params(quantize_serve_params(params))


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


def _run_server(model, params, prompts, T, *, guards=None, registry=None,
                **cfg_kw):
    reg, sink = (registry, None) if registry is not None else _registry()
    cfg_kw.setdefault("prompt_buckets", (4, 8, 16))
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, max_new_tokens=T, kv_layout="paged",
            sampling="device", page_size=4, **cfg_kw,
        ),
        queue_depth=16, registry=reg, guards=guards,
    ).start()
    try:
        reqs = [
            server.submit(p, max_new_tokens=T, seed=i)
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


# ----------------------------------------------------- weight-only int8


def test_weight_only_int8_greedy_bit_identical_on_snapped_grid(lm, snapped):
    """The losslessness pin: with weights on the int8 grid, the weight-only
    int8 engine's greedy streams are bit-identical to the fp32 engine's AND
    to one-shot generate() — weight quantization is a storage change, not a
    numerics change, once the grid is shared."""
    model, _ = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = [
        np.asarray(generate(model, snapped, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]
    fp, stats_fp, _ = _run_server(model, snapped, prompts, T)
    q, stats_q, _ = _run_server(
        model, snapped, prompts, T, weights_dtype="int8",
    )
    for i, (a, b, ref) in enumerate(zip(fp, q, want)):
        np.testing.assert_array_equal(a, ref, err_msg=f"request {i} (fp32)")
        np.testing.assert_array_equal(b, ref, err_msg=f"request {i} (int8)")
    assert stats_fp["variant"] == "fp32"
    assert stats_q["variant"] == "int8"
    assert stats_q["weights_dtype"] == "int8"
    assert stats_q["kv_dtype"] == "float32"


def test_weight_only_int8_resident_tree_halves_projection_bytes(lm):
    """quantize_serve_params rewrites every attention/MLP projection to an
    int8 kernel + fp32 per-output-channel kernel_scale; the projection
    bytes land near 1/4 of fp32 (int8 elements + one fp32 scale per
    channel) and dequantize_serve_params is the exact inverse on the
    snapped grid."""
    from pytorch_distributed_training_tpu.ops.quant import (
        _SERVE_QUANT_MODULES,
    )

    _, params = lm
    q = quantize_serve_params(params)
    assert serve_params_variant(q) == "int8"
    assert serve_params_variant(params) == "fp32"

    def proj_bytes(tree):
        total = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            names = {getattr(k, "key", None) for k in path}
            if names & set(_SERVE_QUANT_MODULES):
                total += int(leaf.size) * leaf.dtype.itemsize
        return total

    ratio = proj_bytes(q) / proj_bytes(params)
    assert ratio < 0.5, ratio
    # idempotent snap: quantizing the dequantized tree reproduces it
    snap = dequantize_serve_params(q)
    q2 = quantize_serve_params(snap)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(q),
        jax.tree_util.tree_leaves_with_path(q2),
    ):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- int8 KV


def test_int8_kv_ops_tolerance_band():
    """Both paged_attention impls dequantize int8 pools in-kernel within a
    tight band of the fp32 pools (symmetric per-page-per-head absmax keeps
    the relative error ~1/127), and the pallas page-walk kernel matches the
    reference on the SAME int8 pools to float tolerance."""
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        tpu_interpret_mode,
    )

    rng = np.random.default_rng(0)
    P, S, B = 6, 4, 3
    k = jnp.asarray(rng.normal(size=(P, S, HEADS, HEAD_DIM)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, S, HEADS, HEAD_DIM)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, HEADS, HEAD_DIM)), jnp.float32)
    bt = jnp.asarray([[1, 2, 0], [3, 4, 5], [2, 0, 0]], jnp.int32)
    lengths = jnp.asarray([6, 11, 3], jnp.int32)
    scale = HEAD_DIM ** -0.5

    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    assert kq.dtype == jnp.int8 and ks.shape == (P, S, HEADS)
    # the engine's lane-dense pools; the scale pools stay per head
    k, v, kq, vq = (fold_heads(x) for x in (k, v, kq, vq))

    exact = paged_attention(q, k, v, bt, lengths, scale=scale,
                            impl="reference")
    ref8 = paged_attention(q, kq, vq, bt, lengths, scale=scale,
                           impl="reference", k_scales=ks, v_scales=vs)
    assert ref8.dtype == jnp.float32
    band = float(jnp.max(jnp.abs(ref8 - exact)))
    assert band < 0.05, band
    with tpu_interpret_mode():
        pl8 = paged_attention(q, kq, vq, bt, lengths, scale=scale,
                              impl="pallas", k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(
        np.asarray(pl8), np.asarray(ref8), atol=1e-5, rtol=1e-5,
    )


def test_int8_kv_serving_band_and_pool_accounting(lm, snapped):
    """The int8-KV engine serves the same greedy answers at this scale
    (first token exact by construction: prefill attends the in-flight fp32
    K/V before quantize-on-write) while the allocator stays dtype-blind —
    identical page capacity and page size — and kv_bytes_per_token drops
    to head_dim+4 bytes per head lane."""
    model, _ = lm
    T = 8
    prompts = _prompts(model, [3, 6, 9, 14], seed=5)
    fp, stats_fp, _ = _run_server(
        model, snapped, prompts, T, weights_dtype="int8",
    )
    q8, stats_q8, _ = _run_server(
        model, snapped, prompts, T, weights_dtype="int8", kv_dtype="int8",
    )
    agree = total = 0
    for i, (a, b) in enumerate(zip(fp, q8)):
        assert a[0] == b[0], f"request {i}: first token drifted"
        agree += int((a == b).sum())
        total += len(a)
    assert agree / total >= 0.8, (agree, total)

    # allocator arithmetic is pool-dtype-invariant
    assert stats_fp["kv_pages_total"] == stats_q8["kv_pages_total"]
    assert stats_fp["kv_page_size"] == stats_q8["kv_page_size"]
    assert stats_fp["page_exhausted"] == stats_q8["page_exhausted"] == 0
    # int8 KV: 1 byte per element + 4 fp32-scale bytes per head lane
    assert stats_fp["kv_bytes_per_token"] == (
        2 * LAYERS * HEADS * HEAD_DIM * 4
    )
    assert stats_q8["kv_bytes_per_token"] == (
        2 * LAYERS * HEADS * (HEAD_DIM + 4)
    )


def test_int8_kv_pool_leaves_are_int8_with_fp32_scales(lm):
    """The resident cache of an int8-KV engine holds int8 lane-dense page
    pools [N, P, H*D] and fp32 scale pools [N, P, H] beside them."""
    model, params = lm
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
            kv_layout="paged", sampling="device", page_size=4,
            weights_dtype="int8", kv_dtype="int8",
        ),
    )
    leaves = jax.tree.leaves(server.engine._cache)
    pools = [x for x in leaves if x.dtype == jnp.int8]
    scales = [x for x in leaves if x.dtype == jnp.float32]
    assert pools and len(pools) == len(scales) == len(leaves) // 2
    for pool, sc in zip(pools, scales):
        assert pool.shape[2] == HEADS * HEAD_DIM
        assert sc.shape == (*pool.shape[:2], HEADS)


# ------------------------------------------------------- tensor parallel


@pytest.mark.tp
def test_tp2_int8_bit_identical_to_tp1_int8(lm, snapped):
    """Quantization composes with head sharding: the tp=2 full-int8 engine
    emits bit-identical greedy streams to the tp=1 full-int8 engine, the
    kernel_scale leaves shard with their kernel's channel axis, and the
    scale pools shard on their last (heads) axis like their page pools."""
    from pytorch_distributed_training_tpu.parallel.sharding import (
        serve_pool_pspec,
    )

    model, _ = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=11)
    kw = dict(weights_dtype="int8", kv_dtype="int8")
    tp1, _, _ = _run_server(model, snapped, prompts, T, tp=1, **kw)

    reg, _ = _registry()
    server = InferenceServer(
        model, snapped,
        EngineConfig(
            num_slots=2, prompt_buckets=(4, 8, 16), max_new_tokens=T,
            kv_layout="paged", sampling="device", page_size=4, tp=2, **kw,
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        reqs = [
            server.submit(p, max_new_tokens=T, seed=i)
            for i, p in enumerate(prompts)
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        )
        for i, (a, r) in enumerate(zip(tp1, reqs)):
            np.testing.assert_array_equal(
                a, np.asarray(r.tokens, np.int32), err_msg=f"request {i}"
            )
        leaves = jax.tree.leaves(server.engine._cache)
        assert {x.dtype for x in leaves} == {
            jnp.dtype(jnp.int8), jnp.dtype(jnp.float32)
        }
        for leaf in leaves:
            assert leaf.sharding.spec == serve_pool_pspec()
            shard = leaf.sharding.shard_shape(leaf.shape)
            per_head = HEAD_DIM if leaf.dtype == jnp.int8 else 1
            assert shard[2] == HEADS // 2 * per_head
    finally:
        server.close()


# ------------------------------------------------- live variant swapping


def test_strict_fp32_int8_swap_drill_zero_retrace(lm, snapped):
    """The fleet-rollback drill: an int8 replica under strict guards takes
    a live swap from an fp32-published tree mid-load. The engine coerces
    the incoming tree to its resident variant, so the warm programs' input
    dtypes never change: zero failed requests, zero retraces, zero
    implicit transfers, the swap record names the incoming variant, and
    post-swap streams equal serving the new weights from scratch."""
    from pytorch_distributed_training_tpu.analysis.guards import GuardSet

    model, _ = lm
    pB = jax.tree.map(lambda x: x + 0.01 * jnp.sign(x + 0.5), snapped)
    reg, sink = _registry()
    gs = GuardSet(mode="strict", registry=reg)
    server = InferenceServer(
        model, snapped,
        EngineConfig(
            num_slots=2, prompt_buckets=(4, 8), max_new_tokens=4,
            kv_layout="paged", sampling="device", page_size=4,
            warmup=True, weights_dtype="int8", kv_dtype="int8",
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
        assert all(r.status == "done" for r in reqs)
        ticket = server.engine.request_swap(pB, 2)  # fp32 tree, int8 engine
        assert ticket.done.wait(30) and ticket.ok
        prompt = _prompts(model, [5], seed=9)[0]
        r_post = server.submit(prompt, max_new_tokens=4)
        assert wait_until(r_post.done.is_set, timeout=120)
        assert r_post.status == "done"
    finally:
        server.close()

    # the engine stays int8-resident; pB answers on ITS snapped grid
    snapB = dequantize_serve_params(quantize_serve_params(pB))
    want = np.asarray(
        generate(model, snapB, prompt[None], max_new_tokens=4)
    )[0, len(prompt):]
    np.testing.assert_array_equal(np.asarray(r_post.tokens), want)

    stats = server.stats()
    assert stats["variant"] == "int8" and stats["weights_step"] == 2
    assert stats["swaps"] == 1 and stats["swap_rollbacks"] == 0
    assert stats["guard_recompiles"] == 0
    assert stats["guard_implicit_transfers"] == 0
    assert not sink.of("recompile") and not sink.of("implicit_transfer")
    (applied,) = sink.of("swap_applied")
    assert applied["variant"] == "fp32"   # the admitted cross-variant swap
    (committed,) = sink.of("swap_committed")
    assert committed["variant"] == "fp32"


def test_publish_variant_roundtrip_and_cross_variant_restore(lm, tmp_path):
    """publish_params_checkpoint(variant=) converts and stamps the sealed
    manifest; load_swap_params restores a matching-variant step partially
    and a cross-variant step whole (different treedef), handing back the
    published tree for the engine to coerce."""
    from pytorch_distributed_training_tpu.serve.hotswap import (
        load_swap_params,
        publish_params_checkpoint,
        read_manifest,
    )

    _, params = lm
    d = str(tmp_path / "pub")
    publish_params_checkpoint(d, 1, params, variant="int8")
    publish_params_checkpoint(d, 2, params, variant="fp32")
    man1 = read_manifest(os.path.join(d, "1"))
    man2 = read_manifest(os.path.join(d, "2"))
    assert man1["variant"] == "int8" and man2["variant"] == "fp32"

    # fp32 replica pulling the int8 step: whole-tree cross-variant restore
    got1 = load_swap_params(d, 1, current_params=params)
    assert serve_params_variant(got1) == "int8"
    # int8 replica pulling the fp32 step: the other direction
    got2 = load_swap_params(
        d, 2, current_params=quantize_serve_params(params)
    )
    assert serve_params_variant(got2) == "fp32"
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(got2)[0]),
        np.asarray(jax.tree.leaves(params)[0]),
    )


def test_publish_rejects_unknown_variant(lm, tmp_path):
    from pytorch_distributed_training_tpu.serve.hotswap import (
        publish_params_checkpoint,
    )

    _, params = lm
    with pytest.raises(ValueError, match="variant"):
        publish_params_checkpoint(
            str(tmp_path / "bad"), 1, params, variant="bf16",
        )


# ------------------------------------------------------------ validation


def test_engine_config_rejects_bad_dtypes():
    with pytest.raises(ValueError, match="weights_dtype must be"):
        EngineConfig(
            num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
            kv_layout="paged", sampling="device", weights_dtype="bf16",
        )
    with pytest.raises(ValueError, match="kv_dtype must be"):
        EngineConfig(
            num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
            kv_layout="paged", sampling="device", kv_dtype="int4",
        )
    with pytest.raises(ValueError, match="--kv-layout dense: .*PR 31"):
        EngineConfig(
            num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
            kv_layout="dense", sampling="host", kv_dtype="int8",
        )


def test_scale_pool_validation_named_axes():
    """The ops contract fires at trace time with named axes: missing
    scales, rank/shape/dtype mismatches, and scales alongside fp32 pools
    are all rejected before any kernel runs."""
    rng = np.random.default_rng(1)
    P, S = 4, 4
    k = jnp.asarray(rng.normal(size=(P, S, HEADS, HEAD_DIM)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, S, HEADS, HEAD_DIM)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, HEADS, HEAD_DIM)), jnp.float32)
    bt = jnp.asarray([[1, 0], [2, 3]], jnp.int32)
    lengths = jnp.asarray([3, 7], jnp.int32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    k, v, kq, vq = (fold_heads(x) for x in (k, v, kq, vq))
    kw = dict(scale=1.0, impl="reference")

    with pytest.raises(ValueError, match="k_scales is missing"):
        paged_attention(q, kq, vq, bt, lengths, v_scales=vs, **kw)
    with pytest.raises(
        ValueError, match=r"page_size \(axis 1\): got 2, want 4"
    ):
        paged_attention(q, kq, vq, bt, lengths,
                        k_scales=ks[:, :2], v_scales=vs, **kw)
    with pytest.raises(ValueError, match="must be float32"):
        paged_attention(q, kq, vq, bt, lengths,
                        k_scales=ks.astype(jnp.float16), v_scales=vs, **kw)
    with pytest.raises(ValueError, match="int8 pages only"):
        paged_attention(q, k, v, bt, lengths, k_scales=ks, v_scales=vs, **kw)
