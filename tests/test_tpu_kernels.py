"""On-TPU kernel tier: the kernel behaviors the CPU suite cannot observe —
``pltpu.prng_random_bits`` is all-zeros in interpret mode (NOTES.md), so
in-kernel dropout statistics, real-Mosaic numerics, and
kernel-under-shard_map execution need the actual chip.

Run, on the chip: PDT_TPU_TESTS=1 python -m pytest tests/ -m tpu -q
(the conftest leaves the backend alone, skips the CPU-mesh tests, and
FAILS the run when there is no TPU — asking for this tier and getting a
skip would read as a pass). All tests here are single-chip; the shard_map
case runs on the trivial 1-device mesh, which still exercises the real
shard_map + Mosaic path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kv_pools import fold_heads  # sibling module (pytest sys.path)

pytestmark = pytest.mark.tpu


def test_mask_scale_keep_rate_statistics():
    """In-kernel Bernoulli keep rate within 3 sigma of 1-rate, and the
    nonzero values are exactly 1/(1-rate)."""
    from pytorch_distributed_training_tpu.ops.dropout import (
        mask_scale_pallas,
    )

    rate = 0.25
    n = 512 * 1024
    out = np.asarray(
        mask_scale_pallas(
            jax.random.key(7, impl="rbg"), (n // 128, 128), rate, jnp.float32
        )
    )
    keep = (out != 0).mean()
    sigma = (rate * (1 - rate) / n) ** 0.5
    assert abs(keep - (1 - rate)) < 3 * sigma, keep
    np.testing.assert_allclose(out[out != 0], 1.0 / (1 - rate), rtol=1e-6)


def test_dal_kernel_dropout_statistics_and_bwd_mask_match():
    """dropout-add-LN with in-kernel dropout: output differs from the
    deterministic path on ~rate of positions, and fwd/bwd reuse the same
    mask (gradient of sum w.r.t. h is zero exactly where h was dropped)."""
    from pytorch_distributed_training_tpu.ops.layer_norm import (
        dropout_add_layer_norm,
    )

    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(64, 128, 512)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(64, 128, 512)), jnp.float32)
    scale = jnp.ones((512,), jnp.float32)
    bias = jnp.zeros((512,), jnp.float32)
    key = jax.random.key(3, impl="rbg")

    # weighted sum, NOT a plain sum: with unit scale the sum of LN outputs
    # is identically zero (rows are mean-centered), which zeroes the
    # gradient everywhere and would hide the mask
    w = jnp.asarray(rng.normal(size=(64, 128, 512)), jnp.float32)

    def out_sum(hh):
        return jnp.sum(
            dropout_add_layer_norm(
                hh, x, scale, bias, rate=0.25, dropout_rng=key,
                deterministic=False, site=0,
            ).astype(jnp.float32)
            * w
        )

    g = np.asarray(jax.grad(out_sum)(h))
    dropped = (g == 0.0).mean()
    # dL/dh == 0 exactly at dropped positions (mask regenerated in bwd)
    sigma = (0.25 * 0.75 / g.size) ** 0.5
    assert abs(dropped - 0.25) < 5 * sigma, dropped


def test_fused_layer_norm_bwd_parity_on_chip():
    """Real-Mosaic fused LN gradients vs the jnp reference math."""
    from pytorch_distributed_training_tpu.ops.layer_norm import (
        layer_norm,
        reference_layer_norm,
    )

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1024, 512)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1024, 512)), jnp.float32)

    def loss_fused(x, s, b):
        return jnp.sum(layer_norm(x, s, b, eps=1e-12) * w)

    def loss_ref(x, s, b):
        return jnp.sum(reference_layer_norm(x, s, b, eps=1e-12) * w)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=2e-4
        )


def test_flash_whole_seq_fwd_bwd_parity_on_chip():
    """The whole-seq (grid-(B,)) flash path vs reference einsum attention,
    forward and gradients, dropout off."""
    from pytorch_distributed_training_tpu.ops.attention import (
        make_attention_bias,
        reference_attention,
    )
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_attention,
    )

    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.normal(size=(4, 128, 8, 64)), jnp.bfloat16)
        for _ in range(3)
    )
    mask = np.ones((4, 128), np.int32)
    mask[1, 100:] = 0
    bias = make_attention_bias(jnp.asarray(mask))

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, bias).astype(jnp.float32) ** 2)

    of = flash_attention(q, k, v, bias)
    orf = reference_attention(q, k, v, bias)
    np.testing.assert_allclose(
        np.asarray(of[0], np.float32), np.asarray(orf[0], np.float32),
        atol=2e-2, rtol=2e-2,
    )
    gf = jax.grad(lambda *a: loss(flash_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    gr = jax.grad(
        lambda *a: loss(reference_attention, *a), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a[0], np.float32), np.asarray(b_[0], np.float32),
            atol=5e-2, rtol=5e-2,
        )


def test_flash_multiblock_512_numerics_on_chip():
    """512-wide blocks (the gpt2 default) vs reference, causal, seq 1024."""
    from pytorch_distributed_training_tpu.ops.attention import (
        reference_attention,
    )
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_attention,
    )

    rng = np.random.default_rng(4)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 1024, 4, 64)), jnp.bfloat16)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, None, causal=True)
    ref = reference_attention(q, k, v, None, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_flash_fused_bwd_multiblock_on_chip():
    """The FUSED single-pass backward (r5 default: _dqkv_kernel, dq in a
    VMEM scratch accumulated across the sequential k-block grid) at the
    gpt2 block geometry, on real Mosaic: gradients vs reference einsum
    attention AND vs the classic two-pass scheme."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa
    from pytorch_distributed_training_tpu.ops.attention import (
        reference_attention,
    )
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_attention,
    )

    rng = np.random.default_rng(11)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 1024, 2, 64)), jnp.bfloat16)
        for _ in range(3)
    )
    cot = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v, None, causal=True).astype(jnp.float32) * cot)

    g_ref = jax.grad(
        lambda *a: loss(reference_attention, *a), argnums=(0, 1, 2)
    )(q, k, v)
    orig = fa.FUSED_BWD
    grads = {}
    try:
        for mode in (True, False):
            fa.FUSED_BWD = mode
            grads[mode] = jax.grad(
                lambda *a: loss(flash_attention, *a), argnums=(0, 1, 2)
            )(q, k, v)
    finally:
        fa.FUSED_BWD = orig
    for gf, gt, gr, name in zip(grads[True], grads[False], g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gt, np.float32),
            atol=2e-2, rtol=2e-2,
            err_msg=f"fused vs two-pass d{name} on chip",
        )
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr, np.float32),
            atol=5e-2, rtol=5e-2,
            err_msg=f"fused vs reference d{name} on chip",
        )


def test_kernels_under_shard_map_on_chip():
    """shard_map-routed kernel dispatch with REAL Mosaic lowering — the
    1-device mesh is trivial but executes the exact code path sharded
    meshes take (ops/dispatch.py), which interpret mode can't reach."""
    from pytorch_distributed_training_tpu.comms.mesh import build_mesh
    from pytorch_distributed_training_tpu.ops import dispatch
    from pytorch_distributed_training_tpu.ops.layer_norm import (
        layer_norm,
        reference_layer_norm,
    )

    from pytorch_distributed_training_tpu.ops.dropout import raw_dropout

    mesh = build_mesh()
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, 128, 512)), jnp.float32)
    scale = jnp.ones((512,), jnp.float32)
    bias = jnp.zeros((512,), jnp.float32)
    ref = reference_layer_norm(x, scale, bias, eps=1e-12)
    before = dispatch.DISPATCH_PATHS["layer_norm:shard_map"]
    with dispatch.use_kernel_mesh(mesh), dispatch.force_shard_map():
        assert dispatch.mode() == "shard_map"
        out = layer_norm(x, scale, bias, eps=1e-12)
        drop = raw_dropout(x, 0.25, jax.random.key(0, impl="rbg"), "kernel")
    assert dispatch.DISPATCH_PATHS["layer_norm:shard_map"] == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )
    # real in-kernel PRNG through the shard_map seed-offset path
    keep = (np.asarray(drop) != 0).mean()
    assert abs(keep - 0.75) < 0.02, keep

def test_int8_dense_numerics_on_real_mxu():
    """VERDICT r3 #1b: quantize → int8 dot → rescale against an fp32
    reference ON THE REAL MXU (the int8 systolic path; CPU emulates the
    same math but not the hardware's int8x int8 → int32 accumulate)."""
    from pytorch_distributed_training_tpu.ops.quant import (
        int8_dense,
        int8_dense_delayed,
        quantize_per_channel,
        quantize_per_tensor,
    )

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)

    # hand-computed expected result from the quantization grid itself
    xq, sx = quantize_per_tensor(x)
    wq, sw = quantize_per_channel(w, contract_axis=(0,))
    expected = (
        np.asarray(xq, np.int32) @ np.asarray(wq, np.int32)
    ).astype(np.float32) * float(sx) * np.asarray(sw, np.float32)

    got = np.asarray(jax.jit(int8_dense, static_argnums=(2, 3))(x, w, 1, "full"))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-4)
    # and against the fp32 reference: pure quantization error, bounded by
    # the per-axis scale resolution (|err| <~ 0.5*sx*|w|_col1 + 0.5*sw*|x|_row1)
    ref = np.asarray(x) @ np.asarray(w)
    denom = np.abs(ref).max()
    assert np.abs(got - ref).max() / denom < 0.05

    # delayed variant with the true amax is bit-identical to dynamic
    y_del, new_amax = jax.jit(
        int8_dense_delayed, static_argnums=(3, 4)
    )(x, w, jnp.max(jnp.abs(x)), 1, "full")
    np.testing.assert_array_equal(np.asarray(y_del), got)
    np.testing.assert_allclose(
        float(new_amax), float(jnp.max(jnp.abs(x))), rtol=1e-6
    )


# ------------------------------------------------- paged attention on chip


def _paged_case(pool_dtype, q_len, *, page_size=128, heads=16, head_dim=64,
                batch=4, windows=3, seed=0):
    """Random lane-dense pools at gpt2-medium's head geometry (16 x 64)
    and the lane-width page size; lengths cover one token, a mid-page
    tail, a full table and a page boundary; entries past the live length
    park on the null page like the engine's."""
    from pytorch_distributed_training_tpu.ops.quant import quantize_kv

    rng = np.random.default_rng(seed)
    num_pages = 1 + batch * windows
    shape = (num_pages, page_size, heads, head_dim)
    k = rng.normal(size=shape).astype(np.float32)
    v = rng.normal(size=shape).astype(np.float32)
    q_shape = (batch, heads, head_dim)
    if q_len is not None:
        q_shape = (batch, q_len, heads, head_dim)
    q = rng.normal(size=q_shape).astype(np.float32)
    bt = rng.permutation(np.arange(1, num_pages))[: batch * windows]
    bt = bt.reshape(batch, windows).astype(np.int32)
    total = windows * page_size
    lengths = np.asarray(
        [q_len or 1, total - 3, total, page_size + 1], np.int32
    )
    for b in range(batch):
        for w in range(windows):
            if w * page_size >= lengths[b]:
                bt[b, w] = 0
    bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    if pool_dtype == jnp.int8:
        kq, ks = quantize_kv(jnp.asarray(k))
        vq, vs = quantize_kv(jnp.asarray(v))
        return (jnp.asarray(q), fold_heads(kq), fold_heads(vq), bt,
                lengths), dict(k_scales=ks, v_scales=vs)
    return (
        jnp.asarray(q, pool_dtype), fold_heads(jnp.asarray(k, pool_dtype)),
        fold_heads(jnp.asarray(v, pool_dtype)), bt, lengths,
    ), {}


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "f32", "int8"])
@pytest.mark.parametrize("q_len", [None, 8], ids=["single", "multi"])
def test_paged_pallas_matches_reference_on_chip(pool_dtype, q_len):
    """``_paged_pallas`` / ``_paged_pallas_mq`` through real Mosaic against
    ``_paged_reference*`` — neither kernel had met the compiler before
    PR 21. bf16 pools (what ``serve_lm`` allocates), fp32 and int8 (the
    engine's ``kv_dtype`` pair). The single-query kernel is exact fp32 VPU
    math; the multi-query kernel's MXU dots round fp32 operands to bf16 at
    default precision, hence its fp32/int8 tolerance."""
    from pytorch_distributed_training_tpu.ops.paged_attention import (
        paged_attention,
    )

    args, scales = _paged_case(pool_dtype, q_len)
    run = jax.jit(
        paged_attention, static_argnames=("scale", "impl")
    )
    kw = dict(scale=64 ** -0.5, **scales)
    ref = np.asarray(run(*args, impl="reference", **kw), np.float32)
    got = np.asarray(run(*args, impl="pallas", **kw), np.float32)
    assert got.shape == ref.shape and np.isfinite(got).all()
    if pool_dtype == jnp.bfloat16:
        tol = 2e-2          # one bf16 rounding of O(1) outputs
    elif q_len is None:
        tol = 1e-5          # VPU fp32 end to end
    else:
        tol = 2e-2          # MXU default precision on fp32 operands
    np.testing.assert_allclose(got, ref, atol=tol, rtol=tol)


def _cell_fill(busy, tokens=None, *, slots=48, windows=64, page_size=16,
               seed=0):
    """Block table and lengths of `gpt2_medium_decode`'s decode step with
    ``busy`` slots at ``tokens`` tokens each (None: 1 .. 1,024, mixed) over
    shuffled page ids; the others idle as the engine parks them (page 0,
    length 1)."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, 1 + slots * windows))
    table = np.zeros((slots, windows), np.int32)
    lengths = np.ones((slots,), np.int32)
    for n, b in enumerate(rng.permutation(slots)[:busy]):
        lengths[b] = tokens or rng.integers(1, windows * page_size + 1)
        live = -(-int(lengths[b]) // page_size)
        table[b, :live] = ids[n * windows:n * windows + live]
    return table, lengths


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_page_walk_matches_reference_at_the_decode_cell_on_chip(pool_dtype):
    """``paged_attn`` through real Mosaic at `gpt2_medium_decode`'s
    geometry (48 slots, 64 pages of 16, 16 x 64 heads, a 3,073-page pool),
    the impl left to the gate: mixed lengths over shuffled pages with idle
    slots between them, then every slot full."""
    from pytorch_distributed_training_tpu.ops import dispatch
    from pytorch_distributed_training_tpu.ops.paged_attention import (
        paged_attention,
    )

    rng = np.random.default_rng(1)
    shape = (3073, 16, 1024)
    k = jnp.asarray(rng.standard_normal(shape, np.float32), pool_dtype)
    v = jnp.asarray(rng.standard_normal(shape, np.float32), pool_dtype)
    q = jnp.asarray(rng.standard_normal((48, 16, 64), np.float32), pool_dtype)
    run = jax.jit(paged_attention, static_argnames=("scale", "impl"))
    dispatch.DISPATCH_PATHS.clear()
    for busy, tokens in ((30, None), (48, 1024)):
        table, lengths = _cell_fill(busy, tokens)
        args = (q, k, v, jnp.asarray(table), jnp.asarray(lengths))
        ref = np.asarray(
            run(*args, scale=0.125, impl="reference"), np.float32
        )
        got = np.asarray(run(*args, scale=0.125), np.float32)
        assert np.isfinite(got).all()
        # f32 pools: every MXU pass (precision HIGHEST); bf16: one bf16
        # rounding of O(1) outputs
        tol = 2e-2 if pool_dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(got, ref, atol=tol, rtol=tol)
    # one trace serves both fills (the shapes are the same)
    assert dispatch.DISPATCH_PATHS["paged_attn:direct"] == 1


def test_decode_step_time_follows_the_fill_on_chip(monkeypatch):
    """The decode program of `gpt2_medium_decode` (gpt2-medium, 48 slots,
    3,073 bf16 pages of 16, device sampling), timed at four fills with the
    page walk (the gate's choice on one chip) and with the XLA formula
    pinned: the kernel's step grows with what is live and stays under the
    formula's, which costs the same whatever the fill. The times go to
    ``chiprun_out/paged_decode_step_fills.json`` for PERF.md."""
    import json
    import os
    import time

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.ops import dispatch
    from pytorch_distributed_training_tpu.ops import paged_attention as pa
    from pytorch_distributed_training_tpu.serve.engine import (
        DecodeEngine,
        EngineConfig,
    )
    from pytorch_distributed_training_tpu.serve.queue import RequestQueue
    from pytorch_distributed_training_tpu.utils.config import model_preset

    model = GPT2LMModel(model_preset("gpt2-medium"))
    params = jax.jit(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )()["params"]
    fills = {"4x200": (4, 200), "24x200": (24, 200), "48x200": (48, 200),
             "48x1024": (48, 1024)}
    step_ms = {}

    class FormulaGate:
        """``dispatch`` as the paged-attention gate reads it while the
        formula engine traces: mode "off" there (its XLA formula), every
        other op's gate as it is."""
        mode = staticmethod(lambda: "off")
        note_path = staticmethod(dispatch.note_path)

    for impl in ("auto", "reference"):
        econf = EngineConfig(
            num_slots=48, prompt_buckets=(64, 128, 256, 512),
            max_new_tokens=512, page_size=16, warmup=False,
        )
        queue = RequestQueue(
            max_depth=16, prompt_buckets=econf.prompt_buckets,
            max_new_tokens=econf.max_new_tokens,
        )
        engine = DecodeEngine(model, params, econf, queue)
        fn, pools = engine._decode_step_fn(), engine._cache
        zeros = np.zeros((48,), np.int32)
        dispatch.DISPATCH_PATHS.clear()
        with monkeypatch.context() as patch:
            if impl == "reference":
                patch.setattr(pa, "dispatch", FormulaGate)
            # the first call traces (one program serves every fill)
            for name, (busy, tokens) in fills.items():
                table, lengths = _cell_fill(busy, tokens)
                # the program appends one token at ctx, then reads ctx + 1
                ctx = (lengths - 1).astype(np.int32)
                ops = (zeros, table, ctx, zeros, zeros,
                       np.zeros((48,), np.float32), zeros)
                times = []
                for _ in range(12):
                    t0 = time.perf_counter()
                    ids, pools = fn(engine._params, pools, *ops)
                    jax.block_until_ready(ids)
                    times.append((time.perf_counter() - t0) * 1e3)
                step_ms[f"{impl}:{name}"] = float(np.median(times[2:]))
        took = "xla" if impl == "reference" else "direct"
        assert dict(dispatch.DISPATCH_PATHS).get(f"paged_attn:{took}") == 24
        del engine, fn, pools
    print("paged_decode_step_fills", json.dumps(step_ms))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_decode_step_fills.json", "w") as f:
        json.dump(step_ms, f, indent=1)
    walk = [step_ms[f"auto:{name}"] for name in fills]
    assert walk == sorted(walk), step_ms
    assert all(
        step_ms[f"auto:{name}"] < step_ms[f"reference:{name}"]
        for name in fills
    ), step_ms


def test_glm52_decode_step_and_row_gather_widths_on_chip():
    """`glm52_agent_decode`'s decode program alone (the `glm-5.2-share16`
    preset in bf16, 48 slots over 1,184-page block tables, 11,777 pages of
    16, every slot at a context of 18,000 over seeded pools), timed beside
    the 32.8 ms its module took before the latent pools were grouped
    (PERF.md, PR 29-31), and what XLA:TPU's row gather costs at the three
    row widths the grouping chose between: 98,304 random rows of 640, 1,280
    and 2,560 bf16 out of 188,432. The times go to
    ``chiprun_out/glm52_decode_step.json`` for PERF.md."""
    import json
    import os
    import time

    from pytorch_distributed_training_tpu.models import latent_moe as lm
    from pytorch_distributed_training_tpu.serve.engine import (
        DecodeEngine,
        EngineConfig,
    )
    from pytorch_distributed_training_tpu.serve.queue import RequestQueue
    from pytorch_distributed_training_tpu.utils.config import model_preset

    out = {"parent_module_ms": 32.8}
    rows, chosen = 11777 * 16, 48 * 2048
    ids = jax.random.randint(jax.random.key(1), (48, 1, 2048), 0, rows)
    gather = jax.jit(lambda pool, ids: pool[ids])
    for width in (640, 1280, 2560):
        pool = jax.random.normal(jax.random.key(width), (rows, width),
                                 jnp.bfloat16)
        gather(pool, ids).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):     # queued: the device's time, not the host's
            got = gather(pool, ids)
        got.block_until_ready()
        ms = (time.perf_counter() - t0) / 20 * 1e3
        out[f"gather_{width}_ms"] = ms
        out[f"gather_{width}_ns_a_row"] = ms * 1e6 / chosen
        del pool, got

    model = lm.LatentMoELM(model_preset("glm-5.2-share16"))
    params = jax.jit(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )()["params"]
    econf = EngineConfig(
        num_slots=48, prompt_buckets=(16896,), max_new_tokens=2048,
        page_size=16, num_pages=11777, prefill_chunk=512, prefix_cache=True,
        weights_dtype="bfloat16", warmup=False,
    )
    queue = RequestQueue(
        max_depth=16, prompt_buckets=econf.prompt_buckets,
        max_new_tokens=econf.max_new_tokens,
    )
    engine = DecodeEngine(model, params, econf, queue)
    assert set(engine._cache) == {
        "latents_0", "latents_1", "layer_0", "layer_4"}
    assert engine._cache["latents_0"]["latent_pages"].shape == (
        11777, 16, 2560)
    fn = engine._decode_step_fn()
    leaves, tree = jax.tree.flatten(engine._cache)
    pools = jax.tree.unflatten(tree, [
        jax.device_put(jax.random.normal(jax.random.key(i), x.shape, x.dtype),
                       x.sharding)
        for i, x in enumerate(leaves)])
    width = econf.pages_per_slot
    table = (1 + (np.arange(48)[:, None] * 245 + np.arange(width)[None])
             % 11776).astype(np.int32)
    zeros = np.zeros((48,), np.int32)
    ops = (zeros, table, np.full((48,), 18000, np.int32), zeros, zeros,
           np.zeros((48,), np.float32), zeros)
    waited = []
    for _ in range(8):
        t0 = time.perf_counter()
        got, pools = fn(engine._params, pools, *ops)
        jax.block_until_ready(got)
        waited.append((time.perf_counter() - t0) * 1e3)
    t0 = time.perf_counter()
    for _ in range(10):
        got, pools = fn(engine._params, pools, *ops)
    jax.block_until_ready(got)
    out["decode_step_queued_ms"] = (time.perf_counter() - t0) / 10 * 1e3
    out["decode_step_waited_ms"] = float(np.median(waited[2:]))
    print("glm52_decode_step", json.dumps(out))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/glm52_decode_step.json", "w") as f:
        json.dump(out, f, indent=1)
    # wider rows cost more, and less than as many narrow ones
    assert out["gather_640_ms"] < out["gather_1280_ms"] < out["gather_2560_ms"]
    assert out["gather_2560_ms"] < 4 * out["gather_640_ms"]
    assert out["decode_step_queued_ms"] < out["parent_module_ms"]


def test_dispatch_paths_at_model_shapes_on_chip():
    """Which path each fused op takes on ONE chip at the shapes the two
    smoke models run: bert-large's block tails (micro 8 x seq 128 x 1024)
    and gpt2-medium's training attention (seq 1024) take the kernels, and
    so does gpt2-medium's 8-token init forward (serve_lm's boot: Mosaic
    refused it while the one k-block was sliced at a dynamic lane offset) —
    checked against the reference math here, since nothing else runs a
    sub-lane-width flash block on the chip. A 4-row decode LayerNorm takes
    XLA (rows do not tile)."""
    from pytorch_distributed_training_tpu.ops.attention import (
        reference_attention,
    )
    from pytorch_distributed_training_tpu.ops import dispatch
    from pytorch_distributed_training_tpu.ops.dropout import raw_dropout
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_attention,
    )
    from pytorch_distributed_training_tpu.ops.layer_norm import (
        dropout_add_layer_norm,
        layer_norm,
    )

    assert dispatch.mode() == "direct"
    key = jax.random.key(0, impl="rbg")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 128, 1024)), jnp.bfloat16)
    scale = jnp.ones((1024,), jnp.float32)
    bias = jnp.zeros((1024,), jnp.float32)
    dispatch.DISPATCH_PATHS.clear()
    jax.block_until_ready([
        layer_norm(x, scale, bias),
        dropout_add_layer_norm(
            x, x, scale, bias, rate=0.1, dropout_rng=key,
            deterministic=False,
        ),
        raw_dropout(jnp.ones((8, 16, 128, 128), jnp.bfloat16), 0.1, key,
                    "kernel"),
        flash_attention(*[jnp.ones((2, 1024, 16, 64), jnp.bfloat16)] * 3,
                        None, causal=True),
    ])
    for seq in (8, 32):
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, seq, 16, 64)), jnp.bfloat16)
            for _ in range(3)
        )
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, None, causal=True),
                       np.float32),
            np.asarray(reference_attention(q, k, v, None, causal=True),
                       np.float32),
            atol=2e-2, rtol=2e-2, err_msg=f"flash vs reference at seq {seq}",
        )
    kernel_paths = dict(dispatch.DISPATCH_PATHS)
    dispatch.DISPATCH_PATHS.clear()
    jax.block_until_ready(layer_norm(x[:4, 0], scale, bias))
    xla_paths = dict(dispatch.DISPATCH_PATHS)
    print("kernel paths", kernel_paths, "xla paths", xla_paths)
    assert kernel_paths == {
        "layer_norm:direct": 1, "dal:direct": 1, "mask_scale:direct": 1,
        "flash:direct": 3,
    }
    assert xla_paths == {"layer_norm:xla": 1}
