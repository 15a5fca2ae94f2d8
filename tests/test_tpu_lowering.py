"""Pallas -> Mosaic gate, without a chip.

Two gates, both from any host:

1. ``jax.export.export(jax.jit(f), platforms=["tpu"])`` lowers a function
   for the TPU: every ``pl.pallas_call`` in it goes through the real
   Pallas-to-Mosaic lowering (no interpreter), which refuses a kernel the
   compiler cannot even express — how the single-query paged kernel's
   head-batched dot was found (PR 21).
2. The Mosaic compiler proper (tiling proofs, VMEM limits). libtpu can
   build a compile-only client for a named topology with no chip attached
   (``jax.experimental.topologies``); ``.lower().compile()`` against one of
   its devices runs the same compiler the chip machine runs. This is the
   gate that refuses the flash kernel's dynamic lane slice at gpt2-medium's
   8-token init forward ("cannot statically prove that index in dimension 3
   is a multiple of 128") — the failure that killed ``serve_lm`` at boot on
   the chip. A host whose libtpu cannot do this runs gate 1 only, and
   ``test_mosaic_compiler_reachable_without_a_chip`` says so by skipping.

Numerics still need the chip: ``tests/test_tpu_kernels.py``.

Each case drives the PUBLIC op at a bert-large or gpt2-medium shape with the
dispatch gate answering as a one-chip TPU would, and checks the op really
took its kernel (a silent XLA fallback would compile trivially).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
    count_kernel_calls,
)
from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops import latent_attention as la
from pytorch_distributed_training_tpu.ops import paged_attention as pa
from pytorch_distributed_training_tpu.ops.dropout import raw_dropout
from pytorch_distributed_training_tpu.ops.flash_attention import (
    flash_attention,
)
from pytorch_distributed_training_tpu.ops.layer_norm import (
    dropout_add_layer_norm,
    layer_norm,
)

S = jax.ShapeDtypeStruct
BF16, F32 = jnp.bfloat16, jnp.float32
HIDDEN, HEADS, HEAD_DIM = 1024, 16, 64  # bert-large == gpt2-medium widths
#: (micro batch, sequence): bert-large recipe, gpt2-medium @1024
MODEL_SHAPES = {"bert-large": (8, 128), "gpt2-medium": (4, 1024)}


@pytest.fixture
def one_chip(monkeypatch):
    """Answer the dispatch gate as a single-device TPU backend does."""
    monkeypatch.setattr(dispatch, "mode", lambda: "direct")
    dispatch.DISPATCH_PATHS.clear()
    yield
    dispatch.DISPATCH_PATHS.clear()


@functools.cache
def _compile_only_tpu():
    """(sharding on one compile-only v5e device, None) or (None, reason)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no files under /tmp
    try:
        from jax.experimental import topologies

        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1,
        )
    except Exception as e:  # noqa: BLE001 — reported by the skip below
        return None, f"{type(e).__name__}: {str(e)[:300]}"
    return jax.sharding.SingleDeviceSharding(topology.devices[0]), None


def lower_for_tpu(f, *specs):
    """Gate 1 always; gate 2 (the Mosaic compiler) wherever libtpu gives a
    compile-only client."""
    jax.export.export(jax.jit(f), platforms=["tpu"])(*specs)
    on_tpu, _ = _compile_only_tpu()
    if on_tpu is not None:
        jax.jit(f).lower(
            *(S(x.shape, x.dtype, sharding=on_tpu) for x in specs)
        ).compile()


def test_mosaic_compiler_reachable_without_a_chip():
    on_tpu, why_not = _compile_only_tpu()
    if on_tpu is None:
        pytest.skip(f"no compile-only TPU client, lowering gate only: {why_not}")
    assert next(iter(on_tpu.device_set)).platform == "tpu"


def took_kernel(op: str) -> bool:
    paths = dispatch.DISPATCH_PATHS
    return paths[f"{op}:direct"] > 0 and paths[f"{op}:xla"] == 0


@pytest.mark.parametrize("model", MODEL_SHAPES)
def test_fused_layer_norm_fwd_bwd_lowers(one_chip, model):
    b, s = MODEL_SHAPES[model]

    def f(x, scale, bias):
        return jax.grad(
            lambda *a: layer_norm(*a, out_dtype=BF16).astype(F32).sum(),
            argnums=(0, 1, 2),
        )(x, scale, bias)

    lower_for_tpu(
        f, S((b, s, HIDDEN), BF16), S((HIDDEN,), F32), S((HIDDEN,), F32)
    )
    assert took_kernel("layer_norm")


@pytest.mark.parametrize("model", MODEL_SHAPES)
def test_dropout_add_layer_norm_fwd_bwd_lowers(one_chip, model):
    b, s = MODEL_SHAPES[model]
    key = jax.random.key(0, impl="rbg")

    def f(h, x, scale, bias):
        return jax.grad(
            lambda *a: dropout_add_layer_norm(
                *a, rate=0.1, dropout_rng=key, deterministic=False,
                out_dtype=BF16,
            ).astype(F32).sum(),
            argnums=(0, 1, 2, 3),
        )(h, x, scale, bias)

    act = S((b, s, HIDDEN), BF16)
    lower_for_tpu(f, act, act, S((HIDDEN,), F32), S((HIDDEN,), F32))
    assert took_kernel("dal")


def test_mask_scale_lowers_at_bert_large_probs(one_chip):
    key = jax.random.key(0, impl="rbg")
    b, s = MODEL_SHAPES["bert-large"]
    lower_for_tpu(
        lambda p: raw_dropout(p, 0.1, key, "kernel"),
        S((b, HEADS, s, s), BF16),
    )
    assert took_kernel("mask_scale")


@pytest.mark.parametrize("model", MODEL_SHAPES)
def test_flash_attention_fwd_bwd_lowers(one_chip, model):
    b, s = MODEL_SHAPES[model]
    causal = model == "gpt2-medium"
    key = jax.random.key(0, impl="rbg")

    def f(q, k, v, bias):
        return jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, None if causal else bias, dropout_rng=key,
                dropout_rate=0.1, deterministic=False, causal=causal,
            ).astype(F32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    qkv = S((b, s, HEADS, HEAD_DIM), BF16)
    lower_for_tpu(f, qkv, qkv, qkv, S((b, 1, 1, s), F32))
    assert took_kernel("flash")


@pytest.mark.parametrize("seq", [8, 32, 100])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_short_sequence_compiles(one_chip, seq, grad):
    """One block of any size: gpt2-medium's 8-token init forward (serve_lm
    boot) was refused on the chip while the single k-block was sliced at a
    dynamic lane offset. It must take the kernel, not the XLA math."""
    def f(q, k, v):
        return flash_attention(q, k, v, None, causal=True)

    def df(q, k, v):
        return jax.grad(
            lambda *a: f(*a).astype(F32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    qkv = S((1, seq, HEADS, HEAD_DIM), BF16)
    lower_for_tpu(df if grad else f, qkv, qkv, qkv)
    assert took_kernel("flash")


@pytest.mark.parametrize("pool_dtype", [BF16, F32, jnp.int8],
                         ids=["bf16", "f32", "int8"])
@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize("q_len", [None, 8], ids=["single", "multi"])
def test_paged_attention_kernels_lower(pool_dtype, page_size, q_len):
    """Both page-walk kernels, float and int8 pools (lane-dense, as the
    engine holds them), at gpt2-medium's head geometry; page size 16 (the
    CLI default) and 128 (the lane width)."""
    batch, windows = 4, 3
    num_pages = 1 + batch * windows
    quantized = pool_dtype == jnp.int8
    q_shape = (batch, HEADS, HEAD_DIM)
    if q_len is not None:
        q_shape = (batch, q_len, HEADS, HEAD_DIM)
    pools = [S((num_pages, page_size, HEADS * HEAD_DIM), pool_dtype)] * 2
    scales = [S((num_pages, page_size, HEADS), F32)] * 2 if quantized else []

    def f(q, kp, vp, bt, ln, *sc):
        kw = dict(zip(("k_scales", "v_scales"), sc))
        return pa.paged_attention(
            q, kp, vp, bt, ln, scale=HEAD_DIM ** -0.5, impl="pallas", **kw
        )

    lower_for_tpu(
        f, S(q_shape, F32 if quantized else pool_dtype), *pools,
        S((batch, windows), jnp.int32), S((batch,), jnp.int32), *scales,
    )


def test_paged_attn_lowers_at_the_decode_cells_geometry(one_chip):
    """The decode step's read as `gpt2_medium_decode` runs it: 48 slots x
    64 pages of 16 over a 3,073-page bf16 pool, the impl left to the gate,
    which answers as one chip does and so takes the page-walk kernel."""
    slots, windows, page_size = 48, 64, 16
    pool = S((1 + slots * windows, page_size, HEADS * HEAD_DIM), BF16)

    def f(q, kp, vp, bt, ln):
        return pa.paged_attention(q, kp, vp, bt, ln, scale=HEAD_DIM ** -0.5)

    lower_for_tpu(
        f, S((slots, HEADS, HEAD_DIM), BF16), pool, pool,
        S((slots, windows), jnp.int32), S((slots,), jnp.int32),
    )
    assert took_kernel("paged_attn")



@pytest.mark.parametrize("memory", ["pool", "ring"])
def test_paged_attn_rows_lowers_at_the_reasoning_cells_geometry(one_chip, memory):
    """The page walk in rows mode as `phi4flash_reason_decode` runs it: 40
    query heads over 20 K/V heads of 64 (two softmaxes over one 128-wide
    value), 64 slots; over the shared 18,433-page pool through 288-page
    block tables, and over a window ring read as a slot's 32 pages. The
    gate answers as one chip does and takes the kernel for both."""
    slots, heads, kv_heads, page_size = 64, 40, 20, 16
    q = S((slots, heads, HEAD_DIM), BF16)
    if memory == "pool":
        windows = 288
        pool = S((1 + slots * windows, page_size, kv_heads * HEAD_DIM), BF16)

        def f(q, kp, vp, bt, ln):
            return pa.differential_paged_decode(
                q, kp, vp, bt, ln, HEAD_DIM ** -0.5)

        lower_for_tpu(f, q, pool, pool, S((slots, windows), jnp.int32),
                      S((slots,), jnp.int32))
    else:
        ring = S((slots, 512, kv_heads * HEAD_DIM), BF16)

        def f(q, kr, vr, live):
            return pa.differential_ring_decode(
                q, kr, vr, None, live, HEAD_DIM ** -0.5, page_size)

        lower_for_tpu(f, q, ring, ring, S((slots,), jnp.int32))
    assert took_kernel("paged_attn_rows")


@pytest.mark.parametrize("slots,k,width,pages,scope", [
    (48, 2048, 2560, 11777, "sparse_attn.gather"),
    (48, 2048, 1280, 11777, "sparse_attn.gather"),
    (64, 2048, 640, 14337, "sparse_attn.gather"),
    (64, 513, 3456, 14337, "window_attn"),
], ids=["glm_group0", "glm_group1", "dots3_full", "dots3_window"])
def test_row_fetch_lowers_at_the_latent_cells_geometry(one_chip, slots, k, width,
                                                       pages, scope):
    """The decode step's group row fetch as the latent cells run it: a
    top-k's 2,048 or a window's 513 entries a slot, over 1,184-page block
    tables, from pools of 16-token pages of the groups' widths. The gate
    answers as one chip does and takes the kernel, which compiles for the
    chip as ONE Mosaic call under the group's scope (the audit's
    `*_row_fetches`) and takes the pool as it lies: no copy of it."""
    def f(pool, table, positions, valid):
        sel = la.fetch_group_rows(
            pool, table, la.Selection(positions, valid), scope=scope)
        return sel.group_rows

    specs = (S((pages, 16, width), BF16), S((slots, 1184), jnp.int32),
             S((slots, 1, k), jnp.int32), S((slots, 1, k), jnp.bool_))
    lower_for_tpu(f, *specs)
    assert took_kernel("row_fetch")
    on_tpu, _ = _compile_only_tpu()
    if on_tpu is not None:
        text = jax.jit(f).lower(*(
            S(x.shape, x.dtype, sharding=on_tpu) for x in specs)).compile().as_text()
        assert count_kernel_calls(text, scope, "row_fetch") == 1
        assert not [line for line in text.splitlines()
                    if " copy(" in line and f"[{pages},16,{width}]" in line]
