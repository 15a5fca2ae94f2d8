"""The page-walk kernel of the decode step's K/V read (``paged_attn``,
ops/paged_attention.py), in the Pallas interpreter on the CPU, against the
XLA formula (``_paged_reference``) it stands in for on the chip — and the
choice between the two, which the dispatch gate makes and
``DISPATCH_PATHS`` counts.

The interpreter fills every scratch buffer with NaN before the kernel
runs, so each case with a partly live block also shows that rows no copy
filled stay out of the result. What only the chip's compiler can say
(tiling, VMEM, the whole decode program) is in tests/test_tpu_lowering.py
and tests/test_paged_pool_layout.py; numerics and times on the chip in
tests/test_tpu_kernels.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops import paged_attention as pa
from pytorch_distributed_training_tpu.ops.flash_attention import (
    tpu_interpret_mode,
)

pytestmark = [pytest.mark.serve]

HEADS, HEAD_DIM = 2, 64  # one 128-lane tile: whole tiles, small pools
SCALE = HEAD_DIM ** -0.5
CACHE_LEN = 1024
BF16_ULP = 2.0 ** -7  # spacing of bf16 values, relative to their size


def _pools(seed, dtype, page_size, lengths, idle=()):
    """Noise-filled pools and a block table of shuffled, non-contiguous
    page ids covering ``lengths``; slots in ``idle`` park every entry on
    page 0, and entries past a slot's last live page point there too."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    windows = CACHE_LEN // page_size
    live = [
        0 if b in idle else -(-int(n) // page_size)
        for b, n in enumerate(lengths)
    ]
    # twice the pages anyone names, so the ids have gaps between them
    num_pages = 1 + 2 * max(sum(live), 1)
    ids = rng.permutation(np.arange(1, num_pages))
    table = np.zeros((slots, windows), np.int32)
    at = 0
    for b, n in enumerate(live):
        table[b, :n] = ids[at:at + n]
        at += n
    shape = (num_pages, page_size, HEADS * HEAD_DIM)
    k = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    v = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    q = jnp.asarray(
        rng.standard_normal((slots, HEADS, HEAD_DIM), np.float32), dtype
    )
    return q, k, v, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


def _fill(name, page_size):
    """(lengths, idle slots) of a named fill."""
    block = max(pa._BLOCK_TOKENS, page_size)
    if name == "idle":
        return [1] * 4, {0, 1, 2, 3}
    if name == "one_token":
        return [1, 1, 1], ()
    if name == "page_edge":
        return [16, 17, page_size, page_size + 1], ()
    if name == "block_edge":
        return [block, block + 1, 2 * block, 2 * block + 1], ()
    if name == "full":
        return [CACHE_LEN, 5, CACHE_LEN - 1], ()
    assert name == "mixed48"
    rng = np.random.default_rng(48)
    lengths = rng.integers(1, CACHE_LEN + 1, size=48)
    lengths[::5] = 1  # every fifth slot idle, as the engine parks them
    return lengths.tolist(), set(range(0, 48, 5))


def _kernel(q, k, v, table, lengths):
    with tpu_interpret_mode():
        return pa.paged_attention(
            q, k, v, table, lengths, scale=SCALE, impl="pallas"
        )


def _assert_close(got, want, dtype):
    assert got.shape == want.shape and got.dtype == want.dtype
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    # bf16 pools: one bf16 step at the size of the head's output row (both
    # sides round probabilities to bf16 before PV, at different scales)
    step = BF16_ULP * np.abs(want).max(axis=-1, keepdims=True)
    assert (np.abs(got - want) <= step).all(), (
        np.abs(got - want).max(), step.min()
    )


@pytest.mark.parametrize(
    "fill",
    ["idle", "one_token", "page_edge", "block_edge", "full", "mixed48"],
)
@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"]
)
def test_page_walk_matches_reference(dtype, page_size, fill):
    lengths, idle = _fill(fill, page_size)
    operands = _pools(7, dtype, page_size, lengths, idle)
    want = pa._paged_reference(*operands, SCALE)
    _assert_close(_kernel(*operands), want, dtype)


@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"]
)
def test_dead_pages_are_never_touched(dtype, page_size):
    """Every WHOLE page no live table entry names — page 0 too, since no
    slot here is idle — is NaN. The reference would read NaN there (0 x NaN
    through its padded window), so it runs on the clean pools; the kernel
    runs on the poisoned ones and must read the same, finite."""
    lengths = [1, 17, 128, 129, 700, CACHE_LEN]
    q, k, v, table, lens = _pools(11, dtype, page_size, lengths)
    want = pa._paged_reference(q, k, v, table, lens, SCALE)
    live = np.zeros(k.shape[0], bool)
    for b, n in enumerate(lengths):
        live[np.asarray(table)[b, : -(-n // page_size)]] = True
    assert not live[0] and live.sum() < live.size - 1
    poison = jnp.asarray(~live)[:, None, None]
    k = jnp.where(poison, jnp.nan, k)
    v = jnp.where(poison, jnp.nan, v)
    # dead table entries point at page 0, which is poisoned
    assert bool(jnp.isnan(k[table[0, 1]]).all())
    _assert_close(_kernel(q, k, v, table, lens), want, dtype)


def test_zero_length_slot_reads_zero():
    """Outside the engine's contract (lengths >= 1), and still no NaN."""
    q, k, v, table, _ = _pools(3, jnp.float32, 16, [40, 40])
    got = _kernel(q, k, v, table, jnp.asarray([0, 40], jnp.int32))
    want = pa._paged_reference(
        q, k, v, table, jnp.asarray([40, 40], jnp.int32), SCALE
    )
    assert not np.asarray(got[0]).any()
    _assert_close(got[1:], want[1:], jnp.float32)


# ---------------------------------------------------------------- the choice


@pytest.fixture
def paths():
    dispatch.DISPATCH_PATHS.clear()
    yield dispatch.DISPATCH_PATHS
    dispatch.DISPATCH_PATHS.clear()


def _only(paths, key):
    mine = {k: n for k, n in paths.items() if k.startswith("paged_attn:")}
    return mine == {key: 1}


def test_choice_is_the_kernel_under_the_interpret_context(paths):
    operands = _pools(1, jnp.bfloat16, 16, [30, 1])
    with tpu_interpret_mode():
        got = pa.paged_attention(*operands, scale=SCALE)
    assert _only(paths, "paged_attn:direct"), dict(paths)
    _assert_close(got, pa._paged_reference(*operands, SCALE), jnp.bfloat16)


def test_choice_is_the_kernel_where_the_gate_says_one_chip(
    paths, monkeypatch
):
    """``mode()`` answering as one TPU device does: the default takes the
    kernel (lowered for the TPU here, not run)."""
    monkeypatch.setattr(dispatch, "mode", lambda: "direct")
    operands = _pools(1, jnp.bfloat16, 16, [30, 1])
    exported = jax.export.export(
        jax.jit(lambda *a: pa.paged_attention(*a, scale=SCALE)),
        platforms=["tpu"],
    )(*operands)
    assert _only(paths, "paged_attn:direct"), dict(paths)
    assert "paged_attn" in exported.mlir_module()


def test_choice_is_xla_on_plain_cpu(paths):
    operands = _pools(1, jnp.bfloat16, 16, [30, 1])
    got = pa.paged_attention(*operands, scale=SCALE)
    assert _only(paths, "paged_attn:xla"), dict(paths)
    want = pa.paged_attention(*operands, scale=SCALE, impl="reference")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("gate", ["off", "shard_map"])
def test_choice_is_xla_off_one_chip(paths, monkeypatch, gate):
    """Several devices (``--tp``, ``fleet_lm``) or no TPU: the XLA path."""
    monkeypatch.setattr(dispatch, "mode", lambda: gate)
    pa.paged_attention(*_pools(1, jnp.bfloat16, 16, [30, 1]), scale=SCALE)
    assert _only(paths, "paged_attn:xla"), dict(paths)


def test_choice_is_xla_for_int8_pools(paths):
    q, k, v, table, lengths = _pools(1, jnp.float32, 16, [30, 1])
    scales = jnp.ones((*k.shape[:2], HEADS), jnp.float32)
    with tpu_interpret_mode():
        pa.paged_attention(
            q, k.astype(jnp.int8), v.astype(jnp.int8), table, lengths,
            scale=SCALE, k_scales=scales, v_scales=scales,
        )
    assert _only(paths, "paged_attn:xla"), dict(paths)


def test_choice_is_xla_for_a_query_block(paths):
    q, k, v, table, lengths = _pools(1, jnp.bfloat16, 16, [30, 9])
    with tpu_interpret_mode():
        got = pa.paged_attention(
            jnp.stack([q] * 4, axis=1), k, v, table, lengths, scale=SCALE
        )
    assert got.shape == (2, 4, HEADS, HEAD_DIM)
    assert _only(paths, "paged_attn:xla"), dict(paths)


def test_choice_is_xla_where_a_page_is_not_whole_tiles(paths):
    """gpt2-tiny's pools (4-token pages, 32 lanes): a page is no whole
    tile, the chip's compiler would refuse its copy, the XLA path runs."""
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((9, 4, 32), np.float32))
    q = jnp.asarray(rng.standard_normal((2, 2, 16), np.float32))
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    with tpu_interpret_mode():
        pa.paged_attention(
            q, k, k, table, jnp.asarray([7, 2], jnp.int32), scale=0.25
        )
    assert _only(paths, "paged_attn:xla"), dict(paths)


def test_choice_is_xla_where_query_and_pools_differ_in_dtype(paths):
    """A float32 query over bf16 pools: the formula widens K, the kernel
    would round the query; no model holds its pools so, the XLA path."""
    q, k, v, table, lengths = _pools(1, jnp.bfloat16, 16, [30, 1])
    with tpu_interpret_mode():
        pa.paged_attention(
            q.astype(jnp.float32), k, v, table, lengths, scale=SCALE
        )
    assert _only(paths, "paged_attn:xla"), dict(paths)


def test_unknown_impl_is_refused():
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        pa.paged_attention(
            *_pools(1, jnp.float32, 16, [3]), scale=SCALE, impl="fast"
        )


def test_engine_defaults_take_the_xla_path_on_cpu(paths):
    """An engine built on the CPU with defaults traces its decode program
    through the XLA formula, so the token-identity pins of tests/
    test_paged.py hold what they held (they run untouched)."""
    from pytorch_distributed_training_tpu.serve.engine import (
        DecodeEngine,
        EngineConfig,
    )
    from pytorch_distributed_training_tpu.utils.config import model_preset

    from test_paged_pool_layout import _gpt2_engine  # sibling module

    # the gate chooses: neither config has a field to say otherwise
    assert not hasattr(EngineConfig(), "paged_attention_impl")
    assert not hasattr(model_preset("gpt2-tiny"), "paged_attention_impl")
    DecodeEngine(*_gpt2_engine(
        "gpt2-tiny", num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
        page_size=4, warmup=True,
    ))
    assert paths["paged_attn:xla"] > 0 and not paths["paged_attn:direct"]
