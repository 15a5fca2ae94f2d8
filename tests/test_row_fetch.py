"""The decode step's row fetch as a kernel (`ops/row_fetch.py`) in the
Pallas interpreter on the CPU, against XLA's block-table look-up and row
gather (`look_up_rows` + `gather_rows`): valid rows equal to the bit,
invalid ones zero, over top-k-like scattered positions, runs that cross a
page, a window's clamped leading positions, a slot with nothing valid and
the cells' row widths; and one decode step of the tiny latent models
through it giving the XLA path's logits."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import latent_moe as lm
from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops import latent_attention as la
from pytorch_distributed_training_tpu.ops import row_fetch as rf
from pytorch_distributed_training_tpu.ops.flash_attention import (
    tpu_interpret_mode,
)
from pytorch_distributed_training_tpu.serve.paged_cache import (
    strip_tables,
    with_tables,
)

PAGE = 16


def _pool(pages, width, seed=0):
    return jax.random.normal(
        jax.random.key(seed), (pages, PAGE, width), jnp.float32
    ).astype(jnp.bfloat16)


def _table(slots, width, pages, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.permutation(np.arange(1, pages))[: slots * width].reshape(
            slots, width), jnp.int32)


def _xla(pool, table, positions, valid):
    sel = la.look_up_rows(la.Selection(positions, valid), table, PAGE)
    return la.gather_rows(pool, sel)


def _fetch(pool, table, positions, valid):
    with tpu_interpret_mode():
        return rf.row_fetch(pool, table, positions, valid)


def _check(pool, table, positions, valid):
    positions, valid = jnp.asarray(positions), jnp.asarray(valid)
    got = np.asarray(_fetch(pool, table, positions, valid))
    want = np.asarray(_xla(pool, table, positions, valid))
    keep = np.asarray(valid)[..., None]
    assert got.shape == want.shape
    assert np.array_equal(np.where(keep, got, 0), np.where(keep, want, 0))
    assert not np.where(keep, 0, got).any()   # invalid rows read zeros
    return got


@pytest.mark.parametrize("width", [640, 1280, 2560, 3456])
def test_valid_rows_equal_the_gather_and_invalid_rows_are_zero(width):
    """Scattered ascending positions as a top-k gives them, a fifth of the
    entries invalid, at each row width the cells fetch."""
    slots, k, table_width = 3, 48, 6
    pool = _pool(40, width)
    table = _table(slots, table_width, 40)
    rng = np.random.default_rng(width)
    positions = np.sort(np.stack([
        rng.choice(table_width * PAGE, k, replace=False)
        for _ in range(slots)]), axis=-1)[:, None].astype(np.int32)
    valid = rng.random((slots, 1, k)) > 0.2
    _check(pool, table, positions, valid)


def test_positions_in_no_order_and_repeated():
    """Correctness does not lean on the order: positions shuffled, some
    repeated, the same tile met again after another."""
    pool, table = _pool(30, 256), _table(2, 4, 30)
    rng = np.random.default_rng(3)
    positions = rng.integers(0, 4 * PAGE, (2, 1, 40)).astype(np.int32)
    positions[0, 0, :6] = [3, 40, 3, 4, 41, 3]
    _check(pool, table, positions, np.ones((2, 1, 40), bool))


def test_runs_that_cross_a_page_boundary():
    """Consecutive positions over three scattered pages, from the middle
    of one page to the middle of the third, beside a slot whose run starts
    on a tile's edge."""
    pool, table = _pool(30, 384), _table(2, 4, 30)
    positions = np.stack([np.arange(9, 9 + 40), np.arange(16, 56)])[:, None]
    _check(pool, table, positions.astype(np.int32), np.ones((2, 1, 40), bool))


def test_the_windows_clamped_leading_positions():
    """A window's selection early in a sequence: positions before the
    start read position 0 and are invalid; the tail of 513 entries (no
    multiple of 16) is one grid step."""
    pool, table = _pool(120, 1152), _table(3, 36, 120)
    sel = la.window_selection(jnp.asarray([[3], [530], [0]], jnp.int32), 513)
    got = _check(pool, table, sel.positions, sel.valid)
    assert not got[0, 0, :509].any() and got[0, 0, 509:].any()


def test_a_slot_with_nothing_valid():
    """An idle slot reads nothing and is zeros, beside a busy one whose
    2,048 entries (four grid steps of 512) are valid in the first 700
    alone: its last two steps hold no valid entry and are zeros too."""
    pool, table = _pool(400, 128), _table(2, 160, 400)
    positions = np.tile(np.arange(2048, dtype=np.int32), (2, 1, 1))
    valid = np.zeros((2, 1, 2048), bool)
    valid[1, 0, :700] = True
    got = _check(pool, table, positions, valid)
    assert not got[0].any()


def test_the_gate_keeps_other_pools_on_xla():
    """The kernel reads 16-bit pools in whole tiles: a float32 pool, or
    pages of 4 rows, take XLA's look-up and gather (and are counted so)."""
    table = _table(1, 4, 20)
    sel = la.Selection(jnp.arange(8, dtype=jnp.int32)[None, None],
                       jnp.ones((1, 1, 8), bool))
    dispatch.DISPATCH_PATHS.clear()
    with tpu_interpret_mode():
        la.fetch_group_rows(_pool(20, 128), table, sel)
        la.fetch_group_rows(_pool(20, 128).astype(jnp.float32), table, sel)
        la.fetch_group_rows(jnp.zeros((20, 4, 128), jnp.bfloat16), table, sel)
    la.fetch_group_rows(_pool(20, 128), table, sel)   # off the chip
    assert dispatch.DISPATCH_PATHS["row_fetch:direct"] == 1
    assert dispatch.DISPATCH_PATHS["row_fetch:xla"] == 3
    dispatch.DISPATCH_PATHS.clear()


@pytest.mark.parametrize("preset", ["latent-moe-tiny", "dots3-tiny"])
def test_a_decode_step_through_the_kernel_gives_the_xla_logits(preset):
    """One decode step of the tiny latent models at bfloat16 over pages of
    16, on random pools: three slots at contexts past `index_topk` and the
    window, under them, and idle. The kernel path's logits and written
    pools equal the XLA path's bit for bit (zeros where XLA fetched rows
    that attention weighed by 0)."""
    cfg = dataclasses.replace(
        lm.preset(preset), decode=True, kv_num_pages=24, kv_page_size=PAGE,
        compute_dtype="bfloat16")
    model = lm.LatentMoELM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32)))
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 1), jnp.int32),
        position_ids=jnp.zeros((1, 1), jnp.int32))["params"]
    leaves, tree = jax.tree.flatten(strip_tables(shapes["cache"]))
    pools = jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.key(i), s.shape, jnp.float32).astype(s.dtype)
        for i, s in enumerate(leaves)])
    table = _table(3, 4, 24)
    ctx = jnp.asarray([45, 5, 0], jnp.int32)
    tokens = jnp.asarray([[7], [11], [0]], jnp.int32)

    def step():
        # a new function each time: jit traces it again, under the gate
        # as it answers then
        def apply(params, pools, tokens, table, ctx):
            logits, vars_ = model.apply(
                {"params": params, "cache": with_tables(pools, table, ctx)},
                tokens, position_ids=ctx[:, None], mutable=["cache"],
                token_mask=(ctx > 0)[:, None])
            return logits, strip_tables(vars_["cache"])

        return jax.jit(apply)(params, pools, tokens, table, ctx)

    dispatch.DISPATCH_PATHS.clear()
    want = step()
    assert dispatch.DISPATCH_PATHS["row_fetch:direct"] == 0
    with tpu_interpret_mode():
        got = step()
    groups = len(cfg.selection_groups[1])
    assert dispatch.DISPATCH_PATHS["row_fetch:direct"] == groups
    dispatch.DISPATCH_PATHS.clear()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
