"""``row_fetch``: the decode step's fetch of chosen rows through a block
table, as one Mosaic kernel with its own DMAs.

What it computes is ``gather_rows(pool, look_up_rows(sel, ...))`` of
``ops/latent_attention.py`` with every row whose ``valid`` is false set to
zero: for each slot ``b`` and chosen entry ``i`` at token position ``p =
positions[b, 0, i]`` the row ``pool[block_table[b, p // page_size], p %
page_size]``. Attention masks the invalid entries to probability 0 before
it reads the rows, and 0 times a zero row adds what 0 times the pool's row
did, so the attention's output is the same; a row left as VMEM held it
could be NaN, and 0 times NaN is not 0.

XLA:TPU's gather fetches every slot's ``k`` rows whatever they hold, at
6.6 ns a row + 7.0 ns a KB (PERF.md, PR 32), and the look-up before it is
a gather of its own. The kernel walks a slot's chosen positions in scalar
memory instead, looks each page up in the slot's block-table row, and
copies only what valid entries need; a chunk of entries with none valid
(all but the first of an idle slot's) is zeros and reads nothing.

**What a copy can fetch.** A pool of 16-bit rows lies in HBM in tiles of 8
rows by 128 lanes, two rows packed in each 32-bit word, and a DMA moves
whole tiles: one row cannot be copied alone. So the kernel copies the
8-row TILE that holds a chosen row into a staging buffer, ONE copy for a
run of entries that fall in one tile (positions in ascending order, as a
top-k's and a window's are, make every such run one copy: a window's 513
positions take 65 or 66 copies), and then assembles the output rows with
32-bit vector loads and stores: an output word holds two output rows, each
half taken from its source word by a shift. Each ``CHUNK`` entries are
staged while the ``CHUNK`` before them are assembled. On one v5e (PERF.md,
PR 38) a busy slot's tile copy costs ~65 ns with its entries' scalar work,
so the kernel beats XLA's look-up and gather where slots are idle or rows
run in pages (a window), and loses to it where every slot is busy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_tpu.ops.flash_attention import _interpreting

#: rows of a 16-bit pool's HBM tile: the smallest thing a DMA moves
TILE = 8
#: entries staged at once (even: output rows are assembled in pairs)
CHUNK = 64
#: output rows a grid step fills, at most
BLOCK_ROWS = 512
#: entries of the staging loop's body (its SMEM loads overlap)
UNROLL = 8


def _row_fetch_kernel(count_ref, pos_ref, bt_ref, pool_hbm, o_ref, stage,
                      src, acc, staged, sems, *, page_size: int, rows: int):
    # positions are >= 0 where used and the sizes powers of two: shifts and
    # masks, not the signed division's corrections, on the scalar core
    page_bits = page_size.bit_length() - 1
    tile_bits = TILE.bit_length() - 1
    words = stage.bitcast(jnp.uint32)       # [2, CHUNK, TILE // 2, width]

    def stage_chunk(c, n_rows):
        """Copy the tiles of entries ``c * CHUNK`` .. ``+ n_rows`` into
        buffer ``c % 2``; note each entry's staged row and whether it is
        valid, as ``row << 1 | valid``."""
        buf = c % 2
        staged[buf] = 0

        def entry(i, carry):
            last, n = carry
            p = pos_ref[0, c * CHUNK + i]
            valid = p >= 0
            q = jnp.maximum(p, 0)
            page = bt_ref[0, q >> page_bits]
            sub = q & (page_size - 1)
            tile = (page << (page_bits - tile_bits)) | (sub >> tile_bits)
            new = valid & (tile != last)

            @pl.when(new)
            def _():
                first = pl.multiple_of(sub & -TILE, TILE)
                pltpu.make_async_copy(
                    pool_hbm.at[page, pl.ds(first, TILE)], stage.at[buf, n],
                    sems.at[buf]).start()

            n = n + new.astype(jnp.int32)
            src[buf, i] = jnp.where(
                valid, ((n - 1) << (tile_bits + 1)) | ((q & (TILE - 1)) << 1) | 1,
                0)
            return jnp.where(new, tile, last), n

        def entries(j, carry):
            for t in range(UNROLL):
                carry = entry(j * UNROLL + t, carry)
            return carry

        @pl.when(count_ref[0, c] > 0)
        def _():
            carry = jax.lax.fori_loop(0, n_rows // UNROLL, entries, (-1, 0))
            for i in range(n_rows // UNROLL * UNROLL, n_rows):
                carry = entry(i, carry)
            staged[buf] = carry[1]
            if n_rows % 2:
                src[buf, n_rows] = 0    # an odd last entry pairs with nothing

    def assemble_chunk(c, n_rows):
        """Wait for buffer ``c % 2`` and write its entries' rows, two rows
        a 32-bit word; a chunk with no valid entry is zeros."""
        buf = c % 2
        pairs = (n_rows + 1) // 2
        first = pl.multiple_of(c * (CHUNK // 2), CHUNK // 2)

        # the copies all signal one semaphore, which counts what they
        # moved: wait for the ``staged`` tiles in binary pieces
        n = staged[buf]
        for piece in (1 << e for e in range(CHUNK.bit_length() - 1, -1, -1)):
            @pl.when((n & piece) != 0)
            def _():
                tiles = stage.at[buf, pl.ds(0, piece)]
                pltpu.make_async_copy(tiles, tiles, sems.at[buf]).wait()

        def half(s):
            """The 16 bits of the staged row ``s >> 1`` in its word, in
            the low half (zero where ``s & 1``, valid, is 0)."""
            r = s >> 1
            word = words[buf, r >> tile_bits, pl.ds((r & (TILE - 1)) >> 1, 1), :]
            shift = ((r & 1) << 4).astype(jnp.uint32)
            keep = ((s & 1) * 0xFFFF).astype(jnp.uint32)
            return (word >> shift) & keep

        def pair(m, carry):
            lo = half(src[buf, 2 * m])
            hi = half(src[buf, 2 * m + 1])
            acc[pl.ds(first + m, 1), :] = lo | (hi << 16)
            return carry

        @pl.when(count_ref[0, c] > 0)
        def _():
            jax.lax.fori_loop(0, pairs, pair, 0)

        @pl.when(count_ref[0, c] == 0)
        def _():
            acc[pl.ds(first, pairs), :] = jnp.zeros(
                (pairs, acc.shape[-1]), jnp.uint32)

    full, tail = divmod(rows, CHUNK)
    last = full if tail else full - 1
    # chunk c + 1 is staged while chunk c is assembled; only the last chunk
    # may be short
    stage_chunk(0, CHUNK if full else tail)

    def step(c, carry):
        stage_chunk(c + 1, CHUNK)
        assemble_chunk(c, CHUNK)
        return carry

    if full > 1:
        jax.lax.fori_loop(0, full - 1, step, 0)
    if tail and full:
        stage_chunk(full, tail)
        assemble_chunk(full - 1, CHUNK)
    assemble_chunk(last, tail if tail else CHUNK)
    o_ref[0] = pltpu.bitcast(acc[...], o_ref.dtype)[:rows]


def _block_rows(k: int) -> int:
    """Rows a grid step fills: the largest power of two from 16 (a tile of
    16-bit rows) up to ``BLOCK_ROWS`` that divides ``k``, else all ``k``."""
    rows = BLOCK_ROWS
    while rows >= 16:
        if k % rows == 0:
            return rows
        rows //= 2
    return k


@functools.partial(jax.jit, static_argnames=("interpret",))
def _row_fetch(pool, block_table, positions, valid, *, interpret):
    batch, q, k = positions.shape
    _, page_size, width = pool.shape
    rows = _block_rows(q * k)
    blocks = q * k // rows
    chunks = -(-rows // CHUNK)
    # scalar blocks keep their last two dimensions whole
    wanted = jnp.where(valid, positions, -1).reshape(batch * blocks, 1, rows)
    counts = jnp.pad(valid.reshape(batch * blocks, rows),
                     ((0, 0), (0, chunks * CHUNK - rows)))
    counts = counts.reshape(batch * blocks, 1, chunks, CHUNK).sum(
        -1, dtype=jnp.int32)
    table = block_table.reshape(batch, 1, block_table.shape[1])
    scalars = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_row_fetch_kernel, page_size=page_size, rows=rows),
        grid=(batch, blocks),
        in_specs=[
            scalars((None, 1, chunks), lambda b, j: (b * blocks + j, 0, 0)),
            scalars((None, 1, rows), lambda b, j: (b * blocks + j, 0, 0)),
            scalars((None, 1, block_table.shape[1]), lambda b, j: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, width), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, q * k, width), pool.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, CHUNK, TILE, width), pool.dtype),
            pltpu.SMEM((2, CHUNK), jnp.int32),
            pltpu.VMEM(((rows + 1) // 2, width), jnp.uint32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # a window's 513 rows of 6,912 B, twice, with their staging and
        # ``acc`` pass the 16 MiB the compiler allows a call by default
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="row_fetch",
        interpret=interpret,
    )(counts, wanted, table, pool)
    return out.reshape(batch, q, k, width)


def fits(pool) -> bool:
    """A pool the kernel reads: 16-bit rows of whole 128-lane tiles in
    pages of whole ``TILE``-row tiles, a power of two of them."""
    _, page_size, width = pool.shape
    return (pool.dtype.itemsize == 2 and page_size % TILE == 0
            and page_size & (page_size - 1) == 0 and width % 128 == 0)


def row_fetch(pool, block_table, positions, valid):
    """The rows [batch, q, k, width] of ``pool`` [pages, page_size, width]
    (16-bit, pages of whole tiles) at token ``positions`` [batch, q, k] of
    the sequences whose pages ``block_table`` [batch, W] lists, zero where
    ``valid`` is false."""
    return _row_fetch(pool, block_table, positions, valid,
                      interpret=_interpreting())
