"""Learned sparse selection inside paged attention over a LATENT cache.

The paged pools of ``ops/paged_attention.py`` hold per-head K and V and the
attention walks every page of a block table. Here a page holds one latent
row per token (the compressed key/value every head shares, with the one
rotary key beside it) and, in the layers that choose, one indexer key per
token in a second pool under the same block table. A step is four stages,
each under its own ``jax.named_scope`` so a device trace can tell them
apart:

- ``sparse_attn.index_scores``: every cached position of a sequence is
  scored against the query's indexer heads through the indexer pool,
  ``I(t, s) = sum_h w_h(t) * relu(qi_h(t) . ki(s))`` for ``s <= t``;
- ``sparse_attn.topk``: the ``index_topk`` positions of largest score, of
  equal scores the lower position first (``select_topk``: exact, without
  sorting the context);
- ``sparse_attn.gather``: those positions' latent rows, fetched through the
  block table token by token: ONE fetch a selection group in the decode
  step (``fetch_group_rows``: on one chip the ``row_fetch`` kernel, which
  looks the pages up itself and reads only what valid entries need; XLA's
  look-up and row gather elsewhere), one gather a layer in a multi-token
  step;
- ``sparse_attn.attend``: attention over the gathered rows only, in the
  latent (the key up-projection absorbed into the query, the value
  up-projection applied to the output).

The chosen positions are a VALUE (``Selection``): a layer without an
indexer is handed the selection of the nearest choosing layer before it,
inside the one compiled step.

A WINDOW layer chooses nothing: its selection is the latest ``window``
positions up to the query's own (``window_selection``), and a run of window
layers is a group like any other, its rows side by side in one pool and
gathered once a decode step. The helpers take the scope their work runs
under: a window group's look-up, gather and attention run under
``window_attn``, the choosing layers' under the ``sparse_attn.*`` names
above. A multi-token step (a prefill chunk, a bucket prefill) of a window
layer fetches the contiguous span its queries reach (``window_span``) and
attends EXPANDED under the band mask (``window_mask``).

A forward without a cache (training, evaluation, the tests' comparisons)
takes the EXPANDED path instead: per-head keys and values are made from
the sequence's own latents and the selection is a mask over the causal
square (``select_mask`` / ``expanded_attention``), not blocked over
queries. Both paths compute the same function; the server runs the
absorbed one alone, bucket prefills too.

Pool layout: rows are lane-dense. The latent row is ``[c_kv | k_rope |
0...]`` padded up to a whole number of 128-lane tiles (576 -> 640), the
indexer key is exactly one tile: a pool's default TPU layout is then the
row-major one the write and the gathers run in, and no program re-lays a
pool out (PERF.md, PR 26). The layers that share a ``Selection`` (a
choosing layer and those after it up to the next: a GROUP of ``G``) keep
their rows SIDE BY SIDE in one pool ``[pages, page_size, G * 640]``, layer
``j`` of the group in columns ``[j * 640, (j + 1) * 640)``: of the 1.53 ms
XLA:TPU's gather takes for 98,304 rows of 640, 0.65 is per row fetched
whatever its width (PERF.md, PR 32), so the decode step fetches each
chosen token's ``G`` rows as one wide row where ``G`` gathers walked the
same row ids. ``kv_pool_relayout_ops`` reads 1 for the decode program on
the chip all the same: XLA:TPU fetches the last full layer's
48 MB indexer pool into on-chip memory ahead of its scores and writes it
back, in the one layout (``kv_pool_space_moves`` 1 says that it is that).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops import row_fetch as rf

LANES = 128
_NEG_INF = float("-inf")


class Selection(NamedTuple):
    """The positions a choosing layer picked for each query: ``positions``
    [batch, q, k] int32 (token positions in the sequence, in no promised
    order), ``valid`` [batch, q, k] bool (false where fewer than k
    positions exist yet) and, on the paged path, ``rows`` [batch, q, k]
    int32: where those tokens live in a pool viewed as [pages * page_size,
    width], looked up through the block table ONCE by the choosing layer
    and handed on with the positions (not built where the decode step's
    kernel looks the pages up itself). In the decode step it also carries
    ``group_rows`` [batch, 1, k, G * width]: the chosen tokens' rows of
    every layer of the group, side by side as the group's pool holds them,
    fetched once by the choosing layer (``fetch_group_rows``)."""

    positions: jax.Array
    valid: jax.Array
    rows: jax.Array | None = None
    group_rows: jax.Array | None = None


def lane_pad(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles."""
    return -(-width // LANES) * LANES


def rope_angles(positions, dim: int, theta: float):
    """cos and sin [..., dim // 2] (float32) of the rotary angles at
    ``positions`` for a ``dim``-wide rotated span."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """Rotate interleaved pairs ``(x[2i], x[2i+1])`` of the last axis by
    the angles ``cos``/``sin`` [..., d/2], which broadcast over any head
    axis in between (insert it in the caller). Float32 inside."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(dtype)


def page_slots(block_table, positions, page_size: int):
    """(page id, offset in page) of token ``positions`` [batch, n] of the
    sequences whose pages ``block_table`` [batch, W] lists."""
    pages = jnp.take_along_axis(block_table, positions // page_size, axis=1)
    return pages, positions % page_size


def write_rows(pool, block_table, positions, rows, column: int = 0):
    """Scatter ``rows`` [batch, n, width] at token ``positions`` [batch, n]
    into columns ``[column, column + width)`` of ``pool`` [pages,
    page_size, >= column + width].

    Where the pool is wider than the rows (a group's pool: other layers'
    columns beside these), the tokens' whole rows are read, these columns
    replaced and the whole rows written back: ``batch * n`` more rows to
    fetch, and the scatter stays the row scatter XLA:TPU runs as one
    operation (a scatter at a column offset it unrolls into a loop of one
    update a row: PERF.md, PR 32)."""
    pages, offs = page_slots(block_table, positions, pool.shape[1])
    rows = rows.astype(pool.dtype)
    if rows.shape[-1] != pool.shape[-1]:
        rows = pool[pages, offs].at[
            ..., column:column + rows.shape[-1]].set(rows)
    return pool.at[pages, offs].set(rows)


def index_scores(qi, weights, index_pages, block_table, q_positions):
    """Indexer scores of every cached position: ``qi`` [batch, q, heads,
    dim], ``weights`` [batch, q, heads] (float32, the head scale folded
    in), the indexer pool [pages, page_size, dim], ``block_table`` [batch,
    W], ``q_positions`` [batch, q]. Returns [batch, q, W * page_size]
    float32 with ``-inf`` at positions after the query's own.

    The [batch, q, heads, context] product lives in float32 before the
    heads are summed: callers bound ``batch * q`` (the decode step has one
    query a sequence; a prefill chunk goes through in blocks of queries)."""
    batch, width = block_table.shape
    page_size, dim = index_pages.shape[1:]
    with jax.named_scope("sparse_attn.index_scores"):
        keys = index_pages[block_table].reshape(batch, width * page_size, dim)
        return _index_scores(qi, weights, keys, q_positions)


def _index_scores(qi, weights, keys, q_positions):
    per_head = jnp.einsum(
        "bqhd,bsd->bqhs", qi, keys, preferred_element_type=jnp.float32)
    scores = jnp.einsum("bqhs,bqh->bqs", jax.nn.relu(per_head), weights)
    s_pos = jnp.arange(keys.shape[1], dtype=jnp.int32)
    seen = s_pos[None, None, :] <= q_positions[:, :, None]
    return jnp.where(seen, scores, _NEG_INF)


def fresh_index_scores(qi, weights, keys, q_positions):
    """``index_scores`` of a fresh sequence: ``keys`` [batch, s, dim] are
    the sequence's own indexer keys, position i at row i."""
    with jax.named_scope("sparse_attn.index_scores"):
        return _index_scores(qi, weights, keys, q_positions)


def _ordered_keys(scores):
    """uint32 keys in the order of the float32 ``scores`` (``-inf``
    lowest): the bit pattern with the sign bit set for positives and every
    bit flipped for negatives."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)  # -0. -> 0.
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(keys, k: int):
    """The k-th largest key of each row, built bit by bit from the top:
    32 passes of a compare and a count over the row, no sort."""
    def body(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, t)

    return jax.lax.fori_loop(
        0, 32, body, jnp.zeros(keys.shape[:-1], jnp.uint32))


def _compact(mask, k: int):
    """The positions of the ``k`` true entries of each row of ``mask``
    [..., n], ascending: each 128-lane block puts its own chosen lanes
    first (a sort of 128), then output slot r reads block ``b(r)`` at
    ``r - chosen before b(r)``."""
    n = mask.shape[-1]
    blocks = -(-n // LANES)
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, blocks * LANES - n)])
    m = m.reshape(*mask.shape[:-1], blocks, LANES)
    lane = jnp.arange(LANES, dtype=jnp.int32)
    lanes = jnp.sort(jnp.where(m, lane, lane + LANES), axis=-1) % LANES
    counts = m.sum(-1, dtype=jnp.int32)
    ends = jnp.cumsum(counts, axis=-1)
    starts = (ends - counts)[..., None, :]                   # [..., 1, blocks]
    slot = jnp.arange(k, dtype=jnp.int32)[:, None]           # [k, 1]
    block = jnp.minimum(
        (ends[..., None, :] <= slot).sum(-1, dtype=jnp.int32), blocks - 1)
    before = jnp.where(starts <= slot, starts, 0).max(-1)    # chosen before it
    flat = block * LANES + (slot[:, 0] - before)
    picked = jnp.take_along_axis(
        lanes.reshape(*mask.shape[:-1], blocks * LANES), flat, axis=-1)
    return block * LANES + picked


def select_topk(scores, k: int, q_positions) -> Selection:
    """The ``k`` positions of largest score for each query (``scores``
    [batch, q, context], ``-inf`` after the query's own position
    ``q_positions`` [batch, q]); of equal scores the lower position is
    taken first. Fewer than ``k`` seen positions leave the rest not valid.

    Exact, and no sort of the context: the k-th largest score is found by
    32 counting passes over the keys' bits, ties at it are taken from the
    lowest position up, and the chosen set is compacted block by block
    (XLA:TPU sorts the whole row for ``jax.lax.top_k`` at this k: 5.5 ms a
    call at 48 x 18,944, a quarter of the decode step; PERF.md, PR 29)."""
    k = min(k, scores.shape[-1])
    with jax.named_scope("sparse_attn.topk"):
        keys = _ordered_keys(scores)
        kth = _kth_largest(keys, k)[..., None]
        above = keys > kth
        ties = keys == kth
        wanted = k - above.sum(-1, keepdims=True, dtype=jnp.int32)
        mask = above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= wanted))
        positions = _compact(mask, k)
        return Selection(positions, positions <= q_positions[..., None])


def window_selection(q_positions, window: int) -> Selection:
    """The latest ``window`` positions up to and including each query's own
    (``q_positions`` [batch, q]), ascending: [batch, q, window]. Positions
    before the sequence's start are not valid (and read position 0)."""
    back = jnp.arange(1 - window, 1, dtype=jnp.int32)
    positions = q_positions[..., None] + back
    return Selection(jnp.maximum(positions, 0), positions >= 0)


def window_mask(q_positions, k_positions, window: int):
    """[batch, q, s]: key position ``k_positions`` [batch, s] lies in the
    window of query position ``q_positions`` [batch, q]: at most ``window
    - 1`` before it, not after it, not before the sequence's start."""
    q = q_positions[:, :, None]
    k = k_positions[:, None, :]
    return (k <= q) & (k > q - window) & (k >= 0)


def window_span(q_positions, window: int):
    """The positions [batch, q + window - 1] a block of consecutive queries
    ``q_positions`` [batch, q] reaches back to in a window of ``window``:
    from ``window - 1`` before the first query to the last one. (A block's
    tail may repeat its last query: the span still covers it.)"""
    q = q_positions.shape[1]
    return q_positions[:, :1] + jnp.arange(
        1 - window, q, dtype=jnp.int32)[None]


def look_up_rows(sel: Selection, block_table, page_size: int,
                 scope: str = "sparse_attn.gather") -> Selection:
    """``sel`` with its tokens' pool rows looked up through the block
    table (a layer that is handed the selection reuses them)."""
    batch, q, k = sel.positions.shape
    with jax.named_scope(scope):
        pages, offs = page_slots(
            block_table, sel.positions.reshape(batch, q * k), page_size)
        return sel._replace(
            rows=(pages * page_size + offs).reshape(batch, q, k))


def layer_pool(latent_pages, column: int, width: int):
    """Columns ``[column, column + width)`` of a group's pool as a pool of
    one layer [pages, page_size, width]: a copy where the group has more
    layers than one, made once a layer by a multi-token step, whose many
    gathers then fetch narrow rows (XLA:TPU unrolls a gather at a column
    offset into a loop of one slice a row: PERF.md, PR 32)."""
    return latent_pages[:, :, column:column + width]


def gather_rows(latent_pages, sel: Selection,
                scope: str = "sparse_attn.gather"):
    """The selected tokens' rows [batch, q, k, width], token by token from
    the pool (``sel.rows``: ``look_up_rows``). Handed a group's pool whole
    (the decode step, once a group: ``Selection.group_rows``) the rows are
    those of every layer of the group, side by side; a layer reads its own
    columns with ``group_slice``."""
    pages, page_size, width = latent_pages.shape
    with jax.named_scope(scope):
        return latent_pages.reshape(pages * page_size, width)[sel.rows]


def fetch_group_rows(latent_pages, block_table, sel: Selection,
                     scope: str = "sparse_attn.gather") -> Selection:
    """``sel`` with its ``group_rows``: the decode step's one fetch of a
    group's rows. Where the gate says one chip (or the interpreter) runs
    kernels and the pool is 16-bit in whole tiles, the ``row_fetch``
    kernel looks the pages up and copies what valid entries need (invalid
    entries read zeros, which attention weighs by 0 as it did the pool's
    rows); elsewhere XLA's look-up and row gather. Counted as
    ``row_fetch:direct`` / ``row_fetch:xla``."""
    kernel = dispatch.mode() == "direct" and rf.fits(latent_pages)
    dispatch.note_path("row_fetch", "direct" if kernel else "xla")
    if kernel:
        with jax.named_scope(scope):
            return sel._replace(group_rows=rf.row_fetch(
                latent_pages, block_table, sel.positions, sel.valid))
    sel = look_up_rows(sel, block_table, latent_pages.shape[1], scope)
    return sel._replace(group_rows=gather_rows(latent_pages, sel, scope))


def group_slice(sel: Selection, column: int, width: int, fresh=None,
                positions=None, scope: str = "sparse_attn.attend"):
    """One layer's rows [batch, 1, k, width] out of ``sel.group_rows``,
    columns ``[column, column + width)``. The group's gather ran before a
    later layer of the group wrote its row of the CURRENT token
    (``positions`` [batch, 1]): where that token is among the chosen, such
    a layer's row is ``fresh`` [batch, 1, width], the row this step
    writes, not the pool's."""
    with jax.named_scope(scope):
        rows = sel.group_rows[..., column:column + width]
        if fresh is None:
            return rows
        current = sel.positions == positions[..., None]
        return jnp.where(
            current[..., None], fresh[:, :, None, :].astype(rows.dtype), rows)


def latent_attention(q_latent, rows, valid, scale: float,
                     scope: str = "sparse_attn.attend"):
    """Attention in the latent over gathered rows: ``q_latent`` [batch, q,
    heads, width] (the absorbed query laid out as a pool row: ``[q_nope .
    W_uk | q_rope | 0]``), ``rows`` [batch, q, k, width]. Returns the
    probability-weighted rows [batch, q, heads, width]: the caller keeps
    the latent span and applies the value up-projection."""
    with jax.named_scope(scope):
        scores = jnp.einsum(
            "bqhw,bqkw->bqhk", q_latent, rows,
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid[:, :, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
        return jnp.einsum(
            "bqhk,bqkw->bqhw", probs, rows,
            preferred_element_type=jnp.float32)


def select_mask(sel: Selection, context: int):
    """The selection as a mask [batch, q, context]: the expanded path's
    form of the same value."""
    batch, q, _ = sel.positions.shape
    b = jnp.arange(batch)[:, None, None]
    r = jnp.arange(q)[None, :, None]
    mask = jnp.zeros((batch, q, context), bool)
    # top_k's positions are distinct for one query: no write meets another
    return mask.at[b, r, sel.positions].set(sel.valid)


def expanded_attention(q_nope, q_rope, k_nope, k_rope, v, mask, scale: float,
                       scope: str = "sparse_attn.attend"):
    """Per-head attention of a fresh sequence over itself, restricted to
    ``mask`` [batch, q, s]: ``q_nope`` [b, q, h, dn], ``q_rope`` [b, q, h,
    dr], ``k_nope`` [b, s, h, dn], ``k_rope`` [b, s, dr] (one rotary key
    for all heads), ``v`` [b, s, h, dv]. Returns [b, q, h, dv]."""
    with jax.named_scope(scope):
        scores = (
            jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhr,bsr->bhqs", q_rope, k_rope,
                         preferred_element_type=jnp.float32)
        ) * scale
        scores = jnp.where(mask[:, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v,
                          preferred_element_type=jnp.float32)
