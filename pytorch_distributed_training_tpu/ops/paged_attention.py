"""Paged decode attention: attention that gathers K/V through a block table
over fixed-size pages (vLLM PagedAttention layout).

Shapes
------
- ``q``:          [batch, heads, head_dim] — ONE query token per sequence
                  (the classic decode step), or
                  [batch, q_len, heads, head_dim] — a multi-token query block
                  (speculative verify / chunked prefill). The q_len tokens
                  are the LAST q_len positions of the sequence and attend
                  causally: query row ``j`` sees positions
                  ``< lengths - q_len + 1 + j``.
- ``k_pages``/``v_pages``: [num_pages, page_size, heads * head_dim] — the
                  engine-resident page pools, LANE-DENSE: a token's heads
                  are folded into one minor axis (head ``n`` owns lanes
                  ``n*head_dim .. (n+1)*head_dim - 1``), so XLA:TPU's
                  default layout for the pool is the row-major one the
                  scatter and the gather run in. (With trailing
                  ``[16, 64]`` axes the default layout put the PAGE axis on
                  the lanes, and every program relaid the whole pool out
                  and back around a one-token write.) ``heads`` and
                  ``head_dim`` are read from ``q``. Page 0 is the reserved
                  null page (see serve/paged_cache.py); idle sequences park
                  their block table on it.
- ``block_table``: [batch, pages_per_seq] int32 — page ids per sequence, in
                  token order; entries past the live length point at page 0.
- ``lengths``:    [batch] int32 — valid tokens per sequence INCLUSIVE of all
                  query tokens (the engine writes the new K/V before
                  attending, so positions ``lengths-q_len .. lengths-1`` are
                  the query block itself). ``lengths >= q_len`` is an engine
                  contract: every query row has at least one visible token.

Which path runs where (``impl="auto"``, the default everywhere: the choice
is made at trace time from what the code can see, and counted as
``paged_attn:direct`` / ``paged_attn:xla`` in ``dispatch.DISPATCH_PATHS``,
which ``serve_lm`` logs):

- **The page-walk kernel ``paged_attn``** (``_paged_page_walk``): a 3-D
  query over float pools where ``dispatch.mode() == "direct"`` (one TPU
  device, or the interpret context) and a page is whole tiles. This is the
  decode step of every GPT-2/BERT-block decoder ``serve_lm`` serves paged on
  one chip (`gpt2_medium_decode`). It takes the pools as they lie, copies
  only the pages a slot's length covers, several a step with the next
  block's copies in flight, and stays lane-dense inside; scores, softmax and
  both accumulations in float32 (see the section below).
- **The XLA formula** (``_paged_reference`` / ``_paged_reference_mq``): XLA
  gather + the exact einsum/softmax formula of the dense flax cache path
  (models/bert.py ``_cached_attend``), everywhere else: the CPU (the whole
  tier-1 suite, and with it every token-identity pin), several devices
  (``--tp``, ``fleet_lm``: the gate says ``"shard_map"`` or ``"off"``),
  int8 pools (their scale pools keep a pages-minor layout), the 4-D query of
  speculative verify, chunked prefill and prefix-cache tails. Masked lanes go
  to ``finfo.min`` so their exp underflows to an exact 0.0 in fp32; its
  output is therefore token-identical to the dense cache whatever the pool
  geometry (same argument that pins slotted serve to one-shot generate). It
  unfolds the whole ``[slots, cache_len, heads, head_dim]`` window whatever
  a slot holds: 49 of a 56 ms step at `gpt2_medium_decode` before the
  kernel took its place there (PERF.md).

``impl="reference"`` and ``impl="pallas"`` pin a path for the tests. An
explicit ``"pallas"`` means ``paged_attn`` for a 3-D query over float pools,
and otherwise the older kernels ``_paged_kernel`` (int8 pools) and
``_paged_kernel_mq`` (4-D query): grid (batch, pages_per_seq), one page a
grid step through the ``index_map``, over a ``(1, page_size, heads,
head_dim)`` block fed by a reshape of the lane-dense pool at the
``pallas_call`` boundary, which on the chip relays the WHOLE pool out every
call; nothing chooses them by itself. The multi-query kernel's dots run on
the MXU at its default precision, which rounds fp32 operands to bf16 (on the
chip: ~6e-3 max abs error against the reference on fp32 pools,
tests/test_tpu_kernels.py). ``interpret=`` falls back to the Pallas
interpreter off-TPU (same ``tpu_interpret_mode()`` contract as
ops/flash_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops.flash_attention import _interpreting

_NEG_INF = jnp.finfo(jnp.float32).min
_LANES = 128


_SCALE_AXES = ("num_pages", "page_size", "heads")


def _check_scale_pool(pool_name, pool, scale_name, scales, heads):
    """Trace-time contract between an int8 page pool and its scale pool,
    in the named-axis error style: int8 pools REQUIRE fp32 scales of shape
    [num_pages, page_size, heads] (one per token per head, ``heads`` from
    ``q``); float pools must not carry scales."""
    if pool.dtype == jnp.int8:
        want = (*pool.shape[:2], heads)
        if scales is None:
            raise ValueError(
                f"{pool_name} is int8 but {scale_name} is missing: int8 "
                f"pools require fp32 per-page-per-head scales of shape "
                f"(num_pages, page_size, heads) = {want}"
            )
        if scales.ndim != 3:
            raise ValueError(
                f"{scale_name} must be [num_pages, page_size, heads]: got "
                f"shape {scales.shape} (rank {scales.ndim}, want 3)"
            )
        if tuple(scales.shape) != want:
            bad = ", ".join(
                f"{name} (axis {i}): got {g}, want {w}"
                for i, (name, g, w) in enumerate(
                    zip(_SCALE_AXES, scales.shape, want)
                )
                if g != w
            )
            raise ValueError(
                f"{scale_name} shape mismatch on {bad} (got {scales.shape},"
                f" want {want} from {pool_name} and q)"
            )
        if scales.dtype != jnp.float32:
            raise ValueError(
                f"{scale_name} must be float32, got {scales.dtype}"
            )
    elif scales is not None:
        raise ValueError(
            f"{scale_name} provided but {pool_name} dtype is "
            f"{pool.dtype}: scale pools accompany int8 pages only"
        )


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
    impl: str = "auto",
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
) -> jax.Array:
    """Attention through a page table. 3-D ``q`` is the single-token decode
    step (returns [batch, heads, head_dim]); 4-D ``q`` is a causal
    multi-token query block (returns [batch, q_len, heads, head_dim]).
    Output dtype is ``v_pages.dtype`` (the dense path's output dtype) —
    except for int8 pools, whose output is fp32 (the dequantized compute
    dtype). int8 pools carry fp32 ``k_scales``/``v_scales`` pools of shape
    [num_pages, page_size, heads]; both impls dequantize in-kernel
    (``page.astype(f32) * scale`` per head lane)."""
    if q.ndim not in (3, 4):
        raise ValueError(
            f"q must be [batch, heads, head_dim] or "
            f"[batch, q_len, heads, head_dim], got {q.shape}"
        )
    pool_axes = ("num_pages", "page_size", "heads*head_dim")
    if k_pages.shape != v_pages.shape:
        bad = ", ".join(
            f"{name} (axis {i}): k_pages={ks} vs v_pages={vs}"
            for i, (name, ks, vs) in enumerate(
                zip(pool_axes, k_pages.shape, v_pages.shape)
            )
            if ks != vs
        ) or f"rank: k_pages={k_pages.ndim} vs v_pages={v_pages.ndim}"
        raise ValueError(
            f"k_pages/v_pages shapes differ on {bad} "
            f"(full shapes {k_pages.shape} vs {v_pages.shape})"
        )
    if k_pages.ndim != 3:
        raise ValueError(
            f"k_pages/v_pages must be [num_pages, page_size, "
            f"heads*head_dim]: got shape {k_pages.shape} (rank "
            f"{k_pages.ndim}, want 3)"
        )
    # q's trailing [heads, head_dim] must fold to the pools' lane axis —
    # the axis that goes wrong first when heads shard over a
    # tensor-parallel mesh and one side of the call still sees the
    # unsharded width
    heads, head_dim = q.shape[-2:]
    if heads * head_dim != k_pages.shape[2]:
        raise ValueError(
            f"q/pool mismatch on axis 'heads*head_dim': q has {heads} x "
            f"{head_dim} = {heads * head_dim}, k_pages/v_pages have "
            f"{k_pages.shape[2]} (q {q.shape}, pools {k_pages.shape})"
        )
    if block_table.ndim != 2 or block_table.shape[0] != q.shape[0]:
        raise ValueError(
            f"block_table must be [batch, pages_per_seq]: got shape "
            f"{block_table.shape} (rank {block_table.ndim}, want 2; axis "
            f"'batch' got {block_table.shape[0] if block_table.ndim else '-'}"
            f", want {q.shape[0]} from q)"
        )
    if lengths.shape != (q.shape[0],):
        raise ValueError(
            f"lengths must be [batch]: got shape {lengths.shape}, want "
            f"({q.shape[0]},) (axis 'batch' from q)"
        )
    if k_pages.dtype != v_pages.dtype:
        raise ValueError(
            f"k_pages/v_pages dtypes differ: {k_pages.dtype} vs "
            f"{v_pages.dtype} (pools quantize together or not at all)"
        )
    _check_scale_pool("k_pages", k_pages, "k_scales", k_scales, heads)
    _check_scale_pool("v_pages", v_pages, "v_scales", v_scales, heads)
    operands = (
        q, k_pages, v_pages, block_table, lengths, scale, k_scales, v_scales
    )
    if impl not in ("auto", "reference", "pallas"):
        raise ValueError(f"unknown paged attention impl {impl!r}")
    page_walk = q.ndim == 3 and k_scales is None
    if impl == "auto":
        # the choice, from what the trace can see: the page-walk kernel for
        # the single-token read of float pools (in the query's dtype, as
        # the models hold them) where the gate says one chip (or the
        # interpreter) runs the kernels and the page is whole tiles
        kernel = (
            page_walk
            and q.dtype == k_pages.dtype
            and dispatch.mode() == "direct"
            and _page_tiles(k_pages)
        )
        impl = "pallas" if kernel else "reference"
    dispatch.note_path("paged_attn", "direct" if impl == "pallas" else "xla")
    if impl == "reference":
        reference = _paged_reference_mq if q.ndim == 4 else _paged_reference
        return reference(*operands)
    if page_walk:
        return _paged_page_walk(
            q, k_pages, v_pages, block_table, lengths,
            scale=scale, interpret=_interpreting(),
        )
    return (_paged_pallas_mq if q.ndim == 4 else _paged_pallas)(*operands)


# ---------------------------------------------------------------- reference


def _gather_dequant(pages, scales, block_table, batch, tokens, heads,
                    head_dim):
    """Gather pages through the block table ([B, W, P, H*D]) and only then
    unfold the gathered WINDOW to [B, T, H, D] — the resident pool itself
    is never reshaped — and, for int8 pools, dequantize against the
    identically-gathered scale pool (one fp32 scale per token per head)."""
    x = pages[block_table].reshape(batch, tokens, heads, head_dim)
    if scales is None:
        return x
    s = scales[block_table].reshape(batch, tokens, heads)
    return x.astype(jnp.float32) * s[..., None]


def _paged_reference(q, k_pages, v_pages, block_table, lengths, scale,
                     k_scales=None, v_scales=None):
    batch, heads, head_dim = q.shape
    page_size = k_pages.shape[1]
    windows = block_table.shape[1]

    # Gather the full (padded) context per sequence: [B, W, P, H*D] →
    # [B, W*P, H, D]. Token order is page order × in-page offset, which is
    # exactly how serve/paged_cache.py lays tokens out.
    tokens = windows * page_size
    k = _gather_dequant(
        k_pages, k_scales, block_table, batch, tokens, heads, head_dim
    )
    v = _gather_dequant(
        v_pages, v_scales, block_table, batch, tokens, heads, head_dim
    )

    # Same contraction/softmax formula as the dense cache attend (fp32
    # scores, finfo.min mask, fp32 softmax, probs cast to V dtype) so the
    # two layouts stay bitwise-comparable on the valid lanes.
    scores = (
        jnp.einsum("bnd,btnd->bnt", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    pos = jax.lax.broadcasted_iota(jnp.int32, (batch, windows * page_size), 1)
    valid = pos < lengths[:, None]
    scores = jnp.where(valid[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bnt,btnd->bnd", probs, v)


# ------------------------------------------- pallas: the single-token read
#
# The decode step's read of float pools, as the pools lie in HBM:
# ``[num_pages, page_size, heads * head_dim]``, a page one contiguous
# ``[page_size, lanes]`` slab of whole tiles. The kernel never sees a pool
# reshaped: both stay in HBM (``pl.ANY``) and the kernel copies the LIVE
# pages of a slot, ``ceil(length / page_size)`` of them and no more, into a
# double buffer of ``_BLOCK_TOKENS``-token blocks, the next block's copies
# in flight while this one is computed — across slots too: the grid is
# ``(slots,)``, and a slot's last block starts the next slot's first. An idle
# slot (parked on page 0, length 1) costs one page.
#
# A head's lanes are never unfolded onto sublanes. With ``Qbd [heads, lanes]``
# holding head ``n``'s query in row ``n``, lanes ``n*head_dim ..`` and zeros
# elsewhere, the scores of a block are ``Qbd @ K^T`` = ``[heads, tokens]`` on
# the MXU (products of the pool's dtype accumulated in float32: the zeros add
# exact zeros, so this is the reference's contraction), the online softmax
# runs in float32 over two vregs, and ``P [heads, tokens] @ V [tokens,
# lanes]`` accumulates ``[heads, lanes]`` in float32, of which head ``n``
# keeps its own lanes at the end. Rows of a buffer that no copy filled hold
# whatever VMEM held: their scores are masked and their V rows zeroed before
# the product (zero times NaN is NaN).

_BLOCK_TOKENS = 128


def _page_tiles(pages) -> bool:
    """A page is whole (sublanes, 128) tiles, 8 rows of float32 or 16 of
    bfloat16, so each page's copy lands on tile borders of the block
    buffer (the chip's compiler refuses the others)."""
    sublanes = 32 // pages.dtype.itemsize
    return pages.shape[2] % _LANES == 0 and pages.shape[1] % sublanes == 0


def _page_walk_kernel(
    bt_ref,  # scalar-prefetch: [B, W] int32
    len_ref,  # scalar-prefetch: [B] int32
    q_ref,  # [1, 1, H*D]; rows mode (head_dim None): [1, H, lanes]
    k_hbm,  # [num_pages, P, H*D], in HBM
    v_hbm,
    o_ref,  # [1, 1, H*D]; rows mode: [1, H, lanes] float32
    kbuf,  # [2, C*P, H*D]: the block being computed and the one in flight
    vbuf,
    sems,  # DMA semaphores [K/V, buffer]
    first_ref,  # SMEM [1]: the buffer that holds this slot's first block
    m_ref,  # [H, 128] running max, lane-replicated
    l_ref,  # [H, 128] running denominator
    acc_ref,  # [H, H*D] float32
    *,
    scale: float,
    page_size: int,
    windows: int,
    block_pages: int,
    heads: int,
    head_dim: int,
):
    b = pl.program_id(0)
    block_tokens = block_pages * page_size
    lanes = kbuf.shape[2]
    # float32 pools: every pass of the MXU, as the reference's products are
    # exact float32; bf16 operands are exact in one
    precision = (
        jax.lax.Precision.HIGHEST if k_hbm.dtype == jnp.float32 else None
    )

    def live_pages(slot):
        # at least one (the engine's lengths are >= 1; a slot always has a
        # block in flight), at most the table's width
        return jnp.clip(
            jax.lax.div(len_ref[slot] + (page_size - 1), page_size),
            1, windows,
        )

    def for_live_pages(slot, block, buf, fn):
        """``fn`` on the copy of each LIVE page of a slot's block: a page
        past the length costs neither a copy nor a wait."""
        pages = live_pages(slot)
        for i in range(block_pages):
            w = block * block_pages + i

            @pl.when(w < pages)
            def _():
                page = bt_ref[slot, w]
                rows = pl.ds(i * page_size, page_size)
                for pool, buffer, sem in (
                    (k_hbm, kbuf, sems.at[0, buf]),
                    (v_hbm, vbuf, sems.at[1, buf]),
                ):
                    fn(pltpu.make_async_copy(
                        pool.at[page], buffer.at[buf, rows], sem
                    ))

    def start(slot, block, buf):
        for_live_pages(slot, block, buf, lambda copy: copy.start())

    def wait(slot, block, buf):
        for_live_pages(slot, block, buf, lambda copy: copy.wait())

    def blank_dead_rows(block, buf):
        """V's rows of the block's dead pages, which no copy filled, hold
        whatever VMEM held: zero them, since their zero probabilities do
        not keep a NaN out of the product. (K's only reach their own
        scores, which the mask replaces.)"""
        for i in range(block_pages):

            @pl.when(block * block_pages + i >= my_pages)
            def _():
                vbuf[buf, pl.ds(i * page_size, page_size)] = jnp.zeros(
                    (page_size, lanes), vbuf.dtype
                )

    @pl.when(b == 0)
    def _prime():
        first_ref[0] = 0
        start(0, 0, 0)

    length = len_ref[b]
    my_pages = live_pages(b)
    num_blocks = jax.lax.div(my_pages + (block_pages - 1), block_pages)
    first = first_ref[0]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    if head_dim is None:
        # rows mode: the caller laid the queries out, one a row, each in
        # the lanes of the key head it scores against (fewer K/V heads
        # than query heads, two softmaxes over one value: the caller's to
        # say), and takes every row's weighted values over ALL lanes
        qbd = q_ref[0]
    else:
        # Qbd: the slot's query, one head a row, each in its own lanes
        row = jax.lax.broadcasted_iota(jnp.int32, (heads, lanes), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (heads, lanes), 1)
        own = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)
        q = jnp.broadcast_to(q_ref[0].astype(jnp.float32), (heads, lanes))
        qbd = jnp.where(own, q, 0.0).astype(k_hbm.dtype)

    def block_step(j, carry):
        buf = (first + j) % 2
        nxt = 1 - buf

        @pl.when(j + 1 < num_blocks)
        def _():
            start(b, j + 1, nxt)

        @pl.when((j + 1 == num_blocks) & (b + 1 < pl.num_programs(0)))
        def _():
            start(b + 1, 0, nxt)

        wait(b, j, buf)
        blank_dead_rows(j, buf)
        k = kbuf[buf]  # [T, lanes]
        v = vbuf[buf]
        s = jax.lax.dot_general(
            qbd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale  # [H, T]
        pos = j * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        valid = pos < length
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]  # [H, 1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [H, T]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # probabilities meet V in the pool's dtype, as the reference's do
        pv = jnp.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32, precision=precision,
        )  # [H, lanes]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, num_blocks, block_step, 0)
    first_ref[0] = (first + num_blocks) % 2

    # length >= 1 by engine contract, so l > 0; the where only shields the
    # all-masked degenerate case from producing NaN
    l = l_ref[...][:, :1]
    out = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
    if head_dim is None:
        o_ref[0] = out.astype(o_ref.dtype)
    else:
        # head n keeps lanes n*head_dim ..: one row of lanes again
        o_ref[0] = jnp.sum(
            jnp.where(own, out, 0.0), axis=0, keepdims=True
        ).astype(o_ref.dtype)


def _page_walk_call(q_rows, k_pages, v_pages, block_table, lengths, *,
                    scale, interpret, heads, head_dim, out_dtype, name):
    """The page-walk kernel over ``q_rows`` [batch, rows a slot, lanes]:
    one row holding every head's query (``head_dim`` given: the kernel
    unfolds it, one head a row, and folds the output back), or ``heads``
    rows the caller laid out (``head_dim`` None: rows mode)."""
    batch, rows, lanes = q_rows.shape
    page_size = k_pages.shape[1]
    windows = block_table.shape[1]
    block_pages = max(1, _BLOCK_TOKENS // page_size)
    slot_row = pl.BlockSpec((1, rows, lanes), lambda b, bt, ln: (b, 0, 0))
    block = (2, block_pages * page_size, lanes)
    return pl.pallas_call(
        functools.partial(
            _page_walk_kernel,
            scale=scale,
            page_size=page_size,
            windows=windows,
            block_pages=block_pages,
            heads=heads,
            head_dim=head_dim,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch,),
            in_specs=[
                slot_row,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=slot_row,
            scratch_shapes=[
                pltpu.VMEM(block, k_pages.dtype),
                pltpu.VMEM(block, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, lanes), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, rows, lanes), out_dtype),
        # the slots run in order: each starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name=name,
        interpret=interpret,
    )(block_table, lengths, q_rows, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_page_walk(q, k_pages, v_pages, block_table, lengths, *, scale,
                     interpret):
    """Jitted, so that a model's layers, which call it with the same
    shapes, share ONE trace and one lowering of the kernel (traced 24
    times, gpt2-medium's decode program took 34 s longer to build)."""
    batch, heads, head_dim = q.shape
    lanes = k_pages.shape[2]
    out = _page_walk_call(
        q.reshape(batch, 1, lanes), k_pages, v_pages, block_table, lengths,
        scale=scale, interpret=interpret, heads=heads, head_dim=head_dim,
        out_dtype=v_pages.dtype, name="paged_attn")
    return out.reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _rows_page_walk(q_rows, k_pages, v_pages, block_table, lengths, *, scale,
                    interpret):
    """``paged_attn_rows``: the same walk in rows mode. ``q_rows`` [batch,
    rows, lanes] in the pools' dtype; returns, float32, each row's softmax
    over the slot's live tokens times V over all lanes. One trace for all
    the layers that read one pool."""
    return _page_walk_call(
        q_rows, k_pages, v_pages, block_table, lengths, scale=scale,
        interpret=interpret, heads=q_rows.shape[1], head_dim=None,
        out_dtype=jnp.float32, name="paged_attn_rows")


# ------------------------------------------- pallas: one page a grid step


def _paged_kernel(
    bt_ref,  # scalar-prefetch: [B, W] int32
    len_ref,  # scalar-prefetch: [B] int32
    q_ref,  # [1, H, D]
    k_ref,  # [1, P, H, D] — the page selected by index_map for this step
    v_ref,  # [1, P, H, D]
    *refs,  # [ks_ref, vs_ref (int8 pools only)], o_ref, m/l/acc scratch
    scale: float,
    page_size: int,
    windows: int,
    quantized: bool,
):
    if quantized:
        # ks/vs: [1, P, H] fp32 — the scale page walked in lockstep with
        # its K/V page through the same block-table index_map
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    w = pl.program_id(1)
    length = len_ref[b]

    @pl.when(w == 0)
    def _init():
        # finfo.min, not -inf: exp(m_prev - m_new) stays NaN-free whatever
        # the first computed page masks (same floor as the mq kernel)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Pages wholly past the live length carry no valid tokens (their block
    # table entries are the null page): skip the whole online-softmax step.
    @pl.when(w * page_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [H, D]
        k = k_ref[0].astype(jnp.float32)  # [P, H, D]
        v = v_ref[0].astype(jnp.float32)  # [P, H, D]
        if quantized:
            # in-kernel dequant: one fp32 scale per (token, head) lane
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]

        # One query row per head has no matrix to multiply: a dot batched
        # over heads would have an lhs [H, D] with no free dimension, which
        # Mosaic refuses. The scores are a lane reduction instead, kept
        # [P, H, 1] so heads stay on sublanes from here to the [H, D]
        # accumulator — no transpose, no relayout, exact fp32 (the MXU
        # would round fp32 operands to bf16). A decode step is bound by
        # the page bytes it streams, not by these P*H*D multiply-adds.
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale  # [P, H, 1]
        pos = w * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        valid = pos < length
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]  # [H, 1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)  # [P, H, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=0)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=0)  # [H, D]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(w == windows - 1)
    def _write():
        # length >= 1 by engine contract, so l > 0; the where only shields
        # the all-masked degenerate case from producing NaN.
        l = l_ref[...][:, :1]
        l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _page_walk_specs(page_size, heads, head_dim, quantized):
    """K/V (and, for int8 pools, scale-pool) BlockSpecs: one page per grid
    step, chosen through the prefetched block table — this is the whole
    point of the layout: the gather happens in the index_map, not in
    HBM-wasting XLA. Scale pages walk through the SAME index_map so a
    token's values and its scales always arrive together."""
    page = pl.BlockSpec(
        (1, page_size, heads, head_dim),
        lambda b, w, bt, ln: (bt[b, w], 0, 0, 0),
    )
    specs = [page, page]
    if quantized:
        scale_page = pl.BlockSpec(
            (1, page_size, heads),
            lambda b, w, bt, ln: (bt[b, w], 0, 0),
        )
        specs += [scale_page, scale_page]
    return specs


def _kernel_pools(q, k_pages, v_pages):
    """The lane-dense pools unfolded to the kernels' ``[num_pages,
    page_size, heads, head_dim]`` block shape. On the chip this reshape
    relays the WHOLE pool out, every call (the 4-D default layout puts the
    page axis on the lanes, the 3-D one does not): the price of keeping
    these two kernels' ``(1, P, H, D)`` page. The decode step's read no
    longer pays it (``_paged_page_walk``); nothing chooses these two
    unless a test pins ``impl="pallas"``."""
    shape = (*k_pages.shape[:2], *q.shape[-2:])
    return k_pages.reshape(shape), v_pages.reshape(shape)


def _paged_pallas(q, k_pages, v_pages, block_table, lengths, scale,
                  k_scales=None, v_scales=None):
    batch, heads, head_dim = q.shape
    page_size = k_pages.shape[1]
    windows = block_table.shape[1]
    quantized = k_scales is not None

    operands = [block_table, lengths, q, *_kernel_pools(q, k_pages, v_pages)]
    if quantized:
        operands += [k_scales, v_scales]
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel,
            scale=scale,
            page_size=page_size,
            windows=windows,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, windows),
            in_specs=[
                pl.BlockSpec((1, heads, head_dim), lambda b, w, bt, ln: (b, 0, 0)),
                *_page_walk_specs(page_size, heads, head_dim, quantized),
            ],
            out_specs=pl.BlockSpec(
                (1, heads, head_dim), lambda b, w, bt, ln: (b, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, _LANES), jnp.float32),
                pltpu.VMEM((heads, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            q.shape, jnp.float32 if quantized else v_pages.dtype
        ),
        interpret=_interpreting(),
    )(*operands)
    return out


# ------------------------------------------------- multi-token query block
#
# Shared by speculative verify (q_len = k+1 candidate tokens) and chunked
# prefill (q_len = chunk tokens appended to an existing context). The query
# block occupies the LAST q_len positions of the sequence, so row j's causal
# horizon is ``pos < lengths - q_len + 1 + j``. With q_len == 1 this reduces
# to the single-query mask above; the 3-D paths are kept verbatim so the
# decode-step numerics (and their token-identity pins) cannot move.


def _paged_reference_mq(q, k_pages, v_pages, block_table, lengths, scale,
                        k_scales=None, v_scales=None):
    batch, q_len, heads, head_dim = q.shape
    page_size = k_pages.shape[1]
    windows = block_table.shape[1]

    tokens = windows * page_size
    k = _gather_dequant(
        k_pages, k_scales, block_table, batch, tokens, heads, head_dim
    )
    v = _gather_dequant(
        v_pages, v_scales, block_table, batch, tokens, heads, head_dim
    )

    scores = (
        jnp.einsum("bqnd,btnd->bnqt", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    pos = jax.lax.broadcasted_iota(jnp.int32, (batch, q_len, windows * page_size), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (batch, q_len, windows * page_size), 1)
    limit = lengths[:, None, None] - (q_len - 1) + row
    valid = pos < limit
    scores = jnp.where(valid[:, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bnqt,btnd->bqnd", probs, v)


def _paged_kernel_mq(
    bt_ref,  # scalar-prefetch: [B, W] int32
    len_ref,  # scalar-prefetch: [B] int32
    q_ref,  # [1, Q, H, D]
    k_ref,  # [1, P, H, D]
    v_ref,  # [1, P, H, D]
    *refs,  # [ks_ref, vs_ref (int8 pools only)], o_ref, m/l/acc scratch
    scale: float,
    page_size: int,
    windows: int,
    q_len: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    w = pl.program_id(1)
    length = len_ref[b]

    @pl.when(w == 0)
    def _init():
        # finfo.min, NOT -inf: a computed page can be fully masked for the
        # earliest query rows (their causal horizon ends before the page),
        # and exp(-inf - -inf) would NaN-poison the rescale. With a finite
        # floor the masked-row algebra stays exact: p is where()-zeroed, so
        # l stays 0 until the first visible token.
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The last valid token overall sits at length-1 (row q_len-1's horizon),
    # so pages at or past `length` carry nothing for any row.
    @pl.when(w * page_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [Q, H, D]
        k = k_ref[0].astype(jnp.float32)  # [P, H, D]
        v = v_ref[0].astype(jnp.float32)  # [P, H, D]
        if quantized:
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]

        # [H, Q, P]: batch over heads (q dim 1 / k dim 1), contract head_dim.
        s = (
            jax.lax.dot_general(
                q, k, (((2,), (2,)), ((1,), (1,))),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        pos = w * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length - (q_len - 1) + row
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...][:, :, :1]  # [H, Q, 1]
        l_prev = l_ref[...][:, :, :1]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # where(), not bare exp: on an all-masked row m_new == _NEG_INF and
        # exp(s - m_new) would be exp(0) == 1 per lane.
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [H, Q, P]
        l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        # [H, Q, D]: batch over heads (p dim 0 / v dim 1), contract lanes.
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(w == windows - 1)
    def _write():
        l = l_ref[...][:, :, :1]
        l = jnp.where(l > 0.0, l, 1.0)
        out = acc_ref[...] / l  # [H, Q, D]
        o_ref[0] = jnp.transpose(out, (1, 0, 2)).astype(o_ref.dtype)


def _paged_pallas_mq(q, k_pages, v_pages, block_table, lengths, scale,
                     k_scales=None, v_scales=None):
    batch, q_len, heads, head_dim = q.shape
    page_size = k_pages.shape[1]
    windows = block_table.shape[1]
    quantized = k_scales is not None

    operands = [block_table, lengths, q, *_kernel_pools(q, k_pages, v_pages)]
    if quantized:
        operands += [k_scales, v_scales]
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel_mq,
            scale=scale,
            page_size=page_size,
            windows=windows,
            q_len=q_len,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, windows),
            in_specs=[
                pl.BlockSpec(
                    (1, q_len, heads, head_dim),
                    lambda b, w, bt, ln: (b, 0, 0, 0),
                ),
                *_page_walk_specs(page_size, heads, head_dim, quantized),
            ],
            out_specs=pl.BlockSpec(
                (1, q_len, heads, head_dim), lambda b, w, bt, ln: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((heads, q_len, _LANES), jnp.float32),
                pltpu.VMEM((heads, q_len, _LANES), jnp.float32),
                pltpu.VMEM((heads, q_len, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            q.shape, jnp.float32 if quantized else v_pages.dtype
        ),
        interpret=_interpreting(),
    )(*operands)
    return out


# ------------------------------------- differential attention, grouped heads
#
# Query heads pair up (``2p``, ``2p + 1``); two pairs share a K/V group
# ``g = p // 2`` of two heads. Query head ``n = 4g + 2j + i`` (pair ``j`` of
# its group, softmax ``i`` of its pair) scores against key head ``2g + i``
# and weighs the group's 2 x head_dim values ``[v_2g; v_2g+1]``; a pair's
# output is its first softmax's less ``lam`` times its second's. Keys carry
# no positions in the family that uses this (models/sambay.py), so a mask is
# all a key's position is for.
#
# ``both``: the two softmaxes' weighted values, float32 ``[..., groups, 2
# (pair), 2 (softmax), 2 * head_dim]``, which ``differential_combine``
# turns into the pairs' outputs. Two ways to it: the XLA formula over
# gathered or dense keys (``differential_scores_attention``: every
# multi-token step, and the decode step off the chip), and the page-walk
# kernel ``paged_attn_rows`` (the decode step's read of a page pool, or of
# a ring seen as a slot's fixed run of pages, where ``dispatch.mode()`` is
# ``"direct"`` and a page is whole tiles), which costs what is live.


def differential_scores_attention(q, k, v, q_pos, k_pos, scale, *,
                                  window=None, block: int = 128):
    """``q`` [b, t, heads, d]; ``k``, ``v`` [b, s, kv_heads, d], heads = 2
    kv_heads; ``q_pos`` [b, t], ``k_pos`` [b, s]: a key is seen where ``0
    <= k_pos <= q_pos`` (and ``k_pos > q_pos - window``). In blocks of
    ``block`` queries. Returns ``both`` [b, t, groups, 2, 2, 2d]."""
    b, t, heads, d = q.shape
    s, groups = k.shape[1], k.shape[2] // 2
    kg = k.reshape(b, s, groups, 2, d)
    vg = v.reshape(b, s, groups, 2 * d)

    def rows(qb, qp):
        qg = qb.reshape(b, qb.shape[1], groups, 2, 2, d)
        scores = jnp.einsum(
            "brgjid,bsgid->bgjirs", qg, kg,
            preferred_element_type=jnp.float32) * scale
        seen = (k_pos[:, None, :] <= qp[:, :, None]) & (k_pos[:, None, :] >= 0)
        if window is not None:
            seen &= k_pos[:, None, :] > qp[:, :, None] - window
        scores = jnp.where(seen[:, None, None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bgjirs,bsge->brgjie", probs, vg,
                          preferred_element_type=jnp.float32)

    if t <= block:
        return rows(q, q_pos)
    n = -(-t // block)

    def split(x):
        x = jnp.pad(x, [(0, 0), (0, n * block - t)] + [(0, 0)] * (x.ndim - 2),
                    mode="edge")
        return jnp.moveaxis(x.reshape(b, n, block, *x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: rows(*a), (split(q), split(q_pos)))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, *out.shape[3:])[:, :t]


def differential_combine(both, lam, gain, eps: float, post: float):
    """``RMSNorm(first - lam * second; gain, eps) * post`` of each pair:
    ``both`` [..., groups, 2, 2, 2d] -> [..., 2 * groups, 2d] float32."""
    o = both[..., 0, :] - lam * both[..., 1, :]
    o = o.reshape(*o.shape[:-3], o.shape[-3] * 2, o.shape[-1])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * gain * post


def _differential_kernel_fits(q, pages) -> bool:
    return (
        q.dtype == pages.dtype
        and dispatch.mode() == "direct"
        and _page_tiles(pages)
    )


def _differential_rows(q, kv_heads: int):
    """The decode step's queries laid out for ``paged_attn_rows``: ``q``
    [b, heads, d] -> [b, rows, kv_heads * d], head ``n = 4g + 2j + i`` in
    row ``n``, lanes of key head ``2g + i``, zeros elsewhere; rows padded
    to whole sublane tiles."""
    b, heads, d = q.shape
    n = jnp.arange(heads)
    key_head = 2 * (n // 4) + n % 2
    own = (key_head[:, None] == jnp.arange(kv_heads)[None, :])   # [heads, K]
    rows = jnp.where(own[None, :, :, None], q[:, :, None, :], 0)
    rows = rows.reshape(b, heads, kv_heads * d)
    return jnp.pad(rows, ((0, 0), (0, -heads % 16), (0, 0)))


def _differential_both(out, heads: int, d: int):
    """``paged_attn_rows``'s output [b, rows, lanes] float32 -> ``both`` [b,
    groups, 2, 2, 2d]: head ``n`` keeps the lanes of its group's values."""
    b, _, lanes = out.shape
    groups = lanes // (2 * d)
    per_group = out[:, :heads].reshape(b, heads, groups, 2 * d)
    group = (jnp.arange(heads) // 4)[None, :, None, None]
    mine = jnp.take_along_axis(per_group, group, axis=2)[:, :, 0]
    return mine.reshape(b, groups, 2, 2, 2 * d)


def differential_paged_chunk(q, k_pages, v_pages, block_table, context,
                             scale, *, block: int = 128):
    """A block of query tokens over a page pool, the XLA formula: ``q`` [b,
    t, heads, d] at positions ``context .. context + t - 1`` of sequences
    whose rows up to there are written; gathers each sequence's pages and
    attends causally. Returns ``both`` [b, t, groups, 2, 2, 2d]."""
    b, t, _, d = q.shape
    kv_heads = k_pages.shape[2] // d
    tokens = block_table.shape[1] * k_pages.shape[1]
    keys = _gather_dequant(k_pages, None, block_table, b, tokens, kv_heads, d)
    values = _gather_dequant(v_pages, None, block_table, b, tokens, kv_heads, d)
    q_pos = context[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    k_pos = jnp.broadcast_to(jnp.arange(tokens, dtype=jnp.int32), (b, tokens))
    return differential_scores_attention(
        q, keys, values, q_pos, k_pos, scale, block=block)


def differential_paged_decode(q, k_pages, v_pages, block_table, lengths,
                              scale):
    """One query token a sequence over a page pool: ``q`` [b, heads, d],
    pools [pages, page_size, kv_heads * d], ``lengths`` [b] inclusive of
    the token. Returns ``both`` [b, groups, 2, 2, 2d]."""
    b, heads, d = q.shape
    kv_heads = k_pages.shape[2] // d
    kernel = _differential_kernel_fits(q, k_pages)
    dispatch.note_path("paged_attn_rows", "direct" if kernel else "xla")
    if kernel:
        out = _rows_page_walk(
            _differential_rows(q, kv_heads), k_pages, v_pages, block_table,
            lengths, scale=scale, interpret=_interpreting())
        return _differential_both(out, heads, d)
    return differential_paged_chunk(
        q[:, None], k_pages, v_pages, block_table, lengths - 1, scale)[:, 0]


def differential_ring_decode(q, k_ring, v_ring, slot, live, scale,
                             page_size: int):
    """One query token a sequence over its slot's ring: rings [slots, ring,
    kv_heads * d]; ``slot`` [b] (None: row ``b`` is slot ``b``); ``live``
    [b]: the ring's first ``live`` rows are the keys (keys carry no
    positions: a ring that has wrapped is all live, one that has not is
    live from row 0). Returns ``both`` [b, groups, 2, 2, 2d]."""
    b, heads, d = q.shape
    slots, ring, width = k_ring.shape
    kv_heads = width // d
    live = jnp.maximum(live, 1)
    rows = jnp.arange(b, dtype=jnp.int32) if slot is None else slot
    paged = ring % page_size == 0
    pages = k_ring.reshape(-1, page_size, width) if paged else k_ring
    kernel = paged and _differential_kernel_fits(q, pages)
    dispatch.note_path("paged_attn_rows", "direct" if kernel else "xla")
    if kernel:
        # a slot's ring is a fixed run of pages: no table is kept, this is it
        run = ring // page_size
        table = rows[:, None] * run + jnp.arange(run, dtype=jnp.int32)[None]
        out = _rows_page_walk(
            _differential_rows(q, kv_heads), pages,
            v_ring.reshape(-1, page_size, width), table, live,
            scale=scale, interpret=_interpreting())
        return _differential_both(out, heads, d)
    keys = (k_ring if slot is None else k_ring[slot]).reshape(
        b, ring, kv_heads, d)
    values = (v_ring if slot is None else v_ring[slot]).reshape(
        b, ring, kv_heads, d)
    k_pos = jnp.broadcast_to(jnp.arange(ring, dtype=jnp.int32), (b, ring))
    return differential_scores_attention(
        q[:, None], keys, values, (live - 1)[:, None], k_pos, scale)[:, 0]
