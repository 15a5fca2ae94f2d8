"""A routed expert layer for a chip that holds SOME of a layer's experts.

The router keeps the layer's full width: every token is scored against all
``n_routed`` experts and picks its ``top_k``, whichever chip holds them.
This chip then computes the part of the result that its own experts give
(``first .. first + held``); what the absent experts would add belongs to
other chips and is left out, here and in whatever consumes the result. No
token is dropped, no capacity limit exists, nothing stands in for the
absent chips.

Routing (``route``): sigmoid scores, the ``top_k`` largest of ``score +
bias`` (the correction bias steers the CHOICE only), weights ``scale *
score / sum of the chosen scores``.

Two products over the held experts, the same function:

- ``dense_experts``: every held expert multiplies every token and the
  routing weight (zero where the token did not choose it) scales the
  result. The work is the same every step whatever the routing: the right
  path for a decode step, where each expert's weights are read anyway for
  a token or two and a step whose time followed the routing would make
  token gaps unsteady.
- ``grouped_experts``: (token, chosen held expert) pairs sorted by expert,
  each expert's group padded to whole row blocks, one gated-MLP product a
  block with that expert's weights: the work follows the pairs this chip
  really holds (a prefill chunk of 512 tokens brings 16 of 256 experts
  about 256 pairs, not 16 x 512).

Held experts are stacked ``block`` to a parameter leaf (``[block, hidden,
width]``): ``experts`` is the list of those leaves' (gate, up, down).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route(x, router_w, bias, top_k: int, scale: float):
    """``x`` [tokens, hidden] -> (chosen experts [tokens, top_k] int32,
    their weights [tokens, top_k] float32). Float32 throughout: a score
    rounded to bfloat16 would reorder near-equal experts."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), weights


def held_weights(chosen, weights, first: int, held: int):
    """[tokens, held] routing weight of each held expert for each token
    (zero where the token did not choose it)."""
    local = chosen - first
    hit = local[:, :, None] == jnp.arange(held, dtype=jnp.int32)
    return jnp.einsum("tk,tke->te", weights, hit.astype(weights.dtype))


def routing_counts(chosen, first: int, held: int, token_mask=None):
    """(tokens routed to each held expert [held], token-expert pairs routed
    to absent experts) over the tokens of ``token_mask`` (all where None)."""
    local = chosen - first
    live = jnp.ones(chosen.shape[:1], bool) if token_mask is None else token_mask
    hit = (local[:, :, None] == jnp.arange(held, dtype=jnp.int32)) & live[:, None, None]
    per_expert = hit.sum((0, 1)).astype(jnp.int32)
    absent = (live.sum() * chosen.shape[1]).astype(jnp.int32) - per_expert.sum()
    return per_expert, absent


def routing_totals(routing) -> tuple:
    """(tokens routed to each held expert, pairs routed to absent experts)
    summed over the expert layers of a step: ``routing`` is the flax
    collection the layers sow ``routing_counts`` into, as ``held_tokens``
    and ``absent_pairs`` under each layer's scope."""
    held = absent = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(routing):
        name = getattr(path[-1], "key", None)
        if name == "held_tokens":
            held = held + leaf
        elif name == "absent_pairs":
            absent = absent + leaf
    return held, absent


def _gated(rows, gate, up, down):
    h = jax.nn.silu(jnp.dot(rows, gate, preferred_element_type=jnp.float32))
    h = (h * jnp.dot(rows, up, preferred_element_type=jnp.float32)).astype(rows.dtype)
    return jnp.dot(h, down, preferred_element_type=jnp.float32)


def dense_experts(x, chosen, weights, first: int, experts):
    """Every held expert over every token: ``x`` [tokens, hidden]; returns
    [tokens, hidden] float32."""
    block = experts[0][0].shape[0]
    held = block * len(experts)
    w = held_weights(chosen, weights, first, held)
    out = jnp.zeros(x.shape, jnp.float32)
    with jax.named_scope("moe.experts"):
        for j, (gate, up, down) in enumerate(experts):
            h = jax.nn.silu(jnp.einsum(
                "th,ehf->etf", x, gate, preferred_element_type=jnp.float32))
            h = (h * jnp.einsum(
                "th,ehf->etf", x, up, preferred_element_type=jnp.float32)
            ).astype(x.dtype)
            y = jnp.einsum(
                "etf,efh->eth", h, down, preferred_element_type=jnp.float32)
            out = out + jnp.einsum(
                "te,eth->th", w[:, j * block:(j + 1) * block], y)
    return out


def grouped_experts(x, chosen, weights, first: int, experts,
                    row_block: int = 128):
    """Grouped products over the pairs this chip holds: ``x`` [tokens,
    hidden]; returns [tokens, hidden] float32.

    Pairs are sorted by held expert (absent ones last), each expert's
    group starts on a row-block boundary of a padded buffer, and one loop
    per parameter leaf walks that leaf's used row blocks, multiplying each
    by its expert's gate, up and down matrices. The loops' trip counts are
    the used blocks: an expert nobody chose costs nothing."""
    tokens, hidden = x.shape
    top_k = chosen.shape[1]
    block = experts[0][0].shape[0]
    held = block * len(experts)
    pairs = tokens * top_k
    n_blocks = -(-pairs // row_block) + held       # static upper bound
    with jax.named_scope("moe.group"):
        local = (chosen - first).reshape(pairs)
        mine = (local >= 0) & (local < held)
        expert = jnp.where(mine, local, held)      # absent pairs sort last
        order = jnp.argsort(expert, stable=True)
        sorted_e = expert[order]
        counts = jnp.zeros((held + 1,), jnp.int32).at[expert].add(1)[:held]
        blocks_of = -(-counts // row_block)
        first_block = jnp.cumsum(blocks_of) - blocks_of
        first_pair = jnp.cumsum(counts) - counts
        e_of = jnp.minimum(sorted_e, held - 1)
        rank = jnp.arange(pairs, dtype=jnp.int32) - first_pair[e_of]
        # padded row of each sorted pair; absent pairs fall off the end
        dest = jnp.where(sorted_e < held,
                         first_block[e_of] * row_block + rank,
                         n_blocks * row_block)
        token_of = order // top_k
        padded = jnp.zeros((n_blocks * row_block, hidden), x.dtype)
        padded = padded.at[dest].set(x[token_of], mode="drop")
        block_end = jnp.cumsum(blocks_of)
        expert_of_block = jnp.searchsorted(
            block_end, jnp.arange(n_blocks, dtype=jnp.int32), side="right")
    out_rows = jnp.zeros((n_blocks * row_block, hidden), x.dtype)
    with jax.named_scope("moe.experts"):
        for j, (gate, up, down) in enumerate(experts):
            lo = first_block[j * block]
            hi = block_end[(j + 1) * block - 1]

            def body(i, out_rows, gate=gate, up=up, down=down, j=j):
                e = expert_of_block[i] - j * block
                rows = jax.lax.dynamic_slice_in_dim(
                    padded, i * row_block, row_block)
                y = _gated(
                    rows,
                    jax.lax.dynamic_index_in_dim(gate, e, keepdims=False),
                    jax.lax.dynamic_index_in_dim(up, e, keepdims=False),
                    jax.lax.dynamic_index_in_dim(down, e, keepdims=False))
                return jax.lax.dynamic_update_slice_in_dim(
                    out_rows, y.astype(out_rows.dtype), i * row_block, 0)

            out_rows = jax.lax.fori_loop(lo, hi, body, out_rows)
    with jax.named_scope("moe.combine"):
        y = out_rows.at[dest].get(mode="fill", fill_value=0)   # sorted pairs
        y = y.astype(jnp.float32) * weights.reshape(pairs)[order][:, None]
        return jnp.zeros((tokens, hidden), jnp.float32).at[token_of].add(y)
