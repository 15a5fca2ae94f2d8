"""On-device batched sampling for the decode engine.

``device_sample`` is the in-jit mirror of ``host_sample`` (numpy, below:
the reference the tests compare against; the engine never calls it):
greedy argmax at temperature<=0, temperature + top-k
``jax.random.categorical`` otherwise, with the per-request stream derived
exactly the same way — ``fold_in(key(seed), step)`` where ``step`` is the
number of tokens already emitted for the request. Folding sampling into
the decode program shrinks the per-tick D2H from ``[slots, vocab]`` fp32
logits to ``[slots]`` int32 token ids, which is the whole point: token
selection must not cost a host round-trip per token on a real accelerator.

Exactness contract (pinned by tests/test_paged.py): for any
(seed, step, temperature, top_k) the returned token equals the host
sampler's bit-for-bit — greedy because both argmax over bitwise-identical
fp32 logits take the first maximum, sampled because key derivation,
temperature scaling, the k-th-value tie-keeping top-k mask, and
``categorical`` are the same operations on the same values.

Everything is traced: temperature/top_k/seed/step arrive as per-slot
arrays and greedy-vs-sampled is a ``jnp.where`` select, never a Python
branch (the analysis/ traced-branch rule).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def device_sample(logits, seeds, steps, temps, top_ks):
    """Sample next tokens for a batch of slots, in-trace.

    Args:
        logits: [slots, vocab] fp32 — the tick's last-position logits.
        seeds: [slots] int32 per-request PRNG seed (``GenRequest.seed``;
            seeds beyond int32 range wrap — the host path's full-width ints
            and this operand agree on every value int32 can carry).
        steps: [slots] int32 — tokens already emitted for the request
            (``len(req.tokens)`` at host sample time: 0 at prefill,
            ``steps_done + 1`` at decode).
        temps: [slots] fp32 temperature; <= 0 selects greedy.
        top_ks: [slots] int32; 0 (or >= vocab) means no truncation.

    Returns:
        [slots] int32 token ids.
    """
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_lane():
        # Temperature scaling; greedy rows divide by a dummy 1.0 (their
        # sampled lane is discarded by the final select, but it must not
        # produce inf/nan that could poison the compiled program's value
        # checks).
        temps_safe = jnp.where(temps > 0.0, temps, 1.0).astype(logits.dtype)
        scaled = logits / temps_safe[:, None]

        # top-k mask, host-identical: keep everything >= the k-th largest
        # value (ties INCLUDED — the host uses np.sort(scaled)[-k] the same
        # way); k clamped to vocab so an oversized client value means "no
        # truncation".
        k = jnp.clip(top_ks, 0, vocab)
        kth_index = jnp.clip(vocab - k, 0, vocab - 1)
        sorted_scaled = jnp.sort(scaled, axis=-1)
        kth = jnp.take_along_axis(sorted_scaled, kth_index[:, None], axis=-1)
        truncate = (k > 0)[:, None] & (scaled < kth)
        masked = jnp.where(truncate, jnp.finfo(jnp.float32).min, scaled)

        def draw(seed, step, row):
            key = jax.random.fold_in(jax.random.key(seed), step)
            return jax.random.categorical(key, row)

        return jax.vmap(draw)(seeds, steps, masked).astype(jnp.int32)

    # The whole sort + per-row RNG lane runs only when SOME row samples —
    # a lax.cond on a batch-reduced scalar (a traced branch, not a Python
    # one; the per-row greedy/sampled mix below stays a where-select).
    # An all-greedy batch pays argmax only, which is what makes the
    # (slots x q_len)-row speculative verify dispatch cheap for greedy
    # traffic; any batch that does sample computes the lane EXACTLY as
    # written, so the host-exactness pin is untouched.
    sampled = jax.lax.cond(
        jnp.any(temps > 0.0), sampled_lane,
        lambda: jnp.zeros_like(greedy),
    )
    return jnp.where(temps <= 0.0, greedy, sampled)


def host_sample(logits, *, temperature, top_k, seed, step) -> int:
    """Next token from one row of fp32 ``[vocab]`` logits, on the host:
    the reference ``device_sample`` is pinned bit-identical against
    (tests/test_paged.py). Greedy mirrors generate()'s argmax; temperature>0
    draws from the request's own deterministic stream, ``seed`` folded with
    ``step`` (tokens already emitted for the request)."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    scaled = logits / temperature
    # clamp to vocab size: top_k >= vocab means "no truncation"
    k = min(top_k, scaled.shape[-1])
    if k > 0:
        kth = np.sort(scaled)[-k]
        scaled = np.where(scaled < kth, np.finfo(np.float32).min, scaled)
    key = jax.random.fold_in(jax.random.key(seed), step)
    return int(jax.random.categorical(key, jnp.asarray(scaled)))


def spec_accept(logits, draft, seeds, steps0, temps, top_ks):
    """Exact-match speculative acceptance over a verify block, in-trace.

    The verify dispatch scores q_len = k+1 positions per slot: row 0 is the
    slot's pending token (the position the non-speculative engine would
    decode this tick), rows 1..k are the k draft candidates. Each row j is
    sampled with its OWN ``fold_in(key(seed), steps0 + j)`` stream — the
    exact stream the non-speculative engine would use when it eventually
    reached that position — and a draft token is accepted iff it EQUALS the
    stream's sample. Acceptance stops at the first mismatch (the sampled
    token there replaces the draft; later rows scored a poisoned prefix and
    are discarded).

    Exact-match (rather than Leviathan's p/q residual acceptance) is what
    makes the accepted stream BIT-IDENTICAL to the non-speculative stream
    for greedy AND for fixed-seed sampling: every emitted token is literally
    the token ``device_sample`` produces for (seed, step) on that position's
    logits, whatever the draft proposed. The draft only controls how many
    positions one dispatch can commit.

    Args:
        logits: [slots, q_len, vocab] fp32 verify logits; row j conditions
            on the pending token plus drafts 0..j-1.
        draft: [slots, q_len - 1] int32 draft candidates.
        seeds: [slots] int32 (as ``device_sample``).
        steps0: [slots] int32 — the step of row 0, i.e. tokens already
            emitted for the request (``steps_done + 1`` at decode time).
        temps: [slots] fp32; top_ks: [slots] int32 (as ``device_sample``).

    Returns:
        (target [slots, q_len] int32, accept [slots] int32): per-position
        stream samples and the leading-match count. The engine emits
        ``target[s, :accept[s] + 1]`` — the accepted drafts plus the one
        token that is correct-by-construction at the first divergence.
    """
    slots, q_len, vocab = logits.shape
    rows = jnp.arange(q_len, dtype=jnp.int32)
    target = device_sample(
        logits.reshape(slots * q_len, vocab),
        jnp.repeat(seeds, q_len),
        (steps0[:, None] + rows[None, :]).reshape(-1),
        jnp.repeat(temps, q_len),
        jnp.repeat(top_ks, q_len),
    ).reshape(slots, q_len)
    matches = (target[:, : q_len - 1] == draft).astype(jnp.int32)
    accept = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
    return target, accept.astype(jnp.int32)
