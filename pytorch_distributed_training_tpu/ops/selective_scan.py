"""The selective state-space scan (Mamba-1) and its causal depthwise
convolution, as a serving step and a trainer alike use them: over a chunk
of tokens with a carried state (a prefill chunk, a whole sequence from a
zero state) or one update (the decode step, the same code at one token).

Everything here is float32 and elementwise: the state ``[batch, d_state,
inner]`` keeps the inner width on the lane axis (16 rows of 5,120 lanes at
the published sizes, where ``[inner, d_state]`` would fill an eighth of
every tile), the decay ``exp(step * A)`` and the read-out are products and
sums on the vector unit, exact in float32 (a float32 contraction on the
matrix unit would round its operands to bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(padded, w, b, chunk: int):
    """``silu(conv + b)`` [batch, chunk, inner] float32 of a depthwise
    causal convolution: ``padded`` [batch, taps - 1 + chunk, inner] holds
    the ``taps - 1`` inputs before the chunk, then the chunk; ``w`` [taps,
    inner], tap ``taps - 1`` on the current token."""
    taps = w.shape[0]
    xf, wf = padded.astype(jnp.float32), w.astype(jnp.float32)
    out = sum(wf[k] * xf[:, k:k + chunk] for k in range(taps))
    return jax.nn.silu(out + b.astype(jnp.float32))


def conv_tail(padded, real, keep: int):
    """The ``keep`` inputs before token ``real`` of each batch row: what
    the next step's convolution needs of this one (``real`` [batch]: the
    row's real tokens; 0 hands the old tail back)."""
    if padded.shape[1] == keep + 1:
        # one token a row (the decode step): a shift or nothing, no gather
        return jnp.where(
            (real > 0)[:, None, None], padded[:, 1:], padded[:, :-1])
    return jax.vmap(
        lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, keep, axis=0)
    )(padded, real)


def selective_scan(c, step, a, b_in, c_out, state):
    """``S_t = exp(step_t (x) A) * S_{t-1} + (step_t * c_t) (x) B_t``, ``y_t
    = S_t C_t`` over the tokens of a chunk. ``c``, ``step`` [batch, chunk,
    inner] float32; ``a`` [inner, d_state] (negative); ``b_in``, ``c_out``
    [batch, chunk, d_state]; ``state`` [batch, d_state, inner]. Returns
    (``y`` [batch, chunk, inner], the state after the chunk). A token whose
    ``step`` is 0 leaves the state as it is."""
    a_t = a.T[None]                                        # [1, n, inner]

    def update(s, inputs):
        step_t, c_t, b_t, c_o = inputs
        s = (jnp.exp(step_t[:, None, :] * a_t) * s
             + b_t[:, :, None] * (step_t * c_t)[:, None, :])
        return s, jnp.sum(s * c_o[:, :, None], axis=1)

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    if c.shape[1] == 1:
        state, y = update(
            state, (step[:, 0], f32(c[:, 0]), f32(b_in[:, 0]),
                    f32(c_out[:, 0])))
        return y[:, None], state
    time_major = lambda t: jnp.moveaxis(f32(t), 1, 0)  # noqa: E731
    state, y = jax.lax.scan(
        update, state,
        (time_major(step), time_major(c), time_major(b_in),
         time_major(c_out)))
    return jnp.moveaxis(y, 0, 1), state
