"""Dropout as a first-class framework op with a selectable mask generator.

The reference inherits torch's dropout inside HF BERT (reference
test_data_parallelism.py:112) — mask generation there is a CUDA kernel. On
TPU the mask generator is a real throughput lever: profiling bert-large
(NOTES.md) showed mask bits competing with the matmuls for VPU cycles, so
the generator is configurable per model (``ModelConfig.dropout_impl``):

- ``"exact"``  — ``jax.random.bernoulli`` (uniform-fp32 compare), bit-exact
  with flax ``nn.Dropout`` under the same key. The numerically conventional
  default for parity runs.
- ``"bits32"`` — compares raw 32-bit PRNG words against ``rate * 2^32``:
  same 1/2^32 keep-probability granularity as a fp32-uniform compare (fp32
  uniforms only carry 24 random bits), but skips the int→float conversion
  so the mask fuses into its consumer as integer VPU ops.

- ``"bits8"``  — one random *byte* per element (a uint32 word drives four
  elements): quarter the PRNG volume of the fp32-uniform path. The keep
  probability quantizes to 1/256 granularity (rate 0.1 → actual drop rate
  26/256 ≈ 0.1016); the inverted-dropout scale uses the *actual* rate so
  E[output] == input exactly. Statistically equivalent regularization,
  cheapest masks — the throughput default would be this if the quantized
  rate mattered less than bits32's exact rate.

Both draw from the key's configured generator (rbg rides the TPU hardware
PRNG; threefry2x32 gives the portable stream — ``TrainConfig.prng_impl``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

DROPOUT_IMPLS = ("exact", "bits32", "bits8", "kernel")


def mask_threshold(rate: float) -> "jnp.uint32":
    """Drop threshold for raw-PRNG-word masks: P(bits >= t) == 1 - rate.
    Single source of truth for every bits32-style generator (jax-stream
    and in-kernel alike) so the keep probability can't drift between
    implementations."""
    return jnp.uint32(min(round(rate * (1 << 32)), (1 << 32) - 1))


def derive_kernel_seed(rng):
    """One int32 scalar tying an in-kernel PRNG stream to a jax key."""
    return jax.lax.bitcast_convert_type(
        jax.random.bits(rng, (1,), jnp.uint32), jnp.int32
    )


def pow2_row_block(rows: int, block_r: int, floor: int = 16) -> int:
    """Largest power-of-2 row block <= block_r dividing rows (>= floor
    required by Mosaic's sublane tiling; returns a value < floor when no
    admissible block exists — callers fall back)."""
    br = block_r
    while br >= floor and rows % br != 0:
        br //= 2
    return br


def mask_scale_jax(rng, shape, rate: float, dtype):
    """jax-stream mask-scale tensor (0 or 1/(1-rate)) — the bits32 mask."""
    bits = jax.random.bits(rng, shape, jnp.uint32)
    scale = jnp.asarray(1.0 / (1.0 - rate), dtype)
    return jnp.where(bits >= mask_threshold(rate), scale, jnp.zeros((), dtype))


def kernel_prng_seed(*seeds) -> None:
    """``pltpu.prng_seed``, skipped in off-TPU interpret mode: the Mosaic
    PRNG primitives have no CPU lowering in this jax, and interpret-mode
    bits are all-zeros anyway (NOTES.md) — seeding a generator that will
    not be read would only crash the interpreter. Every kernel seeds
    through here so the gate can't drift per site."""
    from pytorch_distributed_training_tpu.ops.dispatch import (
        interpret_active,
    )

    if interpret_active():
        return
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(*seeds)


def kernel_keep_mask(shape, rate: float):
    """In-kernel Bernoulli(1-rate) keep mask from the ALREADY-SEEDED
    per-core TPU PRNG (call ``kernel_prng_seed`` first). Shared by every
    Pallas dropout site (flash attention, the LN tails, mask_scale) so the
    threshold semantics cannot drift. Off-TPU interpret mode emulates the
    documented all-zeros-bits contract (every position drops for rate>0)
    without touching the unlowerable Mosaic PRNG primitives."""
    from pytorch_distributed_training_tpu.ops.dispatch import (
        interpret_active,
    )

    if interpret_active():
        bits = jnp.zeros(shape, jnp.uint32)
    else:
        from jax.experimental.pallas import tpu as pltpu

        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= mask_threshold(rate)


def _mask_scale_kernel(seed_ref, o_ref, *, rate: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel_prng_seed(seed_ref[0], pl.program_id(0))
    keep = kernel_keep_mask(o_ref.shape, rate)
    # select in fp32 (same 32-bit tiling as the predicate — a bf16 select
    # here trips a Mosaic i1 relayout), convert once at the store
    scale = jnp.float32(1.0 / (1.0 - rate))
    o_ref[...] = jnp.where(keep, scale, 0.0).astype(o_ref.dtype)


def _mask_scale_from_seed(seed, shape, rate: float, dtype,
                          *, block_r: int = 512):
    """Kernel core of ``mask_scale_pallas`` from an explicit [1] int32 seed
    (shard_map bodies offset the seed per device before calling). Returns
    None when the shape doesn't tile (caller picks its fallback)."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from pytorch_distributed_training_tpu.ops.dispatch import (
        interpret_active,
    )

    n = 1
    for d in shape:
        n *= d
    lanes = 128
    rows = n // lanes
    br = pow2_row_block(rows, block_r)
    if rows * lanes != n or br < 16:
        return None
    out = pl.pallas_call(
        functools.partial(_mask_scale_kernel, rate=rate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // br,),
            in_specs=[],
            out_specs=pl.BlockSpec((br, lanes), lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), dtype),
        interpret=interpret_active(),
    )(seed)
    return out.reshape(shape)


def mask_scale_pallas(rng, shape, rate: float, dtype, *, block_r: int = 512):
    """[shape] tensor of 0 / 1/(1-rate) from the per-core TPU PRNG.

    The x-dtype mask-scale tensor is the ONLY thing that touches HBM —
    the 4-byte random words live and die in VMEM (the XLA path writes the
    u32 words, layout-copies them for the transposed consumer, then reads
    them back: ~3x the bytes on the bert-large probs dropout). The stream
    is seeded from the jax PRNG key, so it is deterministic per key (and
    per row-block) but is NOT the jax.random.bits stream; under
    ``jax.checkpoint`` the regeneration in the backward pass is
    bit-identical because the seed input is identical.
    """
    out = _mask_scale_from_seed(
        derive_kernel_seed(rng), shape, rate, dtype, block_r=block_r
    )
    if out is None:
        # ragged shape: fall back to the jax.random stream
        return mask_scale_jax(rng, shape, rate, dtype)
    return out


def _mask_scale_sharded(x, rate: float, rng):
    """shard_map-routed kernel mask-scale (ops/dispatch.py): dim 0 shards
    over the batch axes; dim 1 over the head axis for 4-D (attention
    probs [B, N, S, S] under tensor parallelism) or the seq axis for 3-D
    activations. Returns None when the registered mesh doesn't divide the
    shape (caller falls back to the jax-stream mask)."""
    from jax.sharding import PartitionSpec as P  # noqa: F401 (body spec)

    from pytorch_distributed_training_tpu.ops import dispatch

    ctx = dispatch.kernel_ctx()
    if ctx is None or x.ndim < 2:
        return None
    _, _, seq_axis, head_axis = ctx
    dim1_axis = head_axis if x.ndim == 4 else seq_axis
    plan = dispatch.plan_shards(
        x.shape, {1: dim1_axis} if x.ndim >= 3 else {}
    )
    if plan is None:
        return None
    mesh, spec, axes_used, local_shape = plan
    # decide tileability on the LOCAL shard shape, outside the body
    n = 1
    for d in local_shape:
        n *= d
    if (n // 128) * 128 != n or pow2_row_block(n // 128, 512) < 16:
        return None
    seed = derive_kernel_seed(rng)

    def body(xl, seedl):
        with dispatch.manual_region():
            seedl = seedl + dispatch.linear_device_index(axes_used, mesh)
            return xl * _mask_scale_from_seed(
                seedl, xl.shape, rate, xl.dtype
            )

    dispatch.note_path("mask_scale", "shard_map")
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, P()), out_specs=spec,
        check_vma=False,
    )(x, seed)


def raw_dropout(x, rate: float, rng, impl: str = "exact"):
    """Apply inverted dropout (train mode) to ``x``. Scale is 1/(1-rate)."""
    if rate <= 0.0:
        return x
    if rate >= 1.0:  # nn.Dropout contract: everything dropped, no inf scale
        return jnp.zeros_like(x)
    if impl == "exact":
        keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
        return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))
    if impl == "bits32":
        # multiply-by-mask-scale (not where(bits, x, 0)): the multiply's
        # backward residual is the small x-dtype mask tensor, so XLA saves
        # that instead of the 4-byte random words (measured: the u32
        # residual copies were 3.6 ms/step on bert-large). IEEE note: a
        # non-finite x stays non-finite at dropped positions (NaN*0=NaN)
        # instead of being quenched to 0 like a select would — deliberate:
        # masking a NaN in 10% of positions only hides real numeric bugs
        # (--debug-nans is the detection tool), and finite inputs are
        # bit-identical to the select form.
        return x * mask_scale_jax(rng, x.shape, rate, x.dtype)
    if impl == "kernel":
        from pytorch_distributed_training_tpu.ops import dispatch

        mode = dispatch.mode()
        if mode == "direct":  # single-device TPU or interpret ctx
            dispatch.note_path("mask_scale", "direct")
            return x * mask_scale_pallas(rng, x.shape, rate, x.dtype)
        if mode == "shard_map":
            out = _mask_scale_sharded(x, rate, rng)
            if out is not None:
                return out
        # off-TPU / non-divisible shapes: same mask-scale form, jax stream
        dispatch.note_path("mask_scale", "xla")
        return raw_dropout(x, rate, rng, "bits32")
    if impl == "bits8":
        thresh_i = min(max(round(rate * 256), 1), 255)
        actual_rate = thresh_i / 256.0  # scale by the rate actually applied
        if x.shape[-1] % 4 == 0:
            words = jax.random.bits(
                rng, (*x.shape[:-1], x.shape[-1] // 4), jnp.uint32
            )
            bits = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
                x.shape
            )
        else:
            bits = jax.random.bits(rng, x.shape, jnp.uint8)
        scale = jnp.asarray(1.0 / (1.0 - actual_rate), x.dtype)
        # same multiply form (and IEEE semantics) as bits32
        mask_scale = jnp.where(
            bits >= jnp.uint8(thresh_i), scale, jnp.zeros((), x.dtype)
        )
        return x * mask_scale
    raise ValueError(f"unknown dropout impl {impl!r}; have {DROPOUT_IMPLS}")


class Dropout(nn.Module):
    """Drop-in for ``nn.Dropout`` with the framework's mask generator.

    Same contract: rng collection ``"dropout"``, ``deterministic=True`` (or
    rate 0) is the identity.
    """

    rate: float
    impl: str = "exact"

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        if deterministic or self.rate <= 0.0:
            return x
        return raw_dropout(x, self.rate, self.make_rng("dropout"), self.impl)
