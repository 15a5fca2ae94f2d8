"""Pallas (Mosaic) fused LayerNorm for TPU — fwd + custom-VJP bwd.

Why this kernel exists: on the bert-large MRPC recipe a profiler trace of
the train step shows XLA lowering every ``nn.LayerNorm`` to kLoop
reduce fusions costing ~0.2 ms per execution — ~37 ms of a ~167 ms step
across the 49 norms/microbatch (fwd ``convert_reduce_fusion`` ~19 ms + bwd
``multiply_reduce_fusion`` ~18 ms), an order of magnitude above the HBM
bandwidth bound for the tensors involved. A hand-fused row-block kernel
reads/writes each activation exactly once and keeps all statistics math in
VMEM/fp32. (The reference has no kernels of its own — it rides torch's
fused LN, reference test_data_parallelism.py:112; this is the TPU-native
equivalent of that fused native op.)

Contract (matches the ``nn.LayerNorm(dtype=fp32)`` + cast usage in
models/bert.py, models/gpt2.py):

- input x [..., H] bf16/f32; normalization over the last axis with fp32
  statistics regardless of input dtype; output = (x - mean) * rsqrt(var +
  eps) * scale + bias cast to ``out_dtype`` (the models always cast the
  fp32 LN output straight to bf16, so the kernel emits bf16 directly).
- ``var`` is the biased variance (ddof=0), eps added inside the rsqrt —
  identical formula to flax/torch LayerNorm.
- backward recomputes x_hat from the saved input + (mean, rstd) statistics
  (no [.., H] fp32 residual), returning dx in x.dtype and fp32 dscale/dbias.

Dispatch: Mosaic lowers on TPU only. Off-TPU (the CPU test mesh) the public
entry point runs a jnp reference with the exact same math unless the
caller is inside ``ops.flash_attention.tpu_interpret_mode`` (kernel parity
tests). Shapes that don't tile (H not a multiple of 128) also fall back.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_tpu.ops.dropout import (
    derive_kernel_seed,
    kernel_prng_seed as _prng_seed,
    kernel_keep_mask as _keep_mask,
    pow2_row_block,
    raw_dropout,
)

_LANES = 128  # stats outputs are lane-broadcast to the minor-dim tile width
_DEFAULT_BLOCK_R = 256


def reference_layer_norm(x, scale, bias, *, eps: float, out_dtype=None):
    """jnp twin of the kernel: fp32 stats, biased variance, cast at the end."""
    out_dtype = out_dtype or x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    c = xf - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    y = c * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(out_dtype)


# ----------------------------------------------------- shared kernel math


def _ln_stats(xf, eps: float):
    """fp32 (mean, rstd, xhat) over the last axis — THE LayerNorm formula,
    shared by every kernel here so fwd and the bwd recompute can't drift."""
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    c = xf - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return mean, rstd, c * rstd


def _ln_dx(xhat, dy, scale_f32, rstd):
    """LayerNorm input gradient from fp32 xhat/dy."""
    wdy = dy * scale_f32
    h = xhat.shape[-1]
    c1 = jnp.sum(wdy * xhat, axis=-1, keepdims=True) / h
    c2 = jnp.sum(wdy, axis=-1, keepdims=True) / h
    return (wdy - xhat * c1 - c2) * rstd


def _write_param_partials(dscale_ref, dbias_ref, dy, xhat):
    """Per-block partial dscale/dbias, sublane-broadcast into [1, 8, H]
    blocks (Mosaic wants >= 8 sublanes; callers read row 0 and sum)."""
    dscale_ref[...] = jnp.broadcast_to(
        jnp.sum(dy * xhat, axis=0)[None, None, :], dscale_ref.shape
    )
    dbias_ref[...] = jnp.broadcast_to(
        jnp.sum(dy, axis=0)[None, None, :], dbias_ref.shape
    )


# --------------------------------------------------------------------- fwd


def _fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, *, eps: float):
    xf = x_ref[...].astype(jnp.float32)  # [block_r, H]
    _, _, xhat = _ln_stats(xf, eps)
    y = xhat * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(
        jnp.float32
    )
    y_ref[...] = y.astype(y_ref.dtype)


def _fwd(x2d, scale, bias, *, eps: float, out_dtype, block_r: int):
    rows, h = x2d.shape
    grid = (rows // block_r,)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), out_dtype),
        interpret=interpret_active(),
    )(x2d, scale[None, :], bias[None, :])


# --------------------------------------------------------------------- bwd


def _bwd_kernel(x_ref, dy_ref, scale_ref,
                dx_ref, dscale_ref, dbias_ref, *, eps: float):
    xf = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    # stats recomputed from the (already loaded) input — cheaper than
    # round-tripping [rows, 128] lane-broadcast fp32 residuals through HBM
    _, rstd, xhat = _ln_stats(xf, eps)
    dx = _ln_dx(xhat, dy, scale_ref[...].astype(jnp.float32), rstd)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    _write_param_partials(dscale_ref, dbias_ref, dy, xhat)


def _bwd(x2d, dy2d, scale, *, eps: float, block_r: int):
    rows, h = x2d.shape
    nblocks = rows // block_r
    dx, dscale_p, dbias_p = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_r, h), lambda i: (i, 0)),
            pl.BlockSpec((block_r, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, h), lambda i: (i, 0)),
            pl.BlockSpec((1, 8, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, h), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), x2d.dtype),
            jax.ShapeDtypeStruct((nblocks, 8, h), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, 8, h), jnp.float32),
        ],
        interpret=interpret_active(),
    )(x2d, dy2d, scale[None, :])
    return dx, jnp.sum(dscale_p[:, 0], axis=0), jnp.sum(dbias_p[:, 0], axis=0)


# ------------------------------------------------------- public entry point


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_layer_norm(x2d, scale, bias, eps, out_dtype, block_r):
    return _fwd(x2d, scale, bias, eps=eps, out_dtype=out_dtype,
                block_r=block_r)


def _fused_vjp_fwd(x2d, scale, bias, eps, out_dtype, block_r):
    y = _fwd(x2d, scale, bias, eps=eps, out_dtype=out_dtype, block_r=block_r)
    return y, (x2d, scale)


def _fused_vjp_bwd(eps, out_dtype, block_r, res, dy):
    x2d, scale = res
    dx, dscale, dbias = _bwd(
        x2d, dy.astype(x2d.dtype), scale, eps=eps, block_r=block_r
    )
    return dx, dscale.astype(scale.dtype), dbias.astype(scale.dtype)


_fused_layer_norm.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)


from pytorch_distributed_training_tpu.ops.dispatch import interpret_active


def _row_shard_plan(x, block_r: int):
    """shard_map plan for a row-wise kernel on ``x`` [..., H]: batch axes
    on dim 0, the seq axis on dim 1 when present (dispatch.plan_shards),
    plus the LOCAL row-block size — or None when the shape doesn't divide
    over the registered mesh (caller falls back to the XLA math)."""
    from pytorch_distributed_training_tpu.ops import dispatch

    ctx = dispatch.kernel_ctx()
    if ctx is None:
        return None
    seq_axis = ctx[2]
    plan = dispatch.plan_shards(
        x.shape, {1: seq_axis} if x.ndim >= 3 else {}
    )
    if plan is None:
        return None
    mesh, spec, axes_used, local_shape = plan
    rows_local = 1
    for d in local_shape[:-1]:
        rows_local *= d
    br = pow2_row_block(rows_local, block_r)
    if br < 16:
        return None
    return mesh, spec, axes_used, br


def layer_norm(
    x,
    scale,
    bias,
    *,
    eps: float = 1e-12,
    out_dtype=None,
    block_r: int = _DEFAULT_BLOCK_R,
    impl: str = "fused",
):
    """LayerNorm over the last axis; fp32 stats; output cast to out_dtype.

    ``impl``: "fused" uses the Pallas kernel when the backend supports it
    and shapes tile (falls back to the jnp reference otherwise);
    "reference" always uses the jnp math.
    """
    if impl not in ("fused", "reference"):
        raise ValueError(
            f"unknown layernorm impl {impl!r}; have ('fused', 'reference')"
        )
    from pytorch_distributed_training_tpu.ops import dispatch

    out_dtype = out_dtype or x.dtype
    h = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    mode = dispatch.mode() if impl == "fused" and h % _LANES == 0 else "off"
    if mode == "shard_map":
        plan = _row_shard_plan(x, block_r)
        if plan is not None:
            mesh, spec, _, br = plan
            from jax.sharding import PartitionSpec as P

            def body(xl, sl, bl):
                with dispatch.manual_region():
                    y = _fused_layer_norm(
                        xl.reshape(-1, h), sl, bl, eps,
                        jnp.dtype(out_dtype), br,
                    )
                return y.reshape(xl.shape[:-1] + (h,))

            dispatch.note_path("layer_norm", "shard_map")
            return jax.shard_map(
                body, mesh=mesh, in_specs=(spec, P(), P()),
                out_specs=spec, check_vma=False,
            )(x, scale, bias)
        mode = "off"
    # largest power-of-2 row block <= block_r dividing rows; Mosaic's bf16
    # tile needs >= 16 sublanes, so smaller row counts use the reference
    br = pow2_row_block(rows, block_r)
    if mode != "direct" or br < 16:
        dispatch.note_path("layer_norm", "xla")
        return reference_layer_norm(x, scale, bias, eps=eps,
                                    out_dtype=out_dtype)
    dispatch.note_path("layer_norm", "direct")
    x2d = x.reshape(rows, h)
    y = _fused_layer_norm(x2d, scale, bias, eps, jnp.dtype(out_dtype), br)
    return y.reshape(*x.shape[:-1], h)


# ------------------------------------------------- dropout + add + LN (v2)
#
# The post-LN block tail is Dropout(h) -> x + h -> LayerNorm. Materializing
# the u32 keep-mask words and running the select in whatever fusion XLA
# picks costs real HBM traffic and throttles neighboring matmul epilogues;
# this variant regenerates the mask from the per-core PRNG INSIDE the
# kernel (flash_attention.py's scheme: reseed per (site, block) so fwd and
# bwd reproduce bit-identical masks) and fuses mask, scale, residual add
# and the normalization into one read of h/x and one write of y.


def _dal_fwd_kernel(seed_ref, h_ref, x_ref, scale_ref, bias_ref,
                    y_ref, *s_out, eps: float, rate: float, site: int):
    i = pl.program_id(0)
    hf = h_ref[...].astype(jnp.float32)
    if rate > 0.0:
        _prng_seed(seed_ref[0], site * pl.num_programs(0) + i)
        keep = _keep_mask(hf.shape, rate)
        hf = jnp.where(keep, hf * (1.0 / (1.0 - rate)), 0.0)
    s = x_ref[...].astype(jnp.float32) + hf
    _, _, xhat = _ln_stats(s, eps)
    y = xhat * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(
        jnp.float32
    )
    y_ref[...] = y.astype(y_ref.dtype)
    if s_out:  # training: save the pre-norm sum for the backward
        s_out[0][...] = s.astype(s_out[0].dtype)


def _dal_fwd(h2d, x2d, scale, bias, seed, *, eps, rate, site, out_dtype,
             block_r, save_s=True):
    rows, hdim = h2d.shape
    grid = (rows // block_r,)
    row_block = lambda i, *_: (i, 0)  # noqa: E731
    one_block = lambda i, *_: (0, 0)  # noqa: E731
    out_specs = [pl.BlockSpec((block_r, hdim), row_block)]
    out_shape = [jax.ShapeDtypeStruct((rows, hdim), out_dtype)]
    if save_s:  # inference-only forwards skip the residual write entirely
        out_specs.append(pl.BlockSpec((block_r, hdim), row_block))
        out_shape.append(jax.ShapeDtypeStruct((rows, hdim), h2d.dtype))
    out = pl.pallas_call(
        functools.partial(_dal_fwd_kernel, eps=eps, rate=rate, site=site),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_r, hdim), row_block),
                pl.BlockSpec((block_r, hdim), row_block),
                pl.BlockSpec((1, hdim), one_block),
                pl.BlockSpec((1, hdim), one_block),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret_active(),
    )(seed, h2d, x2d, scale[None, :], bias[None, :])
    # pallas_call returns a list matching out_shape; normalize to (y, s)
    return (out[0], out[1]) if save_s else (out[0], None)


def _dal_bwd_kernel(seed_ref, s_ref, dy_ref, scale_ref,
                    dh_ref, dx_ref, dscale_ref, dbias_ref, *,
                    eps: float, rate: float, site: int):
    i = pl.program_id(0)
    sf = s_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    # stats recomputed in VMEM from the saved pre-norm sum (see _bwd_kernel)
    _, rstd, xhat = _ln_stats(sf, eps)
    ds = _ln_dx(xhat, dy, scale_ref[...].astype(jnp.float32), rstd)
    dx_ref[...] = ds.astype(dx_ref.dtype)
    if rate > 0.0:
        _prng_seed(seed_ref[0], site * pl.num_programs(0) + i)
        keep = _keep_mask(ds.shape, rate)
        dh = jnp.where(keep, ds * (1.0 / (1.0 - rate)), 0.0)
    else:
        dh = ds
    dh_ref[...] = dh.astype(dh_ref.dtype)
    _write_param_partials(dscale_ref, dbias_ref, dy, xhat)


def _dal_bwd(s2d, dy2d, scale, seed, *, eps, rate, site, h_dtype,
             block_r):
    rows, hdim = s2d.shape
    nblocks = rows // block_r
    row_block = lambda i, *_: (i, 0)  # noqa: E731
    one_block = lambda i, *_: (0, 0)  # noqa: E731
    dh, dx, dscale_p, dbias_p = pl.pallas_call(
        functools.partial(_dal_bwd_kernel, eps=eps, rate=rate, site=site),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((block_r, hdim), row_block),
                pl.BlockSpec((block_r, hdim), row_block),
                pl.BlockSpec((1, hdim), one_block),
            ],
            out_specs=[
                pl.BlockSpec((block_r, hdim), row_block),
                pl.BlockSpec((block_r, hdim), row_block),
                pl.BlockSpec((1, 8, hdim), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, 8, hdim), lambda i, *_: (i, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, hdim), h_dtype),
            jax.ShapeDtypeStruct((rows, hdim), h_dtype),
            jax.ShapeDtypeStruct((nblocks, 8, hdim), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, 8, hdim), jnp.float32),
        ],
        interpret=interpret_active(),
    )(seed, s2d, dy2d, scale[None, :])
    return dh, dx, jnp.sum(dscale_p[:, 0], 0), jnp.sum(dbias_p[:, 0], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_dal(h2d, x2d, scale, bias, seed, eps, rate, site, out_dtype,
               block_r):
    y, _ = _dal_fwd(h2d, x2d, scale, bias, seed, eps=eps, rate=rate,
                    site=site, out_dtype=out_dtype, block_r=block_r,
                    save_s=False)
    return y


def _fused_dal_vjp_fwd(h2d, x2d, scale, bias, seed, eps, rate, site,
                       out_dtype, block_r):
    y, s = _dal_fwd(h2d, x2d, scale, bias, seed, eps=eps, rate=rate,
                    site=site, out_dtype=out_dtype, block_r=block_r)
    return y, (s, scale, seed)


def _fused_dal_vjp_bwd(eps, rate, site, out_dtype, block_r, res, dy):
    s, scale, seed = res
    dh, dx, dscale, dbias = _dal_bwd(
        s, dy.astype(s.dtype), scale, seed, eps=eps, rate=rate,
        site=site, h_dtype=s.dtype, block_r=block_r,
    )
    return dh, dx, dscale.astype(scale.dtype), dbias.astype(scale.dtype), None


_fused_dal.defvjp(_fused_dal_vjp_fwd, _fused_dal_vjp_bwd)


def dropout_add_layer_norm(
    h,
    x,
    scale,
    bias,
    *,
    rate: float,
    dropout_rng=None,
    deterministic: bool = True,
    eps: float = 1e-12,
    site: int = 0,
    out_dtype=None,
    block_r: int = _DEFAULT_BLOCK_R,
    impl: str = "fused",
    dropout_impl: str = "kernel",
):
    """LayerNorm(x + Dropout(h)) over the last axis.

    With ``impl="fused"`` AND ``dropout_impl="kernel"`` on TPU, the whole
    tail runs as one Pallas kernel with the keep-mask regenerated from the
    per-core PRNG (no mask bytes ever hit HBM; fwd and bwd reseed
    identically per (site, row-block), so ``site`` must differ between
    call sites sharing one ``dropout_rng``). Any other ``dropout_impl``
    keeps that generator's documented mask stream (ops/dropout.py — e.g.
    "exact" stays bit-identical to flax nn.Dropout) by applying dropout
    through ``raw_dropout`` and then the LN (still the LN kernel when
    usable). Off-TPU everything falls back to jax.random + reference LN.
    """
    from pytorch_distributed_training_tpu.ops import dispatch

    out_dtype = out_dtype or x.dtype
    hdim = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    rate = 0.0 if deterministic else rate
    mode = (
        dispatch.mode() if impl == "fused" and hdim % _LANES == 0 else "off"
    )
    if rate > 0.0 and dropout_impl != "kernel":
        mode = "off"  # foreign mask streams can't regenerate in-kernel
    if mode == "shard_map":
        plan = _row_shard_plan(x, block_r)
        if plan is None:
            mode = "off"
        else:
            mesh, spec, axes_used, br = plan
            from jax.sharding import PartitionSpec as P

            if rate > 0.0:
                seed = derive_kernel_seed(dropout_rng)
            else:
                seed = jnp.zeros((1,), jnp.int32)

            def body(hl, xl, sl, bl, seedl):
                with dispatch.manual_region():
                    # distinct in-kernel PRNG stream per shard
                    seedl = seedl + dispatch.linear_device_index(
                        axes_used, mesh
                    )
                    y = _fused_dal(
                        hl.reshape(-1, hdim), xl.reshape(-1, hdim), sl, bl,
                        seedl, eps, float(rate), int(site),
                        jnp.dtype(out_dtype), br,
                    )
                return y.reshape(xl.shape[:-1] + (hdim,))

            dispatch.note_path("dal", "shard_map")
            return jax.shard_map(
                body, mesh=mesh, in_specs=(spec, spec, P(), P(), P()),
                out_specs=spec, check_vma=False,
            )(h, x, scale, bias, seed)
    br = pow2_row_block(rows, block_r)
    if mode != "direct" or br < 16:
        dispatch.note_path("dal", "xla")
        if rate > 0.0:
            h = raw_dropout(h, rate, dropout_rng, dropout_impl)
        return layer_norm(x + h, scale, bias, eps=eps, out_dtype=out_dtype,
                          block_r=block_r, impl=impl)
    dispatch.note_path("dal", "direct")
    if rate > 0.0:
        # one int32 seed per call; the kernel folds in the block index.
        seed = derive_kernel_seed(dropout_rng)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    y = _fused_dal(
        h.reshape(rows, hdim), x.reshape(rows, hdim), scale, bias, seed,
        eps, float(rate), int(site), jnp.dtype(out_dtype), br,
    )
    return y.reshape(x.shape[:-1] + (hdim,))


import flax.linen as nn  # noqa: E402


class FusedLayerNorm(nn.Module):
    """flax LayerNorm twin mirroring ``nn.LayerNorm``'s param names/init
    (``scale`` ones, ``bias`` zeros) so checkpoints and the HF weight
    mapper are layout-identical whichever impl a config selects. Output is
    cast to ``out_dtype`` (the models always cast the fp32 LN result to
    the compute dtype anyway — the kernel just does it in-register)."""

    epsilon: float
    param_dtype: jnp.dtype
    out_dtype: jnp.dtype
    impl: str = "fused"

    @nn.compact
    def __call__(self, x):
        h = x.shape[-1]
        scale = self.param(
            "scale", nn.initializers.ones, (h,), self.param_dtype
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (h,), self.param_dtype
        )
        return layer_norm(
            x, scale, bias, eps=self.epsilon,
            out_dtype=self.out_dtype, impl=self.impl,
        )


class FusedDropoutAddLayerNorm(nn.Module):
    """``LayerNorm(x + Dropout(h))`` as one module — the post-LN block
    tail. Param names match ``nn.LayerNorm`` ("scale"/"bias") so the
    checkpoint/HF layouts are unchanged vs the unfused Dropout + LN pair.

    ``site`` disambiguates the in-kernel PRNG stream between the two tails
    of one transformer block (they share the layer's dropout key)."""

    epsilon: float
    rate: float
    param_dtype: jnp.dtype
    out_dtype: jnp.dtype
    impl: str = "fused"
    site: int = 0
    dropout_impl: str = "kernel"

    @nn.compact
    def __call__(self, h, x, deterministic: bool = True):
        hdim = x.shape[-1]
        scale = self.param(
            "scale", nn.initializers.ones, (hdim,), self.param_dtype
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (hdim,), self.param_dtype
        )
        rng = None
        if not deterministic and self.rate > 0.0:
            rng = self.make_rng("dropout")
        return dropout_add_layer_norm(
            h, x, scale, bias, rate=self.rate, dropout_rng=rng,
            deterministic=deterministic, eps=self.epsilon, site=self.site,
            out_dtype=self.out_dtype, impl=self.impl,
            dropout_impl=self.dropout_impl,
        )
