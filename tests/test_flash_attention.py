"""Flash-attention kernel parity tests (interpret mode on the CPU mesh).

The kernel's contract is bit-level agreement with the reference einsum
attention (ops/attention.py) on everything except dropout, whose keep mask
comes from the in-kernel TPU PRNG. Dropout correctness is covered by a
finite-difference check — valid because the kernel PRNG is deterministic in
(seed, block ids), so f is a fixed function of its inputs.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops.attention import (
    dot_product_attention,
    make_attention_bias,
    reference_attention,
)
from pytorch_distributed_training_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_base,
    tpu_interpret_mode,
)


def _qkv(batch=2, seq=32, heads=2, head_dim=8, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(batch, seq, heads, head_dim)), dtype
    )
    return mk(), mk(), mk()


def _padding_mask(batch=2, seq=32, valid_lens=(32, 17)):
    mask = np.zeros((batch, seq), np.int32)
    for i, n in enumerate(valid_lens):
        mask[i, :n] = 1
    return jnp.asarray(mask)


@contextlib.contextmanager
def adapter_on_kernel_path():
    """``tpu_interpret_mode()`` plus the proof that every adapter call in
    the block ran the Pallas kernel: a shape the adapter quietly hands to
    the XLA math would compare the reference to itself and pass."""
    before = dict(dispatch.DISPATCH_PATHS)
    with tpu_interpret_mode():
        yield
    paths = dispatch.DISPATCH_PATHS
    assert paths["flash:direct"] > before.get("flash:direct", 0), dict(paths)
    assert paths["flash:xla"] == before.get("flash:xla", 0), dict(paths)


def test_interpret_probe_sees_context():
    """The dispatch guard must recognize the framework's interpret-mode
    context — otherwise every parity test below would silently compare
    reference to itself."""
    from pytorch_distributed_training_tpu.ops import dispatch

    import jax

    if jax.default_backend() != "tpu":
        assert dispatch.mode() == "off"
    with tpu_interpret_mode():
        assert dispatch.mode() == "direct"


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [32, 128])  # sub-lane-width and lane-aligned
def test_flash_matches_reference_fwd(causal, seq):
    q, k, v = _qkv(seq=seq)
    bias = make_attention_bias(_padding_mask(seq=seq, valid_lens=(seq, 17)))
    with adapter_on_kernel_path():
        out = flash_attention(q, k, v, bias, causal=causal)
    ref = reference_attention(q, k, v, bias, causal=causal)
    # padded key rows produce garbage in padded QUERY rows of ref too; compare
    # only rows the mask marks valid (the model multiplies them out anyway)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out[1, :17]), np.asarray(ref[1, :17]), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_grad(causal):
    q, k, v = _qkv(seed=1)
    bias = make_attention_bias(_padding_mask())
    cot = jnp.asarray(
        np.random.default_rng(2).normal(size=q.shape), jnp.float32
    )
    # zero cotangent on padded query rows: their grads are masked downstream
    cot = cot * _padding_mask()[:, :, None, None]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, bias, causal=causal) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(
            reference_attention(q, k, v, bias, causal=causal) * cot
        )

    with adapter_on_kernel_path():
        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})",
        )


def test_flash_dropout_finite_difference():
    """Custom VJP agrees with central differences under in-kernel dropout."""
    q, k, v = _qkv(batch=1, seq=16, heads=1, head_dim=8, seed=3)
    bias = jnp.zeros((1, 1, 1, 16), jnp.float32)
    seed = jnp.asarray([7], jnp.int32)
    cot = jnp.asarray(
        np.random.default_rng(4).normal(size=q.shape), jnp.float32
    )

    def f(q):
        out = flash_attention_base(
            q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            bias, seed, dropout_rate=0.5, causal=False,
            block_q=16, block_k=16,
        )
        return jnp.sum(out * cot.transpose(0, 2, 1, 3))

    qt = q.transpose(0, 2, 1, 3)
    with tpu_interpret_mode():
        g = jax.grad(f)(qt)
        rng = np.random.default_rng(5)
        for _ in range(3):
            d = jnp.asarray(rng.normal(size=qt.shape), jnp.float32)
            eps = 1e-3
            fd = (f(qt + eps * d) - f(qt - eps * d)) / (2 * eps)
            an = jnp.sum(g * d)
            np.testing.assert_allclose(
                float(fd), float(an), rtol=2e-2, atol=1e-3
            )


def test_flash_dispatch_and_fallback():
    q, k, v = _qkv(seq=24)  # 24 % block fine (block=min(128,24)=24)
    # per-head bias → must fall back to reference, not mis-mask
    bias = jnp.zeros((2, 2, 24, 24), jnp.float32)
    before = dispatch.DISPATCH_PATHS["flash:xla"]
    with tpu_interpret_mode():
        out = dot_product_attention(q, k, v, bias, impl="flash")
    assert dispatch.DISPATCH_PATHS["flash:xla"] == before + 1
    ref = reference_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_multiblock_grad_matches_reference(causal):
    """The general two-pass backward (dq + dkv kernels) — NOT the fused
    single-block fast path — must stay correct: force multiple blocks with
    block sizes smaller than the sequence."""
    q, k, v = _qkv(seq=32, seed=6)
    bias = jnp.zeros((2, 1, 1, 32), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
    cot = jnp.asarray(
        np.random.default_rng(7).normal(size=q.shape), jnp.float32
    )

    def loss_flash(q, k, v):
        out = flash_attention_base(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias, seed,
            causal=causal, block_q=16, block_k=16,
        )
        return jnp.sum(out.transpose(0, 2, 1, 3) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, None, causal=causal) * cot)

    with tpu_interpret_mode():
        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"multi-block d{name} (causal={causal})",
        )


def test_flash_fully_masked_row_stays_finite():
    """A fully-padded sample (all-zero mask row) must give finite outputs
    and gradients in the single-block (save-probs) path — the row max floor
    prevents exp(-inf - -inf) NaNs."""
    q, k, v = _qkv(seed=8)
    mask = np.ones((2, 32), np.int32)
    mask[1, :] = 0  # entire sample masked out
    bias = make_attention_bias(jnp.asarray(mask))

    def loss(q):
        return jnp.sum(flash_attention(q, k, v, bias) ** 2)

    with adapter_on_kernel_path():
        out = flash_attention(q, k, v, bias)
        g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dropout", [0.0, 0.5])
def test_fused_bwd_matches_two_pass(causal, dropout):
    """The fused single-pass backward (_dqkv_kernel: one probs recompute,
    dq accumulated across the sequential k-block grid) must produce the
    same gradients as the classic two-pass scheme — with and without
    in-kernel dropout (identical per-(bh, qi, kj) seeds by construction),
    causal and not, multi-block."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    q, k, v = _qkv(seq=32, seed=11)
    bias = make_attention_bias(_padding_mask())
    seed = jnp.asarray([5], jnp.int32)
    cot = jnp.asarray(
        np.random.default_rng(12).normal(size=q.shape), jnp.float32
    )
    cot = cot * _padding_mask()[:, :, None, None]

    def loss(q, k, v):
        out = flash_attention_base(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias.astype(jnp.float32), seed,
            dropout_rate=dropout, causal=causal, block_q=16, block_k=16,
        )
        return jnp.sum(out.transpose(0, 2, 1, 3) * cot)

    grads = {}
    orig = fa.FUSED_BWD
    try:
        for mode in (True, False):
            fa.FUSED_BWD = mode
            with tpu_interpret_mode():
                grads[mode] = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa.FUSED_BWD = orig
    for gf, gt, name in zip(grads[True], grads[False], "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gt), atol=1e-6, rtol=1e-6,
            err_msg=f"fused-vs-two-pass d{name} "
                    f"(causal={causal}, dropout={dropout})",
        )
