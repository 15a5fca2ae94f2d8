"""shard_map kernel dispatch on sharded meshes (ops/dispatch.py).

Round 2 silently lost every Pallas kernel on >1-device meshes (the GSPMD
partitioner treats a bare custom call as replicated). These tests pin the
round-3 contract on the 8-device CPU mesh, using the interpret context as
the kernel emulator:

- with a registered kernel mesh, each op actually takes the shard_map
  kernel path (trace-time dispatch counters — the observable, since
  interpret-mode HLO hides the custom call), and the numerics match the
  op's XLA reference math on the same global inputs;
- without a registered mesh on a multi-device backend, dispatch reports
  "off" — the documented explicit fallback, never a bare custom call.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.comms.mesh import build_mesh
from pytorch_distributed_training_tpu.ops import dispatch
from pytorch_distributed_training_tpu.ops.flash_attention import (
    tpu_interpret_mode,
)
from pytorch_distributed_training_tpu.ops.layer_norm import (
    dropout_add_layer_norm,
    layer_norm,
    reference_layer_norm,
)
from pytorch_distributed_training_tpu.utils.config import MeshConfig


@pytest.fixture()
def mesh(eight_devices):
    return build_mesh(MeshConfig(data=4, fsdp=2))


def _counts(op):
    return dispatch.DISPATCH_PATHS[f"{op}:shard_map"]


def test_mode_off_without_registered_mesh(eight_devices):
    # 8 CPU devices, no interpret ctx, no mesh: kernels must NOT dispatch
    assert dispatch.mode() == "off"


def test_layer_norm_shard_map_dispatch(mesh):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16, 256)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    ref = reference_layer_norm(x, scale, bias, eps=1e-12)
    before = _counts("layer_norm")
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(mesh):
        assert dispatch.mode() == "shard_map"
        out = layer_norm(x, scale, bias, eps=1e-12)
    assert _counts("layer_norm") == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_layer_norm_indivisible_falls_back(mesh):
    """Batch 6 doesn't divide over data=4 x fsdp=2: explicit XLA fallback
    (correct numerics), not a bare custom call."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 16, 256)), jnp.float32)
    scale = jnp.ones((256,), jnp.float32)
    bias = jnp.zeros((256,), jnp.float32)
    before = _counts("layer_norm")
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(mesh):
        out = layer_norm(x, scale, bias, eps=1e-12)
    assert _counts("layer_norm") == before  # no kernel dispatch
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(reference_layer_norm(x, scale, bias, eps=1e-12)),
        atol=1e-6, rtol=1e-6,
    )


def test_dal_shard_map_dispatch_deterministic(mesh):
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(8, 16, 256)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 16, 256)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    ref = reference_layer_norm(x + h, scale, bias, eps=1e-12)
    before = _counts("dal")
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(mesh):
        out = dropout_add_layer_norm(
            h, x, scale, bias, rate=0.1, deterministic=True, eps=1e-12
        )
    assert _counts("dal") == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_mask_scale_shard_map_per_device_streams(mesh):
    """Kernel dropout under a sharded mesh: kernel path taken, mask values
    are exactly {0, 1/(1-rate)} ... and the per-device seed offset gives
    different shards different masks.

    NOTE: pltpu.prng_random_bits is all-zeros in interpret mode off-TPU
    (NOTES.md), which maps every position to "drop" — so mask STATISTICS
    are unverifiable here (the on-TPU tier covers them); this test pins
    dispatch + shape/value-domain only.
    """
    from pytorch_distributed_training_tpu.ops.dropout import raw_dropout

    x = jnp.ones((8, 16, 256), jnp.float32)
    before = _counts("mask_scale")
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(mesh):
        out = raw_dropout(x, 0.25, jax.random.key(0), "kernel")
    assert _counts("mask_scale") == before + 1
    vals = np.unique(np.asarray(out).round(6))
    assert set(vals).issubset({0.0, np.float32(1 / 0.75).round(6)})


def test_flash_shard_map_dispatch(mesh, monkeypatch):
    """flash routes through shard_map with per-shard seed offsetting.

    The Pallas kernel itself is swapped for its jnp math here: interpret-
    mode kernel emulation inside an 8-way shard_map is pathologically slow
    on the single-core CPU image (minutes per call), and what this test
    pins is the ROUTING — specs, divisibility, counter, numerics of the
    sharded composition. Real kernel-under-shard_map execution is the
    on-TPU tier's job (test_tpu_kernels.py).
    """
    import pytorch_distributed_training_tpu.ops.flash_attention as fa
    from pytorch_distributed_training_tpu.ops.attention import (
        make_attention_bias,
        reference_attention,
    )

    def jnp_base(q, k, v, bias, seed, *, dropout_rate=0.0, causal=False,
                 block_q=None, block_k=None):
        # [B, N, S, D] math twin of flash_attention_base, no dropout
        s = jnp.einsum(
            "bnsd,bntd->bnst", q, k, preferred_element_type=jnp.float32
        ) * (q.shape[-1] ** -0.5)
        s = s + bias
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bnst,bntd->bnsd", p, v)

    monkeypatch.setattr(fa, "flash_attention_base", jnp_base)
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=(8, 128, 4, 64)), jnp.float32)
        for _ in range(3)
    )
    mask = jnp.ones((8, 128), jnp.int32)
    bias = make_attention_bias(mask)
    ref = reference_attention(q, k, v, bias)
    before = _counts("flash")
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(mesh):
        out = fa.flash_attention(q, k, v, bias)
    assert _counts("flash") == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_cp_mesh_falls_back(eight_devices):
    """With an active seq (context-parallel) axis flash must NOT shard_map
    (ring attention owns that regime) — reference fallback instead."""
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_attention,
    )

    cp_mesh = build_mesh(MeshConfig(data=2, seq=4))
    rng = np.random.default_rng(4)
    q, k, v = (
        jnp.asarray(rng.normal(size=(4, 128, 4, 64)), jnp.float32)
        for _ in range(3)
    )
    before = _counts("flash")
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(cp_mesh):
        out = flash_attention(q, k, v, None)
    assert _counts("flash") == before
    assert np.isfinite(np.asarray(out)).all()


def test_bert_layer_end_to_end_sharded_kernels(mesh):
    """A whole BertLayer under jit on the sharded mesh with the kernel
    dispatch active: runs, matches the reference-impl layer at dropout 0."""
    from pytorch_distributed_training_tpu.models.bert import BertLayer
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = model_preset(
        "tiny", compute_dtype="float32",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, 16, cfg.hidden_size)), jnp.float32)
    layer = BertLayer(cfg)
    params = layer.init(jax.random.key(0), x, None, True)["params"]
    ref = layer.apply({"params": params}, x, None, True)
    with tpu_interpret_mode(), dispatch.use_kernel_mesh(mesh):
        out = layer.apply({"params": params}, x, None, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )
