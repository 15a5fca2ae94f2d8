"""Ring attention: sequence/context parallelism over the mesh ``seq`` axis.

The reference has NO long-context machinery — sequences are truncated to the
model max (reference test_data_parallelism.py:75) and padded to 128 on TPU
(:96-98). This framework makes sequence scaling first-class: activations
shard on the sequence dimension over the mesh's ``seq`` axis, and attention
— the one op that needs every key/value — runs as a ring (Liu et al., Ring
Attention with Blockwise Transformers): each device holds its local Q block
for the whole pass while K/V (+ the key-padding bias) blocks hop around the
ring via ``jax.lax.ppermute`` (XLA collective-permute over adjacent-chip ICI
links), combined with the same online-softmax accumulation the flash kernel
uses. Peak memory per device is O(S/P · S/P) scores instead of O(S²), and
each hop's communication overlaps the previous block's compute under XLA's
latency-hiding scheduler.

Implementation notes:
- Entered via ``jax.shard_map`` over the enclosing jit's GSPMD program:
  the op takes GLOBAL [B, S, N, D] arrays (sharded however the trainer laid
  them out), forces the seq-sharded layout at the shard_map boundary, and
  returns the same layout. The concrete Mesh comes from
  ``comms.mesh.current_mesh()`` because flax module calls can't thread a
  Mesh through ``dot_product_attention``'s signature.
- The ring loop is a static python loop (mesh sizes are static): fully
  unrolled, differentiable (reverse-mode AD transposes each ppermute into
  the inverse rotation), and schedulable — XLA overlaps hop j+1's
  collective-permute with hop j's matmuls.
- Causality is enforced with GLOBAL positions (shard offset + local index),
  so a causal model sharded over ``seq`` matches the single-device result;
  whole ring hops that are entirely above the diagonal still pay the
  permute (pipelined away) but skip nothing numerically — their
  contribution is exactly masked.
- Attention-probability dropout folds (ring step, my shard index) into the
  key so every (q-block, kv-block) pair gets an independent keep mask.
- With ``seq`` axis size 1 (or no mesh recorded) this degrades to the plain
  reference implementation — same math, no shard_map.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tpu.comms.mesh import (
    AXIS_SEQ,
    BATCH_AXES,
    current_mesh,
)
from pytorch_distributed_training_tpu.ops.attention import (
    reference_attention,
    register_attention,
)
from pytorch_distributed_training_tpu.ops.dropout import raw_dropout

_NEG_INF = -1e30


def _local_block(q, k, v, bias, *, scale, q_offset, kv_offset, causal,
                 dropout_rng, dropout_rate, dropout_impl):
    """One (local Q) x (one ring hop's K/V) block: scores + online-softmax
    partials. Shapes: q [B, Sq, N, D]; k/v [B, Skv, N, D];
    bias [B, 1, 1, Skv]. Returns (m, l, pv): running-max [B, N, Sq],
    denominator partial [B, N, Sq], weighted values [B, Sq, N, D]."""
    s = jnp.einsum(
        "bsnd,btnd->bnst", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sq, skv = q.shape[1], k.shape[1]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, skv), 0)
        k_pos = kv_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, skv), 1)
        s = s + jnp.where(k_pos <= q_pos, 0.0, _NEG_INF)[None, None]
    m = jnp.max(s, axis=-1)  # [B, N, Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # denominator from the UNDROPPED fp32 p
    p = p.astype(v.dtype)  # cast before dropout: half-width mask residual,
    # same ordering as reference_attention's bf16-policy path
    if dropout_rate > 0.0:
        p = raw_dropout(p, dropout_rate, dropout_rng, dropout_impl)
    pv = jnp.einsum(
        "bnst,btnd->bsnd", p, v,
        preferred_element_type=jnp.float32,
    )
    return m, l, pv


def _ring_shard(q, k, v, bias, *, scale, n_shards, causal, dropout_rng,
                dropout_rate, dropout_impl, axis_name):
    """Per-shard body under shard_map: local Q stays, K/V/bias ring-hop."""
    from pytorch_distributed_training_tpu.ops import dispatch

    with dispatch.manual_region():
        return _ring_shard_body(
            q, k, v, bias, scale=scale, n_shards=n_shards, causal=causal,
            dropout_rng=dropout_rng, dropout_rate=dropout_rate,
            dropout_impl=dropout_impl, axis_name=axis_name,
        )


def _ring_shard_body(q, k, v, bias, *, scale, n_shards, causal, dropout_rng,
                     dropout_rate, dropout_impl, axis_name):
    my = jax.lax.axis_index(axis_name)
    seq_local = q.shape[1]
    perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]  # blocks move left

    m_run = jnp.full(q.shape[:1] + (q.shape[2], seq_local), _NEG_INF,
                     jnp.float32)  # [B, N, Sq]
    l_run = jnp.zeros_like(m_run)
    acc = jnp.zeros(q.shape, jnp.float32)

    k_cur, v_cur, bias_cur = k, v, bias
    for j in range(n_shards):
        src = (my + j) % n_shards  # origin shard of the block now held
        step_rng = (
            jax.random.fold_in(jax.random.fold_in(dropout_rng, j), my)
            if dropout_rate > 0.0
            else None
        )
        m_j, l_j, pv_j = _local_block(
            q, k_cur, v_cur, bias_cur,
            scale=scale,
            q_offset=my * seq_local,
            kv_offset=src * seq_local,
            causal=causal,
            dropout_rng=step_rng,
            dropout_rate=dropout_rate,
            dropout_impl=dropout_impl,
        )
        m_new = jnp.maximum(m_run, m_j)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_j - m_new)
        l_run = l_run * alpha + l_j * beta
        # acc is [B, Sq, N, D]; stats are [B, N, Sq] -> move Sq next to B
        acc = (
            acc * alpha.transpose(0, 2, 1)[..., None]
            + pv_j * beta.transpose(0, 2, 1)[..., None]
        )
        m_run = m_new
        if j + 1 < n_shards:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            if bias_cur is not None:
                bias_cur = jax.lax.ppermute(bias_cur, axis_name, perm)

    l_safe = jnp.maximum(l_run, 1e-30)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


@register_attention("ring")
def ring_attention(
    q: jnp.ndarray,  # [B, S, N, D] (global)
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    *,
    dropout_rng=None,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    causal: bool = False,
    dropout_impl: str = "exact",
):
    """Sequence-parallel attention over the mesh ``seq`` axis.

    Matches the swappable-attention signature (ops/attention.py). Requires
    the key-padding bias form [B, 1, 1, S] (or none); any other bias shape
    falls back to the reference implementation, as does a missing/size-1
    ``seq`` axis.
    """
    mesh = current_mesh()
    rate = 0.0 if deterministic or dropout_rng is None else dropout_rate
    bias_ok = bias is None or (
        bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1
    )
    n_shards = mesh.shape[AXIS_SEQ] if mesh is not None else 1
    if n_shards == 1 or not bias_ok or q.shape[1] % n_shards:
        return reference_attention(
            q, k, v, bias,
            dropout_rng=dropout_rng, dropout_rate=dropout_rate,
            deterministic=deterministic, causal=causal,
            dropout_impl=dropout_impl,
        )

    scale = q.shape[-1] ** -0.5
    # batch rows shard over the data axes only when they divide — a batch
    # smaller than data×fsdp (e.g. the 2-row model-init example) computes
    # replicated instead of failing shard_map's divisibility check; the
    # seq axis (the op's whole point) is already guarded above
    from pytorch_distributed_training_tpu.comms.mesh import dp_degree

    batch_axes = BATCH_AXES if q.shape[0] % dp_degree(mesh) == 0 else None
    qkv_spec = P(batch_axes, AXIS_SEQ, None, None)
    bias_spec = P(batch_axes, None, None, AXIS_SEQ)

    import functools

    # Uniform signature for ONE shard_map: a zeros bias (folded away by XLA)
    # stands in for None, and a dummy key rides along when dropout is off
    # (rate is static, so the body traces no dropout ops from it).
    if bias is None:
        bias = jnp.zeros((q.shape[0], 1, 1, q.shape[1]), jnp.float32)
    rng = dropout_rng if rate > 0.0 else jax.random.key(0)

    body = functools.partial(
        _ring_shard,
        scale=scale,
        n_shards=n_shards,
        causal=causal,
        dropout_rate=rate,
        dropout_impl=dropout_impl,
        axis_name=AXIS_SEQ,
    )
    fn = jax.shard_map(
        lambda q, k, v, b, r: body(q, k, v, b, dropout_rng=r),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec, P()),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, bias, rng)
