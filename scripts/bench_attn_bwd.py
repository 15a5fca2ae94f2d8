"""Size the attention-bwd win: XLA attention fwd vs fwd+bwd cost at seq 128.

Times the attention OP only (no projections), bert-large geometry, micro 32:
  - fwd only (inference path)
  - fwd + bwd via jax.grad (what the train step pays)
  - pallas probs-saving fwd + dqkv-from-probs bwd (the flash single-block path)
Chained iterations; scalar device_get at the end (NOTES.md timing rules).
"""

import functools
import time

import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.ops.attention import reference_attention
from pytorch_distributed_training_tpu.ops.flash_attention import (
    flash_attention_base,
)

B, S, N, D = 32, 128, 16, 64
ITERS = 50


def xla_attn(q, k, v, bias, rng, rate):
    return reference_attention(
        q, k, v, bias, dropout_rng=rng, dropout_rate=rate,
        deterministic=rate == 0.0, dropout_impl="bits32",
    )


def pallas_attn(q, k, v, bias, seed, rate):
    o = flash_attention_base(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bias, seed, dropout_rate=rate,
    )
    return o.transpose(0, 2, 1, 3)


def bench(name, fn, grad: bool, rate: float):
    if grad:
        def loss(q, k, v, bias, r):
            return jnp.sum(fn(q, k, v, bias, r, rate).astype(jnp.float32) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def step(q, k, v, bias, r):
            dq, dk, dv = g(q, k, v, bias, r)
            return (
                (q + dq * 1e-6).astype(q.dtype),
                (k + dk * 1e-6).astype(k.dtype),
                (v + dv * 1e-6).astype(v.dtype),
                jnp.sum(dq.astype(jnp.float32)),
            )
    else:
        @jax.jit
        def step(q, k, v, bias, r):
            o = fn(q, k, v, bias, r, rate)
            return (
                (q + o * 1e-6).astype(q.dtype),
                k,
                v,
                jnp.sum(o.astype(jnp.float32)),
            )

    key = jax.random.key(0, impl="rbg")
    q = jax.random.normal(key, (B, S, N, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, N, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, N, D), jnp.bfloat16)
    bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    r = jnp.array([123], jnp.int32) if "pallas" in name else key
    q, k, v, s = step(q, k, v, bias, r)
    jax.block_until_ready(s)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            q, k, v, s = step(q, k, v, bias, r)
        _ = float(jax.device_get(s))
        best = min(best, (time.perf_counter() - t0) / ITERS * 1e3)
    print(f"{name:36s} {best:7.3f} ms", flush=True)
    return best


if __name__ == "__main__":
    print(f"backend={jax.default_backend()} B={B} S={S} N={N} D={D}")
    for rate in (0.0, 0.1):
        print(f"--- dropout={rate}")
        f = bench(f"xla fwd only", xla_attn, False, rate)
        fb = bench(f"xla fwd+bwd", xla_attn, True, rate)
        print(f"    => xla bwd cost ~{fb - f:.3f} ms")
        pf = bench(f"pallas fwd only", pallas_attn, False, rate)
        pfb = bench(f"pallas fwd+bwd (probs-saving)", pallas_attn, True, rate)
        print(f"    => pallas bwd cost ~{pfb - pf:.3f} ms")
