"""Microbench: attention-block layouts on the real chip.

Compares, for one attention block (q/k/v proj -> attention -> out proj)
under grad, bert-large geometry:

  A. baseline:  DenseGeneral [B,S,N,D] + reference einsum attention
  B. flash-cur: DenseGeneral [B,S,N,D] + flash adapter (boundary transposes)
  C. flash-hm:  head-major einsum projections [B,N,S,D] + flash (no
                adapter transposes); out-proj consumes [B,N,S,D]

Timing per the NOTES.md rules: chain iterations (x = f(x)) and end with a
device_get of a scalar.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.ops.attention import reference_attention
from pytorch_distributed_training_tpu.ops.flash_attention import (
    flash_attention_base,
)

B, S, H, N, D = 32, 128, 1024, 16, 64
DROPOUT = 0.1
ITERS = 50


def init_params(key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = 0.02
    return {
        "wq": (jax.random.normal(k1, (H, N, D), jnp.float32) * scale).astype(jnp.bfloat16),
        "wk": (jax.random.normal(k2, (H, N, D), jnp.float32) * scale).astype(jnp.bfloat16),
        "wv": (jax.random.normal(k3, (H, N, D), jnp.float32) * scale).astype(jnp.bfloat16),
        "wo": (jax.random.normal(k4, (N, D, H), jnp.float32) * scale).astype(jnp.bfloat16),
    }


def block_bsnd(params, x, bias, seed, impl, dropout):
    q = jnp.einsum("bsh,hnd->bsnd", x, params["wq"])
    k = jnp.einsum("bsh,hnd->bsnd", x, params["wk"])
    v = jnp.einsum("bsh,hnd->bsnd", x, params["wv"])
    if impl == "reference":
        rng = jax.random.wrap_key_data(
            jnp.array([[seed[0].astype(jnp.uint32), 0, 0, 0]], jnp.uint32)[0],
            impl="rbg",
        )
        o = reference_attention(
            q, k, v, bias, dropout_rng=rng, dropout_rate=dropout,
            deterministic=dropout == 0.0,
        )
    else:
        o = flash_attention_base(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), bias, seed, dropout_rate=dropout,
        ).transpose(0, 2, 1, 3)
    return jnp.einsum("bsnd,ndh->bsh", o, params["wo"])


def block_bnsd(params, x, bias, seed, dropout):
    q = jnp.einsum("bsh,hnd->bnsd", x, params["wq"])
    k = jnp.einsum("bsh,hnd->bnsd", x, params["wk"])
    v = jnp.einsum("bsh,hnd->bnsd", x, params["wv"])
    o = flash_attention_base(q, k, v, bias, seed, dropout_rate=dropout)
    return jnp.einsum("bnsd,ndh->bsh", o, params["wo"])


def make_step(fn):
    def loss_fn(params, x, bias, seed):
        out = fn(params, x, bias, seed)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    @jax.jit
    def step(params, x, bias, seed):
        (l, out), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, bias, seed
        )
        # chain: feed the block output back in (keeps the device busy)
        nxt = (x + out * 1e-6).astype(x.dtype)
        return nxt, l, grads

    return step


def bench(name, fn, batch):
    step = make_step(fn)
    key = jax.random.key(0)
    params = init_params(key)
    x = jax.random.normal(key, (batch, S, H), jnp.bfloat16)
    bias = jnp.zeros((batch, 1, 1, S), jnp.float32)
    seed = jnp.array([123], jnp.int32)
    x, l, g = step(params, x, bias, seed)  # compile
    jax.block_until_ready(l)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            x, l, g = step(params, x, bias, seed)
        _ = float(jax.device_get(l))
        best = min(best, (time.perf_counter() - t0) / ITERS * 1e3)
    print(f"{name:32s} {best:7.3f} ms/iter", flush=True)
    return best


if __name__ == "__main__":
    print(f"backend={jax.default_backend()} S={S} N={N} D={D}")
    for batch in (32, 96):
        for dropout in (0.0, DROPOUT):
            print(f"--- batch={batch} dropout={dropout}")
            bench("A reference bsnd", functools.partial(
                block_bsnd, impl="reference", dropout=dropout), batch)
            bench("B flash adapter (transposes)", functools.partial(
                block_bsnd, impl="flash", dropout=dropout), batch)
            bench("C flash head-major", functools.partial(
                block_bnsd, dropout=dropout), batch)
