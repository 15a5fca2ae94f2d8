"""Microbench: Pallas fused LN vs XLA nn.LayerNorm on the real chip.

Times fwd and fwd+bwd over the bert-large shape ([32*128, 1024]) with
chained iterations + device_get (NOTES.md timing rules).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.ops.layer_norm import (
    layer_norm,
    reference_layer_norm,
)

R, H, ITERS = 32 * 128, 1024, 50


def timed(fn, *args):
    x = fn(*args)
    jax.block_until_ready(x)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        c = args[0]
        for _ in range(ITERS):
            c = fn(c, *args[1:])  # chain
        float(jax.device_get(jnp.sum(c.astype(jnp.float32))))
        best = min(best, (time.perf_counter() - t0) / ITERS)
    return best * 1e3


def main():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(R, H)), jnp.bfloat16)
    scale = jnp.ones((H,), jnp.float32)
    bias = jnp.zeros((H,), jnp.float32)

    fused_fwd = jax.jit(
        lambda x, s, b: layer_norm(x, s, b, eps=1e-12, out_dtype=jnp.bfloat16)
    )
    ref_fwd = jax.jit(
        lambda x, s, b: reference_layer_norm(
            x, s, b, eps=1e-12, out_dtype=jnp.bfloat16
        )
    )
    print(f"fwd   fused {timed(fused_fwd, x, scale, bias):7.3f} ms   "
          f"ref {timed(ref_fwd, x, scale, bias):7.3f} ms")

    def g(fn):
        def loss(x, s, b):
            return jnp.sum(fn(x, s, b).astype(jnp.float32) ** 2)

        grad = jax.grad(loss)
        return jax.jit(lambda x, s, b: grad(x, s, b).astype(jnp.bfloat16))

    fused_g = g(lambda x, s, b: layer_norm(x, s, b, eps=1e-12,
                                           out_dtype=jnp.bfloat16))
    ref_g = g(lambda x, s, b: reference_layer_norm(x, s, b, eps=1e-12,
                                                   out_dtype=jnp.bfloat16))
    print(f"f+bwd fused {timed(fused_g, x, scale, bias):7.3f} ms   "
          f"ref {timed(ref_g, x, scale, bias):7.3f} ms")


if __name__ == "__main__":
    main()
