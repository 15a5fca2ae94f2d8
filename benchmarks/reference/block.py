"""The plain transformer block both configurations share.

Learned positions, LayerNorm, tanh-GELU MLP, full multi-head attention:
BERT wraps it post-LN (`post_ln_layer`), GPT-2 pre-LN and causal
(`pre_ln_layer`). Straightforward `jax.numpy` in float32 with matmuls at
`highest` precision: no kernels, no cache, no batching tricks, nothing
imported from the program. `matmul` is the one place a lower precision can
be switched in: the controls of `correct` compute the same mathematics with
int8 operands there (the step below the bfloat16 the configurations state).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = float(jnp.finfo(jnp.float32).min)


def _fake_int8(x):
    """Symmetric per-tensor int8 rounding of `x`, kept in float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 127.0
    return jnp.round(x / scale).clip(-127, 127) * scale


def matmul(x, w, precision: str = "float32"):
    """x [..., k] @ w [k, n] in float32/highest, or with both operands
    rounded to int8 first (`precision="int8"`), or to bfloat16."""
    if precision == "int8":
        x, w = _fake_int8(x), _fake_int8(w)
    elif precision == "bfloat16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def attention(x, p, heads: int, bias, precision: str = "float32",
              return_kv: bool = False):
    """Full multi-head attention over x [b, s, h]; `bias` is additive,
    broadcastable to [b, heads, s, s]."""
    b, s, h = x.shape
    d = h // heads

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q = split(matmul(x, p["q_w"], precision) + p["q_b"])
    k = split(matmul(x, p["k_w"], precision) + p["k_b"])
    v = split(matmul(x, p["v_w"], precision) + p["v_b"])
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k, precision=HIGHEST) / (d**0.5)
    if bias is not None:
        scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bnkd->bnqd", probs, v, precision=HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    return matmul(ctx, p["o_w"], precision) + p["o_b"]


def mlp(x, p, precision: str = "float32"):
    up = gelu_tanh(matmul(x, p["up_w"], precision) + p["up_b"])
    return matmul(up, p["down_w"], precision) + p["down_b"]


def post_ln_layer(x, p, heads, bias, eps, precision="float32"):
    """BERT: x = LN(x + attn(x)); x = LN(x + mlp(x))."""
    x = layer_norm(x + attention(x, p, heads, bias, precision),
                   p["ln1_g"], p["ln1_b"], eps)
    return layer_norm(x + mlp(x, p, precision), p["ln2_g"], p["ln2_b"], eps)


def pre_ln_layer(x, p, heads, bias, eps, precision="float32"):
    """GPT-2: x = x + attn(LN(x)); x = x + mlp(LN(x))."""
    x = x + attention(layer_norm(x, p["ln1_g"], p["ln1_b"], eps), p, heads,
                      bias, precision)
    return x + mlp(layer_norm(x, p["ln2_g"], p["ln2_b"], eps), p, precision)


def padding_bias(attention_mask):
    """[b, s] 1/0 mask -> additive [b, 1, 1, s]."""
    return jnp.where(attention_mask[:, None, None, :] > 0, 0.0, NEG)


def causal_bias(s: int):
    i = jnp.arange(s)
    return jnp.where(i[None, :] <= i[:, None], 0.0, NEG)[None, None]


#: per-layer leaves of the block, name -> (shape from (h, f), kind)
def layer_spec(h: int, f: int) -> dict:
    return {
        "q_w": ((h, h), "normal"), "q_b": ((h,), "normal"),
        "k_w": ((h, h), "normal"), "k_b": ((h,), "normal"),
        "v_w": ((h, h), "normal"), "v_b": ((h,), "normal"),
        "o_w": ((h, h), "normal"), "o_b": ((h,), "normal"),
        "ln1_g": ((h,), "scale"), "ln1_b": ((h,), "normal"),
        "up_w": ((h, f), "normal"), "up_b": ((f,), "normal"),
        "down_w": ((f, h), "normal"), "down_b": ((h,), "normal"),
        "ln2_g": ((h,), "scale"), "ln2_b": ((h,), "normal"),
    }
