"""The `phi4flash` family (Phi-4-mini-flash-reasoning, SambaY):
`models/sambay.py`'s decoder against `reference/phi4_mini_flash.py`'s
leaves. State-space layers, window attention, one full-attention layer
whose K/V seven cross layers read, Gated Memory Units, a tied head.

The counts are of what a token MEETS, whatever implements it: every
layer's projections and MLP, the scan's update and read-out, attention
over `min(window, context)` positions in a window layer and over the whole
context in layer 17 and in each cross layer, the head. Padding and idle
slots are not work. What is CACHED is counted once (one layer's K and V a
token; rings and states a slot), what is READ once a reader: the shared
pool eight times a decode step.
"""

import math

import jax
import jax.numpy as jnp

from reference.phi4_mini_flash import layer_kinds

#: program path (models/sambay.py) -> reference leaf
TABLE = [
    (r"embed_(\d+)", r"embed.\1"),
    (r"final_norm/scale", "final_norm_g"), (r"final_norm/bias", "final_norm_b"),
    (r"layer_(\d+)/(mixer|mlp)_norm/scale", r"layers.\1.\2_norm_g"),
    (r"layer_(\d+)/(mixer|mlp)_norm/bias", r"layers.\1.\2_norm_b"),
    (r"layer_(\d+)/mlp/(gate|up|down)", r"layers.\1.mlp_\2"),
    (r"layer_(\d+)/mixer/(in_proj|conv_w|conv_b|x_proj|dt_proj|dt_bias|A_log|D"
     r"|out_proj|subln|lambda_q1|lambda_k1|lambda_q2|lambda_k2)",
     r"layers.\1.\2"),
    (r"layer_(\d+)/mixer/(q|k|v|o)", r"layers.\1.\2_w"),
    (r"layer_(\d+)/mixer/(q|k|v|o)_bias", r"layers.\1.\2_b"),
]

#: the family's own spreads (`assumed.weights` of the configuration): the
#: convolution's taps as Mamba draws them (1 / sqrt(taps)); `x_proj` N(0,
#: 0.05) whatever the matrices' spread, so that the carried state gives
#: about half of the scan's output beside the skip path and a state that
#: was not reset shows. Measured on one layer at the published widths
#: (float32, 600 tokens; PERF.md, PR 34): at 0.02 the state gives 2% of
#: |y| (0.388 against 0.379 from the skip path alone), at 0.05 |y| 0.640,
#: at 0.1 |y| 2.73 and the mixer's output 3.76, four times the MLP's: the
#: scan's output is of degree four in its input (step, c, B, C), so a
#: layer that dominates the stream doubles every relative error handed to
#: it, and 32 such layers in bfloat16 read a widest gap of 2.8 (first chip
#: run) where int8 saturates at 4.5: no limit could part them. The step's
#: bias so
#: that the step lies in 0.001 .. 0.1 (memories of 1 to 1,000 tokens);
#: `A_log` the logarithms of 1 .. d_state; the four lambda vectors N(0, 0.1)
X_PROJ_STD = 0.05
LAMBDA_STD = 0.1
STEP_RANGE = (1e-3, 1e-1)


def init(kind: str, key, shape, std: float):
    if kind == "conv":
        return shape[0] ** -0.5 * jax.random.normal(key, shape, jnp.float32)
    if kind == "x_proj":
        return X_PROJ_STD * jax.random.normal(key, shape, jnp.float32)
    if kind == "lambda":
        return LAMBDA_STD * jax.random.normal(key, shape, jnp.float32)
    if kind == "dt_bias":
        lo, hi = (math.log(v) for v in STEP_RANGE)
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return step + jnp.log(-jnp.expm1(-step))       # softplus's inverse
    if kind == "a_log":
        states = jnp.arange(1, shape[1] + 1, dtype=jnp.float32)
        return jnp.log(states)[None, :] + std * jax.random.normal(
            key, shape, jnp.float32)
    raise ValueError(f"unknown weight kind {kind!r}")


# ------------------------------------------------------------------ counts



def _sizes(m: dict):
    h = m["hidden_size"]
    return (h, m["num_attention_heads"], m["num_key_value_heads"],
            h // m["num_attention_heads"], m["mamba_expand"] * h)


def mixer_params(m: dict, kind: str) -> int:
    """Weights of a layer's mixer that a token is multiplied with."""
    h, heads, kvh, d, di = _sizes(m)
    if kind == "mamba":
        return (h * 2 * di + m["mamba_d_conv"] * di
                + di * (m["mamba_dt_rank"] + 2 * m["mamba_d_state"])
                + m["mamba_dt_rank"] * di + di * h)
    if kind == "gmu":
        return 2 * h * di
    own_kv = 0 if kind == "cross" else 2 * h * kvh * d
    return 2 * h * heads * d + own_kv


def layer_flops(m: dict, kind: str, context: float) -> float:
    """One token's pass through one layer of `kind` with `context`
    positions before and including it."""
    h, heads, _, d, di = _sizes(m)
    total = 2.0 * (mixer_params(m, kind) + 3 * h * m["intermediate_size"])
    if kind == "mamba":
        # the update (decay, input) and the read-out over the state
        total += 6 * di * m["mamba_d_state"]
    elif kind != "gmu":
        seen = min(m["sliding_window"], context) if kind == "window" else context
        # a query head scores d values a key and weighs 2d a value
        total += 2 * heads * seen * 3 * d
    return total


def token_flops(config: dict, context: float) -> float:
    """One token's pass through every layer; no head."""
    m = config["model"]
    return sum(layer_flops(m, kind, context) for kind in layer_kinds(m))


def _head_flops(m: dict) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"]


def prefill_flops(config: dict, prompt: int, observed=None) -> float:
    """Layers 0 .. half + 1 over every prompt token (token i sees i + 1
    positions); the upper half, which keeps nothing, and the head for the
    last token alone (the only one whose logits are used)."""
    m = config["model"]
    kinds = layer_kinds(m)
    lower = [k for k in kinds if k not in ("gmu", "cross")]
    upper = [k for k in kinds if k in ("gmu", "cross")]
    return (prompt * sum(layer_flops(m, k, (prompt + 1) / 2) for k in lower)
            + sum(layer_flops(m, k, prompt) for k in upper) + _head_flops(m))


def decode_flops(config: dict, context: int, observed=None) -> float:
    """One decoded token over `context` cached positions, with the head."""
    return token_flops(config, context) + _head_flops(config["model"])


def cache_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """One token's resident PAGE bytes: layer 17's K and V, kept once
    however many layers read them. phi4_mini_flash in bf16: 5,120."""
    m = config["model"]
    _, _, kvh, d, _ = _sizes(m)
    return 2 * kvh * d * bytes_per_value


def slot_bytes(config: dict, bytes_per_value: int = 2) -> dict:
    """What a slot keeps whatever its context: `ring` (a window of K and V
    rows in each window layer) and `state` (the scan's float32 state and
    the convolution's last inputs in each state-space layer)."""
    m = config["model"]
    _, _, kvh, d, di = _sizes(m)
    kinds = layer_kinds(m)
    return {
        "ring": kinds.count("window") * 2 * m["sliding_window"] * kvh * d
        * bytes_per_value,
        "state": kinds.count("mamba") * di * (
            4 * m["mamba_d_state"] + (m["mamba_d_conv"] - 1) * bytes_per_value),
    }


def cache_read_bytes(config: dict, contexts, bytes_per_value: int = 2) -> float:
    """Bytes one decode tick's mixers have to read of cached state: every
    live sequence's K and V over its whole context ONCE A READER (layer 17
    and each cross layer), `min(window, context)` LIVE ring rows in each
    window layer, and each state-space layer's state (read and written)."""
    m = config["model"]
    _, _, kvh, d, _ = _sizes(m)
    kinds = layer_kinds(m)
    row = 2 * kvh * d * bytes_per_value
    readers = 1 + kinds.count("cross")
    state = slot_bytes(config, bytes_per_value)["state"]
    return float(sum(
        readers * row * c
        + kinds.count("window") * row * min(m["sliding_window"], c)
        + 2 * state
        for c in contexts))


def page_walk_bytes(lanes: int, live_tokens: int, bytes_per_value: int = 2) -> int:
    """HBM bytes the `paged_attn_rows` kernel has to read for `live_tokens`
    LIVE rows (a pool's or a ring's): K and V, `lanes` values each."""
    return 2 * lanes * bytes_per_value * live_tokens


def page_walk_read_bytes(config: dict, contexts, bytes_per_value: int = 2) -> int:
    """What the kernel's calls had to read for the tokens decoded at
    `contexts`: each reads its whole context once a reader of the shared
    pool and `min(window, context)` ring rows once a window layer. What
    `kernel.paged_attn_rows_roofline` divides by 819 GB/s and the calls'
    device time."""
    m = config["model"]
    _, _, kvh, d, _ = _sizes(m)
    kinds = layer_kinds(m)
    readers, windows = 1 + kinds.count("cross"), kinds.count("window")
    rows = sum(readers * c + windows * min(m["sliding_window"], c)
               for c in contexts)
    return page_walk_bytes(kvh * d, rows, bytes_per_value)
