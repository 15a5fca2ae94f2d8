"""The `dots3_note` family (dots3-note-prev): `models/latent_moe.py`'s
decoder with window layers, against `reference/dots3_share8.py`'s leaves.
Two kinds of attention layer, each latent attention (MLA) of its own sizes:
full layers, each choosing its `index_topk` positions with its own learned
indexer, and window layers (the `swa_*` sizes, a latent of their own width)
over the latest `sliding_window_size` positions; a headwise gate on every
layer's attention output; routed experts of which the chip holds a share,
one shared expert, an untied head.

The counts are of what a token MEETS on this chip, whatever implements it:
the attention projections and gates, `min(index_topk, context)` selected
latents and the indexer over the whole context in a full layer,
`min(window, context)` latents in a window layer, the shared expert, and
the routed experts this chip holds at their expected share of a token's
`num_experts_per_tok` (held / all routed). Padding, gathers of unselected
rows and the dense pass over unchosen held experts are not work. `config`
is the whole configuration file: the share is in its `model` group.
"""

import jax
import jax.numpy as jnp

from families import glm_moe_dsa

#: program path (models/latent_moe.py) -> reference leaf
TABLE = [
    (r"embed", "embed"), (r"head", "head"), (r"final_norm/scale", "final_norm"),
    (r"layer_(\d+)/(attention|mlp)_norm/scale", r"layers.\1.\2_norm"),
    (r"layer_(\d+)/attention/(q_a|q_b|kv_a_latent|kv_a_rope|kv_b_k|kv_b_v|o"
     r"|gate|index_q|index_k|index_w)", r"layers.\1.\2"),
    (r"layer_(\d+)/attention/(q_a|kv_a)_norm/scale", r"layers.\1.\2_norm"),
    (r"layer_(\d+)/attention/index_k_norm/scale", r"layers.\1.index_k_norm_g"),
    (r"layer_(\d+)/attention/index_k_norm/bias", r"layers.\1.index_k_norm_b"),
    (r"layer_(\d+)/mlp/(gate|up|down)", r"layers.\1.mlp_\2"),
    (r"layer_(\d+)/experts/(router|router_bias)", r"layers.\1.\2"),
    (r"layer_(\d+)/experts/shared/(gate|up|down)", r"layers.\1.shared_\2"),
    (r"layer_(\d+)/experts/experts_(\d+)_(gate|up|down)",
     r"layers.\1.experts_\3.\2"),
]

#: the family's own spread (`assumed.spreads` of the configuration): a
#: window layer's query and key paths (`q_b`, `kv_a_rope`, `kv_b_k`) are
#: drawn `WINDOW_QK_GAIN` times the configuration's spread, so that its
#: attention is peaked and the window is seen in the logits. At the
#: published window widths and N(0, 0.02) a score spreads by 0.58 over the
#: window and ~370 of its 513 positions share the weight: the window and
#: the whole context then average nearly the same latents, and on one v5e
#: chip showing every window layer the whole context read only twice what
#: bfloat16 reads (PERF.md, section 2). At 2.5 times a score spreads by ~3.6
#: and ~7 positions share the weight (float32, one layer, 4,096 positions).
WINDOW_QK_GAIN = 2.5


def init(kind: str, key, shape, std: float):
    if kind == "window_qk":
        return WINDOW_QK_GAIN * std * jax.random.normal(key, shape, jnp.float32)
    # the router's correction bias, as the GLM family draws it
    return glm_moe_dsa.init(kind, key, shape, std)

#: what the prefix cache served of a request is not work
cached_prompt_tokens = glm_moe_dsa.cached_prompt_tokens


def _sizes(m: dict, window: bool) -> dict:
    p = "swa_" if window else ""
    return {k: m[p + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}


def _layers(m: dict) -> list:
    """(sparse MLP, window attention) of every layer."""
    return [(mlp == "sparse", kind == "sliding_attention")
            for mlp, kind in zip(m["mlp_layer_types"], m["layer_types"])]


def _row(m: dict, window: bool) -> int:
    """Values of one cached latent row: latent plus rotary key."""
    s = _sizes(m, window)
    return s["kv_lora_rank"] + s["qk_rope_head_dim"]


def _attention_params(m: dict, window: bool) -> int:
    s = _sizes(m, window)
    h, heads = m["hidden_size"], s["num_attention_heads"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    qr, rank = s["q_lora_rank"], s["kv_lora_rank"]
    n = (h * qr + qr * heads * (dn + dr) + h * (rank + dr)
         + rank * heads * (dn + dv) + heads * dv * h)
    if m[("swa_" if window else "") + "attention_gate_type"] == "headwise":
        n += h * heads
    if not window:
        n += (qr * m["index_n_heads"] * m["index_head_dim"]
              + h * m["index_head_dim"] + h * m["index_n_heads"])
    return n


def token_flops(config: dict, context: float) -> float:
    """One token's pass through every layer with `context` positions
    before and including it; no head."""
    m = config["model"]
    total = 0.0
    for sparse, window in _layers(m):
        s = _sizes(m, window)
        total += 2 * (_attention_params(m, window)
                      + glm_moe_dsa._mlp_params(m, sparse))
        seen = min(m["sliding_window_size"] if window else m["index_topk"],
                   context)
        # scores over the latent and rotary key, values over the latent
        total += 2 * s["num_attention_heads"] * seen * (
            _row(m, window) + s["kv_lora_rank"])
        if not window:
            total += 2 * m["index_n_heads"] * m["index_head_dim"] * context
    return total


def prefill_flops(config: dict, prompt: int, observed=None) -> float:
    """The prompt tokens REALLY prefilled, the head on the last one."""
    cached = cached_prompt_tokens(config, prompt, observed)
    tail = prompt - cached
    # token i of the prefilled tail sees cached + i + 1 positions
    mean_context = cached + (tail + 1) / 2
    return (tail * token_flops(config, mean_context)
            + glm_moe_dsa._head_flops(config["model"]))


def decode_flops(config: dict, context: int, observed=None) -> float:
    """One decoded token over `context` cached positions, with the head."""
    return token_flops(config, context) + glm_moe_dsa._head_flops(config["model"])


def cache_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """One token's resident state over all layers: a latent row (latent
    plus rotary key) of its kind a layer, an indexer key in the full
    layers. What the mathematics keeps; the program's rows are padded to
    lane tiles (`serving.latent_row_padded`, `serving.window_row_padded`).
    dots3_share8 in bf16: 2 x 576 + 3 x 1,088 + 2 x 128 values = 9,344 B."""
    m = config["model"]
    values = sum(_row(m, window) + (0 if window else m["index_head_dim"])
                 for _, window in _layers(m))
    return values * bytes_per_value


def cache_read_bytes(config: dict, contexts, bytes_per_value: int = 2) -> float:
    """Bytes one decode tick's attention has to read: every live
    sequence's indexer keys over its whole context and `min(index_topk,
    context)` latent rows in the full layers, `min(window, context)` rows
    in the window layers."""
    m = config["model"]
    total = 0
    for c in contexts:
        for _, window in _layers(m):
            if window:
                total += _row(m, True) * min(m["sliding_window_size"], c)
            else:
                total += (m["index_head_dim"] * c
                          + _row(m, False) * min(m["index_topk"], c))
    return float(total) * bytes_per_value
