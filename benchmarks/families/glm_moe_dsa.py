"""The `glm_moe_dsa` family (GLM-5.2): `models/latent_moe.py`'s decoder
against `reference/glm52_share16.py`'s leaves. Latent attention (MLA) with
a learned sparse indexer in the layers typed `full`, routed experts of
which the chip holds a share, one shared expert, an untied head.

The counts are of what a token MEETS on this chip, whatever implements it:
the attention projections, `min(index_topk, context)` selected latents,
the indexer over the whole context in the `full` layers only, the shared
expert, and the routed experts this chip holds at their expected share of
a token's `num_experts_per_tok` (held / all routed). Padding, gathers of
unselected rows and the dense pass over unchosen held experts are not
work. `config` is the whole configuration file: the share is in its
`model` group (`experts_held`, `router_experts`, `vocab_size`).
"""

import jax
import jax.numpy as jnp

#: program path (models/latent_moe.py) -> reference leaf
TABLE = [
    (r"embed", "embed"), (r"head", "head"), (r"final_norm/scale", "final_norm"),
    (r"layer_(\d+)/(attention|mlp)_norm/scale", r"layers.\1.\2_norm"),
    (r"layer_(\d+)/attention/(q_a|q_b|kv_a_latent|kv_a_rope|kv_b_k|kv_b_v|o"
     r"|index_q|index_k|index_w)", r"layers.\1.\2"),
    (r"layer_(\d+)/attention/(q_a|kv_a)_norm/scale", r"layers.\1.\2_norm"),
    (r"layer_(\d+)/attention/index_k_norm/scale", r"layers.\1.index_k_norm_g"),
    (r"layer_(\d+)/attention/index_k_norm/bias", r"layers.\1.index_k_norm_b"),
    (r"layer_(\d+)/mlp/(gate|up|down)", r"layers.\1.mlp_\2"),
    (r"layer_(\d+)/experts/(router|router_bias)", r"layers.\1.\2"),
    (r"layer_(\d+)/experts/shared/(gate|up|down)", r"layers.\1.shared_\2"),
    (r"layer_(\d+)/experts/experts_(\d+)_(gate|up|down)",
     r"layers.\1.experts_\3.\2"),
]

#: every matrix is N(0, 0.02). The issue that brought the family expected
#: attention over 16k positions to come out so nearly uniform then that any
#: 2,048 positions would give the same output, and asked for wider query
#: and key paths. Measured on the chip at the published widths (PERF.md,
#: PR 29; 6,176 tokens, the reference's operands rounded as named,
#: selection fault = the latest 2,048 positions): as it is a head's score
#: logits spread by 0.8 and the widest gaps are bfloat16 0.80, int8 3.56,
#: selection fault 8.72; with those paths 2.0-2.2 x wider (logits spread
#: 3-4) bfloat16 1.52, int8 3.35, selection fault 9.80. The wrong selection
#: is loud as it is, and widening only doubled what sound bfloat16 reads.

#: the router's correction bias: wide enough against sigmoid scores
#: (spread about 0.25) to change a third of the choices
ROUTER_BIAS_STD = 0.1


def init(kind: str, key, shape, std: float):
    if kind == "router_bias":
        return ROUTER_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(f"unknown weight kind {kind!r}")


# ------------------------------------------------------------------ counts


def _attention_params(m: dict, full: bool) -> int:
    h, heads = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    qr, rank = m["q_lora_rank"], m["kv_lora_rank"]
    n = (h * qr + qr * heads * (dn + dr) + h * (rank + dr)
         + rank * heads * (dn + dv) + heads * dv * h)
    if full:
        n += (qr * m["index_n_heads"] * m["index_head_dim"]
              + h * m["index_head_dim"] + h * m["index_n_heads"])
    return n


def _mlp_params(m: dict, sparse: bool) -> float:
    """Weights of a layer's MLP that a token is multiplied with here."""
    h = m["hidden_size"]
    if not sparse:
        return 3 * h * m["intermediate_size"]
    expert = 3 * h * m["moe_intermediate_size"]
    routed_here = (m["num_experts_per_tok"] * m["experts_held"][1]
                   / m["router_experts"])
    return (h * m["router_experts"] + m["n_shared_experts"] * expert
            + routed_here * expert)


def token_flops(config: dict, context: float) -> float:
    """One token's pass through every layer with `context` positions
    before and including it; no head."""
    m = config["model"]
    heads = m["num_attention_heads"]
    width = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    selected = min(m["index_topk"], context)
    total = 0.0
    for mlp, idx in zip(m["mlp_layer_types"], m["indexer_types"]):
        total += 2 * (_attention_params(m, idx == "full")
                      + _mlp_params(m, mlp == "sparse"))
        # scores over the latent and rotary key, values over the latent
        total += 2 * heads * selected * (width + m["kv_lora_rank"])
        if idx == "full":
            total += 2 * m["index_n_heads"] * m["index_head_dim"] * context
    return total


def _head_flops(m: dict) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"]


def prefill_flops(config: dict, prompt: int, observed=None) -> float:
    """The prompt tokens REALLY prefilled, the head on the last one: what
    the prefix cache served (`cached_prompt_tokens`) is not work."""
    cached = cached_prompt_tokens(config, prompt, observed)
    tail = prompt - cached
    # token i of the prefilled tail sees cached + i + 1 positions
    mean_context = cached + (tail + 1) / 2
    return tail * token_flops(config, mean_context) + _head_flops(config["model"])


def cached_prompt_tokens(config: dict, prompt: int, observed=None) -> int:
    """Prompt tokens the prefix cache served of a request: the whole pages
    of its shared prefix (`observed["request"].prefix_len`, the mix's),
    short of the last prompt token, which is always prefilled; nought
    where the run's engine reports no prefix hit at all
    (`observed["engine"]`, its stats). The first request of each context
    is prefilled whole and is counted as a hit here all the same: in a
    cell those fall in the ramp, and `serve.prefix_hit_share` reads what
    the window's requests were really served."""
    if not observed:
        return 0
    request, engine = observed.get("request"), observed.get("engine") or {}
    if not (engine.get("prefix_cache") or {}).get("prefix_hits"):
        return 0
    page = config["serving"]["page_size"]
    return min(prompt - 1, getattr(request, "prefix_len", 0)) // page * page


def decode_flops(config: dict, context: int, observed=None) -> float:
    """One decoded token over `context` cached positions, with the head."""
    return token_flops(config, context) + _head_flops(config["model"])


def cache_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """One token's resident state over all layers: a latent row (latent
    plus rotary key) a layer, an indexer key in the `full` layers. What
    the mathematics keeps; the program's row is padded to lane tiles
    (`serving.latent_row_padded`). glm52_share16 in bf16: 7,424."""
    m = config["model"]
    full = sum(1 for t in m["indexer_types"] if t == "full")
    values = (len(m["indexer_types"]) * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
              + full * m["index_head_dim"])
    return values * bytes_per_value


def cache_read_bytes(config: dict, contexts, bytes_per_value: int = 2) -> float:
    """Bytes one decode tick's attention has to read: every live
    sequence's indexer keys over its whole context in the `full` layers,
    and `min(index_topk, context)` latent rows in every layer."""
    m = config["model"]
    layers = len(m["indexer_types"])
    full = sum(1 for t in m["indexer_types"] if t == "full")
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return float(sum(
        full * m["index_head_dim"] * c + layers * row * min(m["index_topk"], c)
        for c in contexts)) * bytes_per_value
