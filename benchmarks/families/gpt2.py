"""The GPT-2 family: `models/gpt2.py`'s decoder against
`reference/gpt2_medium_paged.py`'s leaves. Dense blocks, causal attention
and K and V cached in every layer, the tied vocabulary head."""

from harness import adapters, flops

TABLE = [
    (r"wte/embedding", "wte"), (r"wpe/embedding", "wpe"),
    *adapters.block_rows("block_", {"ln_1": "ln1", "ln_2": "ln2"}),
    (r"ln_f/scale", "lnf_g"), (r"ln_f/bias", "lnf_b"),
]


def prefill_flops(config: dict, prompt: int, observed=None) -> float:
    """Causal prefill of `prompt` tokens, the head on the last one."""
    return flops.dense_prefill_flops(config["model"], prompt)


def decode_flops(config: dict, context: int, observed=None) -> float:
    """One decoded token attending over `context` cached keys."""
    return flops.dense_decode_flops(config["model"], context)


def cache_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """K and V of one token over all layers. gpt2-medium in bf16: 98,304."""
    return flops.dense_kv_bytes_per_token(config["model"], bytes_per_value)


def cache_read_bytes(config: dict, contexts, bytes_per_value: int = 2) -> float:
    """Bytes one decode tick's attention has to read: K and V of every
    live token of every live sequence, once (all layers)."""
    return float(sum(contexts)) * cache_bytes_per_token(config, bytes_per_value)
