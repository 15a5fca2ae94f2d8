"""Operations and bytes the algorithms need, from the configuration's
widths alone. These are the numerators of every share of a peak or of a
roofline the benchmark reports; they count what the mathematics requires,
whatever implements it (recomputation, padding and gathers of empty pages
are not work).

`model` is a configuration file's `model` group (the published
`config.json` keys).
"""

from __future__ import annotations


def _sizes(model: dict):
    h = model.get("hidden_size", model.get("n_embd"))
    layers = model.get("num_hidden_layers", model.get("n_layer"))
    f = model.get("intermediate_size") or model.get("n_inner") or 4 * h
    return h, layers, f


def layer_matmul_params(model: dict) -> int:
    """Weights of one block that every token is multiplied with."""
    h, _, f = _sizes(model)
    return 4 * h * h + 2 * h * f


def forward_flops_per_token(model: dict, context: float,
                            head_vocab: int = 0) -> float:
    """One token's forward pass attending over `context` keys: two
    operations per weight, QK^T and PV per layer, and an output head of
    `head_vocab` columns (0: none, or negligible as BERT's pooler)."""
    h, layers, _ = _sizes(model)
    dense = 2 * layers * layer_matmul_params(model)
    attn = layers * 4 * context * h
    return dense + attn + 2 * h * head_vocab


def train_flops_per_sample(model: dict, seq: int) -> float:
    """Forward and backward of one sequence of `seq` tokens with full
    attention: the backward costs twice the forward; recomputation is not
    counted. bert-large at 128 tokens: 2.37e11."""
    h = _sizes(model)[0]
    per_token = forward_flops_per_token(model, context=seq)
    return 3 * (seq * per_token + 2 * h * h)  # + the pooler on one token


def prefill_flops(model: dict, prompt: int) -> float:
    """Causal prefill of `prompt` tokens: token i attends over i+1 keys;
    the vocabulary head runs on the last token only."""
    h, layers, _ = _sizes(model)
    dense = 2 * layers * layer_matmul_params(model) * prompt
    attn = layers * 4 * h * prompt * (prompt + 1) / 2
    return dense + attn + 2 * h * model["vocab_size"]


def decode_flops(model: dict, context: int) -> float:
    """One decoded token attending over `context` cached keys."""
    return forward_flops_per_token(model, context, model["vocab_size"])


def kv_bytes_per_token(model: dict, bytes_per_value: int = 2) -> int:
    """K and V of one token over all layers. gpt2-medium in bf16:
    24 x 2 x 1024 x 2 = 98,304."""
    h, layers, _ = _sizes(model)
    return layers * 2 * h * bytes_per_value


def decode_attention_bytes(model: dict, contexts, bytes_per_value: int = 2) -> float:
    """Bytes one decode tick's attention has to read: K and V of every
    live token of every live sequence, once (all layers)."""
    return float(sum(contexts)) * kv_bytes_per_token(model, bytes_per_value)


def dal_bytes(rows: int, hidden: int, act_bytes: int = 2) -> float:
    """Fused dropout-add-LayerNorm, forward call on [rows, hidden]: reads
    the branch and the residual, writes the normalised output and the
    pre-norm sum the backward needs."""
    return 4.0 * rows * hidden * act_bytes
