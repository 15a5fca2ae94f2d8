"""Operations and bytes that more than one family needs: the dense
transformer block's counts (`dense_*`), which a family's file
(`benchmarks/families/<adapter>.py`) calls where its model is built of
that block, and the fused add-LayerNorm kernel's bytes. The harness never
calls a `dense_*` count itself: which count a configuration takes is its
family's to say (`harness/family.py`). They count what the mathematics
requires, whatever implements it (recomputation, padding and gathers of
empty pages are not work).

`model` is a configuration file's `model` group (the published
`config.json` keys) of a model made of dense blocks with full attention
and K and V cached in every layer.
"""

from __future__ import annotations


def _sizes(model: dict):
    h = model.get("hidden_size", model.get("n_embd"))
    layers = model.get("num_hidden_layers", model.get("n_layer"))
    f = model.get("intermediate_size") or model.get("n_inner") or 4 * h
    return h, layers, f


def dense_layer_matmul_params(model: dict) -> int:
    """Weights of one block that every token is multiplied with."""
    h, _, f = _sizes(model)
    return 4 * h * h + 2 * h * f


def dense_forward_flops_per_token(model: dict, context: float,
                                  head_vocab: int = 0) -> float:
    """One token's forward pass attending over `context` keys: two
    operations per weight, QK^T and PV per layer, and an output head of
    `head_vocab` columns (0: none, or negligible as BERT's pooler)."""
    h, layers, _ = _sizes(model)
    dense = 2 * layers * dense_layer_matmul_params(model)
    attn = layers * 4 * context * h
    return dense + attn + 2 * h * head_vocab


def dense_train_flops_per_sample(model: dict, seq: int) -> float:
    """Forward and backward of one sequence of `seq` tokens with full
    attention: the backward costs twice the forward; recomputation is not
    counted. bert-large at 128 tokens: 2.37e11."""
    h = _sizes(model)[0]
    per_token = dense_forward_flops_per_token(model, context=seq)
    return 3 * (seq * per_token + 2 * h * h)  # + the pooler on one token


def dense_prefill_flops(model: dict, prompt: int) -> float:
    """Causal prefill of `prompt` tokens: token i attends over i+1 keys;
    the vocabulary head runs on the last token only."""
    h, layers, _ = _sizes(model)
    dense = 2 * layers * dense_layer_matmul_params(model) * prompt
    attn = layers * 4 * h * prompt * (prompt + 1) / 2
    return dense + attn + 2 * h * model["vocab_size"]


def dense_decode_flops(model: dict, context: int) -> float:
    """One decoded token attending over `context` cached keys."""
    return dense_forward_flops_per_token(model, context, model["vocab_size"])


def dense_kv_bytes_per_token(model: dict, bytes_per_value: int = 2) -> int:
    """K and V of one token over all layers. gpt2-medium in bf16:
    24 x 2 x 1024 x 2 = 98,304."""
    h, layers, _ = _sizes(model)
    return layers * 2 * h * bytes_per_value


def dal_bytes(rows: int, hidden: int, act_bytes: int = 2) -> float:
    """Fused dropout-add-LayerNorm, forward call on [rows, hidden]: reads
    the branch and the residual, writes the normalised output and the
    pre-norm sum the backward needs."""
    return 4.0 * rows * hidden * act_bytes
