"""The BERT family: `models/bert.py`'s sequence classifier against
`reference/bert_large_dp.py`'s leaves. Dense blocks, full attention in
every layer, a pooler on one token."""

from harness import adapters, flops

TABLE = [
    (r"bert/embeddings/word_embeddings/embedding", "emb_word"),
    (r"bert/embeddings/position_embeddings/embedding", "emb_pos"),
    (r"bert/embeddings/token_type_embeddings/embedding", "emb_type"),
    (r"bert/embeddings/norm/scale", "emb_ln_g"),
    (r"bert/embeddings/norm/bias", "emb_ln_b"),
    *adapters.block_rows(
        "bert/layer_", {"attention_norm": "ln1", "mlp_norm": "ln2"}),
    (r"bert/pooler/kernel", "pool_w"), (r"bert/pooler/bias", "pool_b"),
    (r"classifier/kernel", "cls_w"), (r"classifier/bias", "cls_b"),
]


def train_flops_per_sample(config: dict, seq: int) -> float:
    """Forward and backward of one sequence of `seq` tokens with full
    attention in every layer. bert-large at 128 tokens: 2.37e11."""
    return flops.dense_train_flops_per_sample(config["model"], seq)
