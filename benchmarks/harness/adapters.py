"""Between the reference's leaves and the program's parameter trees.

The reference keeps plain matrices stacked over layers under short names
(`layers.q_w` [L, h, h]); the program keeps a flax tree with per-head
kernels (`bert/layer_3/attention/query/kernel` [h, heads, d]). A table per
model family maps one to the other: a slice and a reshape each way, which
leave every norm as it is.
"""

from __future__ import annotations

import re

_ATTENTION = [
    (r"attention/query/kernel", "q_w"), (r"attention/query/bias", "q_b"),
    (r"attention/key/kernel", "k_w"), (r"attention/key/bias", "k_b"),
    (r"attention/value/kernel", "v_w"), (r"attention/value/bias", "v_b"),
    (r"attention/out/kernel", "o_w"), (r"attention/out/bias", "o_b"),
    (r"mlp_up/kernel", "up_w"), (r"mlp_up/bias", "up_b"),
    (r"mlp_down/kernel", "down_w"), (r"mlp_down/bias", "down_b"),
]


def _layers(prefix: str, norms: dict) -> list:
    rows = [(rf"{prefix}(\d+)/{p}", rf"layers.\1.{n}") for p, n in _ATTENTION]
    for module, short in norms.items():
        rows.append((rf"{prefix}(\d+)/{module}/scale", rf"layers.\1.{short}_g"))
        rows.append((rf"{prefix}(\d+)/{module}/bias", rf"layers.\1.{short}_b"))
    return rows


TABLES = {
    "bert": [
        (r"bert/embeddings/word_embeddings/embedding", "emb_word"),
        (r"bert/embeddings/position_embeddings/embedding", "emb_pos"),
        (r"bert/embeddings/token_type_embeddings/embedding", "emb_type"),
        (r"bert/embeddings/norm/scale", "emb_ln_g"),
        (r"bert/embeddings/norm/bias", "emb_ln_b"),
        *_layers("bert/layer_", {"attention_norm": "ln1", "mlp_norm": "ln2"}),
        (r"bert/pooler/kernel", "pool_w"), (r"bert/pooler/bias", "pool_b"),
        (r"classifier/kernel", "cls_w"), (r"classifier/bias", "cls_b"),
    ],
    "gpt2": [
        (r"wte/embedding", "wte"), (r"wpe/embedding", "wpe"),
        *_layers("block_", {"ln_1": "ln1", "ln_2": "ln2"}),
        (r"ln_f/scale", "lnf_g"), (r"ln_f/bias", "lnf_b"),
    ],
}


def leaf_name(path: str, family: str) -> str:
    for pattern, name in TABLES[family]:
        m = re.fullmatch(pattern, path)
        if m:
            return m.expand(name)
    raise KeyError(f"program leaf {path!r} has no name in the {family} reference")


def flat(tree) -> dict:
    """{'a/b/c': leaf} of a nested dict of arrays."""
    from flax import traverse_util

    return {"/".join(k): v for k, v in traverse_util.flatten_dict(tree).items()}


def to_program(ref_weights: dict, template, family: str) -> dict:
    """The reference's leaves laid out as the program's parameter tree
    (`template`: the tree of shapes and dtypes to fill)."""
    from flax import traverse_util

    out = {}
    for key, leaf in traverse_util.flatten_dict(template).items():
        name = leaf_name("/".join(key), family)
        m = re.fullmatch(r"layers\.(\d+)\.(\w+)", name)
        src = (ref_weights["layers." + m.group(2)][int(m.group(1))]
               if m else ref_weights[name])
        out[key] = src.reshape(leaf.shape).astype(leaf.dtype)
    return traverse_util.unflatten_dict(out)


def install(params, spec: dict, key, family: str, std: float):
    """Replace a program's parameter tree by the benchmark's seeded
    weights, in one jitted call, on the old tree's shardings; the old
    leaves are deleted first so that both never live together."""
    import jax

    from harness import weights

    shardings = jax.tree.map(lambda x: x.sharding, params)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    make = jax.jit(
        lambda k: to_program(weights.generate(spec, k, std), template, family),
        out_shardings=shardings)
    return make(key)
