"""Plain reference of `gpt2_medium_paged`: GPT-2's forward pass over a
whole sequence at once, in float32 `jax.numpy`: no cache, no pages, no
buckets, no sampling. What the server produced through bucketed prefill,
the paged KV cache, the decode program and device sampling is judged
against it token by token: how far the served (greedy) token's logit lies
below the reference's best at that position.

Sizes come from the configuration file's `model` group (the published
`config.json` keys), so the same file serves the tests' tiny
configuration.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import block

PAD_TO = 128  # sequences and their served tails are padded to a multiple:
# few compiled shapes, the same from run to run, so the compile cache holds


def weight_spec(model: dict) -> dict:
    h = model["n_embd"]
    f = model.get("n_inner") or 4 * h
    spec = {
        "wte": ((model["vocab_size"], h), "normal"),
        "wpe": ((model["n_positions"], h), "normal"),
    }
    for name, (shape, kind) in block.layer_spec(h, f).items():
        spec[f"layers.{name}"] = ((model["n_layer"], *shape), kind)
    spec.update({"lnf_g": ((h,), "scale"), "lnf_b": ((h,), "normal")})
    return spec


def logits(weights: dict, model: dict, ids, precision: str = "float32"):
    """ids [s] -> logits [s, vocab]; position i sees tokens 0..i."""
    s = ids.shape[0]
    eps = model["layer_norm_epsilon"]
    x = (weights["wte"][ids] + weights["wpe"][jnp.arange(s)])[None]
    bias = block.causal_bias(s)
    layers = {k.split(".", 1)[1]: v for k, v in weights.items()
              if k.startswith("layers.")}

    def body(x, p):
        return block.pre_ln_layer(
            x, p, model["n_head"], bias, eps, precision), None

    x, _ = jax.lax.scan(body, x, layers)
    x = block.layer_norm(x[0], weights["lnf_g"], weights["lnf_b"], eps)
    return block.matmul(x, weights["wte"].T, precision)


@functools.partial(jax.jit, static_argnames=("model_items", "control"))
def _gaps(weights, ids, positions, tokens, model_items, control):
    model = dict(model_items)
    ref = logits(weights, model, ids)
    best = ref.max(axis=-1)
    served = best[positions] - ref[positions, tokens]
    if control is None:
        return served, served
    low = logits(weights, model, ids, control)
    first = jnp.argmax(low[positions], axis=-1)
    return served, best[positions] - ref[positions, first]


def served_token_gaps(config: dict, source, samples: list,
                      control: str | None = None) -> dict:
    """`source`: the run's seeded weights (`harness/weights.py::Source`),
    taken whole: 1.4 GB in float32 at gpt2-medium, beside nothing else.
    `samples`: (prompt ids, served ids) pairs. For every served token,
    the gap by which its logit lies below the reference's best at its
    position; with `control`, also the gap of the token the same
    mathematics in that lower precision puts first there (calibration and
    tests only). Returns the widest of each and the count of tokens."""
    model_items = tuple(sorted(
        (k, v) for k, v in config["model"].items()
        if isinstance(v, (int, float))))
    weights = source.whole()
    worst, worst_control, n = 0.0, 0.0, 0
    for prompt, served in samples:
        if not len(served):
            continue
        seq = np.concatenate([np.asarray(prompt), np.asarray(served)])[:-1]
        padded = -(-len(seq) // PAD_TO) * PAD_TO
        ids = np.zeros(min(padded, config["model"]["n_positions"]), np.int32)
        ids[: len(seq)] = seq
        # the tail padded with copies of its first entry: a gap read twice
        # leaves the widest as it is
        tail = -(-len(served) // PAD_TO) * PAD_TO
        positions = np.full(tail, len(prompt) - 1, np.int32)
        positions[: len(served)] += np.arange(len(served), dtype=np.int32)
        tokens = np.full(tail, served[0], np.int32)
        tokens[: len(served)] = served
        s, c = _gaps(weights, jnp.asarray(ids), jnp.asarray(positions),
                     jnp.asarray(tokens), model_items, control)
        worst = max(worst, float(s.max()))
        worst_control = max(worst_control, float(c.max()))
        n += len(served)
    out = {"max_logit_gap": worst, "tokens": n}
    if control is not None:
        out["control_max_logit_gap"] = worst_control
    return out
