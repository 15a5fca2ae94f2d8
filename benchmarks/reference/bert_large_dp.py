"""Plain reference of `bert_large_dp`: BERT sequence classification,
its loss, gradients, 12-way accumulation and bias-corrected AdamW under
the linear warm-up, in float32 `jax.numpy`.

Sizes come from the configuration file's `model` and `recipe` groups, so
the same file serves the tiny rehearsal configuration of the tests.
Departures from the published model, all following the configuration as
it is run: tanh-approximate GELU and no dropout (see the configuration's
`reduced`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import block


def weight_spec(model: dict) -> dict:
    h, f = model["hidden_size"], model["intermediate_size"]
    n = model["num_hidden_layers"]
    spec = {
        "emb_word": ((model["vocab_size"], h), "normal"),
        "emb_pos": ((model["max_position_embeddings"], h), "normal"),
        "emb_type": ((model["type_vocab_size"], h), "normal"),
        "emb_ln_g": ((h,), "scale"),
        "emb_ln_b": ((h,), "normal"),
    }
    for name, (shape, kind) in block.layer_spec(h, f).items():
        spec[f"layers.{name}"] = ((n, *shape), kind)
    spec.update({
        "pool_w": ((h, h), "normal"), "pool_b": ((h,), "normal"),
        "cls_w": ((h, model["num_labels"]), "normal"),
        "cls_b": ((model["num_labels"],), "normal"),
    })
    return spec


def logits(weights: dict, model: dict, micro: dict, precision="float32"):
    ids = micro["input_ids"]
    eps = model["layer_norm_eps"]
    x = (weights["emb_word"][ids]
         + weights["emb_pos"][jnp.arange(ids.shape[1])][None]
         + weights["emb_type"][micro["token_type_ids"]])
    x = block.layer_norm(x, weights["emb_ln_g"], weights["emb_ln_b"], eps)
    bias = block.padding_bias(micro["attention_mask"])
    layers = {k.split(".", 1)[1]: v for k, v in weights.items()
              if k.startswith("layers.")}

    def body(x, p):
        return block.post_ln_layer(
            x, p, model["num_attention_heads"], bias, eps, precision
        ), None

    x, _ = jax.lax.scan(body, x, layers)
    pooled = jnp.tanh(
        block.matmul(x[:, 0], weights["pool_w"], precision) + weights["pool_b"]
    )
    # the classifier is float32 in the configuration: never lowered
    return block.matmul(pooled, weights["cls_w"]) + weights["cls_b"]


def micro_loss(weights, model, micro, precision="float32"):
    lg = logits(weights, model, micro, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(logp, micro["labels"][:, None], axis=-1)[:, 0]
    return ce.mean()


def learning_rate(recipe: dict, count: int) -> float:
    """transformers' linear warm-up, as the optimizer reads it: update
    number `count` (from 0) uses peak * count / warmup."""
    warm = max(int(recipe["warmup_steps"]), 1)
    if count >= warm:
        raise ValueError("the reference follows warm-up steps only")
    return recipe["learning_rate"] * count / warm


def leaf_norms(tree: dict, model: dict) -> dict:
    """L2 norm of every leaf, stacked leaves one per layer: the leaves of
    the comparison are `name` and `layers.<i>.<name>`."""
    out = {}
    for name, x in tree.items():
        if name.startswith("layers."):
            sq = jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1))
            for i in range(x.shape[0]):
                out[f"layers.{i}.{name[7:]}"] = sq[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(x**2))
    return out


def train(config: dict, source, batches: list, *,
          precision: str = "float32", fault: str | None = None) -> dict:
    """Follow `len(batches)` optimizer updates from `source.whole()`.

    `source` (`harness/weights.py::Source`) makes a fresh copy of the
    seeded weights, taken whole (twice: the start is not kept through the
    updates, so that the reference fits beside nothing but itself). `batches`: per update a dict of
    [accum, micro, ...] integer arrays as the program's step was fed them.
    Returns each update's loss, the per-leaf norms of the first gradient
    (and of its micro-batches' gradients, averaged: `grad1_scale`) and of
    the parameters' change over all the updates. `fault` plants one
    of the faults the output check has to catch (tests and calibration
    only): `half_batch` leaves out the second half of every micro-batch
    and takes the mean over the rest; `no_update` returns the state
    unchanged.
    """
    model, recipe = config["model"], config["recipe"]
    b1, b2, eps = recipe["adam_b1"], recipe["adam_b2"], recipe["adam_eps"]

    @jax.jit
    def grad_micro(w, micro):
        return jax.value_and_grad(
            functools.partial(micro_loss, model=model, precision=precision)
        )(w, micro=micro)

    @functools.partial(jax.jit, donate_argnums=0)
    def accumulate(acc, g, scale):
        return jax.tree.map(lambda a, b: a + scale * b, acc, g)

    @functools.partial(jax.jit, static_argnames="t", donate_argnums=(0, 1, 2))
    def adam(w, mu, nu, g, lr, t: int):
        def one(w, mu, nu, g):
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * (g * g)
            upd = (mu / (1 - b1**t)) / (jnp.sqrt(nu / (1 - b2**t)) + eps)
            return w - lr * upd, mu, nu

        out = jax.tree.map(one, w, mu, nu, g)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda t3: t3[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    norms = jax.jit(functools.partial(leaf_norms, model=model))
    delta_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b), model))
    w = source.whole()
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    losses, grad1, scale1 = [], None, None
    for t, batch in enumerate(batches, start=1):
        accum = batch["input_ids"].shape[0]
        g_acc = jax.tree.map(jnp.zeros_like, w)
        loss = 0.0
        for a in range(accum):
            micro = {k: jnp.asarray(v[a]) for k, v in batch.items()}
            if fault == "half_batch":
                micro = {k: v[: max(v.shape[0] // 2, 1)] for k, v in micro.items()}
            l, g = grad_micro(w, micro)
            if t == 1:
                # what the leaf's norm would be if the micro-batches'
                # gradients did not cancel: the yardstick of its gap
                part = {k: float(v) / accum for k, v in norms(g).items()}
                scale1 = part if scale1 is None else {
                    k: scale1[k] + v for k, v in part.items()}
            g_acc = accumulate(g_acc, g, 1.0 / accum)
            loss += float(l) / accum
        del g
        losses.append(loss)
        if grad1 is None:
            grad1 = {k: float(v) for k, v in norms(g_acc).items()}
        if fault != "no_update":
            w, mu, nu = adam(w, mu, nu, g_acc, learning_rate(recipe, t - 1), t=t)
        del g_acc
    del mu, nu
    delta = delta_norms(w, source.whole())
    return {
        "losses": losses,
        "grad1_norms": grad1,
        "grad1_scale": scale1,
        "delta_norms": {k: float(v) for k, v in delta.items()},
    }
