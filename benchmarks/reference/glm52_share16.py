"""Plain reference of `glm52_share16`: one chip's share of a GLM-5.2 style
decoder (`model_type: glm_moe_dsa`), the whole sequence at once in float32
`jax.numpy` at `highest` matmul precision: no cache, no pages, no kernels,
no batching, nothing imported from the program. What the server produced
through chunked prefill, the prefix cache, the latent and indexer page
pools, selection inside paged decode and device sampling is judged against
it token by token: how far the served (greedy) token's logit lies below the
reference's best at that position.

The equations (`x` a token's hidden state, `t` its position, `s <= t` an
earlier one; RMSNorm eps `rms_norm_eps` before attention and before the
MLP, residual adds, a final RMSNorm, an untied head):

- MLA. `cq = RMSNorm(W_qa x)`; `q = W_qb cq` -> heads x (nope + rope);
  `ckv = RMSNorm(W_kva_latent x)`, `kr = RoPE(W_kva_rope x)` (one rotary
  key for all heads); `q_rope = RoPE(q_rope)` (interleaved pairs, theta
  `rope_theta`); `k_nope = W_kvb_k ckv`, `v = W_kvb_v ckv`; `score_h(t, s)
  = (q_nope_h . k_nope_h(s) + q_rope_h . kr(s)) / sqrt(nope + rope)`;
  softmax over `s in S(t)`; `o = W_o concat_h sum_s p_h(t, s) v_h(s)`.
- Indexer, in a layer typed `full`. `qi = W_iq cq` -> index heads x index
  dim; `ki = LayerNorm(W_ik x)`; RoPE on the first `qk_rope_head_dim` dims
  of both; `w = W_iw x`; `I(t, s) = sum_h w_h(t) / sqrt(heads x dim) x
  ReLU(qi_h(t) . ki(s))`. `S(t)` = the `min(index_topk, t + 1)` positions
  of largest `I`, ties to the lower position. A layer typed `shared` uses
  the `S(t)` of the nearest `full` layer before it.
- Expert layer. `g = sigmoid(W_g x)` over ALL routed experts; the
  `num_experts_per_tok` largest of `g + b` are chosen (`b` steers the
  choice only); `a_e = routed_scaling_factor x g_e / sum_chosen g`; `y =
  Shared(x) + sum over chosen e HELD HERE of a_e x E_e(x)`, `E(x) =
  W_d(SiLU(W_gate x) * W_u x)`. What the absent experts would add is left
  out (as in the program) and that partial `y` goes on.
- Dense layer: the same attention, a gated SiLU MLP.

Sizes come from the configuration file's `model` group, so the tests' tiny
configuration runs the same file. Memory: leaves are named per layer
(`layers.3.experts_gate.0`) and made one layer at a time (an expert layer
of the cell is 3.2 GB in float32); attention and index scores go through in
blocks of rows.

`FAULTS` are the same mathematics with one mechanism broken, for the
controls and the tests: put in the program's place, each must read over
the configuration's limit.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from reference import block

HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 256  # rows of queries a block of attention or index scores

#: one mechanism broken each (``forward(..., fault=)``)
FAULTS = (
    "select_latest",    # S(t) = the latest index_topk positions
    "shared_recompute",  # a shared layer scores anew with its full layer's indexer
    "shared_shift",     # a shared layer uses its full layer's positions, one early
    "bias_in_weights",  # routing weights from g + b instead of g
    "drop_expert",      # the first held expert's part left out
)


def _dims(model: dict):
    return (model["hidden_size"], model["num_attention_heads"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"])


def weight_spec(model: dict) -> dict:
    """name -> (shape, kind), per layer. One kind is the family's own
    (`families/glm_moe_dsa.py::init`): the router's correction bias."""
    h, heads, dn, dr, dv = _dims(model)
    qr, rank = model["q_lora_rank"], model["kv_lora_rank"]
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    f, fe = model["intermediate_size"], model["moe_intermediate_size"]
    held, blk = model["experts_held"][1], model["expert_block"]
    spec = {"embed": ((model["vocab_size"], h), "normal")}
    for i, (mlp, idx) in enumerate(
            zip(model["mlp_layer_types"], model["indexer_types"])):
        p = f"layers.{i}."
        spec.update({
            p + "attention_norm": ((h,), "scale"),
            p + "q_a": ((h, qr), "normal"),
            p + "q_a_norm": ((qr,), "scale"),
            p + "q_b": ((qr, heads * (dn + dr)), "normal"),
            p + "kv_a_latent": ((h, rank), "normal"),
            p + "kv_a_rope": ((h, dr), "normal"),
            p + "kv_a_norm": ((rank,), "scale"),
            p + "kv_b_k": ((rank, heads * dn), "normal"),
            p + "kv_b_v": ((rank, heads * dv), "normal"),
            p + "o": ((heads * dv, h), "normal"),
        })
        if idx == "full":
            spec.update({
                p + "index_q": ((qr, ih * idim), "normal"),
                p + "index_k": ((h, idim), "normal"),
                p + "index_k_norm_g": ((idim,), "scale"),
                p + "index_k_norm_b": ((idim,), "normal"),
                p + "index_w": ((h, ih), "normal"),
            })
        spec[p + "mlp_norm"] = ((h,), "scale")
        if mlp == "dense":
            spec.update({
                p + "mlp_gate": ((h, f), "normal"),
                p + "mlp_up": ((h, f), "normal"),
                p + "mlp_down": ((f, h), "normal"),
            })
        else:
            fs = fe * model["n_shared_experts"]
            spec.update({
                p + "router": ((h, model["router_experts"]), "normal"),
                p + "router_bias": ((model["router_experts"],), "router_bias"),
                p + "shared_gate": ((h, fs), "normal"),
                p + "shared_up": ((h, fs), "normal"),
                p + "shared_down": ((fs, h), "normal"),
            })
            for j in range(held // blk):
                spec.update({
                    p + f"experts_gate.{j}": ((blk, h, fe), "normal"),
                    p + f"experts_up.{j}": ((blk, h, fe), "normal"),
                    p + f"experts_down.{j}": ((blk, fe, h), "normal"),
                })
    spec.update({"final_norm": ((h,), "scale"),
                 "head": ((h, model["vocab_size"]), "normal")})
    return spec


# ------------------------------------------------------------ mathematics


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """Interleaved pairs `(x[2i], x[2i+1])` of the last axis turned by
    `positions x theta^(-2i/d)`; `x` [s, ..., d], `positions` [s]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _rounded(x, precision):
    """`x` as it is, or rounded to int8 (per tensor) or bfloat16 and back:
    a control's operands (`block.matmul` does the same to its own)."""
    if precision == "int8":
        return block._fake_int8(x)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _product(eq, a, b, precision):
    """An einsum in float32/highest, a control's operands rounded first."""
    return jnp.einsum(eq, _rounded(a, precision), _rounded(b, precision),
                      precision=HIGHEST)


def _row_blocks(fn, seq: int, *rows):
    """`fn(block of each of rows, first row)` over blocks of `ROWS` rows,
    results joined along the rows; the tail block is padded."""
    n = -(-seq // ROWS)
    pad = n * ROWS - seq
    padded = [jnp.pad(r, [(0, pad)] + [(0, 0)] * (r.ndim - 1)) for r in rows]
    split = [r.reshape(n, ROWS, *r.shape[1:]) for r in padded]
    out = jax.lax.map(
        lambda a: fn(*a[:-1], a[-1]),
        (*split, jnp.arange(n, dtype=jnp.int32) * ROWS))
    return jax.tree.map(
        lambda o: o.reshape(n * ROWS, *o.shape[2:])[:seq], out)


def index_scores_block(qi, w, ki, first_row):
    """`I(t, s)` for a block of rows `t`: `qi` [r, heads, dim], `w` [r,
    heads] (scaled), `ki` [s, dim] -> [r, s], `-inf` at `s > t`."""
    per_head = jax.nn.relu(jnp.einsum("rhd,sd->rhs", qi, ki, precision=HIGHEST))
    scores = jnp.einsum("rhs,rh->rs", per_head, w, precision=HIGHEST)
    t = first_row + jnp.arange(qi.shape[0])
    seen = jnp.arange(ki.shape[0])[None, :] <= t[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def select_block(scores, topk: int):
    """The mask [r, s] of the `topk` largest scores of each row, ties to
    the lower position (`jax.lax.top_k`'s order); unseen positions out."""
    values, idx = jax.lax.top_k(scores, min(topk, scores.shape[-1]))
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(values > -jnp.inf)


def selection(w: dict, model: dict, cq, x, positions, precision, fault):
    """The mask [s, s] of `S(t)` for every `t`, from a full layer's
    indexer weights."""
    seq = x.shape[0]
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    dr, theta = model["qk_rope_head_dim"], model["rope_parameters"]["rope_theta"]
    if fault == "select_latest":
        s = jnp.arange(seq)
        return (s[None, :] <= s[:, None]) & (
            s[None, :] > s[:, None] - model["index_topk"])
    qi = block.matmul(cq, w["index_q"], precision).reshape(seq, ih, idim)
    ki = block.layer_norm(
        block.matmul(x, w["index_k"], precision), w["index_k_norm_g"],
        w["index_k_norm_b"], model["index_norm_eps"])
    qi = jnp.concatenate(
        [rope(qi[..., :dr], positions, theta), qi[..., dr:]], -1)
    ki = jnp.concatenate(
        [rope(ki[..., :dr], positions, theta), ki[..., dr:]], -1)
    wi = block.matmul(x, w["index_w"], precision) * (ih * idim) ** -0.5
    qi, ki = _rounded(qi, precision), _rounded(ki, precision)
    return _row_blocks(
        lambda qi, wi, first: select_block(
            index_scores_block(qi, wi, ki, first), model["index_topk"]),
        seq, qi, wi)


def attention(w: dict, model: dict, x, positions, mask, precision="float32",
              fault=None, indexer=None):
    """One layer's attention over the whole sequence `x` [s, hidden].
    `mask`: the selection handed down (None in a full layer, which makes
    its own). Returns (output, the selection it used, the selection it
    hands on)."""
    h, heads, dn, dr, dv = _dims(model)
    seq = x.shape[0]
    theta = model["rope_parameters"]["rope_theta"]
    eps = model["rms_norm_eps"]
    cq = rms_norm(block.matmul(x, w["q_a"], precision), w["q_a_norm"], eps)
    q = block.matmul(cq, w["q_b"], precision).reshape(seq, heads, dn + dr)
    ckv = rms_norm(block.matmul(x, w["kv_a_latent"], precision),
                   w["kv_a_norm"], eps)
    kr = rope(block.matmul(x, w["kv_a_rope"], precision), positions, theta)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], positions, theta)
    k_nope = block.matmul(ckv, w["kv_b_k"], precision).reshape(seq, heads, dn)
    v = block.matmul(ckv, w["kv_b_v"], precision).reshape(seq, heads, dv)
    if "index_q" in w:
        mask = selection(w, model, cq, x, positions, precision, fault)
        hand_on = mask
    else:
        hand_on = mask
        if fault == "shared_recompute":
            # as a program would that scored anew in every layer, with the
            # only indexer weights it has: the full layer's
            mask = selection(indexer, model, cq, x, positions, precision, None)
        elif fault == "shared_shift":
            shifted = jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))
            # a row left with nothing (it held position 0 alone) keeps it
            mask = shifted | (mask & ~shifted.any(1, keepdims=True))

    def rows(qn, qr, m, first):
        scores = (_product("rhd,shd->hrs", qn, k_nope, precision)
                  + _product("rhd,sd->hrs", qr, kr, precision)
                  ) * (dn + dr) ** -0.5
        probs = jax.nn.softmax(jnp.where(m[None], scores, -jnp.inf), -1)
        # a padded row (past the sequence) sees nothing: zeros, not nan
        probs = jnp.where(m.any(-1)[None, :, None], probs, 0.0)
        return _product("hrs,shd->rhd", probs, v, precision)

    ctx = _row_blocks(rows, seq, q_nope, q_rope, mask)
    out = block.matmul(ctx.reshape(seq, heads * dv), w["o"], precision)
    return out, mask, hand_on


def gated_mlp(x, gate, up, down, precision="float32"):
    a = jax.nn.silu(block.matmul(x, gate, precision)) * block.matmul(
        x, up, precision)
    return block.matmul(a, down, precision)


def route(w: dict, model: dict, x, fault=None):
    """(chosen experts [s, k], their weights [s, k]) over ALL experts."""
    g = jax.nn.sigmoid(jnp.matmul(x, w["router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(g + w["router_bias"], model["num_experts_per_tok"])
    picked = jnp.take_along_axis(
        g + w["router_bias"] if fault == "bias_in_weights" else g, chosen, -1)
    weights = model["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, weights


def expert_layer(w: dict, model: dict, x, precision="float32", fault=None):
    """(the held experts' routed part, the shared expert's part) of `x`
    [s, hidden]; their sum is the layer's output on this chip."""
    first, held = model["experts_held"]
    blk = model["expert_block"]
    chosen, weights = route(w, model, x, fault)
    routed = jnp.zeros_like(x)
    for e in range(1 if fault == "drop_expert" else 0, held):
        j, r = divmod(e, blk)
        a = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        routed = routed + a[:, None] * gated_mlp(
            x, w[f"experts_gate.{j}"][r], w[f"experts_up.{j}"][r],
            w[f"experts_down.{j}"][r], precision)
    shared = gated_mlp(
        x, w["shared_gate"], w["shared_up"], w["shared_down"], precision)
    return routed, shared


def _expert_mlp(w, model, hidden, precision, fault):
    routed, shared = expert_layer(w, model, hidden, precision, fault)
    return routed + shared


def _dense_mlp(w, model, hidden, precision, fault):
    return gated_mlp(
        hidden, w["mlp_gate"], w["mlp_up"], w["mlp_down"], precision)


def _layer(mlp, w, x, positions, mask, indexer, model_key, precision, fault):
    model = _MODELS[model_key]
    eps = model["rms_norm_eps"]
    a, used, mask = attention(
        w, model, rms_norm(x, w["attention_norm"], eps), positions, mask,
        precision, fault, indexer)
    x = x + a
    hidden = rms_norm(x, w["mlp_norm"], eps)
    return x + mlp(w, model, hidden, precision, fault), used, mask


#: one compiled layer for each kind of MLP
_LAYERS = {
    kind: jax.jit(functools.partial(_layer, mlp),
                  static_argnames=("model_key", "precision", "fault"))
    for kind, mlp in (("sparse", _expert_mlp), ("dense", _dense_mlp))
}


#: configurations' `model` groups by a hashable key (jit's static argument)
_MODELS: dict = {}


def _model_key(model: dict) -> str:
    key = json.dumps(model, sort_keys=True)
    _MODELS[key] = model
    return key


def layer_weights(source, i: int) -> dict:
    """Layer `i`'s leaves under their short names, made alone: never more
    than a layer in float32. One compiled generator a layer and source,
    kept on the source (a run makes each layer once a forward)."""
    prefix = f"layers.{i}."
    made = getattr(source, "_layer_generators", None)
    if made is None:
        made = source._layer_generators = {}
    if i not in made:
        names = frozenset(n for n in source.spec if n.startswith(prefix))
        made[i] = jax.jit(lambda key: source.generate(key, names))
    return {n[len(prefix):]: v for n, v in made[i](source.key()).items()}


def forward(model: dict, source, ids, positions_out, precision="float32",
            fault=None, keep=None):
    """ids [s] -> logits [len(positions_out), vocab]; position i sees
    tokens 0..i. Layer by layer over the whole sequence. `keep`, a dict,
    is handed each layer's selection mask under its index (tests)."""
    key = _model_key(model)
    ids = jnp.asarray(ids)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    x = source.leaf("embed")[ids]
    mask, indexer = None, None
    for i in range(len(model["mlp_layer_types"])):
        w = layer_weights(source, i)
        if "index_q" in w:
            mask = None
            indexer = {k: v for k, v in w.items() if k.startswith("index_")}
        x, used, mask = _LAYERS[model["mlp_layer_types"][i]](
            w, x, positions, mask, None if "index_q" in w else indexer,
            model_key=key, precision=precision, fault=fault)
        if keep is not None:
            keep[i] = np.asarray(used)
        del w
    x = rms_norm(x[jnp.asarray(positions_out)], source.leaf("final_norm"),
                 model["rms_norm_eps"])
    return block.matmul(x, source.leaf("head"), precision)


#: every run prints its gaps' means over blocks of this many tokens too:
#: what a limit, or another block size, is calibrated from
PRINTED_BLOCK = 64


def block_means(gaps, block: int) -> list:
    """The mean gap of each block of `block` consecutive served tokens of
    one request (the blocks follow one another from the first token; a
    tail shorter than half a block joins the block before)."""
    gaps = np.asarray(gaps, np.float64)
    cuts = list(range(0, len(gaps), block))
    if len(cuts) > 1 and len(gaps) - cuts[-1] < block / 2:
        cuts.pop()
    return [float(gaps[a:b].mean())
            for a, b in zip(cuts, cuts[1:] + [len(gaps)])]


def widest_block_mean(gaps, block: int) -> float:
    return max(block_means(gaps, block))


def _summary(per_request: list, block: int) -> dict:
    """What a run prints of its gaps: the judged number first, then the
    widest single gap, the bulk, and every small block's mean, request
    after request."""
    flat = np.concatenate(per_request)
    return {
        "judged": max(widest_block_mean(g, block) for g in per_request),
        "tokens": int(flat.size), "max": float(flat.max()),
        "p99": float(np.percentile(flat, 99)), "mean": float(flat.mean()),
        f"means{PRINTED_BLOCK}": [
            [round(m, 4) for m in block_means(g, PRINTED_BLOCK)]
            for g in per_request],
    }


def served_token_gaps(config: dict, source, samples: list,
                      control: str | None = None) -> dict:
    """`source`: the run's seeded weights (`harness/weights.py::Source`),
    taken leaf by leaf. `samples`: (prompt ids, served ids) pairs. A served
    token's gap is how far its logit lies below the reference's best at
    its position. What is judged (`max_logit_gap`) is the WIDEST MEAN GAP
    over any block of `check.gap_block` consecutive served tokens of a
    sampled request, not the widest single gap: in this family a rounding
    now and then flips a discrete choice (an expert of the 8, a position
    of the 2,048) and one such token reads 0.5-1.5 in bfloat16 and in int8
    alike, so the widest single gap tells the two precisions apart by a
    factor under two, while a block's mean tells them apart by a factor of
    the noise itself (PERF.md, PR 29). With `control` (a precision, `int8`,
    or a name of `FAULTS`, or several joined by commas): the same number
    for the token the same mathematics so computed puts first at every
    position; the smallest over the controls is returned as the control's
    (a limit has to lie under every one). Every run prints a summary of
    its gaps, the controls' too."""
    model = config["model"]
    block = int(config["check"]["gap_block"])
    controls = [c for c in (control or "").split(",") if c]
    sound, low_gaps = [], {c: [] for c in controls}
    pad_to = model["cache_len"]
    for prompt, served in samples:
        if not len(served):
            continue
        seq = np.concatenate([np.asarray(prompt), np.asarray(served)])[:-1]
        # one compiled shape: the sequence padded to the cache's length
        # (the pad sits after every position that is read)
        ids = np.zeros(max(pad_to, len(seq)), np.int32)
        ids[: len(seq)] = seq
        positions = len(prompt) - 1 + np.arange(len(served))
        tokens = jnp.asarray(np.asarray(served, np.int32))
        ref = forward(model, source, ids, positions)
        best = ref.max(-1)
        sound.append(np.asarray(
            best - jnp.take_along_axis(ref, tokens[:, None], 1)[:, 0]))
        for c in controls:
            low = forward(
                model, source, ids, positions,
                **({"fault": c} if c in FAULTS else {"precision": c}))
            first = jnp.argmax(low, -1)
            low_gaps[c].append(np.asarray(
                best - jnp.take_along_axis(ref, first[:, None], 1)[:, 0]))
    if not sound:
        return {"max_logit_gap": float("inf"), "tokens": 0}
    summary = {"served": _summary(sound, block)}
    summary.update({c: _summary(g, block) for c, g in low_gaps.items()})
    print(f"benchmark: reference gaps, blocks of {block}: "
          f"{json.dumps(summary)}", flush=True)
    out = {"max_logit_gap": summary["served"]["judged"],
           "tokens": summary["served"]["tokens"]}
    if controls:
        out["controls"] = {c: summary[c]["judged"] for c in controls}
        out["control_max_logit_gap"] = min(out["controls"].values())
    return out
