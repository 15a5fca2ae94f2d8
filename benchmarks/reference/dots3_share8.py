"""Plain reference of `dots3_share8`: one chip's share of a dots3-note
decoder (`model_type: dots3_note`), the whole sequence at once in float32
`jax.numpy` at `highest` matmul precision, one layer at a time, queries in
blocks: no cache, no pages, no kernels, no batching, nothing imported from
the program. What the server produced through chunked prefill, the prefix
cache, the latent, window and indexer page pools, selection and windows
inside paged decode and device sampling is judged against it token by
token: how far the served (greedy) token's logit lies below the
reference's best at that position.

The equations (`x` a token's RMS-normed layer input, `t` its position,
`s <= t` an earlier one; RMSNorm eps `rms_norm_eps` before attention and
before the MLP, residual adds, a final RMSNorm, an untied head). Two kinds
of attention layer, each MLA with sizes of its own:

- Full layer (`layer_types` `full_attention`). `cq = RMSNorm(W_qa x)`; `q =
  W_qb cq` -> heads x (nope + rope); `ckv = RMSNorm(W_kva_latent x)`, `kr =
  RoPE(W_kva_rope x)` (one rotary key for all heads, interleaved pairs,
  theta `rope_theta`); `k_nope = W_kvb_k ckv`, `v = W_kvb_v ckv`;
  `score_h(t, s) = (q_nope_h . k_nope_h(s) + q_rope_h . kr(s)) / sqrt(nope
  + rope)`; softmax over `s in S(t)`, the `index_topk` positions the layer's
  OWN indexer picks (`reference/glm52_share16.py::selection`: `I(t, s) =
  sum_h w_h(t) / sqrt(heads x dim) x ReLU(qi_h(t) . ki(s))`, `ki =
  LayerNorm(W_ik x)`, rotary on the first `qk_rope_head_dim` dims).
- Window layer (`sliding_attention`): the same with the `swa_*` sizes and
  `swa_rope_theta`, and no indexer: `S(t)` = positions `max(0, t - W +
  1)..t`, `W` = `sliding_window_size` (513: the query itself counts).
- Both: the headwise gate `o_h <- sigmoid(x . g_h) o_h` (no bias, from the
  layer's normed input), then `o = W_o concat_h o_h`.
- Expert layer: `reference/glm52_share16.py::expert_layer` (sigmoid scores
  over ALL routed experts, top-k of score + bias, weights the chosen
  scores' shares x `routed_scaling_factor`; the held experts' part plus the
  shared expert; what absent experts add is left out).
- Dense layer (layer 0): the same attention, a gated SiLU MLP.

Sizes come from the configuration file's `model` group, so the tests' tiny
configuration runs the same file. Leaves are named per layer and made one
layer at a time.

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
from reference import glm52_share16 as glm

ROWS = 128  # rows of queries a block of attention or index scores

#: one mechanism broken each (``forward(..., fault=)``)
FAULTS = (
    "window_all",   # every window layer attends to the whole context
)


def _sizes(model: dict, window: bool):
    """(heads, q rank, kv rank, nope, rope, v, theta, gate) of a layer."""
    p = "swa_" if window else ""
    return (model[p + "num_attention_heads"], model[p + "q_lora_rank"],
            model[p + "kv_lora_rank"], model[p + "qk_nope_head_dim"],
            model[p + "qk_rope_head_dim"], model[p + "v_head_dim"],
            model[p + "rope_theta"], model[p + "attention_gate_type"])


def _windows(model: dict) -> list:
    return [t == "sliding_attention" for t in model["layer_types"]]


def weight_spec(model: dict) -> dict:
    """name -> (shape, kind), per layer. Two kinds are the family's own
    (`families/dots3_note.py::init`): the router's correction bias, and a
    window layer's query and key paths, drawn wider (`window_qk`)."""
    h = model["hidden_size"]
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    f, fe = model["intermediate_size"], model["moe_intermediate_size"]
    held, blk = model["experts_held"][1], model["expert_block"]
    spec = {"embed": ((model["vocab_size"], h), "normal")}
    for i, (mlp, window) in enumerate(
            zip(model["mlp_layer_types"], _windows(model))):
        heads, qr, rank, dn, dr, dv, _, gate = _sizes(model, window)
        qk = "window_qk" if window else "normal"
        p = f"layers.{i}."
        spec.update({
            p + "attention_norm": ((h,), "scale"),
            p + "q_a": ((h, qr), "normal"),
            p + "q_a_norm": ((qr,), "scale"),
            p + "q_b": ((qr, heads * (dn + dr)), qk),
            p + "kv_a_latent": ((h, rank), "normal"),
            p + "kv_a_rope": ((h, dr), qk),
            p + "kv_a_norm": ((rank,), "scale"),
            p + "kv_b_k": ((rank, heads * dn), qk),
            p + "kv_b_v": ((rank, heads * dv), "normal"),
            p + "o": ((heads * dv, h), "normal"),
        })
        if gate == "headwise":
            spec[p + "gate"] = ((h, heads), "normal")
        if not window:
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
    return out.reshape(n * ROWS, *out.shape[2:])[:seq]


def _attend(qn, qr, k_nope, kr, v, mask, scale, precision):
    """A block of queries `qn` [r, heads, nope], `qr` [r, heads, rope] over
    keys [s, ...] under `mask` [r, s] -> [r, heads, v]."""
    scores = (glm._product("rhd,shd->hrs", qn, k_nope, precision)
              + glm._product("rhd,sd->hrs", qr, kr, precision)) * scale
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    # a padded row (past the sequence) sees nothing: zeros, not nan
    probs = jnp.where(mask.any(-1)[None, :, None], probs, 0.0)
    return glm._product("hrs,shd->rhd", probs, v, precision)


def attention(w: dict, model: dict, x, positions, window: bool,
              precision="float32", fault=None):
    """One layer's attention over the whole sequence `x` [s, hidden]:
    its own indexer's selection in a full layer, the window in a window
    layer, the gate, the output projection."""
    heads, _, _, dn, dr, dv, theta, _ = _sizes(model, window)
    seq = x.shape[0]
    eps = model["rms_norm_eps"]
    scale = (dn + dr) ** -0.5
    cq = glm.rms_norm(block.matmul(x, w["q_a"], precision), w["q_a_norm"], eps)
    q = block.matmul(cq, w["q_b"], precision).reshape(seq, heads, dn + dr)
    ckv = glm.rms_norm(block.matmul(x, w["kv_a_latent"], precision),
                       w["kv_a_norm"], eps)
    kr = glm.rope(block.matmul(x, w["kv_a_rope"], precision), positions, theta)
    q_nope, q_rope = q[..., :dn], glm.rope(q[..., dn:], positions, theta)
    k_nope = block.matmul(ckv, w["kv_b_k"], precision).reshape(seq, heads, dn)
    v = block.matmul(ckv, w["kv_b_v"], precision).reshape(seq, heads, dv)
    if window and fault != "window_all":
        # a block of queries reaches back W - 1 rows before its first:
        # keys padded in front by W - 1 and behind by the tail block's pad
        span = model["sliding_window_size"]
        front = span - 1
        back = -(-seq // ROWS) * ROWS - seq
        keys = [jnp.pad(a, [(front, back)] + [(0, 0)] * (a.ndim - 1))
                for a in (k_nope, kr, v)]

        def rows(qn, qr, first):
            t = first + jnp.arange(ROWS)
            s = first - front + jnp.arange(ROWS + front)
            mask = ((s[None, :] <= t[:, None]) & (s[None, :] > t[:, None] - span)
                    & (s[None, :] >= 0) & (t[:, None] < seq))
            near = [jax.lax.dynamic_slice_in_dim(a, first, ROWS + front)
                    for a in keys]
            return _attend(qn, qr, *near, mask, scale, precision)

        ctx = _row_blocks(rows, seq, q_nope, q_rope)
    else:
        if window:
            s = jnp.arange(seq)
            mask = s[None, :] <= s[:, None]
        else:
            view = dict(model, rope_parameters={"rope_theta": theta})
            mask = glm.selection(w, view, cq, x, positions, precision, None)
        ctx = _row_blocks(
            lambda qn, qr, m, first: _attend(
                qn, qr, k_nope, kr, v, m, scale, precision),
            seq, q_nope, q_rope, mask)
    if "gate" in w:
        gate = jax.nn.sigmoid(block.matmul(x, w["gate"], precision))
        ctx = ctx * gate[..., None]
    return block.matmul(ctx.reshape(seq, heads * dv), w["o"], precision)


def _layer(mlp, window, w, x, positions, model_key, precision, fault):
    model = _MODELS[model_key]
    eps = model["rms_norm_eps"]
    x = x + attention(w, model, glm.rms_norm(x, w["attention_norm"], eps),
                      positions, window, precision, fault)
    hidden = glm.rms_norm(x, w["mlp_norm"], eps)
    if mlp == "dense":
        return x + glm.gated_mlp(
            hidden, w["mlp_gate"], w["mlp_up"], w["mlp_down"], precision)
    routed, shared = glm.expert_layer(w, model, hidden, precision)
    return x + routed + shared


#: one compiled layer for each kind of MLP and attention
_LAYERS = {
    (mlp, window): jax.jit(functools.partial(_layer, mlp, window),
                           static_argnames=("model_key", "precision", "fault"))
    for mlp in ("dense", "sparse") for window in (False, True)
}

#: configurations' `model` groups by a hashable key (jit's static argument)
_MODELS: dict = {}


def _model_key(model: dict) -> str:
    key = json.dumps(model, sort_keys=True)
    _MODELS[key] = model
    return key


def forward(model: dict, source, ids, positions_out, precision="float32",
            fault=None):
    """ids [s] -> logits [len(positions_out), vocab]; position i sees
    tokens 0..i. Layer by layer over the whole sequence."""
    key = _model_key(model)
    ids = jnp.asarray(ids)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    x = source.leaf("embed")[ids]
    for i, (mlp, window) in enumerate(
            zip(model["mlp_layer_types"], _windows(model))):
        w = glm.layer_weights(source, i)
        x = _LAYERS[mlp, window](w, x, positions, model_key=key,
                                 precision=precision, fault=fault)
        del w
    x = glm.rms_norm(x[jnp.asarray(positions_out)], source.leaf("final_norm"),
                     model["rms_norm_eps"])
    return block.matmul(x, source.leaf("head"), precision)


def served_token_gaps(config: dict, source, samples: list,
                      control: str | None = None) -> dict:
    """`source`: the run's seeded weights (`harness/weights.py::Source`),
    taken leaf by leaf. `samples`: (prompt ids, served ids) pairs. A served
    token's gap is how far its logit lies below the reference's best at
    its position. What is judged (`max_logit_gap`) is the WIDEST MEAN GAP
    over any block of `check.gap_block` consecutive served tokens of a
    sampled request, as `reference/glm52_share16.py` judges it and for its
    reason: a rounding now and then flips a discrete choice (an expert, a
    position of the 2,048) and that one token reads as wide in bfloat16 as
    in int8. With `control` (a precision, `int8`, or a name of `FAULTS`, or
    several joined by commas): the same number for the token the same
    mathematics so computed puts first at every position; the smallest over
    the controls is returned as the control's. Every run prints a summary
    of its gaps, the controls' too."""
    model = config["model"]
    gap_block = int(config["check"]["gap_block"])
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
    summary = {"served": glm._summary(sound, gap_block)}
    summary.update({c: glm._summary(g, gap_block) for c, g in low_gaps.items()})
    print(f"benchmark: reference gaps, blocks of {gap_block}: "
          f"{json.dumps(summary)}", flush=True)
    out = {"max_logit_gap": summary["served"]["judged"],
           "tokens": summary["served"]["tokens"]}
    if controls:
        out["controls"] = {c: summary[c]["judged"] for c in controls}
        out["control_max_logit_gap"] = min(out["controls"].values())
    return out
