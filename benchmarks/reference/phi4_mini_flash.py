"""Plain reference of `phi4_mini_flash`: Phi-4-mini-flash-reasoning
(`model_type: phi4flash`; the family's paper is arXiv:2507.06607,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", SambaY), the whole sequence at once in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no rings, no carried state,
no kernels, no batching, nothing imported from the program. What the server
produced through chunked prefill, the recurrent states, the window rings,
the one shared K/V page pool and device sampling is judged against it
token by token: how far the served (greedy) token's logit lies below the
reference's best at that position, and of those gaps the widest MEAN over
`GAP_BLOCK` consecutive served tokens of a request (`served_token_gaps`).

The equations (`x` a token's hidden state, `t` its position; every layer
`h = x + Mixer(LN(x))`, `x' = h + W_down(W_up LN'(h) * silu(W_gate
LN'(h)))`, LayerNorm with gain and bias, eps `layer_norm_eps`; a final
LayerNorm and the head TIED to the embedding; no positions of any kind).
The mixer by the layer's index `l` of `L` (`half = L / 2`):

- `l <= half`, `l` even: Mamba-1. `[xs; z] = W_in u`; `c_t = silu(sum_k
  w_k xs_{t-3+k} + b)` (4 taps, causal, depthwise); `[r; B_t; C_t] = W_x
  c_t`; `D_t = softplus(W_dt r + b_dt)`; `A = -exp(A_log)`; `S_t = exp(D_t
  (x) A) * S_{t-1} + (D_t * c_t) (x) B_t`, `S_{-1} = 0`; `y_t = S_t C_t +
  D_skip * c_t`; out `W_out (y_t * silu(z_t))`. Layer `half` also hands
  `m_t = y_t` (before the gate) down the stack.
- `l < half`, `l` odd: differential attention, causal, keys of positions
  `t - window + 1 .. t`.
- `l = half + 1`: differential attention, causal, all positions; its keys
  and values are what every cross layer reads.
- above, `l` even: Gated Memory Unit `W_out2 (m_t * silu(W_in2 u_t))`.
- above, `l` odd: cross attention: its own `W_q`, `W_o`, lambdas and
  sub-norm; K and V are layer `half + 1`'s.

Differential attention: query heads `2p`, `2p + 1` are pair `p`; K/V
group `g = p // 2` holds key heads `2g`, `2g + 1` and value heads `2g`,
`2g + 1`; `A1 = softmax(q_2p k_2g^T / sqrt(d))`, `A2 = softmax(q_2p+1
k_2g+1^T / sqrt(d))`; `o_p = (A1 - lam A2) [v_2g; v_2g+1]`; `o_p =
RMSNorm(o_p; gain, eps) * (1 - lambda_init)`; `lam = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda_init`, `lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)`.

Departures from the published description, each because the catalog's
`config` has no key for it and no `config.json` or weights are on this
machine (the configuration file lists them under `assumed`): the Mamba
sizes (`d_state` 16, `d_conv` 4, `expand` 2, `dt_rank = ceil(hidden /
16)`); biases on the attention projections and none on the Mamba
projections; the gate and up projections are two leaves where the
published layer keeps one fused `gate_up` (a layout, not mathematics); the
embedding is `vocab_blocks` leaves of rows (`embed.0 ..`), which is how it
installs under the harness's cap on one generated leaf; differential
attention itself is in the paper's abstract ("enhanced with Differential
Attention") and not in the catalog's `described_as`.

Sizes come from the configuration file's `model` group, so the tests' tiny
configuration runs the same file. Memory: leaves are named per layer and
made one layer at a time (a Mamba layer is 0.48 GB in float32); attention
goes through in blocks of rows, the head in blocks of positions.

`FAULTS` are the same mathematics with one mechanism broken, for the
controls and the tests: put in the program's place, each must read over
the configuration's limit.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import block

HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 256      # rows of queries a block of attention
HEAD_ROWS = 512  # positions a block of the head

#: one mechanism broken each (``forward(..., fault=)``)
FAULTS = (
    "window_all",    # a window layer sees the whole context
    "stale_state",   # a state-space layer starts from the state a sequence
                     # like this one left behind, not from zeros
)


def layer_kinds(model: dict) -> list:
    n, half = model["num_hidden_layers"], model["num_hidden_layers"] // 2
    kinds = []
    for l in range(n):
        if l <= half:
            kinds.append("mamba" if l % model["mb_per_layer"] == 0 else "window")
        elif l == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if (l - half) % 2 == 0 else "cross")
    return kinds


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _sizes(model: dict):
    h = model["hidden_size"]
    heads, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    return h, heads, kvh, h // heads, model["mamba_expand"] * h


def weight_spec(model: dict) -> dict:
    """name -> (shape, kind), per layer. Four kinds are the family's own
    (`families/phi4flash.py::init`): the convolution's taps, the step's
    bias, `A_log` and the lambda vectors; `x_proj` is drawn wider."""
    h, heads, kvh, d, di = _sizes(model)
    f, ds = model["intermediate_size"], model["mamba_d_state"]
    rank, taps = model["mamba_dt_rank"], model["mamba_d_conv"]
    blocks = model["vocab_blocks"]
    spec = {f"embed.{j}": ((model["vocab_size"] // blocks, h), "normal")
            for j in range(blocks)}
    for i, kind in enumerate(layer_kinds(model)):
        p = f"layers.{i}."
        spec.update({p + "mixer_norm_g": ((h,), "scale"),
                     p + "mixer_norm_b": ((h,), "normal")})
        if kind == "mamba":
            spec.update({
                p + "in_proj": ((h, 2 * di), "normal"),
                p + "conv_w": ((taps, di), "conv"),
                p + "conv_b": ((di,), "normal"),
                p + "x_proj": ((di, rank + 2 * ds), "x_proj"),
                p + "dt_proj": ((rank, di), "normal"),
                p + "dt_bias": ((di,), "dt_bias"),
                p + "A_log": ((di, ds), "a_log"),
                p + "D": ((di,), "scale"),
                p + "out_proj": ((di, h), "normal"),
            })
        elif kind == "gmu":
            spec.update({p + "in_proj": ((h, di), "normal"),
                         p + "out_proj": ((di, h), "normal")})
        else:
            spec.update({p + "q_w": ((h, heads * d), "normal"),
                         p + "q_b": ((heads * d,), "normal")})
            if kind != "cross":
                spec.update({p + "k_w": ((h, kvh * d), "normal"),
                             p + "k_b": ((kvh * d,), "normal"),
                             p + "v_w": ((h, kvh * d), "normal"),
                             p + "v_b": ((kvh * d,), "normal")})
            spec.update({p + "o_w": ((heads * d, h), "normal"),
                         p + "o_b": ((h,), "normal")})
            spec.update({p + n: ((d,), "lambda") for n in (
                "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")})
            spec[p + "subln"] = ((2 * d,), "scale")
        spec.update({
            p + "mlp_norm_g": ((h,), "scale"), p + "mlp_norm_b": ((h,), "normal"),
            p + "mlp_gate": ((h, f), "normal"), p + "mlp_up": ((h, f), "normal"),
            p + "mlp_down": ((f, h), "normal"),
        })
    spec.update({"final_norm_g": ((h,), "scale"),
                 "final_norm_b": ((h,), "normal")})
    return spec


# ------------------------------------------------------------ mathematics


def _rounded(x, precision):
    """`x` as it is, or rounded to int8 (per tensor) or bfloat16 and back:
    a control's operands (`block.matmul` does the same to its own)."""
    if precision == "int8":
        return block._fake_int8(x)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _product(eq, a, b, precision):
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
    return out.reshape(n * ROWS, *out.shape[2:])[:seq]


def mamba(w: dict, model: dict, u, precision="float32", fault=None):
    """(the layer's output, its scan output before the gate) over the
    whole sequence `u` [s, hidden]."""
    _, _, _, _, di = _sizes(model)
    ds, rank, taps = (model["mamba_d_state"], model["mamba_dt_rank"],
                      model["mamba_d_conv"])
    seq = u.shape[0]
    xz = block.matmul(u, w["in_proj"], precision)
    xs, z = xz[:, :di], xz[:, di:]
    padded = jnp.pad(xs, ((taps - 1, 0), (0, 0)))
    c = jax.nn.silu(
        sum(w["conv_w"][k] * padded[k:k + seq] for k in range(taps))
        + w["conv_b"])
    rbc = block.matmul(c, w["x_proj"], precision)
    step = jax.nn.softplus(
        block.matmul(rbc[:, :rank], w["dt_proj"], precision) + w["dt_bias"])
    b_in, c_out = rbc[:, rank:rank + ds], rbc[:, rank + ds:]
    a = -jnp.exp(w["A_log"])                                 # [inner, n]

    def update(s, inputs):
        step_t, c_t, b_t, c_o = inputs
        s = (jnp.exp(step_t[:, None] * a) * s
             + (step_t * c_t)[:, None] * b_t[None, :])
        return s, jnp.sum(s * c_o[None, :], axis=-1)

    s0 = jnp.zeros((di, ds), jnp.float32)
    if fault == "stale_state":
        s0, _ = jax.lax.scan(update, s0, (step, c, b_in, c_out))
    _, y = jax.lax.scan(update, s0, (step, c, b_in, c_out))
    y = y + w["D"] * c
    return block.matmul(y * jax.nn.silu(z), w["out_proj"], precision), y


def differential_attention(w: dict, model: dict, lam0, u, kv, window,
                           precision="float32"):
    """One layer's differential attention over the whole sequence `u` [s,
    hidden]. `lam0`: the layer's `lambda_init`. `kv`: (keys, values) [s,
    kv_heads, d] handed down (a cross layer) or None (the layer makes its
    own). Returns (output, (keys, values))."""
    h, heads, kvh, d, _ = _sizes(model)
    seq = u.shape[0]
    q = (block.matmul(u, w["q_w"], precision) + w["q_b"]).reshape(seq, heads, d)
    if kv is None:
        kv = tuple(
            (block.matmul(u, w[n + "_w"], precision) + w[n + "_b"]).reshape(
                seq, kvh, d) for n in ("k", "v"))
    k, v = kv
    groups = kvh // 2
    kg = k.reshape(seq, groups, 2, d)
    vg = v.reshape(seq, groups, 2 * d)
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0)
    s_pos = jnp.arange(seq)

    def rows(qb, first):
        t = first + jnp.arange(qb.shape[0])
        seen = s_pos[None, :] <= t[:, None]
        if window is not None:
            seen &= s_pos[None, :] > t[:, None] - window
        # head n = 4g + 2j + i: pair j of group g, softmax i of the pair
        qg = qb.reshape(qb.shape[0], groups, 2, 2, d)
        scores = _product("rgjid,sgid->gjirs", qg, kg, precision) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        probs = jnp.where(seen.any(-1)[:, None], probs, 0.0)   # padded rows
        both = _product("gjirs,sge->rgjie", probs, vg, precision)
        o = both[..., 0, :] - lam * both[..., 1, :]             # [r, g, j, 2d]
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + model["layer_norm_eps"])
        return (o * w["subln"] * (1.0 - lam0)).reshape(qb.shape[0], heads * d)

    ctx = _row_blocks(rows, seq, q)
    return block.matmul(ctx, w["o_w"], precision) + w["o_b"], kv


def gated_mlp(x, gate, up, down, precision="float32"):
    a = jax.nn.silu(block.matmul(x, gate, precision)) * block.matmul(
        x, up, precision)
    return block.matmul(a, down, precision)


def _layer(kind, w, x, memory, kv, lam0, model_key, precision, fault):
    """(the layer's output, its scan output where it is a state-space
    layer, the keys and values it hands on)."""
    model = _MODELS[model_key]
    eps = model["layer_norm_eps"]
    u = block.layer_norm(x, w["mixer_norm_g"], w["mixer_norm_b"], eps)
    y = None
    if kind == "mamba":
        a, y = mamba(w, model, u, precision, fault)
    elif kind == "gmu":
        a = block.matmul(
            memory * jax.nn.silu(block.matmul(u, w["in_proj"], precision)),
            w["out_proj"], precision)
    else:
        window = model["sliding_window"] if (
            kind == "window" and fault != "window_all") else None
        a, made = differential_attention(
            w, model, lam0, u, kv if kind == "cross" else None, window,
            precision)
        if kind == "full":
            kv = made
    x = x + a
    hidden = block.layer_norm(x, w["mlp_norm_g"], w["mlp_norm_b"], eps)
    return x + gated_mlp(hidden, w["mlp_gate"], w["mlp_up"], w["mlp_down"],
                         precision), y, kv


#: one compiled layer a kind
_LAYERS = {
    kind: jax.jit(functools.partial(_layer, kind),
                  static_argnames=("model_key", "precision", "fault"))
    for kind in ("mamba", "window", "full", "gmu", "cross")
}


#: configurations' `model` groups by a hashable key (jit's static argument)
_MODELS: dict = {}


def _model_key(model: dict) -> str:
    key = json.dumps(model, sort_keys=True)
    _MODELS[key] = model
    return key


def layer_weights(source, i: int) -> dict:
    """Layer `i`'s leaves under their short names, made alone: never more
    than a layer in float32."""
    prefix = f"layers.{i}."
    made = getattr(source, "_layer_generators", None)
    if made is None:
        made = source._layer_generators = {}
    if i not in made:
        names = frozenset(n for n in source.spec if n.startswith(prefix))
        made[i] = jax.jit(lambda key: source.generate(key, names))
    return {n[len(prefix):]: v for n, v in made[i](source.key()).items()}


def embedding(model: dict, source) -> list:
    return [source.leaf(f"embed.{j}") for j in range(model["vocab_blocks"])]


def forward(model: dict, source, ids, positions_out, precision="float32",
            fault=None, keep=None):
    """ids [s] -> logits [len(positions_out), vocab]; position i sees
    tokens 0..i. Layer by layer over the whole sequence. `keep`, a dict,
    is handed each layer's output under its index (tests)."""
    key = _model_key(model)
    ids = jnp.asarray(ids)
    table = embedding(model, source)
    rows = model["vocab_size"] // model["vocab_blocks"]
    x = sum(jnp.where((ids // rows == j)[:, None],
                      t[jnp.clip(ids - j * rows, 0, rows - 1)], 0.0)
            for j, t in enumerate(table))
    memory = kv = None
    for i, kind in enumerate(layer_kinds(model)):
        w = layer_weights(source, i)
        x, y, kv = _LAYERS[kind](
            w, x, memory, kv, jnp.float32(lambda_init(i)), model_key=key,
            precision=precision, fault=fault)
        if i == model["num_hidden_layers"] // 2:
            memory = y    # the Gated Memory Units' input from here on
        if keep is not None:
            keep[i] = np.asarray(x)
        del w
    x = block.layer_norm(
        x[jnp.asarray(positions_out)], source.leaf("final_norm_g"),
        source.leaf("final_norm_b"), model["layer_norm_eps"])
    # tied head, a block of positions and a block of rows at a time
    out = []
    for first in range(0, x.shape[0], HEAD_ROWS):
        part = x[first:first + HEAD_ROWS]
        out.append(jnp.concatenate(
            [block.matmul(part, t.T, precision) for t in table], axis=-1))
    return jnp.concatenate(out, axis=0)


def block_means(gaps, size: int) -> list:
    """The mean gap of each block of `size` consecutive served tokens of
    one request (a tail shorter than half a block joins the block
    before)."""
    gaps = np.asarray(gaps, np.float64)
    cuts = list(range(0, len(gaps), size))
    if len(cuts) > 1 and len(gaps) - cuts[-1] < size / 2:
        cuts.pop()
    return [float(gaps[a:b].mean())
            for a, b in zip(cuts, cuts[1:] + [len(gaps)])]


#: served tokens a block: what is judged is the widest mean gap over a
#: block of this many consecutive served tokens of a request
GAP_BLOCK = 256


def _summary(per_request: list) -> dict:
    """What a run prints of its gaps: the judged number first (the widest
    mean over `GAP_BLOCK` consecutive served tokens of a request), then
    the widest single gap and the bulk."""
    flat = np.concatenate(per_request)
    return {"judged": max(max(block_means(g, GAP_BLOCK)) for g in per_request),
            "tokens": int(flat.size), "max": float(flat.max()),
            "p99": float(np.percentile(flat, 99)), "mean": float(flat.mean())}


def served_token_gaps(config: dict, source, samples: list,
                      control: str | None = None) -> dict:
    """`source`: the run's seeded weights (`harness/weights.py::Source`),
    taken leaf by leaf. `samples`: (prompt ids, served ids) pairs. A served
    token's gap is how far its logit lies below the reference's best at
    its position; what is judged (`max_logit_gap`) is the widest MEAN gap
    over `GAP_BLOCK` consecutive served tokens of a request (a request
    shorter than a block and a half is one block), as `glm52_share16` has
    it. The widest SINGLE gap parts sound bfloat16 from the controls by 9
    times here (0.39-0.80 against 6.85: 32 layers of mixers of degree two
    to four hand one token's rounding on at a gain above one), the block
    mean by 69 (0.024-0.044 against 3.06; PERF.md section 2), so a fault
    that moves a stretch of the answer by a few tenths shows. With `control` (a
    precision, `int8`, or a name of `FAULTS`, or several joined by
    commas): the same number for the token the same mathematics so
    computed puts first at every position; the smallest over the controls
    is returned as the control's (a limit has to lie under every one).
    Every run prints a summary of its gaps, the controls' too."""
    model = config["model"]
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
        tokens = np.asarray(served, np.int32)
        ref = forward(model, source, ids, positions)
        best = np.asarray(ref.max(-1))
        at = np.arange(len(tokens))
        ref = np.asarray(ref)
        sound.append(best - ref[at, tokens])
        for c in controls:
            low = forward(
                model, source, ids, positions,
                **({"fault": c} if c in FAULTS else {"precision": c}))
            first = np.asarray(jnp.argmax(low, -1))
            del low
            low_gaps[c].append(best - ref[at, first])
    if not sound:
        return {"max_logit_gap": float("inf"), "tokens": 0}
    summary = {"served": _summary(sound)}
    summary.update({c: _summary(g) for c, g in low_gaps.items()})
    print(f"benchmark: reference gaps: {json.dumps(summary)}", flush=True)
    out = {"max_logit_gap": summary["served"]["judged"],
           "tokens": summary["served"]["tokens"]}
    if controls:
        out["controls"] = {c: summary[c]["judged"] for c in controls}
        out["control_max_logit_gap"] = min(out["controls"].values())
    return out
