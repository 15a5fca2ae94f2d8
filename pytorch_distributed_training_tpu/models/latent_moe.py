"""A latent-attention, sparse-selection, routed-expert decoder (flax.linen).

The block the GPT-2 family lacks, by mechanism:

- RMSNorm before attention and before the MLP, residual adds, a final
  RMSNorm and an UNTIED vocabulary head; rotary positions on interleaved
  pairs; gated SiLU MLPs.
- Latent attention (MLA): queries through a low-rank bottleneck, keys and
  values through ONE shared latent per token plus one rotary key for all
  heads. The cache holds the latent row, never per-head K/V. Two paths
  that compute the same function (``ops/latent_attention.py``): a fresh
  sequence EXPANDS the latents to per-head keys and values; a step over a
  paged cache ABSORBS the key up-projection into the query and the value
  up-projection into the output and attends in the latent.
- A learned sparse-attention indexer in the layers typed ``full``: its
  scores pick the ``index_topk`` cached positions a query attends to. A
  layer typed ``shared`` has no indexer weights and no indexer pool: it is
  handed the selection of the nearest ``full`` layer before it, a value
  passed from layer to layer inside the compiled step.
- Window layers (typed ``window``): latent attention of their own sizes
  (the ``swa_*`` fields: heads, low ranks, head dims, rotary base) over the
  latest ``sliding_window_size`` positions, the query's own included, and
  no indexer. A run of them is a selection group whose selection is the
  window itself.
- An optional headwise gate on the attention output (``attention_gate_type``
  / ``swa_attention_gate_type`` ``"headwise"``): ``o_h <- sigmoid(x . g_h)
  o_h`` before the output projection, ``x`` the layer's normed input
  (arXiv:2505.06708).
- Expert layers (``ops/moe.py``) that are told which experts they hold:
  the router scores all ``n_routed_experts``, the chip computes its own
  experts' part plus the shared expert.

Serving contract (``serve/engine.py``, ``serve/paged_cache.py``): the same
flax "cache" collection pattern as ``models/bert.py::_paged_attend``. A
selection GROUP (a ``full`` layer and the ``shared`` layers after it up to
the next ``full`` one: ``LatentMoEConfig.selection_groups``) keeps ONE
``latent_pages`` pool ``[pages, page_size, G * 640]`` (``LatentPool``, the
cache node ``latents_<g>``): layer ``j`` of the group owns columns ``[j *
640, (j + 1) * 640)``, a lane-dense row ``[c_kv 512 | k_rope 64 | 0]``.
The ``full`` layers keep an ``index_pages`` pool ``[pages, page_size,
128]`` of their own; one block table addresses all, so a prefix-cache hit
maps them together and copy-on-write copies all. A window group's pool has
rows of its own width (``window_row``: its latent plus rotary key, lane
padded) under the same block table: its rows live in pages, so a prefix
hit at any page boundary hands a window layer its last window of rows with
nothing to snapshot. Every step writes first and reads after. The decode
step (one token a sequence, seen from the input's shape) gathers a group's
chosen rows ONCE, in its first layer, and hands them on with the
``Selection``; a bucket prefill and a prefill chunk gather a layer's own
columns layer by layer, because their tokens attend to rows their own
chunk writes (a window layer: the contiguous span its block of queries
reaches, attended expanded under the band mask).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.ops import latent_attention as la
from pytorch_distributed_training_tpu.ops import moe

#: the scope of a window group's look-up, gather, attention and gate
WINDOW_SCOPE = "window_attn"


@dataclasses.dataclass
class LatentMoEConfig:
    """Sizes under the names of the family's published ``config.json``,
    then the serving fields the engine sets (as ``ModelConfig`` has them)."""

    vocab_size: int                 # rows held (a share of the published)
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int          # width of a dense layer's MLP
    moe_intermediate_size: int      # width of one expert
    n_routed_experts: int           # the router's outputs: ALL experts
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    mlp_layer_types: tuple          # "dense" | "sparse", one a layer
    # "full" | "shared" | "window", one a layer: a full layer chooses with
    # its indexer, a shared one takes the selection of the full layer
    # before it, a window layer attends to its latest sliding_window_size
    # positions with the swa_* sizes
    indexer_types: tuple
    max_position_embeddings: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    # "none" | "headwise": a sigmoid gate a head on the attention output of
    # the full and shared layers, and of the window layers
    attention_gate_type: str = "none"
    swa_attention_gate_type: str = "none"
    # the window layers' own sizes (unused without a "window" layer)
    sliding_window_size: int = 0
    swa_num_attention_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    # the experts THIS chip holds: (first, count) of the routed experts
    experts_held: tuple = (0, 0)
    # held experts stacked to one parameter leaf
    expert_block: int = 8
    # at or under this many tokens a step every held expert multiplies
    # every token (same work whatever the routing); above it the products
    # are grouped by expert (ops/moe.py)
    moe_dense_tokens: int = 64
    # queries a block when a multi-token step selects and attends
    attention_query_block: int = 128
    compute_dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    initializer_range: float = 0.02
    # ---- what the serving engine reads and sets (ModelConfig's names)
    causal: bool = True
    scan_layers: bool = False
    decode: bool = False
    kv_layout: str = "paged"
    kv_page_size: int = 16
    kv_num_pages: int = 0
    kv_cache_dtype: str = "auto"
    paged_multiquery: bool = False

    def __post_init__(self):
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        self.indexer_types = tuple(self.indexer_types)
        self.experts_held = tuple(int(v) for v in self.experts_held)
        if len(self.mlp_layer_types) != len(self.indexer_types):
            raise ValueError("mlp_layer_types and indexer_types differ in length")
        for i, kind in enumerate(self.indexer_types):
            before = self.indexer_types[i - 1] if i else None
            if kind not in ("full", "shared", "window"):
                raise ValueError(f"unknown indexer type {kind!r}")
            if kind == "shared" and before not in ("full", "shared"):
                raise ValueError(
                    f"layer {i} is typed 'shared' and follows {before!r}: a "
                    "'shared' layer takes its selection from a 'full' layer "
                    "before it")
        if "window" in self.indexer_types and self.sliding_window_size < 1:
            raise ValueError("window layers need sliding_window_size >= 1")
        for gate in (self.attention_gate_type, self.swa_attention_gate_type):
            if gate not in ("none", "headwise"):
                raise ValueError(f"unknown attention gate type {gate!r}")
        first, held = self.experts_held
        if "sparse" in self.mlp_layer_types:
            if held < 1 or first < 0 or first + held > self.n_routed_experts:
                raise ValueError(
                    f"experts_held {self.experts_held} is no range of the "
                    f"{self.n_routed_experts} routed experts")
            if held % self.expert_block:
                raise ValueError(
                    f"expert_block {self.expert_block} does not divide the "
                    f"{held} experts held")
        if self.scan_layers:
            raise ValueError("layers of different kinds cannot be scanned")

    @property
    def num_layers(self) -> int:
        return len(self.mlp_layer_types)

    @property
    def selection_groups(self) -> tuple:
        """(group, place in it) of every layer, and the groups' sizes: a
        group is a ``full`` layer and the ``shared`` ones that reuse its
        selection, or a run of ``window`` layers."""
        places, sizes = [], []
        before = None
        for kind in self.indexer_types:
            if kind == "full" or (kind == "window" and before != "window"):
                sizes.append(0)
            places.append((len(sizes) - 1, sizes[-1]))
            sizes[-1] += 1
            before = kind
        return tuple(places), tuple(sizes)

    @property
    def group_windows(self) -> tuple:
        """Whether each selection group is a run of window layers."""
        places, sizes = self.selection_groups
        first = [self.indexer_types[places.index((g, 0))]
                 for g in range(len(sizes))]
        return tuple(kind == "window" for kind in first)

    def attention_sizes(self, window: bool) -> AttentionSizes:
        """The sizes of a window layer's attention, or of a full or
        shared layer's."""
        if window:
            return AttentionSizes(
                self.swa_num_attention_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta, self.swa_attention_gate_type)
        return AttentionSizes(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, self.attention_gate_type)

    @property
    def latent_row(self) -> int:
        """Values of one cached latent row of a full or shared layer:
        latent plus rotary key, padded to whole lane tiles."""
        return la.lane_pad(self.kv_lora_rank + self.qk_rope_head_dim)

    @property
    def window_row(self) -> int:
        """Values of one cached latent row of a window layer (0 where the
        model has none)."""
        if "window" not in self.indexer_types:
            return 0
        return la.lane_pad(self.swa_kv_lora_rank + self.swa_qk_rope_head_dim)

    @property
    def window_rows_per_slot(self) -> Optional[int]:
        """Rows a decode step reads a sequence for its window group (None
        where the model has no window layer)."""
        if "window" not in self.indexer_types:
            return None
        return self.sliding_window_size

    def cache_values_per_token(self) -> int:
        """Resident pool values one token occupies over all layers."""
        full = sum(1 for t in self.indexer_types if t == "full")
        window = sum(1 for t in self.indexer_types if t == "window")
        return ((self.num_layers - window) * self.latent_row
                + window * self.window_row + full * self.index_head_dim)

    def check_serving(self, engine) -> None:
        """Refuse, by the flag's name, what this family's serving path does
        not have (``engine``: an ``EngineConfig`` or the parsed CLI
        arguments: the same attribute names)."""
        def bad(flag, why):
            raise ValueError(
                f"{flag} is not supported for a latent-attention "
                f"expert model: {why}")

        if getattr(engine, "tp", 1) != 1:
            bad("--tp", "the latent pools have no head axis to shard and "
                "no expert axis exists yet")
        if getattr(engine, "spec_k", 0):
            bad("--spec-k", "no draft lane (the multi-token-prediction "
                "block is not loaded)")
        if getattr(engine, "weights_dtype", "bfloat16") == "int8":
            bad("--weights-dtype int8", "expert leaves have no int8 form; "
                "pass --weights-dtype bfloat16")
        if getattr(engine, "kv_dtype", "float32") == "int8":
            bad("--kv-dtype int8", "the latent pools have no scale pools")


class AttentionSizes(NamedTuple):
    """One kind of layer's attention sizes (``LatentMoEConfig.
    attention_sizes``)."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    gate: str


def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _init(cfg):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class RMSNorm(nn.Module):
    eps: float
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (x32 * scale.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    """x [..., k] @ w [k, ...]: compute-dtype operands, float32 sums."""
    return jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


class GatedMLP(nn.Module):
    """``down(silu(gate x) * up x)``."""

    config: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = cfg.hidden_size
        gate = self.param("gate", _init(cfg), (h, self.width), _pdt(cfg))
        up = self.param("up", _init(cfg), (h, self.width), _pdt(cfg))
        down = self.param("down", _init(cfg), (self.width, h), _pdt(cfg))
        a = (jax.nn.silu(_mm(x, gate)) * _mm(x, up)).astype(x.dtype)
        return _mm(a, down)


class ExpertLayer(nn.Module):
    """Router over all experts, this chip's experts' part, the shared
    expert. Sows the step's routing counts into the ``routing`` collection
    where the caller asks for it."""

    config: LatentMoEConfig

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.config
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        first, held = cfg.experts_held
        block = cfg.expert_block
        router = self.param(
            "router", _init(cfg), (h, cfg.n_routed_experts), jnp.float32)
        bias = self.param(
            "router_bias", nn.initializers.zeros, (cfg.n_routed_experts,),
            jnp.float32)
        experts = []
        for j in range(held // block):
            experts.append(tuple(
                self.param(f"experts_{j}_{name}", _init(cfg), shape, _pdt(cfg))
                for name, shape in (("gate", (block, h, f)),
                                    ("up", (block, h, f)),
                                    ("down", (block, f, h)))))
        lead = x.shape[:-1]
        flat = x.reshape(-1, h)
        with jax.named_scope("moe"):
            chosen, weights = moe.route(
                flat, router, bias, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor)
            if self.is_mutable_collection("routing"):
                mask = None if token_mask is None else token_mask.reshape(-1)
                per_expert, absent = moe.routing_counts(
                    chosen, first, held, mask)
                self.sow("routing", "held_tokens", per_expert,
                         reduce_fn=lambda a, b: a + b,
                         init_fn=lambda: jnp.zeros((held,), jnp.int32))
                self.sow("routing", "absent_pairs", absent,
                         reduce_fn=lambda a, b: a + b,
                         init_fn=lambda: jnp.zeros((), jnp.int32))
            product = (moe.dense_experts
                       if flat.shape[0] <= cfg.moe_dense_tokens
                       else moe.grouped_experts)
            routed = product(flat, chosen, weights, first, experts)
            with jax.named_scope("moe.shared"):
                shared = GatedMLP(
                    cfg, f * cfg.n_shared_experts, name="shared")(flat)
            return (routed + shared).reshape(*lead, h)


class LatentPool(nn.Module):
    """One selection group's latent rows: the cache variables
    ``latent_pages`` [pages, page_size, layers * row] (``row``: the
    ``latent_row``, or a window group's ``window_row``), the group's layers
    side by side in a token's row, and the block table every pool of the
    group is read through. Made by the model, which hands the two variables
    to the group's layers."""

    config: LatentMoEConfig
    layers: int
    window: bool = False

    @nn.compact
    def __call__(self):
        cfg = self.config
        if cfg.kv_num_pages < 2:
            raise ValueError(
                "paged serving needs kv_num_pages >= 2 (page 0 is the "
                f"reserved null page), got {cfg.kv_num_pages}")
        row = cfg.window_row if self.window else cfg.latent_row
        shape = (cfg.kv_num_pages, cfg.kv_page_size, self.layers * row)
        pages = self.variable(
            "cache", "latent_pages", lambda: jnp.zeros(shape, _cdt(cfg)))
        # a placeholder: the engine supplies it per call (with_tables)
        block_table = self.variable(
            "cache", "block_table", lambda: jnp.zeros((1, 1), jnp.int32))
        return pages, block_table


class LatentAttention(nn.Module):
    """MLA with an optional indexer (``indexer=True``: a layer typed
    ``full``), or over a window (``window=True``: a layer typed
    ``window``, of its own sizes), at ``place`` in its selection group,
    whose ``pool`` (the variables of a ``LatentPool``) it is handed when
    serving. Returns (output, selection): the selection is this layer's own
    where it has an indexer or is the first of a window group, the one it
    was handed otherwise."""

    config: LatentMoEConfig
    indexer: bool
    place: int = 0
    window: bool = False

    @nn.compact
    def __call__(self, x, positions, selection, pool=None):
        cfg = self.config
        dt = _cdt(cfg)
        sizes = cfg.attention_sizes(self.window)
        h, heads = cfg.hidden_size, sizes.heads
        dn, dr, dv = sizes.nope, sizes.rope, sizes.v
        rank = sizes.kv_lora_rank
        init, pdt = _init(cfg), _pdt(cfg)
        q_a = self.param("q_a", init, (h, sizes.q_lora_rank), pdt)
        q_b = self.param("q_b", init, (sizes.q_lora_rank, heads, dn + dr), pdt)
        # the latent and the rotary key are two projections of x (one
        # leaf each: they are drawn, loaded and sharded apart)
        kv_a_latent = self.param("kv_a_latent", init, (h, rank), pdt)
        kv_a_rope = self.param("kv_a_rope", init, (h, dr), pdt)
        kv_b_k = self.param("kv_b_k", init, (rank, heads, dn), pdt)
        kv_b_v = self.param("kv_b_v", init, (rank, heads, dv), pdt)
        o_w = self.param("o", init, (heads, dv, h), pdt)
        gate = None
        if sizes.gate == "headwise":
            gate = self.param("gate", init, (h, heads), pdt)

        cq = RMSNorm(cfg.rms_norm_eps, pdt, name="q_a_norm")(
            _mm(x, q_a).astype(dt))
        q = _mm(cq, q_b).astype(dt)                       # [b, t, heads, dn+dr]
        ckv = RMSNorm(cfg.rms_norm_eps, pdt, name="kv_a_norm")(
            _mm(x, kv_a_latent).astype(dt))
        cos, sin = la.rope_angles(positions, dr, sizes.theta)
        k_rope = la.apply_rope(_mm(x, kv_a_rope).astype(dt), cos, sin)
        q_nope = q[..., :dn]
        q_rope = la.apply_rope(q[..., dn:], cos[:, :, None], sin[:, :, None])
        scale = (dn + dr) ** -0.5

        qi = ki = wi = None
        if self.indexer:
            ih, idim = cfg.index_n_heads, cfg.index_head_dim
            iq_w = self.param("index_q", init, (sizes.q_lora_rank, ih, idim), pdt)
            ik_w = self.param("index_k", init, (h, idim), pdt)
            iw_w = self.param("index_w", init, (h, ih), pdt)
            qi = _mm(cq, iq_w).astype(dt)
            ki = nn.LayerNorm(
                epsilon=cfg.index_norm_eps, dtype=dt, param_dtype=pdt,
                name="index_k_norm")(_mm(x, ik_w).astype(dt))
            # the rotary span is the first qk_rope_head_dim dims of both
            qi = jnp.concatenate([
                la.apply_rope(qi[..., :dr], cos[:, :, None], sin[:, :, None]),
                qi[..., dr:]], axis=-1)
            ki = jnp.concatenate(
                [la.apply_rope(ki[..., :dr], cos, sin), ki[..., dr:]], axis=-1)
            wi = _mm(x, iw_w) * (ih * idim) ** -0.5       # float32

        serving = cfg.decode and not self.is_initializing()
        if serving:
            # the decode step, a prefill chunk and a bucket prefill alike
            # (a bucket is one chunk at context 0)
            row, index = self._write(pool, ckv, k_rope, ki, positions)
            if self.window:
                ctx, selection = self._window_paged(
                    pool, row, q_nope, q_rope, positions, selection,
                    kv_b_k, kv_b_v, scale)
            else:
                ctx, selection = self._paged(
                    pool, index, row, q_nope, q_rope, qi, wi, positions,
                    selection, kv_b_k, kv_b_v, scale)
        else:
            # no cache (training, evaluation, the tests' comparisons, and
            # declaring the cache's shapes): the sequence's own latents are
            # the whole context
            ctx, selection = self._fresh(
                q_nope, q_rope, ckv, k_rope, qi, ki, wi, positions,
                selection, kv_b_k, kv_b_v, scale)
            if cfg.decode:
                self._index_pool()   # initializing: declare the cache's shapes
        if gate is not None:
            # the headwise gate, from the layer's normed input; a window
            # layer's under its attention's scope
            with (jax.named_scope(WINDOW_SCOPE) if self.window
                  else contextlib.nullcontext()):
                ctx = ctx * jax.nn.sigmoid(_mm(x, gate))[..., None]
        out = jnp.einsum("bqhv,hvd->bqd", ctx.astype(dt), o_w.astype(dt),
                         preferred_element_type=jnp.float32)
        if self.is_mutable_collection("selection"):
            self.sow("selection", "positions", selection.positions)
            self.sow("selection", "valid", selection.valid)
        return out.astype(dt), selection

    # ---------------------------------------------------------- fresh path

    def _fresh(self, q_nope, q_rope, ckv, k_rope, qi, ki, wi, positions,
               selection, kv_b_k, kv_b_v, scale):
        """A sequence over itself, no cache: EXPAND the latents. Not
        blocked over queries (the causal square is whole): for sequences
        of the tests' and a trainer's lengths, never the server's."""
        cfg = self.config
        seq = ckv.shape[1]
        if self.window:
            return self._expanded(
                q_nope, q_rope, ckv, k_rope, kv_b_k, kv_b_v,
                la.window_mask(positions, positions, cfg.sliding_window_size),
                scale), la.window_selection(positions, cfg.sliding_window_size)
        if self.indexer:
            selection = la.select_topk(
                la.fresh_index_scores(qi, wi, ki, positions), cfg.index_topk,
                positions)
        ctx = self._expanded(
            q_nope, q_rope, ckv, k_rope, kv_b_k, kv_b_v,
            la.select_mask(selection, seq), scale)
        return ctx, selection

    def _expanded(self, q_nope, q_rope, ckv, k_rope, kv_b_k, kv_b_v, mask,
                  scale):
        """Per-head keys and values from latents ``ckv`` [b, s, rank] and
        attention under ``mask`` [b, q, s]."""
        dt = ckv.dtype
        k_nope = jnp.einsum("bsc,chd->bshd", ckv, kv_b_k.astype(dt),
                            preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("bsc,chv->bshv", ckv, kv_b_v.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        return la.expanded_attention(
            q_nope, q_rope, k_nope, k_rope, v, mask, scale,
            **({"scope": WINDOW_SCOPE} if self.window else {}))

    # ---------------------------------------------------------- paged path

    def _index_pool(self):
        """The indexer keys' pool of a ``full`` layer (None in a ``shared``
        one), under the group's block table."""
        cfg = self.config
        if not self.indexer:
            return None
        return self.variable(
            "cache", "index_pages",
            lambda: jnp.zeros((cfg.kv_num_pages, cfg.kv_page_size,
                               cfg.index_head_dim), _cdt(cfg)))

    def _write(self, pool, ckv, k_rope, ki, positions):
        """This step's rows into the pools, through the block table: the
        latent row ``[c_kv | k_rope | 0]`` into this layer's columns of
        the group's pool and, with an indexer, its key. Returns the rows
        and the indexer pool."""
        dt = ckv.dtype
        index = self._index_pool()
        batch, chunk = positions.shape
        width = self._row()
        pad = width - ckv.shape[-1] - k_rope.shape[-1]
        rows = jnp.concatenate(
            [ckv, k_rope, jnp.zeros((batch, chunk, pad), dt)], axis=-1)
        latent, bt = pool
        latent.value = la.write_rows(
            latent.value, bt.value, positions, rows, self.place * width)
        if self.indexer:
            index.value = la.write_rows(index.value, bt.value, positions, ki)
        return rows, index

    def _row(self) -> int:
        cfg = self.config
        return cfg.window_row if self.window else cfg.latent_row

    def _absorbed_query(self, q_nope, q_rope, kv_b_k):
        """The query with the key up-projection absorbed, laid out as a
        pool row: ``[q_nope . W_uk | q_rope | 0]``."""
        dt = q_nope.dtype
        batch, chunk, heads = q_rope.shape[:3]
        pad = self._row() - kv_b_k.shape[0] - q_rope.shape[-1]
        q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, kv_b_k.astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        return jnp.concatenate([
            q_lat, q_rope, jnp.zeros((batch, chunk, heads, pad), dt)], axis=-1)

    def _values(self, ctx, kv_b_v):
        """The probability-weighted latent ``ctx`` [b, q, heads, row], up
        to per-head values [b, q, heads, v] (float32)."""
        dt = _cdt(self.config)
        return jnp.einsum(
            "bqhc,chv->bqhv", ctx[..., :kv_b_v.shape[0]].astype(dt),
            kv_b_v.astype(dt), preferred_element_type=jnp.float32)

    def _window_paged(self, pool, row, q_nope, q_rope, positions, selection,
                      kv_b_k, kv_b_v, scale):
        """A window layer through the block table, after the step's own
        rows are written. The decode step: the group's first layer fetches
        the window's rows of the whole group ONCE (one wide row a position,
        ``fetch_group_rows``) and every layer attends in the latent (ABSORB),
        the later layers with the row they have just written in place of
        the gathered one. A multi-token step: each block of queries fetches
        the span it reaches, this layer's columns of it, and attends
        EXPANDED under the band mask."""
        cfg = self.config
        window = cfg.sliding_window_size
        latent, bt = pool
        chunk = positions.shape[1]
        width, rank = self._row(), kv_b_k.shape[0]
        column = self.place * width
        scope = {"scope": WINDOW_SCOPE}
        if chunk == 1:
            q_row = self._absorbed_query(q_nope, q_rope, kv_b_k)
            if self.place == 0:
                selection = la.fetch_group_rows(
                    latent.value, bt.value,
                    la.window_selection(positions, window), **scope)
                rows = la.group_slice(selection, column, width, **scope)
            else:
                rows = la.group_slice(
                    selection, column, width, row, positions, **scope)
            ctx = la.latent_attention(
                q_row, rows, selection.valid, scale, **scope)
            return self._values(ctx, kv_b_v), selection

        def block(q_nope, q_rope, positions):
            span = la.window_span(positions, window)
            sel = la.look_up_rows(
                la.Selection(jnp.maximum(span, 0)[:, None], (span >= 0)[:, None]),
                bt.value, cfg.kv_page_size, **scope)
            kept = la.gather_rows(latent.value, sel, **scope)[
                :, 0, :, column:column + width]
            dr = q_rope.shape[-1]
            return self._expanded(
                q_nope, q_rope, kept[..., :rank],
                kept[..., rank:rank + dr], kv_b_k, kv_b_v,
                la.window_mask(positions, span, window), scale)

        ctx = _in_query_blocks(
            block, (q_nope, q_rope, positions), cfg.attention_query_block)
        return ctx, la.window_selection(positions, window)

    def _paged(self, pool, index, row, q_nope, q_rope, qi, wi, positions,
               selection, kv_b_k, kv_b_v, scale):
        """Select, gather and attend in the latent through the block
        table, after the step's own rows are written: ABSORB. The decode
        step (one token a sequence) fetches the group's rows once, in the
        choosing layer; a prefill chunk at a nonzero context and a bucket
        prefill at context 0 gather this layer's columns."""
        cfg = self.config
        latent, bt = pool
        chunk = positions.shape[1]
        column = self.place * cfg.latent_row
        q_row = self._absorbed_query(q_nope, q_rope, kv_b_k)

        def values(ctx):
            return self._values(ctx, kv_b_v)

        def choose(qi, wi, positions):
            return la.select_topk(
                la.index_scores(qi, wi, index.value, bt.value, positions),
                cfg.index_topk, positions)

        if chunk == 1:
            # every later layer of the group finds its rows in the choosing
            # layer's one fetch, all but the row it has just written
            if self.indexer:
                selection = la.fetch_group_rows(
                    latent.value, bt.value, choose(qi, wi, positions))
                rows = la.group_slice(selection, column, cfg.latent_row)
            else:
                rows = la.group_slice(
                    selection, column, cfg.latent_row, row, positions)
            ctx = la.latent_attention(q_row, rows, selection.valid, scale)
            return values(ctx), selection

        # a multi-token step: its tokens attend to rows of their own chunk,
        # which the group's later layers have yet to write, so each layer
        # gathers for itself, out of its own columns
        own = la.layer_pool(latent.value, column, cfg.latent_row)

        def block(q_row, qi, wi, positions, selection):
            if self.indexer:
                selection = la.look_up_rows(
                    choose(qi, wi, positions), bt.value, cfg.kv_page_size)
            rows = la.gather_rows(own, selection)
            ctx = la.latent_attention(q_row, rows, selection.valid, scale)
            return values(ctx), selection

        # the indexer's [queries, heads, context] float32 scores and the
        # gathered rows are bounded by the block, not by the chunk or the
        # bucket
        return _in_query_blocks(
            block, (q_row, qi, wi, positions,
                    None if self.indexer else selection),
            cfg.attention_query_block)


def _in_query_blocks(fn, args, block: int):
    """``fn(*args)`` over blocks of ``block`` queries: every array of
    ``args`` (a tree; None passes) has the queries on axis 1, and so has
    every array ``fn`` returns. One call where the queries fit one block;
    else a ``lax.map`` over blocks, a last block that is not whole filled
    with copies of the last query and cut off again."""
    batch, chunk = jax.tree.leaves(args)[0].shape[:2]
    if chunk <= block:
        return fn(*args)
    n = -(-chunk // block)

    def split(t):
        t = jnp.pad(t, [(0, 0), (0, n * block - chunk)]
                    + [(0, 0)] * (t.ndim - 2), mode="edge")
        return jnp.moveaxis(t.reshape(batch, n, block, *t.shape[2:]), 1, 0)

    def merge(t):
        return jnp.moveaxis(t, 0, 1).reshape(
            batch, n * block, *t.shape[3:])[:, :chunk]

    out = jax.lax.map(lambda a: fn(*a), jax.tree.map(split, args))
    return jax.tree.map(merge, out)


class DecoderLayer(nn.Module):
    config: LatentMoEConfig
    sparse: bool
    indexer: bool
    place: int = 0
    window: bool = False

    @nn.compact
    def __call__(self, x, positions, selection, token_mask, pool=None):
        cfg = self.config
        pdt = _pdt(cfg)
        h = RMSNorm(cfg.rms_norm_eps, pdt, name="attention_norm")(x)
        a, selection = LatentAttention(
            cfg, self.indexer, self.place, self.window, name="attention")(
            h, positions, selection, pool)
        x = x + a
        h = RMSNorm(cfg.rms_norm_eps, pdt, name="mlp_norm")(x)
        if self.sparse:
            m = ExpertLayer(cfg, name="experts")(h, token_mask)
        else:
            with jax.named_scope("dense_mlp"):
                m = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + m.astype(x.dtype), selection


class LatentMoELM(nn.Module):
    """Embedding -> layers -> final RMSNorm -> untied head. Signature as
    ``GPT2LMModel``'s (the serving engine drives either): ``position_ids``
    [batch, seq] are the tokens' positions; ``token_mask`` [batch, seq]
    marks the tokens the routing counts take (all where None)."""

    config: LatentMoEConfig

    @property
    def trace_scopes(self) -> tuple:
        """The ``jax.named_scope`` names a device trace is read by
        (``analysis/spmd/hlo.scope_instructions``; ops/latent_attention.py,
        ops/moe.py), ``window_attn`` where the model has window layers."""
        scopes = ("sparse_attn.index_scores", "sparse_attn.topk",
                  "sparse_attn.gather", "sparse_attn.attend", "moe")
        if "window" in self.config.indexer_types:
            scopes += (WINDOW_SCOPE,)
        return scopes

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 position_ids=None, deterministic: bool = True,
                 token_mask=None):
        cfg = self.config
        if attention_mask is not None:
            raise ValueError(
                "padding is expressed through positions and context_len")
        batch, seq = input_ids.shape
        if position_ids is None:
            if cfg.decode and not self.is_initializing():
                raise ValueError("paged serving passes position_ids")
            position_ids = jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
        dt = _cdt(cfg)
        embed = self.param("embed", _init(cfg),
                           (cfg.vocab_size, cfg.hidden_size), _pdt(cfg))
        head = self.param("head", _init(cfg),
                          (cfg.hidden_size, cfg.vocab_size), _pdt(cfg))
        x = embed[input_ids].astype(dt)
        selection: Optional[la.Selection] = None
        places, sizes = cfg.selection_groups
        pools = [LatentPool(cfg, size, window, name=f"latents_{g}")()
                 for g, (size, window) in enumerate(
                     zip(sizes, cfg.group_windows))] if cfg.decode else None
        for i, (mlp, idx) in enumerate(
                zip(cfg.mlp_layer_types, cfg.indexer_types)):
            group, place = places[i]
            x, selection = DecoderLayer(
                cfg, mlp == "sparse", idx == "full", place, idx == "window",
                name=f"layer_{i}")(
                x, position_ids, selection, token_mask,
                pools[group] if pools else None)
        x = RMSNorm(cfg.rms_norm_eps, _pdt(cfg), name="final_norm")(x)
        return _mm(x, head)


#: published sizes of the family's presets (``utils/config.model_preset``
#: finds them here); a ``-shareN`` preset is the cut a chip holds when N
#: chips share each layer
PRESETS: dict[str, dict[str, Any]] = {
    # https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json, cut
    # as benchmarks/configs/glm52_share16.json states: layers 2..7 of 78,
    # experts 0..15 of 256, an eighth of the vocabulary, no MTP block
    "glm-5.2-share16": dict(
        vocab_size=19360, hidden_size=6144, num_attention_heads=64,
        q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, intermediate_size=12288,
        moe_intermediate_size=2048, n_routed_experts=256,
        num_experts_per_tok=8, n_shared_experts=1,
        routed_scaling_factor=2.5, index_n_heads=32, index_head_dim=128,
        index_topk=2048,
        mlp_layer_types=("dense",) + ("sparse",) * 5,
        indexer_types=("full", "shared", "shared", "shared", "full", "shared"),
        max_position_embeddings=1048576, rope_theta=8e6, rms_norm_eps=1e-5,
        experts_held=(0, 16), expert_block=8,
    ),
    # https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json,
    # cut as benchmarks/configs/dots3_share8.json states: 8 chips share each
    # layer; the published layers 0..4 of 46 (full/dense, full, window x3:
    # the leading dense layer and one whole period), experts 0..31 of 256,
    # an eighth of the vocabulary; no vision or audio tower, no MTP block
    "dots3-note-share8": dict(
        vocab_size=19008, hidden_size=5120, num_attention_heads=128,
        q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=13824,
        moe_intermediate_size=1536, n_routed_experts=256,
        num_experts_per_tok=8, n_shared_experts=1,
        routed_scaling_factor=1.0, index_n_heads=64, index_head_dim=128,
        index_topk=2048,
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        indexer_types=("full", "full", "window", "window", "window"),
        max_position_embeddings=524288, rope_theta=8e7, rms_norm_eps=1e-5,
        attention_gate_type="headwise", swa_attention_gate_type="headwise",
        sliding_window_size=513, swa_num_attention_heads=64,
        swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
        swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64, swa_v_head_dim=128,
        swa_rope_theta=5e4,
        experts_held=(0, 32), expert_block=8,
    ),
    # the CPU tests' size: every mechanism, contexts past index_topk
    "latent-moe-tiny": dict(
        vocab_size=512, hidden_size=64, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1,
        routed_scaling_factor=2.5, index_n_heads=4, index_head_dim=16,
        index_topk=8,
        mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
        indexer_types=("full", "shared", "full", "shared"),
        max_position_embeddings=4096, rope_theta=8e6, rms_norm_eps=1e-5,
        experts_held=(0, 2), expert_block=1, moe_dense_tokens=4,
        attention_query_block=4, compute_dtype="float32",
        param_dtype="float32",
    ),
    # the CPU tests' size of dots3-note-share8's pattern: two latent widths
    # (rows of 128 and 256 lanes), two head counts, gates, a window of 9
    # (2 pages of 4 and the query, as 513 is 32 pages of 16 and the query);
    # contexts run past the window and past index_topk
    "dots3-tiny": dict(
        vocab_size=512, hidden_size=64, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1,
        routed_scaling_factor=1.0, index_n_heads=4, index_head_dim=16,
        index_topk=8,
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        indexer_types=("full", "full", "window", "window", "window"),
        max_position_embeddings=4096, rope_theta=8e7, rms_norm_eps=1e-5,
        attention_gate_type="headwise", swa_attention_gate_type="headwise",
        sliding_window_size=9, swa_num_attention_heads=2,
        swa_q_lora_rank=32, swa_kv_lora_rank=128, swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4,
        experts_held=(0, 2), expert_block=1, moe_dense_tokens=4,
        attention_query_block=4, compute_dtype="float32",
        param_dtype="float32",
    ),
}


def preset(name: str, **overrides: Any) -> LatentMoEConfig:
    kwargs = dict(PRESETS[name])
    kwargs.update(overrides)
    return LatentMoEConfig(**kwargs)
