"""A decoder-hybrid-decoder (SambaY) language model (flax.linen): state-space
layers, window attention, ONE full-attention layer whose K/V every later
attention layer reads, and Gated Memory Units fed by one state-space
layer's scan output.

The block neither older family has, by mechanism:

- LayerNorm (gain and bias, ``ops/layer_norm.FusedLayerNorm``) before the
  mixer and before the MLP, residual adds, a gated SiLU MLP
  (``models/latent_moe.GatedMLP``), a final LayerNorm and a head TIED to the
  embedding; NO positions of any kind (no table, no rotary).
- Five kinds of mixer, by the layer's index ``l`` of ``L`` (``half = L //
  2``; ``SambaYConfig.layer_kinds``): ``l <= half`` even: a selective
  state-space layer (Mamba-1: causal depthwise convolution, input-dependent
  step, diagonal state); ``l < half`` odd: differential attention over the
  latest ``sliding_window`` positions; ``l == half + 1``: differential
  attention over all positions, THE cache of the upper half; above it, even:
  a Gated Memory Unit ``W_out (m * silu(W_in h))`` with ``m`` layer
  ``half``'s scan output before its gate; odd: cross attention with its own
  query and output projections over layer ``half + 1``'s K/V.
- Differential attention with fewer K/V heads than query heads: query
  heads pair up (``2p, 2p + 1``), two pairs share a K/V group of two heads;
  ``o_p = (softmax(q_2p k_2g) - lam softmax(q_2p+1 k_2g+1)) [v_2g; v_2g+1]``
  (``ops/paged_attention.differential_*``).

Serving contract (``serve/engine.py``, ``serve/paged_cache.py``): three
kinds of per-sequence memory, declared by ``SambaYConfig.slot_memory`` as
``paged_cache.SlotMemory`` entries and found by their path, not by the
names of their leaves:

- ``pages``, one: layer ``half + 1``'s ``k_pages``/``v_pages`` ``[pages,
  page_size, kv_heads * head_dim]`` (lane-dense), addressed through the
  block table; layer ``half + 1`` writes, it and every cross layer read
  (the updated pools are handed down the stack as a ``SharedKV``, as the
  latent family hands its ``Selection`` on).
- ``ring``, one a window layer: ``k_ring``/``v_ring`` ``[slots,
  sliding_window, kv_heads * head_dim]``, a token's row at ``position %
  sliding_window`` of its slot. Never reset: a row whose position would be
  negative is masked (keys carry no positions, so a ring is read as the
  set of its live rows).
- ``state``, one a state-space layer: ``ssm`` ``[slots, d_state, inner]``
  float32 (the inner width on the lanes) and ``conv`` ``[slots, d_conv - 1, inner]``, the last inputs of
  the convolution. Reset by the step itself: a step at context 0 starts
  from zeros.

Per call every memory's node is handed ``context_len`` [batch] and, in a
prefill (batch 1), ``slot`` [batch] (the slot each batch row is; absent:
row ``b`` is slot ``b``, the decode step) and ``chunk_len`` [batch] (the
REAL tokens of the step: a padded chunk's pad tokens change no state and
no ring; absent: one where ``context_len > 0``, none in an idle slot). A
prefill runs layers ``0 .. half + 1`` over its chunk and the upper half for
ONE row, ``logit_index``, the only one whose logits are used: the upper
half keeps nothing, which is what the family is built for.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.models.latent_moe import GatedMLP, _mm
from pytorch_distributed_training_tpu.ops import paged_attention as pa
from pytorch_distributed_training_tpu.ops import selective_scan as ss
from pytorch_distributed_training_tpu.ops.layer_norm import FusedLayerNorm


@dataclasses.dataclass
class SambaYConfig:
    """Sizes under the names of the family's published ``config.json``,
    the sizes it has no key for (the family's convention), then the
    serving fields the engine sets (as ``ModelConfig`` has them)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    sliding_window: int
    max_position_embeddings: int
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    # ---- no key in the published config: Mamba-1's convention
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0          # 0: ceil(hidden_size / 16)
    #: DEBT (ROADMAP C12): the embedding (and tied head) in this many leaves
    #: of rows, because the benchmark's install generates a leaf of at most
    #: 512 MiB in float32 (harness/adapters.py); the published model has ONE
    vocab_blocks: int = 1
    # queries a block when a multi-token step attends
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
    kv_num_slots: int = 0
    kv_cache_dtype: str = "auto"
    paged_multiquery: bool = False

    def __post_init__(self):
        if self.mamba_dt_rank == 0:
            self.mamba_dt_rank = math.ceil(self.hidden_size / 16)
        half = self.num_hidden_layers // 2
        if self.num_hidden_layers % 2 or half % self.mb_per_layer or (
                self.num_hidden_layers < 4):
            raise ValueError(
                f"{self.num_hidden_layers} layers do not split into a "
                f"self-decoder that ends on a state-space layer and a "
                f"cross-decoder that begins on the full-attention one")
        if self.num_attention_heads % 4 or (
                self.num_attention_heads != 2 * self.num_key_value_heads):
            raise ValueError(
                "differential attention here pairs query heads two to a "
                "K/V group of two: num_attention_heads must be twice "
                "num_key_value_heads and a multiple of 4")
        if self.vocab_size % self.vocab_blocks:
            raise ValueError("vocab_blocks does not divide vocab_size")
        if self.scan_layers:
            raise ValueError("layers of different kinds cannot be scanned")

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_width(self) -> int:
        """Values of one token's K (or V) row: the pools' lane axis."""
        return self.num_key_value_heads * self.head_dim

    @property
    def inner_size(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_kinds(self) -> tuple:
        """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``, one a
        layer; layer ``L // 2`` is the state-space layer whose scan output
        the Gated Memory Units take."""
        half = self.num_hidden_layers // 2
        kinds = []
        for l in range(self.num_hidden_layers):
            if l <= half:
                kinds.append("mamba" if l % self.mb_per_layer == 0 else "window")
            elif l == half + 1:
                kinds.append("full")
            else:
                kinds.append("gmu" if (l - half) % 2 == 0 else "cross")
        return tuple(kinds)

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def slot_memory(self) -> tuple:
        """What each layer keeps per sequence while serving, as the
        engine's cache interface names it (``paged_cache.SlotMemory``)."""
        from pytorch_distributed_training_tpu.serve.paged_cache import (
            SlotMemory,
        )

        item = jnp.dtype(self.compute_dtype).itemsize
        kinds = self.layer_kinds
        out = []
        for l, kind in enumerate(kinds):
            path = (f"layer_{l}", "mixer")
            if kind == "full":
                out.append(SlotMemory(
                    "pages", path, bytes_per_token=2 * self.kv_width * item,
                    readers=1 + kinds.count("cross")))
            elif kind == "window":
                out.append(SlotMemory(
                    "ring", path, bytes_per_slot=(
                        2 * self.sliding_window * self.kv_width * item)))
            elif kind == "mamba":
                out.append(SlotMemory(
                    "state", path, bytes_per_slot=self.inner_size * (
                        4 * self.mamba_d_state
                        + (self.mamba_d_conv - 1) * item)))
        return tuple(out)

    def check_serving(self, engine) -> None:
        """Refuse, by the flag's name, what this family's serving path does
        not have (``engine``: an ``EngineConfig`` or the parsed CLI
        arguments: the same attribute names)."""
        def bad(flag, why):
            raise ValueError(
                f"{flag} is not supported for a state-space hybrid "
                f"model: {why}")

        if getattr(engine, "tp", 1) != 1:
            bad("--tp", "the scan's inner width has no sharding rule yet")
        if getattr(engine, "spec_k", 0):
            bad("--spec-k", "a rejected draft would need the recurrent "
                "state and the rings rolled back")
        if getattr(engine, "prefix_cache", False):
            bad("--prefix-cache", "a prefix hit would need a snapshot of "
                "state and rings at the prefix's end")
        if getattr(engine, "weights_dtype", "bfloat16") == "int8":
            bad("--weights-dtype int8", "the scan's leaves have no int8 "
                "form; pass --weights-dtype bfloat16")
        if getattr(engine, "kv_dtype", "float32") == "int8":
            bad("--kv-dtype int8", "rings and states have no scale pools")


def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _init(cfg):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class SharedKV(NamedTuple):
    """Layer ``half + 1``'s keys and values, handed down the stack. Without
    a cache: ``k``/``v`` [batch, seq, kv_heads, head_dim] of the sequence
    itself, ``block_table`` None. Serving: the page pools as the layer has
    just written them, the block table, and ``context`` [batch], the
    positions cached before this step."""

    k: Any
    v: Any
    block_table: Any = None
    context: Any = None


class _Mixer(nn.Module):
    """What the five mixers share: the per-call operands of a memory's
    cache node."""

    def _operand(self, name: str):
        if self.has_variable("cache", name):
            return self.get_variable("cache", name)
        return None

    def _step(self, chunk: int):
        """(context [batch], real tokens of the step [batch], slot [batch]
        or None) of a serving step."""
        context = self._operand("context_len")
        real = self._operand("chunk_len")
        if real is None:
            # the decode step: a live slot appends one token, an idle one
            # (context 0, which no live slot has) none
            real = jnp.where(context > 0, chunk, 0).astype(jnp.int32)
        return context, real, self._operand("slot")

    def _serving(self) -> bool:
        return self.config.decode and not self.is_initializing()

    @staticmethod
    def _rows(memory, slot):
        """A per-slot memory's rows of this step's batch."""
        return memory.value if slot is None else memory.value[slot]

    @staticmethod
    def _keep(memory, slot, new, active):
        """Write ``new`` where the batch row is ``active``; an idle slot's
        (and a slot's that another program is still prefilling) stays."""
        if slot is None:
            memory.value = jnp.where(active, new, memory.value)
        else:
            memory.value = memory.value.at[slot].set(
                jnp.where(active, new, memory.value[slot]))


class MambaMixer(_Mixer):
    """Selective state-space layer (Mamba-1). Returns (output, scan output
    before the gate): the second is what the Gated Memory Units take."""

    config: SambaYConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dt, pdt, init = _cdt(cfg), _pdt(cfg), _init(cfg)
        h, di, ds = cfg.hidden_size, cfg.inner_size, cfg.mamba_d_state
        rank, taps = cfg.mamba_dt_rank, cfg.mamba_d_conv
        batch, chunk, _ = x.shape
        in_proj = self.param("in_proj", init, (h, 2 * di), pdt)
        conv_w = self.param("conv_w", init, (taps, di), pdt)
        conv_b = self.param("conv_b", nn.initializers.zeros, (di,), pdt)
        x_proj = self.param("x_proj", init, (di, rank + 2 * ds), pdt)
        dt_proj = self.param("dt_proj", init, (rank, di), pdt)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (di,), pdt)
        a_log = self.param(
            "A_log", lambda k, s, d: jnp.log(jnp.broadcast_to(
                jnp.arange(1, s[1] + 1, dtype=jnp.float32), s)).astype(d),
            (di, ds), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (di,), jnp.float32)
        out_proj = self.param("out_proj", init, (di, h), pdt)

        state = tail = None
        if cfg.decode:
            if cfg.kv_num_slots < 1:
                raise ValueError(
                    "serving a state-space layer needs kv_num_slots >= 1 "
                    f"(one state a slot), got {cfg.kv_num_slots}")
            state = self.variable(
                "cache", "ssm",
                lambda: jnp.zeros((cfg.kv_num_slots, ds, di), jnp.float32))
            tail = self.variable(
                "cache", "conv",
                lambda: jnp.zeros((cfg.kv_num_slots, taps - 1, di), dt))
        with jax.named_scope("ssm"):
            xz = _mm(x, in_proj).astype(dt)
            xs, z = xz[..., :di], xz[..., di:]
            if self._serving():
                context, real, slot = self._step(chunk)
                s0, t0 = self._rows(state, slot), self._rows(tail, slot)
                # admission resets nothing: a step at context 0 starts
                # from zeros whatever the slot held
                fresh = (context == 0)[:, None, None]
                s0 = jnp.where(fresh, 0.0, s0)
                t0 = jnp.where(fresh, jnp.zeros((), dt), t0)
            else:
                real = None
                s0 = jnp.zeros((batch, ds, di), jnp.float32)
                t0 = jnp.zeros((batch, taps - 1, di), dt)
            padded = jnp.concatenate([t0, xs], axis=1)
            c = ss.causal_conv(padded, conv_w, conv_b, chunk)   # float32
            rbc = _mm(c.astype(dt), x_proj)
            step = jax.nn.softplus(
                _mm(rbc[..., :rank].astype(dt), dt_proj)
                + dt_bias.astype(jnp.float32))
            if real is not None:
                # a pad token of a ragged chunk, an idle slot's token: a
                # step of zero leaves the state as it is
                live = jnp.arange(chunk)[None, :] < real[:, None]
                step = jnp.where(live[..., None], step, 0.0)
            y, s1 = ss.selective_scan(
                c, step, -jnp.exp(a_log.astype(jnp.float32)),
                rbc[..., rank:rank + ds], rbc[..., rank + ds:], s0)
            y = y + d_skip * c
            if self._serving():
                active = (real > 0)[:, None, None]
                self._keep(state, slot, s1, active)
                self._keep(tail, slot, ss.conv_tail(padded, real, taps - 1),
                           active)
            out = _mm((y * jax.nn.silu(z.astype(jnp.float32))).astype(dt),
                      out_proj)
        return out.astype(dt), y.astype(dt)


class GatedMemoryUnit(nn.Module):
    """``W_out (m * silu(W_in h))``: the memory layer's scan output, gated
    by this layer's own input. Keeps nothing."""

    config: SambaYConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.config
        dt, pdt, init = _cdt(cfg), _pdt(cfg), _init(cfg)
        h, di = cfg.hidden_size, cfg.inner_size
        in_proj = self.param("in_proj", init, (h, di), pdt)
        out_proj = self.param("out_proj", init, (di, h), pdt)
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(_mm(x, in_proj))
            return _mm((memory.astype(jnp.float32) * gate).astype(dt),
                       out_proj).astype(dt)


class DifferentialAttention(_Mixer):
    """``kind``: ``window`` (own K/V, a ring), ``full`` (own K/V, THE page
    pool; hands a ``SharedKV`` on) or ``cross`` (query and output only,
    over the ``SharedKV`` it is handed). Returns (output, SharedKV)."""

    config: SambaYConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, x, shared: Optional[SharedKV], positions):
        cfg = self.config
        dt, pdt, init = _cdt(cfg), _pdt(cfg), _init(cfg)
        h, heads, kvh, d = (cfg.hidden_size, cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        zeros = nn.initializers.zeros
        lam0 = cfg.lambda_init(self.layer)
        scope = "window_attn" if self.kind == "window" else "shared_attn"

        def proj(name, n):
            w = self.param(name, init, (h, n, d), pdt)
            b = self.param(name + "_bias", zeros, (n, d), pdt)
            y = jax.lax.dot_general(
                x, w.astype(dt), (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return (y + b.astype(jnp.float32)).astype(dt)

        with jax.named_scope(scope):
            q = proj("q", heads)
            if self.kind != "cross":
                k, v = proj("k", kvh), proj("v", kvh)
            o_w = self.param("o", init, (heads // 2, 2 * d, h), pdt)
            o_b = self.param("o_bias", zeros, (h,), pdt)
            lam_init = nn.initializers.normal(stddev=0.1)
            lq1, lk1, lq2, lk2 = (
                self.param(n, lam_init, (d,), jnp.float32)
                for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
            gain = self.param("subln", nn.initializers.ones, (2 * d,), pdt)
            lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lam0)
            scale = d ** -0.5
            if self._serving() and self.kind == "window":
                both = self._ring(q, k, v, scale)
            elif self._serving():
                if self.kind == "full":
                    shared = self._write_pages(k, v)
                both = self._paged(q, shared, scale)
            else:
                if cfg.decode and self.kind != "cross":
                    self._declare(self.kind)   # initializing: the shapes
                if self.kind == "full":
                    shared = SharedKV(k, v)
                keys, values = (k, v) if self.kind == "window" else (
                    shared.k, shared.v)
                window = cfg.sliding_window if self.kind == "window" else None
                both = pa.differential_scores_attention(
                    q, keys, values, positions, positions, scale,
                    window=window, block=cfg.attention_query_block)
            ctx = pa.differential_combine(
                both, lam, gain.astype(jnp.float32), cfg.layer_norm_eps,
                1.0 - lam0)
            out = jnp.einsum(
                "bqpe,peh->bqh", ctx.astype(dt), o_w.astype(dt),
                preferred_element_type=jnp.float32) + o_b.astype(jnp.float32)
        return out.astype(dt), shared

    # ------------------------------------------------------------ memories

    def _declare(self, kind: str):
        cfg = self.config
        dt = _cdt(cfg)
        if kind == "window":
            if cfg.kv_num_slots < 1:
                raise ValueError(
                    "serving a window layer needs kv_num_slots >= 1 (one "
                    f"ring a slot), got {cfg.kv_num_slots}")
            shape = (cfg.kv_num_slots, cfg.sliding_window, cfg.kv_width)
            names = ("k_ring", "v_ring")
        else:
            if cfg.kv_num_pages < 2:
                raise ValueError(
                    "paged serving needs kv_num_pages >= 2 (page 0 is the "
                    f"reserved null page), got {cfg.kv_num_pages}")
            shape = (cfg.kv_num_pages, cfg.kv_page_size, cfg.kv_width)
            names = ("k_pages", "v_pages")
        return tuple(
            self.variable("cache", n, lambda: jnp.zeros(shape, dt))
            for n in names)

    def _write_pages(self, k, v) -> SharedKV:
        """This step's rows into THE pool through the block table (pad
        tokens of a ragged chunk too: dead lanes past the context, as in
        every paged family), and the pool handed on."""
        cfg = self.config
        kp, vp = self._declare("full")
        context = self._operand("context_len")
        bt = self._operand("block_table")
        batch, chunk = k.shape[:2]
        pos = context[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None]
        pages = jnp.take_along_axis(bt, pos // cfg.kv_page_size, axis=1)
        offs = pos % cfg.kv_page_size
        kp.value = kp.value.at[pages, offs].set(
            k.reshape(batch, chunk, cfg.kv_width))
        vp.value = vp.value.at[pages, offs].set(
            v.reshape(batch, chunk, cfg.kv_width))
        return SharedKV(kp.value, vp.value, bt, context)

    def _paged(self, q, shared: SharedKV, scale):
        """Layer ``half + 1``'s own read and every cross layer's: through
        the one block table, over everything written up to each query."""
        cfg = self.config
        chunk = q.shape[1]
        if chunk == 1:
            return pa.differential_paged_decode(
                q[:, 0], shared.k, shared.v, shared.block_table,
                shared.context + 1, scale)[:, None]
        # a prefill chunk (layer half + 1 only: the cross layers of a
        # prefill see one row): gather the slot's pages, attend causally
        return pa.differential_paged_chunk(
            q, shared.k, shared.v, shared.block_table, shared.context, scale,
            block=cfg.attention_query_block)

    def _ring(self, q, k, v, scale):
        """A window layer's step: the decode step writes its row and reads
        the ring's live rows; a prefill chunk attends ring and chunk
        together, then writes the chunk's real rows that a later step can
        still see."""
        cfg = self.config
        ring, width = cfg.sliding_window, cfg.kv_width
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        kr, vr = self._declare("window")
        batch, chunk = q.shape[:2]
        context, real, slot = self._step(chunk)
        rows = jnp.arange(batch, dtype=jnp.int32) if slot is None else slot
        pos = context[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None]
        if chunk > 1:
            old_k = kr.value[rows].reshape(batch, ring, kvh, d)
            old_v = vr.value[rows].reshape(batch, ring, kvh, d)
            # ring row j holds the latest position before this chunk that
            # is j modulo the ring; negative: never written by this
            # sequence (whatever the slot held before is not seen)
            j = jnp.arange(ring, dtype=jnp.int32)[None]
            last = context[:, None] - 1
            old_pos = last - jnp.mod(last - j, ring)
            k_pos = jnp.concatenate([old_pos, pos], axis=1)
            both = pa.differential_scores_attention(
                q, jnp.concatenate([old_k, k], axis=1),
                jnp.concatenate([old_v, v], axis=1), pos, k_pos, scale,
                window=ring, block=cfg.attention_query_block)
        # write: the step's real rows no newer row of the step overwrites
        end = context + real
        keep = (pos < end[:, None]) & (pos >= end[:, None] - ring)
        # a row that is not written goes out of range, and is dropped
        where = jnp.where(keep, jnp.mod(pos, ring), ring)
        at = (rows[:, None], where)
        kr.value = kr.value.at[at].set(
            k.reshape(batch, chunk, width), mode="drop")
        vr.value = vr.value.at[at].set(
            v.reshape(batch, chunk, width), mode="drop")
        if chunk > 1:
            return both
        # keys carry no positions: the ring is the SET of the latest
        # min(context + 1, ring) rows, and those are its first rows until
        # it has wrapped, all of them after
        return pa.differential_ring_decode(
            q[:, 0], kr.value, vr.value, rows, jnp.minimum(end, ring),
            scale, cfg.kv_page_size)[:, None]


class DecoderLayer(nn.Module):
    config: SambaYConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, x, memory, shared, positions):
        cfg = self.config
        dt, pdt = _cdt(cfg), _pdt(cfg)

        def norm(name):
            return FusedLayerNorm(
                epsilon=cfg.layer_norm_eps, param_dtype=pdt, out_dtype=dt,
                name=name)

        h = norm("mixer_norm")(x)
        if self.kind == "mamba":
            a, y = MambaMixer(cfg, name="mixer")(h)
            if self.layer == cfg.num_hidden_layers // 2:
                memory = y
        elif self.kind == "gmu":
            a = GatedMemoryUnit(cfg, name="mixer")(h, memory)
        else:
            a, shared = DifferentialAttention(
                cfg, self.kind, self.layer, name="mixer")(h, shared, positions)
        x = x + a
        h = norm("mlp_norm")(x)
        with jax.named_scope("dense_mlp"):
            m = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + m.astype(dt), memory, shared


class SambaYLM(nn.Module):
    """Embedding -> layers -> final LayerNorm -> tied head. Signature as
    ``GPT2LMModel``'s (the serving engine drives either). ``logit_index``
    [batch] (serving prefills only): the one row of the step whose logits
    are wanted; the upper half of the stack then runs for that row alone
    and the logits come back ``[batch, 1, vocab]``."""

    config: SambaYConfig
    #: named scopes a trace is read by (``analysis/spmd/hlo.scope_instructions``)
    trace_scopes = ("ssm", "gmu", "window_attn", "shared_attn")
    takes_logit_index = True   # what ``serve/engine.py::_row_logits`` asks

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 position_ids=None, deterministic: bool = True,
                 logit_index=None):
        cfg = self.config
        if attention_mask is not None:
            raise ValueError(
                "padding is expressed through context_len and chunk_len")
        batch, seq = input_ids.shape
        if position_ids is None:
            if cfg.decode and not self.is_initializing():
                raise ValueError("paged serving passes position_ids")
            position_ids = jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
        dt = _cdt(cfg)
        rows = cfg.vocab_size // cfg.vocab_blocks
        embed = [self.param(f"embed_{j}", _init(cfg),
                            (rows, cfg.hidden_size), _pdt(cfg))
                 for j in range(cfg.vocab_blocks)]
        x = sum(
            jnp.where(((input_ids // rows) == j)[..., None],
                      block[jnp.clip(input_ids - j * rows, 0, rows - 1)], 0)
            for j, block in enumerate(embed)).astype(dt)
        kinds = cfg.layer_kinds
        upper = cfg.num_hidden_layers // 2 + 2
        if logit_index is not None and not (
                cfg.decode and not self.is_initializing()):
            raise ValueError("logit_index is a serving prefill's")
        memory = shared = None
        for l, kind in enumerate(kinds):
            if l == upper and logit_index is not None:
                # a prefill: the upper half keeps nothing, so it runs for
                # the one row whose logits are used
                pick = logit_index[:, None]
                x = jnp.take_along_axis(x, pick[..., None], axis=1)
                memory = jnp.take_along_axis(memory, pick[..., None], axis=1)
                position_ids = jnp.take_along_axis(position_ids, pick, axis=1)
                if shared.block_table is not None:
                    shared = shared._replace(
                        context=shared.context + logit_index)
            x, memory, shared = DecoderLayer(
                cfg, kind, l, name=f"layer_{l}")(
                x, memory, shared, position_ids)
        x = FusedLayerNorm(
            epsilon=cfg.layer_norm_eps, param_dtype=_pdt(cfg), out_dtype=dt,
            name="final_norm")(x)
        # tied: a row of the table is a token's output direction
        return jnp.concatenate([
            jax.lax.dot_general(
                x, block.astype(dt), (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for block in embed], axis=-1)


#: published sizes of the family's presets (``utils/config.model_preset``
#: finds them here)
PRESETS: dict[str, dict[str, Any]] = {
    # https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/
    # config.json (model_type phi4flash), whole: 3.85 G parameters
    "phi-4-mini-flash": dict(
        vocab_size=200064, hidden_size=2560, num_hidden_layers=32,
        num_attention_heads=40, num_key_value_heads=20,
        intermediate_size=10240, sliding_window=512,
        max_position_embeddings=262144, mb_per_layer=2, layer_norm_eps=1e-5,
        vocab_blocks=4,
    ),
    # the CPU tests' size: M, W, M, W, M*, F, G, X; contexts of more than
    # three windows
    "sambay-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=8, num_key_value_heads=4, intermediate_size=128,
        sliding_window=8, max_position_embeddings=4096, mamba_d_state=4,
        mamba_dt_rank=8, vocab_blocks=2, attention_query_block=4,
        compute_dtype="float32", param_dtype="float32",
    ),
}


def preset(name: str, **overrides: Any) -> SambaYConfig:
    kwargs = dict(PRESETS[name])
    kwargs.update(overrides)
    return SambaYConfig(**kwargs)
